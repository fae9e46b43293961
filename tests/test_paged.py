"""Paged KV cache + the three serving bugfixes of this PR.

Tentpole regressions: the paged decode path must be BIT-exact against the
contiguous deferred decode (identity block tables), the Pallas paged
kernel must match the gather oracle, and the paged scheduler must produce
token-identical greedy outputs to the contiguous slot scheduler across
GQA variants (tinyllama, gemma2 sliding-window+softcap, internlm2) while
resident blocks scale with live tokens.

Satellite regressions (each failed before its fix):
- top-p value-threshold filtering kept every token tied with the cutoff
  logit (whole vocab on tied logits) and make_sampler's p/temperature were
  unreachable from generate/serve;
- finished slots kept decoding with stale tok while pos advanced every
  chunk, drifting past cache_len;
- EOS-less engines padded responses with literal token 0, indistinguishable
  from a real vocab-0 token.
"""

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_cases import BS as PAGED_BS
from paged_cases import interpret_kernel, paged_case, parity_params
from repro.core import flags
from repro.models.common import decode_mask
from repro.models.registry import build, load_config
from repro.serving.batching import (
    Request,
    SlotScheduler,
    serve_bucketed,
    serve_continuous,
    serve_ragged,
)
from repro.serving.engine import InferenceEngine
from repro.serving.paged import BlockPool, PagedScheduler, serve_paged
from repro.serving.sampling import make_sampler, nucleus_mask

MESH16 = SimpleNamespace(shape={"data": 16, "model": 16},
                         axis_names=("data", "model"))


@pytest.fixture(scope="module")
def tiny():
    cfg = load_config("tinyllama-1.1b").reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def engine(tiny):
    _, model, params = tiny
    return InferenceEngine(model, params, cache_len=40)


def _direct(engine, prompt, n, **kw):
    res = engine.generate({"tokens": jnp.asarray([prompt], jnp.int32)}, n, **kw)
    return np.asarray(res.tokens[0])


PROMPTS = [[5, 3], [7, 1, 4], list(range(1, 11)), list(range(2, 14))]


# ---------------------------------------------------------------------------
# satellite 1: top-p nucleus + sampler-kwarg plumbing
# ---------------------------------------------------------------------------

def test_nucleus_mask_no_overinclusion_on_ties():
    """All-tied logits, p=0.5 over 8 tokens: the minimal set is 4 tokens.
    The old `logits >= cutoff` filter kept all 8 (the cutoff VALUE ties with
    every token), inflating the nucleus to the whole vocab."""
    kept = np.asarray(nucleus_mask(jnp.zeros((1, 8)), 0.5))
    assert kept.sum() == 4, kept


def test_nucleus_mask_mass_property():
    """Minimal-mass property on random logits: kept mass reaches p, and
    dropping the smallest kept token falls below p (no over-inclusion)."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(16, 64)).astype(np.float32) * 3)
    kept = np.asarray(nucleus_mask(logits, 0.7))
    probs = np.asarray(jax.nn.softmax(logits, -1))
    for i in range(16):
        mass = probs[i][kept[i]].sum()
        assert mass >= 0.7 - 1e-6
        assert mass - probs[i][kept[i]].min() < 0.7
    # top token always survives even for tiny p
    assert np.all(np.asarray(nucleus_mask(logits, 1e-9)).sum(-1) == 1)


def test_sampler_kwargs_reach_engine_and_schedulers(engine):
    """p -> 0 nucleus == greedy: if the kwargs didn't reach the sampler the
    default p=0.9 would diverge from greedy on these random-weight logits.
    (Before the fix, generate/serve had no way to pass them at all.)"""
    skw = {"p": 1e-9, "temperature": 1.0}
    greedy = [_direct(engine, p, 6) for p in PROMPTS]
    got = [_direct(engine, p, 6, sampler="top_p", sampler_kw=skw)
           for p in PROMPTS]
    for g, w in zip(got, greedy):
        np.testing.assert_array_equal(g, w)
    reqs = [Request(i, p) for i, p in enumerate(PROMPTS)]
    for mode in ("bucketed", "continuous", "paged"):
        out = serve_ragged(engine, reqs, 6, sampler="top_p", sampler_kw=skw,
                           mode=mode)
        for r, w in zip(out, greedy):
            np.testing.assert_array_equal(r.tokens, w)


def test_make_sampler_rejects_greedy_kwargs():
    with pytest.raises(ValueError, match="greedy"):
        make_sampler("greedy", p=0.9)


# ---------------------------------------------------------------------------
# satellite 2: finished-slot freeze in the contiguous scheduler
# ---------------------------------------------------------------------------

def test_slot_scheduler_freezes_finished_slots(tiny):
    """One slot finishes at budget 2 while its neighbor decodes 20 more
    tokens with nothing pending: the dead slot's position must freeze at its
    finish point instead of advancing every chunk toward (and past)
    cache_len. Outputs must still match direct generation."""
    _, model, params = tiny
    eng = InferenceEngine(model, params, cache_len=40)
    sched = SlotScheduler(eng, slots=2, chunk=2)
    reqs = [Request(0, PROMPTS[0], max_new=2), Request(1, PROMPTS[1], max_new=22)]
    out = sched.serve(reqs, 22)
    for r, req in zip(out, reqs):
        np.testing.assert_array_equal(
            r.tokens, _direct(eng, req.tokens, req.max_new))
    pos = sched.last_positions
    # slot of request 0: prompt len 2 + first token + 1 committed decode
    # step before the freeze kicked in at its finish
    assert int(pos.min()) <= len(PROMPTS[0]) + 2, pos
    assert int(pos.max()) < eng.cache_len, pos


def test_slot_scheduler_long_trace_positions_stay_bounded(tiny):
    """Long mixed-budget trace through few slots with a tight cache: every
    live position must stay < cache_len (host-asserted each chunk) and every
    response must match direct generation."""
    _, model, params = tiny
    eng = InferenceEngine(model, params, cache_len=40)
    budgets = [2, 26, 3, 5, 2, 4]
    reqs = [Request(i, PROMPTS[i % len(PROMPTS)], max_new=b)
            for i, b in enumerate(budgets)]
    out = serve_continuous(eng, reqs, 26, slots=2, chunk=4)
    for r, req in zip(out, reqs):
        np.testing.assert_array_equal(
            r.tokens, _direct(eng, req.tokens, req.max_new))
    assert int(np.max(np.asarray(out[1].tokens.shape))) == 26


# ---------------------------------------------------------------------------
# satellite 3: true generated length on Response
# ---------------------------------------------------------------------------

def test_response_length_without_eos(tiny):
    """eos_id=None: padding uses token 0, which is a legal vocab id — the
    true length must ride on the Response instead of being inferred."""
    _, model, params = tiny
    eng = InferenceEngine(model, params, cache_len=40)   # eos None
    reqs = [Request(0, PROMPTS[0], max_new=3), Request(1, PROMPTS[2])]
    for out in (serve_bucketed(eng, reqs, 5),
                serve_continuous(eng, reqs, 5, slots=2, chunk=2),
                serve_paged(eng, reqs, 5, slots=2, chunk=2)):
        assert out[0].length == 3 and out[0].tokens.shape == (3,)
        assert out[1].length == 5 and out[1].tokens.shape == (5,)


def test_response_length_with_eos(tiny):
    """EOS mid-budget: length counts the real tokens (EOS inclusive), the
    tail is EOS padding."""
    _, model, params = tiny
    probe = InferenceEngine(model, params, cache_len=40)
    first = int(_direct(probe, PROMPTS[0], 1)[0])
    eng = InferenceEngine(model, params, cache_len=40, eos_id=first)
    reqs = [Request(0, PROMPTS[0])]
    for out in (serve_bucketed(eng, reqs, 4),
                serve_continuous(eng, reqs, 4, slots=2, chunk=2),
                serve_paged(eng, reqs, 4, slots=2, chunk=2)):
        assert out[0].length == 1
        assert np.all(np.asarray(out[0].tokens) == first)


# ---------------------------------------------------------------------------
# tentpole: block pool allocator
# ---------------------------------------------------------------------------

def test_block_pool_invariants():
    pool = BlockPool(8, 4)
    assert pool.free_blocks == 7            # block 0 is the sink
    a = pool.alloc(3)
    assert 0 not in a and len(set(a)) == 3
    assert pool.live_blocks == 3 and pool.peak_live == 3
    pool.free(a[:2])
    assert pool.free_blocks == 6 and pool.peak_live == 3
    b = pool.alloc(6)
    assert pool.peak_live == 7
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(1)
    pool.free(b + a[2:])
    assert pool.live_blocks == 0


# ---------------------------------------------------------------------------
# tentpole: paged == contiguous parity
# ---------------------------------------------------------------------------

def test_paged_decode_bit_exact_vs_contiguous_deferred(tiny):
    """Identity block tables over a reshaped contiguous cache: the paged
    decode logits must be BITWISE equal to the contiguous deferred path."""
    from repro.models.transformer import contiguous_to_paged

    _, model, params = tiny
    rng = np.random.default_rng(3)
    cfg = load_config("tinyllama-1.1b").reduced()
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 6)), jnp.int32)
    lens = jnp.asarray([4, 6], jnp.int32)
    with flags.overrides(deferred_decode_cache=True):
        logits, cache = model.prefill(params, {"tokens": toks, "lengths": lens}, 16)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pool, table = contiguous_to_paged(cache, 8)
        pos = lens
        for _ in range(4):
            lc, cache = model.decode(params, tok, cache, pos)
            lp, pool = model.decode_paged(params, tok, pool, table, pos)
            np.testing.assert_array_equal(np.asarray(lc), np.asarray(lp))
            tok = jnp.argmax(lc, -1).astype(jnp.int32)
            pos = pos + 1


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "gemma2-2b", "internlm2-1.8b"])
def test_paged_matches_continuous_greedy(arch):
    """Mixed-length mixed-budget trace: the paged scheduler must be
    token-identical to the contiguous slot scheduler AND direct generation
    across GQA variants (gemma2 exercises sliding window + softcap through
    the paged kernel path)."""
    cfg = load_config(arch).reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, cache_len=40)
    budgets = [2, 6, 3, 5, 4]
    reqs = [Request(i, PROMPTS[i % len(PROMPTS)], max_new=b)
            for i, b in enumerate(budgets)]
    cont = serve_continuous(eng, reqs, 6, slots=2, chunk=2)
    paged = serve_paged(eng, reqs, 6, slots=2, chunk=2, block_size=8)
    for rc, rp, req in zip(cont, paged, reqs):
        want = _direct(eng, req.tokens, req.max_new)
        np.testing.assert_array_equal(rc.tokens, want)
        np.testing.assert_array_equal(rp.tokens, want)
        assert rc.length == rp.length


def test_paged_engine_generate_parity(tiny, engine):
    """engine.generate(paged=True): block-table decode over the identity
    pool must reproduce the contiguous tokens (uniform and ragged)."""
    rng = np.random.default_rng(1)
    cfg = tiny[0]
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (3, 7)), jnp.int32)}
    want = np.asarray(engine.generate(batch, 5).tokens)
    got = np.asarray(engine.generate(batch, 5, paged=True).tokens)
    np.testing.assert_array_equal(got, want)
    lens = np.asarray([3, 7, 5], np.int32)
    want = np.asarray(engine.generate(batch, 5, lengths=lens).tokens)
    got = np.asarray(engine.generate(batch, 5, lengths=lens, paged=True).tokens)
    np.testing.assert_array_equal(got, want)


def test_paged_backpressure_small_pool(tiny):
    """A pool far smaller than slots x cache_len: admission waits for block
    reclaim, outputs stay exact, and the allocator never exhausts."""
    _, model, params = tiny
    eng = InferenceEngine(model, params, cache_len=40)
    budgets = [2, 6, 3, 5, 2, 4]
    reqs = [Request(i, PROMPTS[i % len(PROMPTS)], max_new=b)
            for i, b in enumerate(budgets)]
    # 9 usable blocks of 4 = 36 token slots, vs 3 slots x 40 = 120 contiguous
    sched = PagedScheduler(eng, slots=3, chunk=2, block_size=4, num_blocks=10)
    out = sched.serve(reqs, 6)
    for r, req in zip(out, reqs):
        np.testing.assert_array_equal(
            r.tokens, _direct(eng, req.tokens, req.max_new))
    assert sched.last_peak_blocks <= 9


def test_paged_resident_blocks_scale_with_live_tokens(tiny):
    """Short requests must not hold cache_len-sized regions: the pool's
    high-water mark stays well under the contiguous slots x cache_len
    equivalent."""
    _, model, params = tiny
    eng = InferenceEngine(model, params, cache_len=40)
    reqs = [Request(i, PROMPTS[i % len(PROMPTS)], max_new=2) for i in range(6)]
    sched = PagedScheduler(eng, slots=3, chunk=2, block_size=8)
    sched.serve(reqs, 2)
    contiguous_equiv = 3 * math.ceil(40 / 8)          # slots x blocks(cache_len)
    assert sched.last_peak_blocks < contiguous_equiv // 2, (
        sched.last_peak_blocks, contiguous_equiv)


def test_auto_mode_falls_back_under_kv_layout_flags(engine):
    """mode="auto" must keep resolving to the contiguous scheduler under the
    kvt/int8 KV-cache flags — the paged pool only speaks the base float
    layout, and auto-mode serving worked with those flags before the paged
    scheduler became the preferred default."""
    from repro.serving.batching import resolve_mode

    assert resolve_mode(engine, "auto") == "paged"
    with flags.overrides(int8_kv_cache=True):
        assert resolve_mode(engine, "auto") == "continuous"
    with flags.overrides(kvt_cache_layout=True):
        assert resolve_mode(engine, "auto") == "continuous"
    assert resolve_mode(engine, "bucketed") == "bucketed"


def test_paged_validates_capacity_and_layout(tiny, engine):
    _, model, params = tiny
    sched = PagedScheduler(engine, slots=2, chunk=2, block_size=8)
    with pytest.raises(ValueError, match="cache slots"):
        sched.serve([Request(0, list(range(38)), max_new=8)], 8)
    with pytest.raises(ValueError, match="layout"):
        with flags.overrides(kvt_cache_layout=True):
            sched.serve([Request(0, [1, 2])], 2)
    rwkv = build(load_config("rwkv6-7b").reduced())
    reng = InferenceEngine(rwkv, rwkv.init(jax.random.PRNGKey(0)), cache_len=16)
    assert not rwkv.supports_paged
    with pytest.raises(ValueError, match="paged"):
        PagedScheduler(reng)
    mla = load_config("minicpm3-4b").reduced()
    assert not build(mla).supports_paged


# ---------------------------------------------------------------------------
# tentpole: kernel parity + sharding rule + snapshot
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("geometry,window,softcap,poison", parity_params())
def test_paged_attention_kernel_vs_oracle(geometry, window, softcap, poison):
    """Float pool: the Pallas kernel (interpret mode) against the XLA gather
    oracle over shuffled tables and ragged positions. Poisoned: every block
    that only dead table entries name is NaN for the kernel and zero for
    the oracle, so equal outputs show the kernel never reads them."""
    from repro.kernels.ref import paged_attention_ref

    rng = np.random.default_rng(0)
    kv, hd = 2, 16
    q, kp, vp, table, pos, kn, vn, dead = paged_case(rng, geometry, kv, 4, hd)
    mask = decode_mask(table.shape[1] * PAGED_BS, pos, window)
    kw = dict(scale=hd**-0.5, softcap=softcap)
    kp_in, vp_in = kp.copy(), vp.copy()
    if poison:
        kp_in[dead], vp_in[dead] = np.nan, np.nan
        kp[dead], vp[dead] = 0.0, 0.0
    ref = paged_attention_ref(q, kp, vp, table, pos, kn, vn, mask, **kw)
    pal = interpret_kernel(q, kp_in, vp_in, table, pos, kn, vn, mask,
                           **kw)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               rtol=2e-5, atol=2e-5)


def test_paged_pool_sharding_never_splits_blocks():
    """`*_pages` leaves: kv heads -> model, block axis ALWAYS whole (blocks
    migrate between requests through the tables)."""
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import cache_spec

    spec = cache_spec("k_pages", (22, 4096, 16, 32, 128), mesh=MESH16, batch=4096)
    assert spec == P(None, None, None, "model", None)
    # heads not divisible -> fully replicated, block axis still whole
    spec = cache_spec("v_pages", (22, 4096, 16, 3, 128), mesh=MESH16, batch=4096)
    assert spec == P(None, None, None, None, None)


def test_snapshot_restore_carries_block_table(tiny, engine):
    _, model, _ = tiny
    cache = model.init_paged_cache(6, 8, jnp.float32)
    table = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    snap = engine.snapshot(cache, jnp.asarray([4, 5]), jnp.asarray([7, 9]),
                           block_table=table)
    c2, pos2, toks2, table2 = engine.restore(snap)
    np.testing.assert_array_equal(np.asarray(table2), np.asarray(table))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                            np.asarray(b)),
                 cache, c2)
    # contiguous snapshots keep the 3-tuple contract
    assert len(engine.restore(engine.snapshot(cache, pos2, toks2))) == 3
