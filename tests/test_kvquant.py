"""Quantized KV cache (``kv_quant``): parity, pool accounting, validation.

The tentpole contract: with the KV cache stored int8/fp8 (per-row f32
scale leaves, dequantized inside attention), greedy decode must be
token-identical across ALL THREE paths — direct contiguous generate, the
contiguous slot scheduler, and the paged scheduler — for every supported
kv_quant format. The quantized model is a different model than the float
one (cache rows are rounded), so parity is quantized-vs-quantized; the
float engine is only the accounting baseline.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_cases import BS as PAGED_BS
from paged_cases import interpret_kernel, paged_case, parity_params
from repro.models.registry import build, load_config
from repro.serving.batching import Request, serve_continuous
from repro.serving.engine import InferenceEngine
from repro.serving.paged import serve_paged

KV_FORMATS = ("int8", "fp8")
PROMPTS = [[5, 3], [7, 1, 4], list(range(1, 11)), list(range(2, 14))]


@pytest.fixture(scope="module")
def tiny():
    cfg = load_config("tinyllama-1.1b").reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _direct(engine, prompt, n, **kw):
    res = engine.generate({"tokens": jnp.asarray([prompt], jnp.int32)}, n, **kw)
    return np.asarray(res.tokens[0])


# ---------------------------------------------------------------------------
# parity: direct == contiguous slots == paged, per kv_quant format
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kvq", KV_FORMATS)
def test_kvquant_paged_eq_contiguous_eq_direct(tiny, kvq):
    _, model, params = tiny
    eng = InferenceEngine(model, params, cache_len=40, kv_quant=kvq)
    budgets = [2, 6, 3, 5]
    reqs = [Request(i, p, max_new=b)
            for i, (p, b) in enumerate(zip(PROMPTS, budgets))]
    cont = serve_continuous(eng, reqs, 6, slots=2, chunk=2)
    paged = serve_paged(eng, reqs, 6, slots=2, chunk=2, block_size=8)
    for rc, rp, req in zip(cont, paged, reqs):
        want = _direct(eng, req.tokens, req.max_new)
        np.testing.assert_array_equal(rc.tokens, want)
        np.testing.assert_array_equal(rp.tokens, want)
        assert rc.length == rp.length


@pytest.mark.parametrize("arch", ["gemma2-2b", "internlm2-1.8b"])
def test_kvquant_parity_across_gqa_variants(arch):
    """Sliding window + softcap (gemma2) and plain GQA (internlm2) through
    the quantized-pool kernel path."""
    cfg = load_config(arch).reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = InferenceEngine(model, params, cache_len=40, kv_quant="int8")
    reqs = [Request(i, p, max_new=4) for i, p in enumerate(PROMPTS[:3])]
    paged = serve_paged(eng, reqs, 4, slots=2, chunk=2, block_size=8)
    for rp, req in zip(paged, reqs):
        np.testing.assert_array_equal(
            rp.tokens, _direct(eng, req.tokens, req.max_new))


def test_kvquant_close_to_float_decode(tiny):
    """int8 KV rows carry ~0.4% relative rounding — greedy tokens on this
    reduced model should mostly agree with the float path (sanity that the
    quantized cache is an approximation, not a different computation)."""
    _, model, params = tiny
    feng = InferenceEngine(model, params, cache_len=40)
    qeng = InferenceEngine(model, params, cache_len=40, kv_quant="int8")
    agree = np.mean([
        np.mean(_direct(feng, p, 6) == _direct(qeng, p, 6)) for p in PROMPTS])
    assert agree >= 0.5, agree


@pytest.mark.parametrize(
    "kvq,geometry,window,softcap,poison",
    [pytest.param(kvq, *c.values, id=c.id) for kvq in KV_FORMATS
     for c in parity_params(prefix=f"{kvq}-", base=kvq)])
def test_paged_quant_kernel_vs_oracle(kvq, geometry, window, softcap, poison):
    """The quantized-pool Pallas kernel (interpret mode) against the XLA
    gather oracle at tinyllama's head geometry (KV=4, G=8, hd=64): in-VMEM
    dequantization must equal the oracle's scales-outside-the-dots form
    up to f32 reassociation. Poisoned: the blocks only dead table entries
    name carry NaN scales (and NaN rows where the format holds NaN) for
    the kernel and zeros for the oracle."""
    from repro.kernels.ref import paged_attention_ref
    from repro.models.attention import _quantize_rows
    from repro.models.common import decode_mask

    rng = np.random.default_rng(1)
    hd = 64
    q, kp, vp, table, pos, kn, vn, dead = paged_case(rng, geometry, 4, 8, hd)
    kq, ks = (np.array(a) for a in _quantize_rows(jnp.asarray(kp), kvq))
    vq, vs = (np.array(a) for a in _quantize_rows(jnp.asarray(vp), kvq))
    pool = [kq, vq, ks, vs]
    if poison:
        pool_in = [a.copy() for a in pool]
        for a, z in zip(pool_in, pool):
            if jnp.issubdtype(a.dtype, jnp.floating):
                a[dead] = np.nan
            z[dead] = 0
    else:
        pool_in = pool
    mask = decode_mask(table.shape[1] * PAGED_BS, pos, window)
    kw = dict(scale=hd**-0.5, softcap=softcap)
    kq, vq, ks, vs = pool
    ref = paged_attention_ref(q, kq, vq, table, pos, kn, vn, mask, **kw,
                              k_scales=ks, v_scales=vs)
    kq, vq, ks, vs = pool_in
    pal = interpret_kernel(q, kq, vq, table, pos, kn, vn, mask, **kw,
                           k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# cache structure + bytes accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kvq", KV_FORMATS)
def test_kvquant_pool_structure_and_bytes(tiny, kvq):
    cfg, model, params = tiny
    eng = InferenceEngine(model, params, cache_len=40, kv_quant=kvq)
    pool = jax.eval_shape(
        lambda: eng.model.init_paged_cache(6, 8, eng.cfg.cdtype()))
    assert set(pool) == {"k_pages", "k_scales", "v_pages", "v_scales"}
    store = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}[kvq]
    assert pool["k_pages"].dtype == store
    assert pool["k_scales"].dtype == jnp.float32
    # scales are per cached row: pages minus the head_dim axis
    assert pool["k_scales"].shape == pool["k_pages"].shape[:-1]

    def nbytes(tree):
        return sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
                   for l in jax.tree.leaves(tree))

    fpool = jax.eval_shape(lambda: model.init_paged_cache(6, 8, cfg.cdtype()))
    # 1-byte rows + f32/head_dim scale overhead must beat the f32 pool >= 3x
    assert nbytes(fpool) / nbytes(pool) >= 3.0


def test_kvquant_contiguous_cache_structure(tiny):
    _, model, params = tiny
    eng = InferenceEngine(model, params, cache_len=40, kv_quant="int8")
    cache = jax.eval_shape(
        lambda: eng.model.init_cache(2, 40, eng.cfg.cdtype()))
    assert set(cache) == {"k_q", "k_s", "v_q", "v_s"}
    assert cache["k_q"].dtype == jnp.int8
    assert cache["k_s"].dtype == jnp.float32
    assert cache["k_s"].shape == cache["k_q"].shape[:-1]


def test_kvquant_scale_leaf_sharding_rule():
    """`*_scales` pool leaves follow their pages: kv heads -> model axis,
    block axis NEVER sharded (blocks migrate through the tables)."""
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import cache_spec

    mesh = SimpleNamespace(shape={"data": 16, "model": 16},
                           axis_names=("data", "model"))
    spec = cache_spec("k_scales", (22, 4096, 16, 32), mesh=mesh, batch=4096)
    assert spec == P(None, None, None, "model")
    # heads not divisible -> replicated; the block axis must stay whole even
    # though 4096 divides the data axis (the batch-search fallback hazard)
    spec = cache_spec("v_scales", (22, 4096, 16, 3), mesh=mesh, batch=4096)
    assert spec == P(None, None, None, None)


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def test_kvquant_unknown_format_raises(tiny):
    _, model, params = tiny
    with pytest.raises(ValueError, match="unknown kv_quant"):
        InferenceEngine(model, params, cache_len=40, kv_quant="int3")


def test_kvquant_rejects_non_paged_families():
    rwkv = build(load_config("rwkv6-7b").reduced())
    with pytest.raises(ValueError, match="GQA decoder_lm"):
        InferenceEngine(rwkv, rwkv.init(jax.random.PRNGKey(0)),
                        cache_len=16, kv_quant="int8")


def test_kvquant_incompatible_with_spec_decode(tiny):
    _, model, params = tiny
    eng = InferenceEngine(model, params, cache_len=40, kv_quant="int8")
    with pytest.raises(ValueError, match="kv_quant"):
        eng.generate({"tokens": jnp.asarray([PROMPTS[0]], jnp.int32)},
                     4, spec_k=2)
