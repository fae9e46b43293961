"""The float32 reference agrees with the program's prefill and paged
decode at a tiny size on the CPU, and its int4 control does not."""

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference
from bench.tests.record_trace import TINY

SEED = 2**32 + 99


def program_logits(cfg, prompt, steps):
    """Greedy prefill + paged decode through the program; returns the
    served tokens and the logits that chose each."""
    from repro.models.registry import build
    from repro.models.transformer import contiguous_to_paged

    model = build(harness.model_config(cfg))
    params = harness.served_params(model, cfg, SEED)
    total = len(prompt) + steps + 7
    cache_len = -(-total // 8) * 8
    toks = jnp.asarray([prompt], jnp.int32)
    logits, cache = model.prefill(params, {"tokens": toks, "lengths": jnp.asarray(
        [len(prompt)], jnp.int32)}, cache_len)
    pool, table = contiguous_to_paged(cache, 8)
    out, served = [logits[0]], [int(jnp.argmax(logits[0]))]
    pos = jnp.asarray([len(prompt)], jnp.int32)
    for _ in range(steps - 1):
        logits, pool = model.decode_paged(params, jnp.asarray([served[-1]], jnp.int32),
                                          pool, table, pos)
        out.append(logits[0])
        served.append(int(jnp.argmax(logits[0])))
        pos = pos + 1
    return served, np.stack([np.asarray(x, np.float32) for x in out])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_reference_matches_program_prefill_and_paged_decode(dtype):
    cfg = dict(TINY, dtype=dtype)
    prompt = np.random.default_rng(0).integers(0, cfg["vocab_size"], 37).tolist()
    served, prog = program_logits(cfg, prompt, 12)
    tokens, rows, targets, n = reference.teacher_forced([prompt], [served], row_multiple=1)
    assert tokens.shape == (1, 512) and list(targets) == served and n == 12
    ref = np.asarray(reference.Reference(cfg).logits(SEED, tokens, rows))
    scale = np.abs(ref).max()
    err = np.abs(prog - ref).max() / scale
    # int8 activations (W8A8) and, for bfloat16, bf16 rounding over 2 layers
    assert err < (0.03 if dtype == "float32" else 0.06), err
    gaps = np.asarray(reference.gaps(jnp.asarray(ref), jnp.asarray(targets)))
    assert gaps.max() < 0.1 * scale
    ctl = np.asarray(reference.Reference(cfg, weight_bits=4).logits(SEED, tokens, rows))
    assert np.abs(ctl - ref).max() / scale > 3 * err


def test_teacher_forced_pads_to_fixed_sizes_without_changing_a_maximum():
    tokens, rows, targets, n = reference.teacher_forced(
        [[5, 6, 7], [8]], [[1, 2], [3, 4, 9]], sequences=4, length=600, row_multiple=8)
    assert tokens.shape == (4, 1024) and n == 5 and len(rows) == len(targets) == 8
    assert tokens[0, :4].tolist() == [5, 6, 7, 1] and tokens[1, :3].tolist() == [8, 3, 4]
    assert rows[:n].tolist() == [[0, 2], [0, 3], [1, 0], [1, 1], [1, 2]]
    assert targets[:n].tolist() == [1, 2, 3, 4, 9]
    assert (rows[n:] == rows[0]).all() and (targets[n:] == targets[0]).all()


def test_reference_layers_are_made_one_at_a_time_like_the_whole_model():
    """The reference regenerates each layer alone; the harness makes all
    layers in one call. Both must give the same weights."""
    from repro.models.registry import build

    cfg = dict(TINY)
    params = harness.served_params(build(harness.model_config(cfg)), cfg, SEED)
    from bench import weights as W

    lw = W.layer_weights(W.weight_key(SEED), 1, cfg)
    wqkv = params["layers"]["attn"]["wqkv"]
    q = np.concatenate([np.asarray(lw[k][0]) for k in ("wq", "wk", "wv")])
    np.testing.assert_array_equal(np.asarray(wqkv.qvalues[1]), q)
    np.testing.assert_array_equal(np.asarray(params["layers"]["ffn_norm"][1]),
                                  np.asarray(lw["ffn_norm"]))
