"""Compile the serving path's Pallas kernels for a TPU v5e, with no chip.

The TPU compiler is installed with jax; it compiles for a described
``v5e:2x2`` topology. This catches the block shapes and vector ops the
chip's kernel compiler refuses, which interpret mode cannot see. Shapes are
tinyllama-1.1b's: the 5632x2048 and 2048x5632 weight matrices at GS=256,
and paged attention at KV=4, G=8, hd=64 with 8-token blocks; paged
attention also at the benchmark cells' decode shapes. GQMM also reads one
layer of a stacked weight in place at the cells' largest matrices, and a
two-layer dense model's paged decode and prefill are compiled whole to
check that no layer's weights are copied out of their stacks.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.core.policy import quantize_params
from repro.kernels import ops
from repro.kernels.gqmv import gqmm_pallas, gqmv_pallas
from repro.kernels.paged_attn import paged_attention_pallas

GS = 256
FORMATS = {                       # storage dtype, pack, pack_storage
    "int8": (jnp.int8, 1, 1),
    "int4": (jnp.int8, 2, 1),
    "int3": (jnp.uint8, 8, 3),
    "fp8": (jnp.float8_e4m3fn, 1, 1),
}
WIDTHS = [(5632, 2048), (2048, 5632)]     # (m, n): up/gate and down proj


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes) -> str:
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _weights(sharding, fmt, m, n):
    dtype, pack, pack_storage = FORMATS[fmt]
    return (jax.ShapeDtypeStruct((m, n // pack * pack_storage), dtype,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((m, n // GS), jnp.float32, sharding=sharding))


@pytest.mark.parametrize("fmt", list(FORMATS))
@pytest.mark.parametrize("m,n", WIDTHS)
def test_gqmv_compiles_for_v5e(one_chip, fmt, m, n):
    wq, ws = _weights(one_chip, fmt, m, n)
    xq = jax.ShapeDtypeStruct((n,), jnp.int8, sharding=one_chip)
    xs = jax.ShapeDtypeStruct((n // GS,), jnp.float32, sharding=one_chip)
    _compile(partial(gqmv_pallas, group_size=GS, fmt=fmt), wq, ws, xq, xs)


@pytest.mark.parametrize("b", [1, 8, 64])
@pytest.mark.parametrize("fmt", list(FORMATS))
@pytest.mark.parametrize("m,n", WIDTHS)
def test_gqmm_compiles_for_v5e(one_chip, fmt, m, n, b):
    wq, ws = _weights(one_chip, fmt, m, n)
    xq = jax.ShapeDtypeStruct((b, n), jnp.int8, sharding=one_chip)
    xs = jax.ShapeDtypeStruct((b, n // GS), jnp.float32, sharding=one_chip)
    _compile(partial(gqmm_pallas, group_size=GS, fmt=fmt), wq, ws, xq, xs)


def test_gqmm_pads_rows_beyond_one_block(one_chip):
    """200 prefill rows: more than one 128-row block and not a multiple of
    it, so the batch is padded up to whole blocks."""
    wq, ws = _weights(one_chip, "int8", 2048, 5632)
    xq = jax.ShapeDtypeStruct((200, 5632), jnp.int8, sharding=one_chip)
    xs = jax.ShapeDtypeStruct((200, 5632 // GS), jnp.float32, sharding=one_chip)
    _compile(partial(gqmm_pallas, group_size=GS, fmt="int8"), wq, ws, xq, xs)


# (L, m, n): the largest stacked matrices of the benchmark cells
# (bench/configs/): dscoder33b-s16's gate|up and down, internlm2-1.8b's
# gate|up
STACKED = {
    "dscoder33b-s16.w13": (16, 38400, 7168),
    "dscoder33b-s16.w2": (16, 7168, 19200),
    "internlm2-1.8b.w13": (24, 16384, 2048),
}


@pytest.mark.parametrize("b", [32, 256])
@pytest.mark.parametrize("matrix", list(STACKED))
def test_gqmm_stacked_layer_compiles_for_v5e(one_chip, matrix, b):
    L, m, n = STACKED[matrix]
    wq = jax.ShapeDtypeStruct((L, m, n), jnp.int8, sharding=one_chip)
    ws = jax.ShapeDtypeStruct((L, m, n // GS), jnp.float32, sharding=one_chip)
    xq = jax.ShapeDtypeStruct((b, n), jnp.int8, sharding=one_chip)
    xs = jax.ShapeDtypeStruct((b, n // GS), jnp.float32, sharding=one_chip)
    layer = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = _compile(lambda *a: gqmm_pallas(*a[:4], group_size=GS, layer=a[4]),
                    wq, ws, xq, xs, layer)
    assert f"s8[{L},{m},{n}]" in text


_OP = re.compile(r"\s*(?:ROOT )?%(\S+) = (.*?)\s([a-z][\w\-]*)\((.*)")


def _ops(text):
    """(name, result type, opcode, rest of the line) of each instruction."""
    return [m.groups() for m in map(_OP.match, text.splitlines()) if m]


def test_layer_walk_reads_weights_in_place_on_v5e(one_chip, monkeypatch):
    """A two-layer dense model's paged decode step and prefill: no op
    produces an array of one layer's int8 weights; no dynamic slice or copy
    yields one layer's scales, and nothing inside the layer loop does; and
    each layer's four GQMM calls take the stacked leaves."""
    monkeypatch.setattr(ops, "_default_impl", lambda: "pallas")
    from repro.models.registry import build

    cfg = ModelConfig(arch_id="walk", family="dense", model_type="decoder_lm",
                      num_layers=2, d_model=4096, num_heads=32, num_kv_heads=8,
                      head_dim=128, d_ff=11008, vocab_size=32000,
                      group_size=GS, param_dtype="bfloat16",
                      compute_dtype="bfloat16")
    model = build(cfg)

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = abstract(jax.eval_shape(
        lambda: quantize_params(model.init(jax.random.PRNGKey(0)), GS)))
    b, bs, mb = 8, 8, 16
    pool = abstract(jax.eval_shape(
        lambda: model.init_paged_cache(b * mb + 1, bs, jnp.bfloat16)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    # (m, n) of each layer matrix: q|k|v, output, gate|up, down
    d, f, kv = cfg.d_model, cfg.d_ff, cfg.num_kv_heads * cfg.head_dim
    mats = [(d + 2 * kv, d), (d, d), (2 * f, d), (d, f)]
    weights = [f"[{a},{c}]" for m, n in mats for a, c in ((m, n), (f"1,{m}", n))]
    scales = [f"[{a},{n // GS}]" for m, n in mats for a in (m, f"1,{m}")]
    stacks = [f"s8[2,{m},{n}]" for m, n in mats]

    decode = _compile(model.decode_paged, params, i32(b), pool, i32(b, mb),
                      i32(b))
    prefill = _compile(
        lambda p, t, n: model.prefill(p, {"tokens": t, "lengths": n}, 128),
        params, i32(b, 128), i32(b))
    for text in (decode, prefill):
        for name, result, op, rest in _ops(text):
            if op in ("parameter", "get-tuple-element", "bitcast", "tuple",
                      "while"):
                continue
            assert not any(f"s8{w}" in result for w in weights), (name, result)
            # XLA may still stage a whole scale stack (1/64 of the weight
            # bytes) into on-chip memory before the loop, in static slices
            layer_scales = any(f"f32{w}" in result for w in scales)
            copies = (op.startswith(("dynamic-slice", "copy"))
                      or "dynamic-slice" in name)
            assert not (layer_scales and copies), (name, result)
            assert not (layer_scales and "/while/body/" in rest), (name, result)
        calls = [rest for _, _, op, rest in _ops(text)
                 if op == "custom-call" and "gqmm_int8" in rest]
        layer_calls = [c for c in calls if any(st in c for st in stacks)]
        assert len(calls) == 5 and len(layer_calls) == 4, calls


# (b, KV, G, hd, MB) of 8-token blocks: tinyllama's heads at 20 blocks (the
# last of two 16-block compute blocks ragged), and the benchmark cells'
# decode shapes (bench/workloads/)
PAGED = {
    "tinyllama": (8, 4, 8, 64, 20),
    "dscoder33b-s16": (32, 8, 7, 128, 256),
    "internlm2-1.8b": (8, 8, 2, 128, 288),
}


@pytest.mark.parametrize("geometry,pool", [
    *(pytest.param("tinyllama", pool, id=pool)
      for pool in ["bfloat16", "float32", "int8", "float8_e4m3fn"]),
    *(pytest.param(cell, pool, id=f"{cell}-{pool}")
      for cell, pool in [("dscoder33b-s16", "bfloat16"),
                         ("internlm2-1.8b", "bfloat16"),
                         ("internlm2-1.8b", "int8")]),
])
def test_paged_attention_compiles_for_v5e(one_chip, geometry, pool):
    b, kv, g, hd, mb = PAGED[geometry]
    bs = 8
    nb = b * mb + 1
    quant = pool in ("int8", "float8_e4m3fn")
    act = jnp.float32 if pool == "float32" else jnp.bfloat16

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = [s((b, kv, g, hd), act), s((nb, bs, kv, hd), jnp.dtype(pool)),
              s((nb, bs, kv, hd), jnp.dtype(pool)), s((b, mb), jnp.int32),
              s((b,), jnp.int32), s((b, kv, hd), act), s((b, kv, hd), act),
              s((b, mb * bs), jnp.float32)]
    if quant:
        shapes += [s((nb, bs, kv), jnp.float32)] * 2

    def attend(q, kp, vp, bt, pos, kn, vn, mask, ks=None, vs=None):
        return paged_attention_pallas(q, kp, vp, bt, pos, kn, vn, mask,
                                      scale=hd ** -0.5, k_scales=ks,
                                      v_scales=vs)

    _compile(attend, *shapes)
