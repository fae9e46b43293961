"""Compile the serving path's Pallas kernels for a TPU v5e, with no chip.

The TPU compiler is installed with jax; it compiles for a described
``v5e:2x2`` topology. This catches the block shapes and vector ops the
chip's kernel compiler refuses, which interpret mode cannot see. Shapes are
tinyllama-1.1b's: the 5632x2048 and 2048x5632 weight matrices at GS=256,
and paged attention at KV=4, G=8, hd=64 with 8-token blocks; paged
attention also at the benchmark cells' decode shapes.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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
