"""Pallas GQMV/GQMM kernels vs the pure-jnp oracle (paper Alg. 1).

Kernels execute in interpret mode on the CPU; shapes/dtypes/GS swept.
tests/test_tpu_compile.py compiles the same kernels for a TPU v5e.
"""

from functools import partial

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core.quant import (
    quantize_activation,
    quantize_fp8,
    quantize_groupwise,
    quantize_int3,
    quantize_int4,
)
from repro.kernels import ops
from repro.kernels.gqmv import gqmm_pallas, gqmv_pallas
from repro.kernels.ref import (
    gqmm_fp8_ref,
    gqmm_int3_ref,
    gqmm_int4_ref,
    gqmm_ref,
    gqmv_fp8_ref,
    gqmv_int3_ref,
    gqmv_int4_ref,
    gqmv_ref,
)

gqmv_int4_pallas = partial(gqmv_pallas, fmt="int4")
gqmm_int4_pallas = partial(gqmm_pallas, fmt="int4")
gqmv_int3_pallas = partial(gqmv_pallas, fmt="int3")
gqmm_int3_pallas = partial(gqmm_pallas, fmt="int3")
gqmv_fp8_pallas = partial(gqmv_pallas, fmt="fp8")
gqmm_fp8_pallas = partial(gqmm_pallas, fmt="fp8")


def _mk(m, n, gs, seed=0, b=None):
    rng = np.random.default_rng(seed)
    w = quantize_groupwise(
        jnp.asarray(rng.normal(size=(m, n)).astype(np.float32)), gs
    )
    shape = (n,) if b is None else (b, n)
    x = quantize_activation(
        jnp.asarray(rng.normal(size=shape).astype(np.float32)), gs
    )
    return w, x


GQMV_SHAPES = [
    # (m, n, GS) - includes paper-exact TinyLlama dims (2048, 5632, GS=256)
    (8, 64, 32),
    (128, 256, 256),
    (256, 2048, 256),     # kernel1 column size = dim (paper §III-B)
    (2048, 5632, 256),    # kernel2 column size = hidden_dim (paper §III-B)
    (96, 384, 128),
    (512, 512, 64),
]


@pytest.mark.parametrize("m,n,gs", GQMV_SHAPES)
def test_gqmv_matches_ref(m, n, gs):
    w, x = _mk(m, n, gs, seed=m + n)
    got = gqmv_pallas(w.qvalues, w.scales, x.qvalues, x.scales,
                      group_size=gs, interpret=True)
    want = gqmv_ref(w.qvalues, w.scales, x.qvalues, x.scales, group_size=gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-4, atol=1e-4)


@pytest.mark.parametrize("m,n,gs,b", [
    (64, 128, 32, 4),
    (128, 512, 256, 16),
    (256, 2048, 256, 8),
    (32, 256, 64, 1),
    (2048, 5632, 256, 2),
    (128, 256, 128, 200),   # > one 128-row block: rows padded to 256
])
def test_gqmm_matches_ref(m, n, gs, b):
    w, x = _mk(m, n, gs, seed=m + n + b, b=b)
    got = gqmm_pallas(w.qvalues, w.scales, x.qvalues, x.scales,
                      group_size=gs, interpret=True)
    want = gqmm_ref(w.qvalues, w.scales, x.qvalues, x.scales, group_size=gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-4, atol=1e-4)


@pytest.mark.parametrize("block_m", [8, 16, 32])
def test_gqmv_block_shape_sweep(block_m):
    """Block shape is a tuning knob; result must be invariant to it."""
    w, x = _mk(64, 512, 64, seed=7)
    want = gqmv_ref(w.qvalues, w.scales, x.qvalues, x.scales, group_size=64)
    got = gqmv_pallas(w.qvalues, w.scales, x.qvalues, x.scales, group_size=64,
                      block_m=block_m, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-4, atol=1e-4)


def test_gqmv_against_fp32_matmul():
    """GQMV approximates the fp32 matmul within dequantization error."""
    rng = np.random.default_rng(11)
    wf = rng.normal(scale=0.05, size=(256, 1024)).astype(np.float32)
    xf = rng.normal(size=(1024,)).astype(np.float32)
    w = quantize_groupwise(jnp.asarray(wf), 256)
    x = quantize_activation(jnp.asarray(xf), 256)
    got = gqmv_pallas(w.qvalues, w.scales, x.qvalues, x.scales,
                      group_size=256, interpret=True)
    exact = wf @ xf
    # relative Frobenius error small (paper Table IV: mean element error 2.65e-4)
    rel = np.linalg.norm(np.asarray(got) - exact) / np.linalg.norm(exact)
    assert rel < 0.02, rel


def test_ops_dispatch_xla_equals_interpret():
    w, x = _mk(128, 512, 128, seed=5)
    a = ops.gqmv(w.qvalues, w.scales, x.qvalues, x.scales,
                 group_size=128, impl="xla")
    b = ops.gqmv(w.qvalues, w.scales, x.qvalues, x.scales,
                 group_size=128, impl="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-4)


def test_quantized_matmul_shapes():
    rng = np.random.default_rng(9)
    w = quantize_groupwise(jnp.asarray(rng.normal(size=(96, 256)).astype(np.float32)), 64)
    y1 = ops.quantized_matmul(jnp.ones((256,)), w, impl="xla")
    y2 = ops.quantized_matmul(jnp.ones((4, 256)), w, impl="xla")
    y3 = ops.quantized_matmul(jnp.ones((2, 3, 256)), w, impl="xla")
    assert y1.shape == (96,)
    assert y2.shape == (4, 96)
    assert y3.shape == (2, 3, 96)
    np.testing.assert_allclose(np.asarray(y3[0, 0]), np.asarray(y1), rtol=1e-5)


@settings(deadline=None, max_examples=15)
@given(
    mi=st.integers(1, 4),
    gi=st.integers(1, 4),
    gs=st.sampled_from([32, 64]),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_gqmv_pallas_vs_ref(mi, gi, gs, seed):
    m, n = 8 * mi, gs * gi
    w, x = _mk(m, n, gs, seed=seed)
    got = gqmv_pallas(w.qvalues, w.scales, x.qvalues, x.scales,
                      group_size=gs, interpret=True)
    want = gqmv_ref(w.qvalues, w.scales, x.qvalues, x.scales, group_size=gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# packed int4 (unpack-in-VMEM kernels vs XLA oracle)
# ---------------------------------------------------------------------------

def _mk4(m, n, gs, seed=0, b=None):
    rng = np.random.default_rng(seed)
    w = quantize_int4(jnp.asarray(rng.normal(size=(m, n)).astype(np.float32)), gs)
    shape = (n,) if b is None else (b, n)
    x = quantize_activation(
        jnp.asarray(rng.normal(size=shape).astype(np.float32)), gs
    )
    return w, x


@pytest.mark.parametrize("m,n,gs", [
    (8, 64, 32),
    (128, 256, 256),
    (256, 1024, 256),
    (96, 384, 128),
])
def test_gqmv_int4_interpret_exact_vs_ref(m, n, gs):
    """The interpret-mode kernel and the XLA oracle share the combined-scale
    association and the order of the cross-group sum -> bitwise-equal
    outputs."""
    w, x = _mk4(m, n, gs, seed=m + n)
    got = gqmv_int4_pallas(w.qvalues, w.scales, x.qvalues, x.scales,
                           group_size=gs, interpret=True)
    want = gqmv_int4_ref(w.qvalues, w.scales, x.qvalues, x.scales, group_size=gs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,n,gs", [
    (2048, 5632, 256),    # paper kernel2 dims; many row blocks
    (256, 2048, 256),
])
def test_gqmv_int4_multiblock_matches_ref(m, n, gs):
    w, x = _mk4(m, n, gs, seed=m + n)
    got = gqmv_int4_pallas(w.qvalues, w.scales, x.qvalues, x.scales,
                           group_size=gs, interpret=True)
    want = gqmv_int4_ref(w.qvalues, w.scales, x.qvalues, x.scales, group_size=gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,n,gs,b", [
    (64, 128, 32, 4),
    (128, 512, 256, 16),
    (2048, 5632, 256, 2),
    (32, 256, 64, 1),
])
def test_gqmm_int4_matches_ref(m, n, gs, b):
    w, x = _mk4(m, n, gs, seed=m + n + b, b=b)
    got = gqmm_int4_pallas(w.qvalues, w.scales, x.qvalues, x.scales,
                           group_size=gs, interpret=True)
    want = gqmm_int4_ref(w.qvalues, w.scales, x.qvalues, x.scales, group_size=gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-4, atol=1e-4)


def test_int4_dispatch_xla_equals_interpret():
    w, x = _mk4(128, 512, 128, seed=5)
    a = ops.gqmv(w.qvalues, w.scales, x.qvalues, x.scales,
                 group_size=128, impl="xla", kernel="gqmv_int4")
    b = ops.gqmv(w.qvalues, w.scales, x.qvalues, x.scales,
                 group_size=128, impl="interpret", kernel="gqmv_int4")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_int4_quantized_matmul_approximates_fp32():
    """End-to-end dispatch through the registry's kernel hook: int4 GQMV
    approximates the fp32 matmul within dequantization error."""
    rng = np.random.default_rng(13)
    wf = rng.normal(scale=0.05, size=(256, 1024)).astype(np.float32)
    xf = rng.normal(size=(1024,)).astype(np.float32)
    w = quantize_int4(jnp.asarray(wf), 256)
    got = ops.quantized_matmul(jnp.asarray(xf), w, impl="interpret")
    exact = wf @ xf
    rel = np.linalg.norm(np.asarray(got) - exact) / np.linalg.norm(exact)
    assert rel < 0.2, rel   # ~17x the int8 error budget (4 bits vs 8)


# ---------------------------------------------------------------------------
# packed int3 (8 values per 3 bytes; unpack-in-VMEM kernels vs XLA oracle)
# ---------------------------------------------------------------------------

def _mkq(fmt_fn, m, n, gs, seed=0, b=None):
    rng = np.random.default_rng(seed)
    w = fmt_fn(jnp.asarray(rng.normal(size=(m, n)).astype(np.float32)), gs)
    shape = (n,) if b is None else (b, n)
    x = quantize_activation(
        jnp.asarray(rng.normal(size=shape).astype(np.float32)), gs
    )
    return w, x


@pytest.mark.parametrize("m,n,gs", [
    (8, 64, 32),
    (128, 256, 256),
    (256, 1024, 256),
    (96, 384, 128),
])
def test_gqmv_int3_interpret_exact_vs_ref(m, n, gs):
    """Integer datapath: the interpret-mode kernel and the XLA oracle share
    the combined-scale association -> bitwise-equal outputs (like int4)."""
    w, x = _mkq(quantize_int3, m, n, gs, seed=m + n)
    got = gqmv_int3_pallas(w.qvalues, w.scales, x.qvalues, x.scales,
                           group_size=gs, interpret=True)
    want = gqmv_int3_ref(w.qvalues, w.scales, x.qvalues, x.scales, group_size=gs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,n,gs", [
    (2048, 5632, 256),    # paper kernel2 dims; many row blocks
    (256, 2048, 256),
])
def test_gqmv_int3_multiblock_matches_ref(m, n, gs):
    w, x = _mkq(quantize_int3, m, n, gs, seed=m + n)
    got = gqmv_int3_pallas(w.qvalues, w.scales, x.qvalues, x.scales,
                           group_size=gs, interpret=True)
    want = gqmv_int3_ref(w.qvalues, w.scales, x.qvalues, x.scales, group_size=gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-4, atol=1e-4)


@pytest.mark.parametrize("m,n,gs,b", [
    (64, 128, 32, 4),
    (128, 512, 256, 16),
    (2048, 5632, 256, 2),
    (32, 256, 64, 1),
])
def test_gqmm_int3_matches_ref(m, n, gs, b):
    w, x = _mkq(quantize_int3, m, n, gs, seed=m + n + b, b=b)
    got = gqmm_int3_pallas(w.qvalues, w.scales, x.qvalues, x.scales,
                           group_size=gs, interpret=True)
    want = gqmm_int3_ref(w.qvalues, w.scales, x.qvalues, x.scales, group_size=gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-4, atol=1e-4)


def test_int3_dispatch_xla_equals_interpret():
    w, x = _mkq(quantize_int3, 128, 512, 128, seed=5)
    a = ops.gqmv(w.qvalues, w.scales, x.qvalues, x.scales,
                 group_size=128, impl="xla", kernel="gqmv_int3")
    b = ops.gqmv(w.qvalues, w.scales, x.qvalues, x.scales,
                 group_size=128, impl="interpret", kernel="gqmv_int3")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_int3_quantized_matmul_approximates_fp32():
    """3-bit grid has 7 levels: error ~2x int4's but the registry dispatch
    must still land in the same ballpark as the fp32 matmul."""
    rng = np.random.default_rng(17)
    wf = rng.normal(scale=0.05, size=(256, 1024)).astype(np.float32)
    xf = rng.normal(size=(1024,)).astype(np.float32)
    w = quantize_int3(jnp.asarray(wf), 256)
    got = ops.quantized_matmul(jnp.asarray(xf), w, impl="interpret")
    exact = wf @ xf
    rel = np.linalg.norm(np.asarray(got) - exact) / np.linalg.norm(exact)
    assert rel < 0.4, rel   # measured ~0.17 on this init family


# ---------------------------------------------------------------------------
# fp8 (e4m3 weights, float datapath; tolerance-based vs oracle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,gs", [
    (8, 64, 32),
    (128, 256, 256),
    (256, 2048, 256),
    (2048, 5632, 256),
])
def test_gqmv_fp8_matches_ref(m, n, gs):
    """Float datapath: no exact integer stage, so the comparison is
    tolerance-based (f32 dot reassociation across lanes may differ)."""
    w, x = _mkq(quantize_fp8, m, n, gs, seed=m + n)
    got = gqmv_fp8_pallas(w.qvalues, w.scales, x.qvalues, x.scales,
                          group_size=gs, interpret=True)
    want = gqmv_fp8_ref(w.qvalues, w.scales, x.qvalues, x.scales, group_size=gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-4, atol=1e-4)


@pytest.mark.parametrize("m,n,gs,b", [
    (64, 128, 32, 4),
    (128, 512, 256, 16),
    (2048, 5632, 256, 2),
    (32, 256, 64, 1),
])
def test_gqmm_fp8_matches_ref(m, n, gs, b):
    w, x = _mkq(quantize_fp8, m, n, gs, seed=m + n + b, b=b)
    got = gqmm_fp8_pallas(w.qvalues, w.scales, x.qvalues, x.scales,
                          group_size=gs, interpret=True)
    want = gqmm_fp8_ref(w.qvalues, w.scales, x.qvalues, x.scales, group_size=gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-4, atol=1e-4)


def test_fp8_dispatch_xla_equals_interpret():
    w, x = _mkq(quantize_fp8, 128, 512, 128, seed=5)
    a = ops.gqmv(w.qvalues, w.scales, x.qvalues, x.scales,
                 group_size=128, impl="xla", kernel="gqmv_fp8")
    b = ops.gqmv(w.qvalues, w.scales, x.qvalues, x.scales,
                 group_size=128, impl="interpret", kernel="gqmv_fp8")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-4)


def test_fp8_quantized_matmul_approximates_fp32():
    rng = np.random.default_rng(19)
    wf = rng.normal(scale=0.05, size=(256, 1024)).astype(np.float32)
    xf = rng.normal(size=(1024,)).astype(np.float32)
    w = quantize_fp8(jnp.asarray(wf), 256)
    got = ops.quantized_matmul(jnp.asarray(xf), w, impl="interpret")
    exact = wf @ xf
    rel = np.linalg.norm(np.asarray(got) - exact) / np.linalg.norm(exact)
    assert rel < 0.05, rel   # e4m3 weights: near the int8 error budget


def test_int4_quantized_matmul_batched_shapes():
    rng = np.random.default_rng(14)
    w = quantize_int4(jnp.asarray(rng.normal(size=(96, 256)).astype(np.float32)), 64)
    y1 = ops.quantized_matmul(jnp.ones((256,)), w, impl="xla")
    y3 = ops.quantized_matmul(jnp.ones((2, 3, 256)), w, impl="xla")
    assert y1.shape == (96,)
    assert y3.shape == (2, 3, 96)
    # GQMV and GQMM oracles associate the fp32 scale product differently
    np.testing.assert_allclose(np.asarray(y3[0, 0]), np.asarray(y1), rtol=5e-4, atol=1e-4)
