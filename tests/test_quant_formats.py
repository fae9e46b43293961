"""QuantFormat registry: packed int4, mixed precision, ckpt/sharding glue."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import (
    FP8_MAX,
    QuantizedTensor,
    available_formats,
    choose_group_size,
    dequantize,
    get_format,
    largest_pow2_group,
    pack_int3,
    pack_int4,
    quantization_error_stats,
    quantize,
    quantize_fp8,
    quantize_groupwise,
    quantize_int3,
    quantize_int4,
    unpack_int3,
    unpack_int4,
)

jax.config.update("jax_enable_x64", False)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_contents():
    assert set(available_formats()) >= {"int8", "int4"}
    f8, f4 = get_format("int8"), get_format("int4")
    assert (f8.bits, f8.pack, f8.qmax) == (8, 1, 127)
    assert (f4.bits, f4.pack, f4.qmax) == (4, 2, 7)
    with pytest.raises(ValueError, match="unknown quant format"):
        get_format("fp3")


def test_int8_via_registry_bit_identical():
    """The registry's int8 path IS quantize_groupwise — same arrays, same
    scales, same fmt aux (the acceptance bar for the redesign)."""
    rng = np.random.default_rng(0)
    r = jnp.asarray(rng.normal(size=(32, 256)).astype(np.float32))
    a = quantize_groupwise(r, 64)
    b = quantize(r, 64, "int8")
    np.testing.assert_array_equal(np.asarray(a.qvalues), np.asarray(b.qvalues))
    np.testing.assert_array_equal(np.asarray(a.scales), np.asarray(b.scales))
    assert a.fmt == b.fmt == "int8"
    np.testing.assert_array_equal(
        np.asarray(dequantize(a)), np.asarray(dequantize(b))
    )


# ---------------------------------------------------------------------------
# int4 packing
# ---------------------------------------------------------------------------

def test_pack_unpack_roundtrip_exact():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.integers(-7, 8, size=(16, 64)).astype(np.int8))
    p = pack_int4(q, 32)
    assert p.shape == (16, 32) and p.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(unpack_int4(p, 32)), np.asarray(q))


def test_pack_odd_axis_raises():
    with pytest.raises(ValueError, match="even group_size dividing"):
        pack_int4(jnp.zeros((4, 33), jnp.int8), 32)
    with pytest.raises(ValueError, match="even group_size dividing"):
        pack_int4(jnp.zeros((4, 66), jnp.int8), 33)


@pytest.mark.parametrize("gs", [16, 256])
def test_pack_int4_byte_holds_both_group_halves(gs):
    """Storage order the TPU kernel unpacks without a lane interleave: in
    each group, byte k carries element k (low nibble) and k + gs/2 (high)."""
    rng = np.random.default_rng(3)
    q = rng.integers(-7, 8, size=(4, 2 * gs)).astype(np.int8)
    p = np.asarray(pack_int4(jnp.asarray(q), gs)).astype(np.int32)
    h = gs // 2
    for g in range(2):
        byte = p[:, g * h:(g + 1) * h]
        lo = ((byte & 0xF) ^ 8) - 8
        hi = byte >> 4
        np.testing.assert_array_equal(lo, q[:, g * gs:g * gs + h])
        np.testing.assert_array_equal(hi, q[:, g * gs + h:(g + 1) * gs])


def test_int4_quantize_shapes_and_range():
    rng = np.random.default_rng(2)
    r = jnp.asarray(rng.normal(size=(8, 256)).astype(np.float32))
    qt = quantize_int4(r, 64)
    assert qt.fmt == "int4"
    assert qt.storage_shape == (8, 128)         # packed
    assert qt.shape == qt.logical_shape == (8, 256)
    assert qt.scales.shape == (8, 4)
    vals = np.asarray(unpack_int4(qt.qvalues, qt.group_size))
    assert vals.max() <= 7 and vals.min() >= -7
    assert vals.max() == 7 or vals.min() == -7  # full range used per Eq. 1


def test_int4_roundtrip_error_bound():
    """|r_hat - r| <= S/2 per element, S = 2*max|r|/15 per group."""
    rng = np.random.default_rng(3)
    r = jnp.asarray(rng.normal(size=(64, 512)).astype(np.float32))
    qt = quantize_int4(r, 128)
    err = np.abs(np.asarray(dequantize(qt)) - np.asarray(r))
    half = np.repeat(np.asarray(qt.scales), 128, axis=-1) / 2
    assert np.all(err <= half + 1e-6)


def test_int4_zero_group_safe():
    qt = quantize_int4(jnp.zeros((2, 64)), 32)
    assert bool(jnp.all(qt.qvalues == 0))
    assert bool(jnp.all(jnp.isfinite(dequantize(qt))))


def test_int4_groupwise_beats_per_tensor():
    """Group-wise fp32 scales must beat one scale per tensor at 4 bits
    (rows with wildly different magnitudes — the regime PTQ actually sees)."""
    rng = np.random.default_rng(4)
    rows = [rng.normal(size=(1, 512)) * 10.0 ** (i % 5 - 2) for i in range(16)]
    r = np.concatenate(rows).astype(np.float32)
    stats = quantization_error_stats(jnp.asarray(r), 64, "int4")
    s = 2.0 * np.abs(r).max() / 15.0
    naive = np.clip(np.round(r / s), -7, 7) * s
    naive_err = np.abs(naive - r)
    naive_rel = naive_err / np.abs(r)
    assert stats["mean"] < float(naive_err.mean()), (stats["mean"], naive_err.mean())
    # the decisive effect: one per-tensor scale flattens small-magnitude rows
    # to ~100% relative error; per-group scales keep them resolved
    assert stats["rel_mean_pct"] < float(100 * naive_rel.mean()) / 3


def test_int4_error_stats_between_int8_and_naive():
    rng = np.random.default_rng(5)
    r = jnp.asarray((rng.normal(size=(128, 2048)) * 0.02).astype(np.float32))
    e8 = quantization_error_stats(r, 256, "int8")["mean"]
    e4 = quantization_error_stats(r, 256, "int4")["mean"]
    assert e8 < e4 < 30 * e8  # 4-bit costs ~17x mean error, not orders more


# ---------------------------------------------------------------------------
# int3 packing (8 logical values per 3 storage bytes)
# ---------------------------------------------------------------------------

def test_int3_registry_entry():
    assert {"int3", "fp8"} <= set(available_formats())
    f3 = get_format("int3")
    assert (f3.bits, f3.pack, f3.pack_storage, f3.qmax) == (3, 8, 3, 3)
    assert f3.storage_dtype == jnp.uint8 and f3.kind == "int"
    # the bit law the quant-invariants checker enforces
    assert f3.bits * f3.pack == 8 * jnp.dtype(f3.storage_dtype).itemsize * f3.pack_storage


def test_pack_unpack_int3_roundtrip_exact():
    rng = np.random.default_rng(21)
    q = jnp.asarray(rng.integers(-3, 4, size=(16, 64)).astype(np.int8))
    p = pack_int3(q, 32)
    assert p.shape == (16, 24) and p.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(unpack_int3(p, 32)), np.asarray(q))


def test_pack_int3_bad_axis_raises():
    with pytest.raises(ValueError, match="divisible by 8"):
        pack_int3(jnp.zeros((4, 28), jnp.int8), 28)
    with pytest.raises(ValueError, match="whole groups"):
        unpack_int3(jnp.zeros((4, 28), jnp.uint8), 32)


@pytest.mark.parametrize("gs", [32, 256])
def test_pack_int3_planes_per_group(gs):
    """Storage order the TPU kernel unpacks without a lane interleave: each
    group is three byte planes of w = gs/8 bytes, and field c of the 24-bit
    word k is group element c*w + k."""
    rng = np.random.default_rng(4)
    q = rng.integers(-3, 4, size=(4, 2 * gs)).astype(np.int8)
    p = np.asarray(pack_int3(jnp.asarray(q), gs)).astype(np.int32)
    w = gs // 8
    for g in range(2):
        b = p[:, 3 * w * g:3 * w * (g + 1)]
        word = b[:, :w] | (b[:, w:2 * w] << 8) | (b[:, 2 * w:] << 16)
        for c in range(8):
            field = (((word >> (3 * c)) & 7) ^ 4) - 4
            np.testing.assert_array_equal(
                field, q[:, g * gs + c * w:g * gs + (c + 1) * w])


def test_int3_quantize_shapes_and_range():
    rng = np.random.default_rng(22)
    r = jnp.asarray(rng.normal(size=(8, 256)).astype(np.float32))
    qt = quantize_int3(r, 64)
    assert qt.fmt == "int3"
    assert qt.storage_shape == (8, 96)          # 8 values per 3 bytes
    assert qt.shape == qt.logical_shape == (8, 256)
    assert qt.scales.shape == (8, 4)
    vals = np.asarray(unpack_int3(qt.qvalues, qt.group_size))
    assert vals.max() <= 3 and vals.min() >= -3
    assert vals.max() == 3 or vals.min() == -3  # full range used per Eq. 1


def test_int3_roundtrip_error_bound():
    """|r_hat - r| <= S/2 per element, S = 2*max|r|/7 per group."""
    rng = np.random.default_rng(23)
    r = jnp.asarray(rng.normal(size=(64, 512)).astype(np.float32))
    qt = quantize_int3(r, 128)
    err = np.abs(np.asarray(dequantize(qt)) - np.asarray(r))
    half = np.repeat(np.asarray(qt.scales), 128, axis=-1) / 2
    assert np.all(err <= half + 1e-6)


def test_int3_group_size_must_divide_pack():
    with pytest.raises(ValueError, match="divisible by 8"):
        quantize_int3(jnp.ones((4, 48)), 12)


# ---------------------------------------------------------------------------
# fp8 (e4m3 storage, per-group scale)
# ---------------------------------------------------------------------------

def test_fp8_registry_entry():
    f8 = get_format("fp8")
    assert (f8.bits, f8.pack, f8.pack_storage) == (8, 1, 1)
    assert f8.kind == "float"
    assert f8.storage_dtype == jnp.float8_e4m3fn


def test_fp8_quantize_shapes_and_storage():
    rng = np.random.default_rng(24)
    r = jnp.asarray(rng.normal(size=(8, 256)).astype(np.float32))
    qt = quantize_fp8(r, 64)
    assert qt.fmt == "fp8"
    assert qt.qvalues.dtype == jnp.float8_e4m3fn
    assert qt.storage_shape == qt.logical_shape == (8, 256)
    assert qt.scales.shape == (8, 4)
    # group absmax maps onto the e4m3 grid endpoint
    vals = np.abs(np.asarray(qt.qvalues.astype(jnp.float32)))
    assert vals.max() == pytest.approx(FP8_MAX)


def test_fp8_relative_error_follows_magnitude():
    """e4m3 is a float grid: relative error is roughly flat across magnitudes
    (vs int8 whose absolute step is constant within a group)."""
    rng = np.random.default_rng(25)
    r = jnp.asarray(rng.normal(size=(32, 512)).astype(np.float32))
    qt = quantize_fp8(r, 128)
    back = np.asarray(dequantize(qt))
    w = np.asarray(r)
    rel = np.abs(back - w) / np.maximum(np.abs(w), 1e-9)
    # 3 mantissa bits -> worst-case relative step 2^-4 = 6.25% of the value
    assert np.median(rel) < 0.0625


def test_fp8_zero_group_safe():
    qt = quantize_fp8(jnp.zeros((2, 64)), 32)
    assert bool(jnp.all(jnp.isfinite(dequantize(qt))))
    np.testing.assert_array_equal(np.asarray(dequantize(qt)), 0.0)


# ---------------------------------------------------------------------------
# QuantizedTensor aux / accounting
# ---------------------------------------------------------------------------

def test_pytree_roundtrip_preserves_fmt():
    qt = quantize_int4(jnp.ones((8, 128)), 32)
    leaves, treedef = jax.tree_util.tree_flatten(qt)
    qt2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert qt2.fmt == "int4" and qt2.group_size == 32
    np.testing.assert_array_equal(np.asarray(qt2.qvalues), np.asarray(qt.qvalues))


def test_bits_per_weight():
    r = jnp.ones((64, 256))
    assert quantize(r, 256, "int8").bits_per_weight() == pytest.approx(8.125)
    assert quantize(r, 256, "int4").bits_per_weight() == pytest.approx(4.125)
    assert quantize(r, 256, "int3").bits_per_weight() == pytest.approx(3.125)
    assert quantize(r, 256, "fp8").bits_per_weight() == pytest.approx(8.125)
    # nbytes is true storage: packed int4 halves the qvalues bytes,
    # int3 stores 3 bytes per 8 weights
    assert quantize(r, 256, "int4").nbytes() == 64 * 128 + 4 * 64
    assert quantize(r, 256, "int3").nbytes() == 64 * 96 + 4 * 64


def test_quantize_under_eval_shape():
    """The dry-run quantizes ShapeDtypeStructs via eval_shape — packed
    formats must trace (pack is pure jnp bit-ops)."""
    out = jax.eval_shape(lambda x: quantize_int4(x, 64), jnp.zeros((32, 256)))
    assert isinstance(out, QuantizedTensor)
    assert out.qvalues.shape == (32, 128) and out.qvalues.dtype == jnp.int8
    assert out.scales.shape == (32, 4)
    out3 = jax.eval_shape(lambda x: quantize_int3(x, 64), jnp.zeros((32, 256)))
    assert out3.qvalues.shape == (32, 96) and out3.qvalues.dtype == jnp.uint8
    out8 = jax.eval_shape(lambda x: quantize_fp8(x, 64), jnp.zeros((32, 256)))
    assert out8.qvalues.dtype == jnp.float8_e4m3fn
    assert out8.qvalues.shape == (32, 256)


# ---------------------------------------------------------------------------
# unified group-size search (satellite: choose_group_size / leaf_group_size)
# ---------------------------------------------------------------------------

def test_largest_pow2_group():
    assert largest_pow2_group(2048, 256, 16) == 256
    assert largest_pow2_group(1408, 256, 16) == 128
    assert largest_pow2_group(1200, 256, 16) == 16
    assert largest_pow2_group(33, 256, 16) is None
    assert largest_pow2_group(48, 256, 32) is None  # floor respected


def test_choose_group_size_uses_shared_search():
    assert choose_group_size([2048, 5632]) == 256
    assert choose_group_size([2048, 1408]) == 128
    with pytest.raises(ValueError):
        choose_group_size([33])
    # same search, policy floor: leaf_group_size delegates to the helper
    from repro.core.policy import leaf_group_size
    assert leaf_group_size("layers/attn/wqkv", jnp.zeros((8, 1200)), 256) == 16
    assert leaf_group_size("layers/attn/wqkv", jnp.zeros((8, 1200 * 2)), 256, tp=1) == 32


# ---------------------------------------------------------------------------
# checkpoint + sharding glue
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_format_mismatch(tmp_path):
    from repro.checkpoint import ckpt

    rng = np.random.default_rng(6)
    w = jnp.asarray(rng.normal(size=(32, 128)).astype(np.float32))
    tree4 = {"attn": {"wo": quantize(w, 32, "int4")}, "norm": jnp.ones((8,))}
    ckpt.save(str(tmp_path), 1, tree4)
    back, step, _ = ckpt.restore(str(tmp_path), tree4)
    assert step == 1 and back["attn"]["wo"].fmt == "int4"
    np.testing.assert_array_equal(
        np.asarray(back["attn"]["wo"].qvalues),
        np.asarray(tree4["attn"]["wo"].qvalues),
    )
    # restoring into an int8-shaped tree must refuse, not reinterpret
    tree8 = {"attn": {"wo": quantize(w, 32, "int8")}, "norm": jnp.ones((8,))}
    with pytest.raises(ValueError, match="quantization mismatch"):
        ckpt.restore(str(tmp_path), tree8)


def test_checkpoint_refuses_old_packing_order(tmp_path):
    """A format-1 manifest packed int4 as adjacent pairs; the group-local
    unpack would misread it, so restore must refuse instead."""
    import json
    import os

    from repro.checkpoint import ckpt

    w = jnp.asarray(np.random.default_rng(7).normal(size=(8, 64)), jnp.float32)
    tree = {"wo": quantize(w, 32, "int4"), "w8": quantize(w, 32, "int8")}
    path = ckpt.save(str(tmp_path), 1, tree)
    ckpt.restore(str(tmp_path), tree)                    # current format: fine
    manifest = os.path.join(path, ckpt.MANIFEST)
    with open(manifest) as f:
        meta = json.load(f)
    meta["format"] = 1
    with open(manifest, "w") as f:
        json.dump(meta, f)
    with pytest.raises(ValueError, match="older int4/int3 storage order"):
        ckpt.restore(str(tmp_path), tree)
    ckpt.restore(str(tmp_path), {"w8": tree["w8"]})     # int8 order unchanged


def test_validate_quant_partition():
    from jax.sharding import Mesh
    from repro.core.policy import quantize_params
    from repro.dist.sharding import validate_quant_partition

    devs = np.array(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(devs, ("data", "model"))
    params = {"attn": {"wo": jnp.zeros((16, 256), jnp.float32)}}
    qp = quantize_params(params, 64, formats="int4")
    validate_quant_partition(qp, mesh, mode="serve")  # must not raise

    # a hand-built geometry that WOULD split groups: 4-way model axis over a
    # row-parallel packed contraction whose shard holds half a group
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 1, "model": 4}

    bad = {"attn": {"wo": QuantizedTensor(
        qvalues=jnp.zeros((16, 128), jnp.int8),   # packed: 256 logical
        scales=jnp.zeros((16, 2), jnp.float32),   # GS=128 -> 64 bytes/group
        group_size=128, fmt="int4")}}
    with pytest.raises(ValueError, match="splits quantization groups"):
        validate_quant_partition(bad, FakeMesh(), mode="serve")
