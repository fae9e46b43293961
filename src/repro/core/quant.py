"""Pluggable group-wise quantization formats (paper §II-B, §III-A).

The paper's scheme is symmetric group-wise PTQ with per-group fp32 scales:

  Q(r)  = Int(r / S),            S = 2 * max(|r|) / (2^b - 1)     (Eq. 1)
  r_hat = Q(r) * S                                                (Eq. 2)

with the contraction axis split into groups of ``GS`` elements (GS=256 in
the paper) and one scale per group. The paper instantiates b=8; follow-up
work (Hummingbird, arXiv 2507.03308; arXiv 2502.10659) shows decode is
weight-bandwidth-bound well below 8 bits, so this module exposes the scheme
as a :class:`QuantFormat` REGISTRY instead of hardwiring int8:

  int8   storage int8, 1 value/byte, range [-127, 127]  (paper behavior,
         bit-identical to the original ``quantize_groupwise``)
  int4   storage int8, 2 nibbles/byte packed along the last axis (the two
         halves of each group share bytes), range [-7, 7] — halves weight
         HBM traffic per decode step
  int3   storage uint8, 8 values per 3 bytes (true 3-bit packing, no pow2
         padding; three byte planes per group), range [-3, 3] —
         0.375 B/weight, below the int4 floor
  fp8    storage float8_e4m3fn, 1 value/byte, per-group scale S=absmax/448
         (the e4m3 max-finite) — int8's byte cost with a float value grid

A format is a small spec object: name, storage dtype, pack geometry
(``pack`` logical elements per ``pack_storage`` storage elements),
``quantize(r, gs) -> QuantizedTensor``, ``dequantize``, pack/unpack,
bits-per-weight, and a kernel hook name consumed by ``kernels/ops.py``.
Adding a new format (int2, mx4, ...) is one ``register_format`` call plus a
kernel-hook entry — no edits to qlinear/policy/sharding/checkpoint.

The quantized weight of a (m, n) matrix is stored like the paper's
flattened ``wq``/``ws`` arrays, kept 2-D for JAX/sharding friendliness:

  qvalues : storage dtype (m, n // pack)  -- row-major, groups along n,
                                             packed formats keep each
                                             group's bytes contiguous
  scales  : float32 (m, n // GS)          -- one scale per (row, group)

Activations are always quantized at run time to int8 along their last axis
(paper Alg. 2 lines 3/8/13/16) — sub-byte weight formats are W4A8-style.
"""

from __future__ import annotations

import dataclasses
import math
from contextlib import contextmanager
from functools import partial, reduce
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_GROUP_SIZE = 256  # paper §III-A: GS=256 divides every TinyLlama dim

__all__ = [
    "DEFAULT_GROUP_SIZE",
    "QuantFormat",
    "QuantNumericsError",
    "QuantizedTensor",
    "LayerView",
    "layer_of",
    "numerics_checks",
    "numerics_checks_enabled",
    "set_numerics_checks",
    "register_format",
    "get_format",
    "available_formats",
    "quantize",
    "quantize_groupwise",
    "quantize_int4",
    "quantize_int3",
    "quantize_fp8",
    "int4_halves",
    "pack_int4",
    "unpack_int4",
    "int3_fields",
    "pack_int3",
    "unpack_int3",
    "FP8_MAX",
    "dequantize",
    "quantize_activation",
    "choose_group_size",
    "largest_pow2_group",
    "quantization_error_stats",
]


# ---------------------------------------------------------------------------
# repro-san numerics tripwires (opt-in; analysis/sanitizer.py enables them)
# ---------------------------------------------------------------------------
# A corrupted scale (NaN/Inf, or absmax overflow from an already-broken
# weight) quantizes to garbage that then dequantizes to *finite-looking*
# noise — the second silent-corruption class next to stale KV blocks. With
# checks on, the format-dispatched quantize/dequantize entry points guard
# inputs, scales, and outputs on the HOST side only (tracers and non-float
# dtypes pass through untouched), so jitted compute paths pay nothing and
# the flag is free when off. quant stays import-free of repro.analysis —
# the sanitizer imports us, not the reverse.

_OVERFLOW_LIMIT = 1e30          # |x| beyond this at a boundary is an error
_NUMERICS = {"on": False}       # process-global, like the format registry


class QuantNumericsError(ArithmeticError):
    """NaN/Inf/overflow crossing a quantize/dequantize boundary."""


def set_numerics_checks(on: bool) -> None:
    _NUMERICS["on"] = bool(on)


def numerics_checks_enabled() -> bool:
    return _NUMERICS["on"]


@contextmanager
def numerics_checks(on: bool = True):
    """Scoped enable/disable for tests and one-off audits."""
    prev = _NUMERICS["on"]
    _NUMERICS["on"] = bool(on)
    try:
        yield
    finally:
        _NUMERICS["on"] = prev


def _numerics_guard(tag: str, x) -> None:
    if isinstance(x, jax.core.Tracer):
        return                  # jitted call sites: checks are host-only
    a = np.asarray(x)
    if not np.issubdtype(a.dtype, np.inexact):
        return
    bad = ~np.isfinite(a) | (np.abs(a) > _OVERFLOW_LIMIT)
    n = int(bad.sum())
    if n:
        idx = tuple(int(i) for i in np.argwhere(bad)[0])
        raise QuantNumericsError(
            f"repro-san[numerics]: {tag}: {n} non-finite/overflow value(s) "
            f"of {a.size}, first at index {idx} = {a[idx]!r}")


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass(frozen=True)
class QuantizedTensor:
    """A group-wise symmetric quantized tensor in some registered format.

    ``qvalues`` holds the storage array: the original shape for unpacked
    formats, last axis divided by ``format.pack`` for packed ones. ``scales``
    has the original shape with the last axis reduced by ``group_size``.
    Groups run along the LAST (logical) axis, which by convention is the
    contraction axis of the matmul that consumes this tensor (paper stores W
    row-major with groups along the column/input dim). ``fmt`` is the
    registry name carried as pytree aux data, so checkpoint/sharding paths
    (``.../qvalues``, ``.../scales``) are stable across formats.
    """

    qvalues: jax.Array  # storage dtype, shape (..., n // pack)
    scales: jax.Array   # float32, shape (..., n // group_size)
    group_size: int
    fmt: str = "int8"

    # -- pytree protocol (keyed, so checkpoint/sharding paths stay readable)
    def tree_flatten_with_keys(self):
        ga = jax.tree_util.GetAttrKey
        return (
            ((ga("qvalues"), self.qvalues), (ga("scales"), self.scales)),
            (self.group_size, self.fmt),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        qvalues, scales = children
        return cls(qvalues=qvalues, scales=scales, group_size=aux[0], fmt=aux[1])

    # -- conveniences -------------------------------------------------------
    @property
    def format(self) -> "QuantFormat":
        return get_format(self.fmt)

    @property
    def shape(self):
        """LOGICAL shape — what dequantize() returns. Packing is a storage
        detail: model code reading dims off a weight leaf (e.g. the fused
        SwiGLU split) must see the represented tensor, not the byte layout."""
        return self.logical_shape

    @property
    def storage_shape(self):
        return self.qvalues.shape

    @property
    def logical_shape(self):
        s = self.qvalues.shape
        f = self.format
        return (*s[:-1], s[-1] * f.pack // f.pack_storage)

    @property
    def num_groups(self):
        return self.scales.shape[-1]

    def astuple(self):
        return self.qvalues, self.scales

    def dequantize(self, dtype=jnp.float32) -> jax.Array:
        return dequantize(self, dtype=dtype)

    def storage_bits(self) -> int:
        """Total stored bits (qvalues + scales), format-aware."""
        return 8 * self.nbytes()

    def nbytes(self) -> int:
        def _nb(a):
            return int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize

        return _nb(self.qvalues) + _nb(self.scales)

    def bits_per_weight(self) -> float:
        """Stored bits per LOGICAL weight element, scales included
        (e.g. int8/GS=256: 8.125; packed int4/GS=256: 4.125)."""
        return self.storage_bits() / int(np.prod(self.logical_shape))


def layer_of(a: jax.Array, layer: jax.Array) -> jax.Array:
    """Row ``layer`` of a stacked array, sliced as ``lax.scan`` slices its
    inputs (the index is in range; no wrap-around of negative indices)."""
    return jax.lax.dynamic_index_in_dim(a, layer, keepdims=False,
                                        allow_negative_indices=False)


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass(frozen=True)
class LayerView:
    """One layer of a stacked QuantizedTensor (L, m, n), left in place.

    The layer walk (models/transformer.py) hands these to ``linear`` so the
    GQMM kernel reads the layer's rows straight out of the stack, chosen by
    ``layer``, instead of a copied (m, n) slice. ``shape`` is the layer's
    logical shape; ``slice()`` copies the layer out for the consumers that
    need a plain QuantizedTensor (dequantization).
    """

    stack: QuantizedTensor
    layer: jax.Array    # int32 scalar

    def tree_flatten_with_keys(self):
        ga = jax.tree_util.GetAttrKey
        return ((ga("stack"), self.stack), (ga("layer"), self.layer)), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.stack.shape[1:]

    def slice(self) -> QuantizedTensor:
        return jax.tree.map(lambda a: layer_of(a, self.layer), self.stack)

    def dequantize(self, dtype=jnp.float32) -> jax.Array:
        return self.slice().dequantize(dtype)


# ---------------------------------------------------------------------------
# format registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QuantFormat:
    """Spec for one quantization format.

    ``kernel`` names the GQMV/GQMM kernel family in ``kernels/ops.py``
    (``KERNEL_HOOKS``); quant.py stays import-free of the kernels package.
    ``pack``/``unpack_values`` convert between storage and logical values
    (identity for unpacked formats). Pack geometry is a ratio: ``pack``
    logical elements occupy ``pack_storage`` storage elements (int4: 2/1,
    int3: 8/3 — eight 3-bit fields in three bytes). Sharding relies on
    groups being whole multiples of ``pack`` so a pack unit never straddles
    groups. ``kind`` is "int" for symmetric integer grids (the ``qmax`` law
    applies) or "float" for fp8-style value grids (``qmax`` records the
    max-finite magnitude instead).
    """

    name: str
    bits: int                      # stored bits per logical weight element
    storage_dtype: Any             # dtype of QuantizedTensor.qvalues
    pack: int                      # logical elements per pack unit
    qmax: int                      # symmetric range [-qmax, qmax]
    kernel: str                    # hook name consumed by kernels/ops.py
    quantize_fn: Callable = dataclasses.field(repr=False, default=None)
    dequantize_fn: Callable = dataclasses.field(repr=False, default=None)
    pack_fn: Callable = dataclasses.field(repr=False, default=None)
    unpack_fn: Callable = dataclasses.field(repr=False, default=None)
    pack_storage: int = 1          # storage elements per pack unit
    kind: str = "int"              # "int" | "float" value grid

    def quantize(self, r: jax.Array, group_size: int) -> "QuantizedTensor":
        if _NUMERICS["on"]:
            _numerics_guard(f"quantize[{self.name}].input", r)
        qt = self.quantize_fn(r, group_size=group_size)
        if _NUMERICS["on"]:
            _numerics_guard(f"quantize[{self.name}].scales", qt.scales)
        return qt

    def dequantize(self, qt: "QuantizedTensor", dtype=jnp.float32) -> jax.Array:
        if _NUMERICS["on"]:
            _numerics_guard(f"dequantize[{self.name}].scales", qt.scales)
        out = self.dequantize_fn(qt, dtype=dtype)
        if _NUMERICS["on"]:
            _numerics_guard(f"dequantize[{self.name}].output", out)
        return out

    def unpack_values(self, qvalues: jax.Array, group_size: int) -> jax.Array:
        """Storage array -> logical values (int8 for integer formats, the
        storage dtype itself for float formats; identity when pack == 1).
        Packed layouts are group-local, so unpacking needs the group size."""
        return (qvalues if self.unpack_fn is None
                else self.unpack_fn(qvalues, group_size))

    def pack_values(self, values: jax.Array, group_size: int) -> jax.Array:
        return (values if self.pack_fn is None
                else self.pack_fn(values, group_size))


_FORMATS: dict[str, QuantFormat] = {}


def register_format(fmt: QuantFormat) -> QuantFormat:
    if fmt.name in _FORMATS:
        raise ValueError(f"quant format {fmt.name!r} already registered")
    _FORMATS[fmt.name] = fmt
    return fmt


def get_format(name: str) -> QuantFormat:
    try:
        return _FORMATS[name]
    except KeyError:
        raise ValueError(
            f"unknown quant format {name!r}; registered: {available_formats()}"
        ) from None


def available_formats() -> tuple[str, ...]:
    return tuple(sorted(_FORMATS))


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _check_group_size(n: int, group_size: int) -> None:
    if n % group_size != 0:
        raise ValueError(
            f"last axis ({n}) must be divisible by group_size ({group_size}); "
            "pick GS per paper §III-A (GS must divide every quantized dim)"
        )


def _group_quantize(r: jax.Array, group_size: int, qmax: int):
    """Shared Eq. 1 core: per-group scale S = 2*max|r|/(2*qmax+1) and
    round-clip to [-qmax, qmax]. Returns (q int8 logical values, scales)."""
    n = r.shape[-1]
    _check_group_size(n, group_size)
    g = r.reshape(*r.shape[:-1], n // group_size, group_size).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(g), axis=-1)
    scales = absmax * (2.0 / (2 * qmax + 1))
    # Avoid 0/0 for all-zero groups; scale value is irrelevant there (q==0).
    safe = jnp.where(scales > 0, scales, 1.0)
    q = jnp.clip(jnp.round(g / safe[..., None]), -qmax, qmax).astype(jnp.int8)
    return q.reshape(r.shape), scales.astype(jnp.float32)


def largest_pow2_group(n: int, preferred: int, min_gs: int) -> int | None:
    """Largest power-of-two group size <= ``preferred`` and >= ``min_gs``
    that divides ``n``; None if no such size exists.

    The single power-of-two descent shared by :func:`choose_group_size`
    (config-level, floor 32) and ``policy.leaf_group_size`` (per-leaf,
    floor 16) — the two floors differ, the search must not.
    """
    gs = preferred
    while gs >= min_gs:
        if n % gs == 0:
            return gs
        gs //= 2
    return None


def choose_group_size(
    dims: list[int], preferred: int = DEFAULT_GROUP_SIZE, min_gs: int = 32
) -> int:
    """Pick the largest GS <= preferred that divides every quantized dim.

    Paper picks 256 because every TinyLlama dim divides by it; assigned archs
    have dims like 5632/14336/10752 where this still holds, but e.g. a 1408
    FFN (deepseek-v2-lite) needs GS=128. Powers of two only, >= ``min_gs``.
    """
    gs = largest_pow2_group(reduce(math.gcd, dims), preferred, min_gs)
    if gs is None:
        raise ValueError(f"no group size in [{min_gs}, {preferred}] divides all of {dims}")
    return gs


# ---------------------------------------------------------------------------
# int8 (paper W8A8; bit-identical to the pre-registry implementation)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("group_size",))
def quantize_groupwise(r: jax.Array, group_size: int = DEFAULT_GROUP_SIZE) -> QuantizedTensor:
    """Symmetric int8 group-wise quantization along the last axis (Eq. 1).

    S = 2*max|r|/255 per group, so r/S spans [-127.5, 127.5]; rounding to
    nearest then clipping to [-127, 127] uses the full signed-int8 range the
    way the paper's Int() does, without the -128 asymmetry.
    """
    q, scales = _group_quantize(r, group_size, qmax=127)
    return QuantizedTensor(qvalues=q, scales=scales, group_size=group_size, fmt="int8")


@partial(jax.jit, static_argnames=("dtype",))
def _dequantize_int8(qt: QuantizedTensor, dtype=jnp.float32) -> jax.Array:
    """r_hat = Q(r) * S (Eq. 2)."""
    g = qt.qvalues.reshape(*qt.qvalues.shape[:-1], qt.num_groups, qt.group_size)
    out = g.astype(jnp.float32) * qt.scales[..., None]
    return out.reshape(qt.qvalues.shape).astype(dtype)


# ---------------------------------------------------------------------------
# int4, packed two nibbles per int8 byte (W4A8)
# ---------------------------------------------------------------------------
# Layouts of the sub-byte formats are chosen for the TPU kernel: there each
# unpack is shifts on int32 (the v5e vector unit has no 8-bit shifts) plus
# one lane-aligned concatenation, never a lane interleave; the XLA unpacks
# below apply the same shifts as one broadcast over a field axis. Both
# layouts are group-local, so a storage slice of whole groups is a shard of
# whole groups (dist/sharding.py).

_INT4_SHIFTS = (28, 24)      # low, high nibble: (byte << s) >>a 28


def int4_halves(p: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Packed int4 bytes -> (low, high) nibble values, sign-extended int8."""
    w = p.astype(jnp.int32)
    return tuple(((w << s) >> 28).astype(jnp.int8) for s in _INT4_SHIFTS)


def pack_int4(q: jax.Array, group_size: int) -> jax.Array:
    """int8 logical values in [-7, 7], (..., n) -> packed int8 (..., n // 2).

    Within each group of ``group_size`` elements, byte k holds element k in
    its low nibble and element k + group_size/2 in its high nibble."""
    n = q.shape[-1]
    if group_size % 2 or n % group_size:
        raise ValueError(f"int4 packing needs an even group_size dividing the "
                         f"last axis, got {q.shape} with group_size={group_size}")
    g = q.reshape(*q.shape[:-1], n // group_size, 2, group_size // 2)
    lo = jnp.bitwise_and(g[..., 0, :], 0x0F)
    hi = jnp.left_shift(g[..., 1, :], 4)            # int8 shift wraps mod 256
    return jnp.bitwise_or(lo, hi).reshape(*q.shape[:-1], n // 2)


def unpack_int4(p: jax.Array, group_size: int) -> jax.Array:
    """Packed int8 (..., n // 2) -> sign-extended int8 logical values (..., n).

    Both halves come from one broadcast shift, with no concatenation, so XLA
    fuses the whole decode into one loop — the form the xray bytes audit
    (analysis/hlo.py ``is_unpack_fusion``) normalizes to a packed read."""
    w = p.reshape(*p.shape[:-1], -1, 1, group_size // 2).astype(jnp.int32)
    shifts = jnp.asarray(_INT4_SHIFTS, jnp.int32)[:, None]
    v = ((w << shifts) >> 28).astype(jnp.int8)        # (..., G, 2, GS/2)
    return v.reshape(*p.shape[:-1], p.shape[-1] * 2)


@partial(jax.jit, static_argnames=("group_size",))
def quantize_int4(r: jax.Array, group_size: int = DEFAULT_GROUP_SIZE) -> QuantizedTensor:
    """Symmetric packed-int4 group-wise quantization (Eq. 1 with b=4).

    S = 2*max|r|/15 per group, round-clip to [-7, 7], then pack nibble pairs;
    weight bytes drop ~2x vs int8 — the off-chip-bandwidth axis the paper
    optimizes (§II-B) pushed below one byte per weight.
    """
    if group_size % 2:
        raise ValueError(f"int4 needs an even group_size, got {group_size}")
    q, scales = _group_quantize(r, group_size, qmax=7)
    return QuantizedTensor(
        qvalues=pack_int4(q, group_size), scales=scales, group_size=group_size,
        fmt="int4",
    )


@partial(jax.jit, static_argnames=("dtype",))
def _dequantize_int4(qt: QuantizedTensor, dtype=jnp.float32) -> jax.Array:
    vals = unpack_int4(qt.qvalues, qt.group_size)
    g = vals.reshape(*vals.shape[:-1], qt.num_groups, qt.group_size)
    out = g.astype(jnp.float32) * qt.scales[..., None]
    return out.reshape(vals.shape).astype(dtype)


# ---------------------------------------------------------------------------
# int3, true 3-bit packing: 8 values per 3 bytes (W3A8)
# ---------------------------------------------------------------------------
# Pow2-padding 3-bit fields to nibbles would store int3 at int4's byte cost
# and erase the whole point; instead eight 3-bit two's-complement fields
# share one 24-bit word (3 uint8 storage bytes). Each group of GS elements
# is stored as three byte PLANES of w = GS/8 bytes: the 24-bit word k is
# plane0[k] | plane1[k] << 8 | plane2[k] << 16, and its field c (bits
# 3c..3c+2) is group element c*w + k. Unpacking is then shifts on whole
# planes and one concatenation of the eight field planes.

_INT3_SHIFTS = tuple(29 - 3 * c for c in range(8))   # field c: (u << s) >>a 29


def int3_fields(b0: jax.Array, b1: jax.Array, b2: jax.Array) -> list[jax.Array]:
    """Three uint8 byte planes -> the eight 3-bit field planes, sign-extended
    int8: the TPU kernel's decode. Each field is cut from a 16-bit word,
    fields 0-4 from ``b0 | b1 << 8`` and fields 5-7 from ``b1 | b2 << 8``:
    on a TPU v5e the kernel compiler drops the bits of ``b2 << 16`` (the
    24-bit word of :func:`unpack_int3`), which decoded fields 5-7 wrong on
    the chip while interpret mode was exact."""
    b0, b1, b2 = (b.astype(jnp.int32) for b in (b0, b1, b2))
    lo, hi = b0 | (b1 << 8), b1 | (b2 << 8)
    return [((lo << (29 - 3 * c)) >> 29).astype(jnp.int8) for c in range(5)] + [
        ((hi << (37 - 3 * c)) >> 29).astype(jnp.int8) for c in range(5, 8)]


def pack_int3(q: jax.Array, group_size: int) -> jax.Array:
    """int8 logical values in [-3, 3], (..., n) -> packed uint8 (..., n//8*3)
    in the per-group plane layout above."""
    n = q.shape[-1]
    if group_size % 8 or n % group_size:
        raise ValueError(f"int3 packing needs a group_size divisible by 8 that "
                         f"divides the last axis, got {q.shape} with "
                         f"group_size={group_size}")
    w = group_size // 8
    u = jnp.bitwise_and(q.astype(jnp.int32), 0x7)
    u = u.reshape(*q.shape[:-1], n // group_size, 8, w)
    word = jnp.sum(jnp.left_shift(u, jnp.arange(8, dtype=jnp.int32)[:, None] * 3),
                   axis=-2)                                      # (..., G, w)
    planes = jnp.stack([word & 0xFF, (word >> 8) & 0xFF, (word >> 16) & 0xFF],
                       axis=-2)                                  # (..., G, 3, w)
    return planes.astype(jnp.uint8).reshape(*q.shape[:-1], n // 8 * 3)


def unpack_int3(p: jax.Array, group_size: int) -> jax.Array:
    """Packed uint8 (..., n//8*3) -> sign-extended int8 logical values (..., n)."""
    w = group_size // 8
    if group_size % 8 or p.shape[-1] % (3 * w):
        raise ValueError(f"int3 storage last axis must hold whole groups of "
                         f"{3 * w} bytes, got {p.shape}")
    g = p.reshape(*p.shape[:-1], -1, 3, w).astype(jnp.int32)
    u = (g[..., 0, :] | (g[..., 1, :] << 8) | (g[..., 2, :] << 16))[..., None, :]
    # all eight fields from one broadcast shift (one XLA loop fusion, as in
    # unpack_int4)
    shifts = jnp.asarray(_INT3_SHIFTS, jnp.int32)[:, None]
    v = ((u << shifts) >> 29).astype(jnp.int8)        # (..., G, 8, w)
    return v.reshape(*p.shape[:-1], p.shape[-1] // 3 * 8)


@partial(jax.jit, static_argnames=("group_size",))
def quantize_int3(r: jax.Array, group_size: int = DEFAULT_GROUP_SIZE) -> QuantizedTensor:
    """Symmetric packed-int3 group-wise quantization (Eq. 1 with b=3).

    S = 2*max|r|/7 per group, round-clip to [-3, 3], pack 8-per-3-bytes:
    0.375 B/weight, ~2.67x less weight HBM per decode step than int8 and
    ~1.33x less than packed int4."""
    if group_size % 8:
        raise ValueError(f"int3 needs a group_size divisible by 8, got {group_size}")
    q, scales = _group_quantize(r, group_size, qmax=3)
    return QuantizedTensor(
        qvalues=pack_int3(q, group_size), scales=scales, group_size=group_size,
        fmt="int3",
    )


@partial(jax.jit, static_argnames=("dtype",))
def _dequantize_int3(qt: QuantizedTensor, dtype=jnp.float32) -> jax.Array:
    vals = unpack_int3(qt.qvalues, qt.group_size)
    g = vals.reshape(*vals.shape[:-1], qt.num_groups, qt.group_size)
    out = g.astype(jnp.float32) * qt.scales[..., None]
    return out.reshape(vals.shape).astype(dtype)


# ---------------------------------------------------------------------------
# fp8 (e4m3, per-group scale): a float value grid at int8's byte cost
# ---------------------------------------------------------------------------

FP8_MAX = 448.0      # float8_e4m3fn max finite (no inf encoding in e4m3fn)


@partial(jax.jit, static_argnames=("group_size",))
def quantize_fp8(r: jax.Array, group_size: int = DEFAULT_GROUP_SIZE) -> QuantizedTensor:
    """Group-wise fp8 (e4m3): S = max|r|/448 maps each group onto the full
    e4m3 exponent range; the storage cast rounds-to-nearest onto the float
    grid. Same byte cost as int8 but a relative-error profile that follows
    magnitude — the frontier choice for outlier-heavy layer classes."""
    n = r.shape[-1]
    _check_group_size(n, group_size)
    g = r.reshape(*r.shape[:-1], n // group_size, group_size).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(g), axis=-1)
    scales = absmax * (1.0 / FP8_MAX)
    safe = jnp.where(scales > 0, scales, 1.0)
    q = (g / safe[..., None]).astype(jnp.float8_e4m3fn)
    return QuantizedTensor(
        qvalues=q.reshape(r.shape), scales=scales.astype(jnp.float32),
        group_size=group_size, fmt="fp8",
    )


@partial(jax.jit, static_argnames=("dtype",))
def _dequantize_fp8(qt: QuantizedTensor, dtype=jnp.float32) -> jax.Array:
    g = qt.qvalues.reshape(*qt.qvalues.shape[:-1], qt.num_groups, qt.group_size)
    out = g.astype(jnp.float32) * qt.scales[..., None]
    return out.reshape(qt.qvalues.shape).astype(dtype)


register_format(QuantFormat(
    name="int8", bits=8, storage_dtype=jnp.int8, pack=1, qmax=127,
    kernel="gqmv_int8",
    quantize_fn=quantize_groupwise, dequantize_fn=_dequantize_int8,
))

register_format(QuantFormat(
    name="int4", bits=4, storage_dtype=jnp.int8, pack=2, qmax=7,
    kernel="gqmv_int4",
    quantize_fn=quantize_int4, dequantize_fn=_dequantize_int4,
    pack_fn=pack_int4, unpack_fn=unpack_int4,
))

register_format(QuantFormat(
    name="int3", bits=3, storage_dtype=jnp.uint8, pack=8, pack_storage=3,
    qmax=3, kernel="gqmv_int3",
    quantize_fn=quantize_int3, dequantize_fn=_dequantize_int3,
    pack_fn=pack_int3, unpack_fn=unpack_int3,
))

register_format(QuantFormat(
    name="fp8", bits=8, storage_dtype=jnp.float8_e4m3fn, pack=1,
    qmax=int(FP8_MAX), kernel="gqmv_fp8", kind="float",
    quantize_fn=quantize_fp8, dequantize_fn=_dequantize_fp8,
))


# ---------------------------------------------------------------------------
# generic entry points (format-dispatched)
# ---------------------------------------------------------------------------

def quantize(
    r: jax.Array, group_size: int = DEFAULT_GROUP_SIZE, fmt: str = "int8"
) -> QuantizedTensor:
    """Quantize ``r`` group-wise in registry format ``fmt``."""
    return get_format(fmt).quantize(r, group_size)


def dequantize(qt: QuantizedTensor, dtype=jnp.float32) -> jax.Array:
    """r_hat = Q(r) * S (Eq. 2), dispatched on ``qt.fmt``."""
    return qt.format.dequantize(qt, dtype=dtype)


def quantize_activation(x: jax.Array, group_size: int = DEFAULT_GROUP_SIZE) -> QuantizedTensor:
    """Run-time activation quantization (paper Alg. 2 lines 3/8/13/16).

    Always int8, regardless of the weight format: sub-byte WEIGHTS are what
    cut decode HBM traffic (weights dominate, §II-B); activations are tiny
    and re-quantized per step, so W4A8 keeps the accumulation exact in the
    same int8*int8->int32 datapath.
    """
    return quantize_groupwise(x, group_size=group_size)


def quantization_error_stats(
    r: jax.Array, group_size: int = DEFAULT_GROUP_SIZE, fmt: str = "int8"
) -> dict[str, float]:
    """Per-element |r_hat - r| statistics (paper Table IV, Eq. 3)."""
    qt = quantize(r, group_size, fmt)
    err = jnp.abs(qt.dequantize() - r.astype(jnp.float32))
    denom = jnp.where(jnp.abs(r) > 0, jnp.abs(r), 1.0)
    rel = err / denom
    return {
        "max": float(jnp.max(err)),
        "min": float(jnp.min(err)),
        "mean": float(jnp.mean(err)),
        "std": float(jnp.std(err)),
        "rel_mean_pct": float(100.0 * jnp.mean(rel)),
        "rel_std_pct": float(100.0 * jnp.std(rel)),
    }
