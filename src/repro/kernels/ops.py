"""Public jit'd entry points for quantized matmul kernels.

Dispatch is two-dimensional:

``impl`` (backend):
  'pallas'    pl.pallas_call, compiled for TPU (Mosaic)
  'interpret' same kernel body, Pallas interpreter on CPU (validation)
  'xla'       pure-XLA int8 dot_general path, bit-identical math; used by
              the distributed models and the dry-run, where the CPU backend
              cannot compile Mosaic kernels (see DESIGN.md §2)
  'auto'      pallas on TPU, xla elsewhere

kernel hook (weight format): every :class:`~repro.core.quant.QuantFormat`
names a hook (``fmt.kernel``); ``KERNEL_HOOKS`` maps it to the XLA oracles
for both the matrix-vector (GQMV) and batched (GQMM) shapes and to the
decode stage of the one Pallas kernel (GQMV runs as a one-row GQMM).
Registering a new weight format therefore means one ``QuantFormat`` entry
in core/quant.py, one ``KernelHook`` row here and one decode stage in
kernels/gqmv.py — qlinear/policy/engine code never changes (DESIGN.md §8).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax

from repro.core.quant import QuantizedTensor, get_format, quantize_activation
from repro.kernels import gqmv as _pallas
from repro.kernels import paged_attn as _paged
from repro.kernels import ref as _ref


def _default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _resolve(impl: str) -> str:
    return _default_impl() if impl == "auto" else impl


@dataclasses.dataclass(frozen=True)
class KernelHook:
    """GQMV/GQMM implementations for one weight storage format. Both XLA
    callables share the signature (wq, ws, xq, xs, *, group_size); ``wq``
    is the format's STORAGE array (packed for sub-byte formats),
    activations are always int8 (W{b}A8). ``pallas_fmt`` names the decode
    stage of the one Pallas kernel (kernels/gqmv.py ``_GROUP_WEIGHTS``)."""

    gqmv_xla: Callable
    gqmm_xla: Callable
    pallas_fmt: str


KERNEL_HOOKS: dict[str, KernelHook] = {
    "gqmv_int8": KernelHook(_ref.gqmv_ref, _ref.gqmm_ref, "int8"),
    "gqmv_int4": KernelHook(_ref.gqmv_int4_ref, _ref.gqmm_int4_ref, "int4"),
    "gqmv_int3": KernelHook(_ref.gqmv_int3_ref, _ref.gqmm_int3_ref, "int3"),
    "gqmv_fp8": KernelHook(_ref.gqmv_fp8_ref, _ref.gqmm_fp8_ref, "fp8"),
}


def _hook(kernel: str) -> KernelHook:
    try:
        return KERNEL_HOOKS[kernel]
    except KeyError:
        raise ValueError(
            f"unknown kernel hook {kernel!r} (a QuantFormat named a hook with "
            f"no KERNEL_HOOKS row); registered: {sorted(KERNEL_HOOKS)}"
        ) from None


@partial(jax.jit, static_argnames=("group_size", "impl", "kernel"))
def gqmv(
    wq: jax.Array,
    ws: jax.Array,
    xq: jax.Array,
    xs: jax.Array,
    *,
    group_size: int,
    impl: str = "auto",
    kernel: str = "gqmv_int8",
) -> jax.Array:
    """out (m,) = groupwise-quantized W (m,n) @ x (n,). Paper Alg. 1/3.

    ``wq`` is the storage array of the format that owns ``kernel`` (plain
    int8 rows for the default hook, packed nibbles for ``gqmv_int4``)."""
    impl = _resolve(impl)
    hook = _hook(kernel)
    if impl == "xla":
        return hook.gqmv_xla(wq, ws, xq, xs, group_size=group_size)
    return _pallas.gqmv_pallas(
        wq, ws, xq, xs, group_size=group_size, fmt=hook.pallas_fmt,
        interpret=(impl == "interpret"))


@partial(jax.jit, static_argnames=("group_size", "impl", "kernel"))
def gqmm(
    wq: jax.Array,
    ws: jax.Array,
    xq: jax.Array,
    xs: jax.Array,
    *,
    group_size: int,
    impl: str = "auto",
    kernel: str = "gqmv_int8",
) -> jax.Array:
    """out (b, m) = batched GQMV; b = tokens for prefill / batch for decode."""
    impl = _resolve(impl)
    hook = _hook(kernel)
    if impl == "xla":
        return hook.gqmm_xla(wq, ws, xq, xs, group_size=group_size)
    return _pallas.gqmm_pallas(
        wq, ws, xq, xs, group_size=group_size, fmt=hook.pallas_fmt,
        interpret=(impl == "interpret"))


def paged_attention(
    q: jax.Array,            # (b, KV, G, hd)
    k_pages: jax.Array,      # (NB, BS, KV, hd) one layer's block pool
    v_pages: jax.Array,
    block_table: jax.Array,  # (b, MB) int32
    pos: jax.Array,          # (b,) int32
    k_new: jax.Array,        # (b, KV, hd) current-token row (uncommitted)
    v_new: jax.Array,
    mask: jax.Array,         # (b, MB * BS) additive decode mask
    *,
    scale: float,
    softcap: float | None = None,
    k_scales: jax.Array | None = None,   # (NB, BS, KV) per-row dequant scales
    v_scales: jax.Array | None = None,
    impl: str = "auto",
) -> jax.Array:
    """One paged decode-attention step -> ctx (b, KV*G*hd).

    Same backend dispatch as gqmv/gqmm: the XLA path gathers the virtual
    sequence through the block table (bit-exact vs the contiguous deferred
    decode on identity tables); the Pallas kernel streams only the live
    physical blocks HBM->VMEM via scalar-prefetch index maps: each row
    copies and computes in ceil((pos+1) / 128) grid steps of 128 tokens
    (at 8-token blocks), and its table entries past the block that holds
    ``pos`` are never read. With
    ``k_scales``/``v_scales`` the pool holds quantized rows (int8/fp8) and
    dequantization is fused into the attention read — the streamed KV bytes
    stay at storage width."""
    impl = _resolve(impl)
    if impl == "xla":
        return _ref.paged_attention_ref(
            q, k_pages, v_pages, block_table, pos, k_new, v_new, mask,
            scale=scale, softcap=softcap, k_scales=k_scales, v_scales=v_scales,
        )
    return _paged.paged_attention_pallas(
        q, k_pages, v_pages, block_table, pos, k_new, v_new, mask,
        scale=scale, softcap=softcap, k_scales=k_scales, v_scales=v_scales,
        interpret=(impl == "interpret"),
    )


def paged_verify(
    q: jax.Array,            # (b, S, KV, G, hd) verify-chunk queries
    k_pages: jax.Array,      # (NB, BS, KV, hd)
    v_pages: jax.Array,
    block_table: jax.Array,  # (b, MB) int32
    pos: jax.Array,          # (b,) int32 chunk start positions
    k_new: jax.Array,        # (b, S, KV, hd) the chunk's own K/V rows
    v_new: jax.Array,
    mask: jax.Array,         # (b, S, MB * BS) additive verify mask
    *,
    scale: float,
    softcap: float | None = None,
    impl: str = "auto",
) -> jax.Array:
    """k-token speculative-verify attention over the block pool -> ctx
    (b, S, KV*G*hd). The multi-query sibling of :func:`paged_attention`.

    The verify shape (a handful of query rows against a long virtual
    sequence) is served by the XLA gather path on every backend for now:
    the m<=8 chunk makes attention a tiny fraction of the verify step —
    the step's cost is the weight stream, which the GQMM kernels already
    amortize over the chunk — so a dedicated Mosaic kernel is future work,
    not a bandwidth lever (DESIGN.md §10)."""
    del impl  # one implementation today; signature mirrors paged_attention
    return _ref.paged_verify_ref(
        q, k_pages, v_pages, block_table, pos, k_new, v_new, mask,
        scale=scale, softcap=softcap,
    )


def quantized_matmul(
    x: jax.Array, w: QuantizedTensor, *, impl: str = "auto"
) -> jax.Array:
    """y = x @ dequant(w).T with run-time int8 activation quantization.

    ``x`` is float (..., n); weights are a QuantizedTensor (m, n logical)
    in ANY registered format with groups along n. Returns float32 (..., m).
    This is the composable entry point the model layers use (paper Alg. 2:
    "RMSNorm and quantize x; kernel1(...)"); the format's kernel hook picks
    the matching GQMV/GQMM pair.
    """
    fmt = get_format(w.fmt)
    xq = quantize_activation(x, group_size=w.group_size)
    lead = x.shape[:-1]
    if lead == ():
        out = gqmv(w.qvalues, w.scales, xq.qvalues, xq.scales,
                   group_size=w.group_size, impl=impl, kernel=fmt.kernel)
        return out
    flat_q = xq.qvalues.reshape(-1, x.shape[-1])
    flat_s = xq.scales.reshape(-1, xq.scales.shape[-1])
    out = gqmm(w.qvalues, w.scales, flat_q, flat_s,
               group_size=w.group_size, impl=impl, kernel=fmt.kernel)
    return out.reshape(*lead, w.shape[0])
