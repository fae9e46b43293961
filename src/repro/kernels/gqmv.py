"""Pallas TPU kernel for group-wise quantized matrix multiply (GQMM), with
the matrix-vector product (GQMV) as its one-row case.

TPU adaptation of the paper's 3-stage pipelined FPGA accelerator (§IV):

  FPGA stage            TPU analogue (this file)
  -------------------   ----------------------------------------------------
  pre-processing:       Pallas grid pipelining: each (bm, n) block of whole
  DDR->BRAM streaming   weight rows is DMA'd HBM->VMEM double-buffered while
  of wq/ws blocks       the previous block computes  (paper C3, Fig. 2)
  dot-product: SIMD     one int8 x int8 -> int32 MXU dot per quantization
  mul + depth-8 adder   group, (bb, GS) x (bm, GS)^T
  tree per group        (the MXU reduction replaces the adder tree)
  accumulate: fp32      group_sums * (xs * ws) in fp32, added group by group
  scale + writeback     in order, then one write of the (bb, bm) out block

Progressive INT8->INT16->INT32 widening from the paper is collapsed to
int8 MACs with native int32 accumulation (FPGA DSP packing artifact; see
DESIGN.md §2). Group size GS=256 = 2x128 TPU lanes, so every group slice
is lane-aligned.

Block layout (what the TPU compiler accepts):

  * a block holds WHOLE rows of ``wq`` (the full contraction axis), so the
    per-group scales ``ws`` (bm, n/GS) and ``xs`` (bb, n/GS) are blocks of
    the full group axis and every group index is static;
  * ``ws`` is transposed in VMEM to (n/GS, bm), putting the output rows on
    the lane axis, as in the (bb, bm) output block;
  * the grid runs row blocks of ``wq`` outermost, so each weight byte is
    streamed once per call; activation rows beyond one block are padded up
    to a whole block.

Four weight formats share the dot-product and accumulate stages (see
core/quant.py registry); ``_GROUP_WEIGHTS`` holds each one's decode stage:

  int8  wq streamed as int8 blocks (the paper's layout)
  int4  wq streamed PACKED (two nibbles per byte, half the HBM traffic of
        int8 — the paper's §II-B bandwidth lever pushed below one byte) and
        sign-extended to int8 in VMEM, one group at a time, just before the
        group dot. Only the DMA'd bytes shrink.
  int3  wq streamed as true 3-bit packing (8 values per 3 uint8 bytes,
        0.375 B/weight) and sign-extended in VMEM — the sub-int4 point of
        the same streaming argument.
  fp8   wq streamed as float8_e4m3fn bytes; the group dot runs on bf16
        operands with f32 accumulation (e4m3 and int8 values are exact in
        bf16).

Validated on CPU with ``interpret=True`` against ``ref.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quant import int3_fields, int4_halves

DEFAULT_BM = 256   # output rows per block (a multiple of the 128 lanes)
DEFAULT_BB = 128   # activation rows per block

_NT = (((1,), (1,)), ((), ()))   # (bb, GS) x (bm, GS) -> (bb, bm)


def _check_divides(dim: int, blk: int, axis: str) -> int:
    """Validate a (possibly caller-supplied) block size: the grid is built
    as ``dim // blk``, so a non-dividing block would silently drop the tail
    rows."""
    if dim % blk:
        raise ValueError(
            f"block {blk} invalid for {axis}={dim}: the grid would drop the tail")
    return blk


def _row_block(m: int) -> int:
    """Output rows per block: a multiple of 128 (the output block's lane
    axis) when m has one, else all of m (a full-dim block is always legal)."""
    return next((bm for bm in (DEFAULT_BM, 128) if m % bm == 0), m)


# ---------------------------------------------------------------------------
# decode stage: storage block -> group g's (bm, GS) weight values in VMEM
# ---------------------------------------------------------------------------

def _plain_group(w_ref, g: int, gs: int):
    return w_ref[:, g * gs:(g + 1) * gs]


def _int4_group(w_ref, g: int, gs: int):
    h = gs // 2                       # group g's bytes hold both halves
    return jnp.concatenate(int4_halves(w_ref[:, g * h:(g + 1) * h]), axis=-1)


def _int3_group(w_ref, g: int, gs: int):
    w = gs // 8                       # three byte planes of w bytes per group
    o = 3 * w * g
    planes = (w_ref[:, o:o + w], w_ref[:, o + w:o + 2 * w],
              w_ref[:, o + 2 * w:o + 3 * w])
    return jnp.concatenate(int3_fields(*planes), axis=-1)


_GROUP_WEIGHTS = {"int8": _plain_group, "fp8": _plain_group,
                  "int4": _int4_group, "int3": _int3_group}


def _gqmm_kernel(xq_ref, xs_ref, wq_ref, ws_ref, out_ref, *, group_size: int,
                 group_weights):
    xs = xs_ref[...]                  # (bb, G)
    ws = ws_ref[...].T                # (G, bm): rows on lanes, like out
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for g in range(xs.shape[1]):
        w = group_weights(wq_ref, g, group_size)                     # (bm, GS)
        x = xq_ref[:, g * group_size:(g + 1) * group_size]           # (bb, GS)
        if jnp.issubdtype(w.dtype, jnp.integer):
            sums = jax.lax.dot_general(x, w, _NT,
                                       preferred_element_type=jnp.int32)
        else:
            sums = jax.lax.dot_general(x.astype(jnp.bfloat16),
                                       w.astype(jnp.bfloat16), _NT,
                                       preferred_element_type=jnp.float32)
        acc = acc + sums.astype(jnp.float32) * (xs[:, g:g + 1] * ws[g:g + 1, :])
    out_ref[...] = acc


def gqmm_pallas(
    wq: jax.Array,   # storage (m, n // pack * pack_storage)
    ws: jax.Array,   # f32 (m, n // GS)
    xq: jax.Array,   # int8 (b, n)
    xs: jax.Array,   # f32 (b, n // GS)
    *,
    group_size: int,
    fmt: str = "int8",
    block_m: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """out (b, m) f32 = X(q) (b, n) @ W(q)^T for weights stored in registry
    format ``fmt``; b = tokens for prefill, slots for batched decode."""
    m = wq.shape[0]
    b, n = xq.shape
    ng = n // group_size
    bm = _check_divides(m, block_m or _row_block(m), "m")
    # a block of every row when they fit (full-dim blocks are legal at any
    # b), else DEFAULT_BB-row blocks with the last one zero-padded
    bb = min(b, DEFAULT_BB)
    row_blocks = -(-b // bb)
    bp = row_blocks * bb
    if bp != b:
        xq = jnp.pad(xq, ((0, bp - b), (0, 0)))
        xs = jnp.pad(xs, ((0, bp - b), (0, 0)))
    grid = (m // bm, row_blocks)      # weight row blocks outermost

    out = pl.pallas_call(
        functools.partial(_gqmm_kernel, group_size=group_size,
                          group_weights=_GROUP_WEIGHTS[fmt]),
        grid=grid,
        name=f"gqmm_{fmt}",
        in_specs=[
            pl.BlockSpec((bb, n), lambda i, j: (j, 0)),               # xq
            pl.BlockSpec((bb, ng), lambda i, j: (j, 0)),              # xs
            pl.BlockSpec((bm, wq.shape[1]), lambda i, j: (i, 0)),     # wq
            pl.BlockSpec((bm, ng), lambda i, j: (i, 0)),              # ws
        ],
        out_specs=pl.BlockSpec((bb, bm), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((bp, m), jnp.float32),
        interpret=interpret,
    )(xq, xs, wq, ws)
    return out[:b]


def gqmv_pallas(
    wq: jax.Array,   # storage (m, n // pack * pack_storage)
    ws: jax.Array,   # f32 (m, n // GS)
    xq: jax.Array,   # int8 (n,)
    xs: jax.Array,   # f32 (n // GS,)
    *,
    group_size: int,
    fmt: str = "int8",
    block_m: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """out (m,) = W(q) (m, n) @ x(q) (n,) -- the paper's batch-1 core, run
    as a one-row GQMM."""
    return gqmm_pallas(wq, ws, xq[None], xs[None], group_size=group_size,
                       fmt=fmt, block_m=block_m, interpret=interpret)[0]
