"""Pallas TPU paged-attention decode kernel (block-table gather).

The paged KV pool keeps one layer's cache as (NB, BS, KV, hd) fixed-size
blocks; each decode row owns a BLOCK TABLE of physical block ids. The XLA
oracle (kernels/ref.py::paged_attention_ref) materializes the gathered
(b, T, KV, hd) virtual sequence; this kernel never does — the physical
block ids ride in as a scalar-prefetch argument and the BlockSpec index
maps DMA each row's *live* physical K/V blocks HBM->VMEM directly, so HBM
traffic is the live blocks only (the same streaming argument as
kernels/flash_attn.py, applied to the paged layout).

Grid: (b, ceil(MB / P)) — one program per (row, compute block), a compute
block being P pool blocks, P = min(MB, max(1, 128 // BS)): 128 tokens per
step at BS = 8, so a step's fixed cost is paid per 128 tokens. The last
compute block of a row may be ragged (MB need not divide by P). A row's
live compute blocks are those that start at or before its ``pos``; its
other steps issue no copy and no compute.

DMA: the pool enters as P operands, operand p holding pool block
``block_table[row, c*P + p]`` of compute block c, one (BS, KV, hd) block of
the pool's two minor dims whole (the TPU compiler accepts it for any KV and
hd, where a hand-sliced copy out of HBM needs a lane dim of 128, which
hd = 64 and the (NB, BS, KV) scales lack). The wrapper gathers, through
live table entries only, the block each operand holds at each grid step,
and the index maps read one scalar each:
- a dead step keeps the row's last live compute block, and an unchanged
  block index copies nothing;
- an operand past the block that holds ``pos`` takes the block it will hold
  at the row's next step, or the last live block in a row of one compute
  block.
A row walks its compute blocks last to first (the online softmax takes any
order), dead ones first: the pipeline fetches the next step's blocks while
this step computes, so the next row's first live blocks arrive during the
last live step of this row.

Compute: online softmax per KV head over the (P*BS, hd) tile, state in VMEM
scratch across the row's steps. The current token's K/V (not yet committed
to the pool) is handled in-kernel: its score overwrites the virtual column
``pos`` and its value row replaces the stale pool row, so recycled/sink
blocks never leak. The additive mask (window, future) is applied in
(1, P*BS) slices; columns past ``pos`` are also removed by select and their
value rows zeroed, because an operand past the block that holds ``pos``
holds another block. Validated in interpret mode against the oracle
(tests/test_paged.py, tests/test_kvquant.py) and compiled for a TPU v5e in
tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# f32 operands at f32 accuracy: the MXU's default is one bf16 pass, which
# would round the softmax weights and dequantized rows to 8 mantissa bits
_F32 = jax.lax.Precision.HIGHEST
# tokens per compute block
_TOKENS_PER_STEP = 128


def _compute_block(i, ncb, pos, chunk):
    """Grid step ``i`` of a row -> (compute block it works on, live?).
    Steps run the row's compute blocks last to first; a dead step keeps the
    row's last live compute block."""
    last = pos // chunk
    c = ncb - 1 - i
    return jnp.minimum(c, last), c <= last


def _paged_kernel(steps_ref, pos_ref, q_ref, kn_ref, vn_ref, mask_ref, *refs,
                  scale: float, softcap: float | None, bs: int, nblk: int,
                  quant: bool):
    """One (row, compute block) grid step over every KV head.

    With ``quant`` the DMA'd K/V blocks are int8/fp8 storage rows plus
    per-row f32 scales; dequantization happens here in VMEM, so the HBM
    stream stays at storage width (the cache-side twin of the GQMM
    unpack-in-VMEM argument)."""
    pools = [refs[n * nblk:(n + 1) * nblk] for n in range(4 if quant else 2)]
    o_ref, m_scr, l_scr, acc_scr = refs[len(pools) * nblk:]
    i, ncb = pl.program_id(1), pl.num_programs(1)
    chunk = nblk * bs
    pos = pos_ref[pl.program_id(0)]
    c, live = _compute_block(i, ncb, pos, chunk)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(blocks, h, scales=None):
        # (P*BS, hd) f32 rows of KV head h, dequantized
        rows = [ref[0, :, h, :].astype(jnp.float32) for ref in blocks]
        if scales is not None:
            rows = [x * s[0][:, h:h + 1] for x, s in zip(rows, scales)]
        return jnp.concatenate(rows, axis=0)

    @pl.when(live)
    def _attend():
        # current token: its pool slot is committed AFTER attention, so the
        # row at ``pos`` holds stale data — substitute the fresh K score /
        # V row; columns past it are dead (masked, value rows zeroed)
        col = pos - c * chunk                  # >= chunk: not in this block
        mask = mask_ref[0, 0].astype(jnp.float32)          # (1, chunk)
        for h in range(q_ref.shape[1]):
            q = q_ref[0, h].astype(jnp.float32)            # (g, hd)
            k = tile(pools[0], h, pools[2] if quant else None)
            v = tile(pools[1], h, pools[3] if quant else None)
            k_new = kn_ref[0, h:h + 1].astype(jnp.float32)  # (1, hd)
            v_new = vn_ref[0, h:h + 1].astype(jnp.float32)

            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    precision=_F32,
                                    preferred_element_type=jnp.float32)
            cur = jnp.sum(q * k_new, axis=-1, keepdims=True)         # (g, 1)
            sc = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(sc == col, cur, s)
            s = s * scale
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(sc <= col, s + mask, NEG_INF)
            vr = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(vr == col, v_new, jnp.where(vr < col, v, 0.0))

            m_prev, l_prev = m_scr[h], l_scr[h]            # (g, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                         # (g, chunk)
            l_scr[h] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), precision=_F32,
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(i == ncb - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention_pallas(
    q: jax.Array,            # (b, KV, G, hd)
    k_pages: jax.Array,      # (NB, BS, KV, hd)
    v_pages: jax.Array,
    block_table: jax.Array,  # (b, MB) int32
    pos: jax.Array,          # (b,) int32
    k_new: jax.Array,        # (b, KV, hd)
    v_new: jax.Array,
    mask: jax.Array,         # (b, MB * BS) additive float32
    *,
    scale: float,
    softcap: float | None = None,
    k_scales: jax.Array | None = None,   # (NB, BS, KV) quantized-pool scales
    v_scales: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, kv, g, hd = q.shape
    bs = k_pages.shape[1]
    mb = block_table.shape[1]
    nblk = min(mb, max(1, _TOKENS_PER_STEP // bs))   # P
    ncb = pl.cdiv(mb, nblk)
    chunk = nblk * bs
    pos = pos.astype(jnp.int32)
    # one (1, P*BS) mask row per compute block: the row axis is a full dim;
    # a ragged last block is padded with masked columns
    if ncb * chunk != mb * bs:
        mask = jnp.pad(mask, ((0, 0), (0, ncb * chunk - mb * bs)),
                       constant_values=NEG_INF)
    mask = mask.reshape(b, ncb, 1, chunk)

    # (row, grid step, operand) -> physical block, gathered through live
    # table entries only
    c, _ = _compute_block(jnp.arange(ncb)[None, :, None], ncb,
                          pos[:, None, None], chunk)
    last = (pos // bs)[:, None, None]          # block that holds pos
    j = c * nblk + jnp.arange(nblk)
    # past pos: the block this operand holds at the row's next step
    j = jnp.where(j <= last, j, jnp.where(c > 0, j - nblk, last))
    steps = jnp.take_along_axis(block_table.astype(jnp.int32),
                                j.reshape(b, -1), axis=1).reshape(-1)

    # index maps receive grid indices first, then the scalar refs
    def pool_index(p, rank):
        def index(ib, i, st, ps):
            return (st[(ib * ncb + i) * nblk + p],) + (0,) * (rank - 1)
        return index

    def row_index(ib, i, st, ps):
        return (ib, 0, 0)

    def mask_index(ib, i, st, ps):
        return (ib, _compute_block(i, ncb, ps[ib], chunk)[0], 0, 0)

    operands = [k_pages, v_pages]
    shapes = [(1, bs, kv, hd)] * 2
    if k_scales is not None:
        # per-row f32 scales ride the same block ids as their rows
        operands += [k_scales, v_scales]
        shapes += [(1, bs, kv)] * 2
    in_specs = [
        pl.BlockSpec((1, kv, g, hd), lambda ib, i, st, ps: (ib, 0, 0, 0)),
        pl.BlockSpec((1, kv, hd), row_index),
        pl.BlockSpec((1, kv, hd), row_index),
        pl.BlockSpec((1, 1, 1, chunk), mask_index),
    ]
    in_specs += [pl.BlockSpec(shape, pool_index(p, len(shape)))
                 for shape in shapes for p in range(nblk)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # steps, pos
        grid=(b, ncb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kv, g, hd), lambda ib, i, st, ps: (ib, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, g, 1), jnp.float32),     # running max
            pltpu.VMEM((kv, g, 1), jnp.float32),     # running denominator
            pltpu.VMEM((kv, g, hd), jnp.float32),    # output accumulator
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=scale, softcap=softcap,
                               bs=bs, nblk=nblk, quant=k_scales is not None)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="paged_attention",
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
    )(steps, pos, q, k_new, v_new, mask,
      *(x for x in operands for _ in range(nblk)))
    return out.reshape(b, kv * g * hd)
