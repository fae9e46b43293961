"""Pallas TPU paged-attention decode kernel (block-table gather).

The paged KV pool keeps one layer's cache as (NB, BS, KV, hd) fixed-size
blocks; each decode row owns a BLOCK TABLE of physical block ids. The XLA
oracle (kernels/ref.py::paged_attention_ref) materializes the gathered
(b, T, KV, hd) virtual sequence; this kernel never does — the block table
rides in as a scalar-prefetch argument and the BlockSpec index maps DMA each
row's *physical* K/V blocks HBM->VMEM directly, so HBM traffic is the live
blocks only (the same streaming argument as kernels/flash_attn.py, applied
to the paged layout).

Grid: (b, MB) — one program per (row, virtual block). Each step DMAs the
physical block for every KV head at once, (BS, KV, hd): a block of the
pool's two minor dims whole, which the TPU compiler accepts for any KV.
Online-softmax state per KV head lives in VMEM scratch across the MB
dimension. The current token's K/V (not yet committed to the pool) is
handled in-kernel: its score overwrites the virtual column at ``pos`` and
its value row replaces the stale pool row, so recycled/sink blocks never
leak. Validated in interpret mode against the oracle (tests/test_paged.py,
tests/test_kvquant.py) and compiled for a TPU v5e in
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


def _paged_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, *refs,
                  scale: float, softcap: float | None, bs: int, nb: int,
                  quant: bool):
    """One (row, virtual block) grid step over every KV head of the block.

    With ``quant`` the DMA'd K/V blocks are int8/fp8 storage rows plus
    per-row f32 scales; dequantization happens here in VMEM, so the HBM
    stream stays at storage width (the cache-side twin of the GQMM
    unpack-in-VMEM argument)."""
    if quant:
        ks_ref, vs_ref, *refs = refs
    kn_ref, vn_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr = refs
    j = pl.program_id(1)                               # virtual block index

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # current token: its pool slot is committed AFTER attention, so the row
    # at ``pos`` holds stale data — substitute the fresh K score / V row
    col = pos_ref[pl.program_id(0)] - j * bs           # off-block: no match
    mask = mask_ref[0, 0].astype(jnp.float32)          # (1, bs) additive
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(jnp.float32)            # (g, hd)
        k = k_ref[0, :, h, :].astype(jnp.float32)      # (bs, hd)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        if quant:
            k = k * ks_ref[0][:, h:h + 1]
            v = v * vs_ref[0][:, h:h + 1]
        k_new = kn_ref[0, h:h + 1].astype(jnp.float32)  # (1, hd)
        v_new = vn_ref[0, h:h + 1].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=_F32,
                                preferred_element_type=jnp.float32)  # (g, bs)
        cur = jnp.sum(q * k_new, axis=-1, keepdims=True)            # (g, 1)
        s = jnp.where(jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) == col,
                      cur, s)
        v = jnp.where(jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) == col,
                      v_new, v)
        s = s * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = s + mask

        m_prev, l_prev = m_scr[h], l_scr[h]            # (g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                         # (g, bs)
        l_scr[h] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32)
        m_scr[h] = m_new

    @pl.when(j == nb - 1)
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
    # one (1, BS) mask row per virtual block: the row axis is a full dim
    mask = mask.reshape(b, mb, 1, bs)
    quant = k_scales is not None

    def pool_index(ib, j, bt, pos_s):
        # scalar-prefetched block table picks the physical block to DMA
        # (index maps receive grid indices first, then the scalar refs)
        return (bt[ib, j], 0, 0, 0)

    def scale_index(ib, j, bt, pos_s):
        return (bt[ib, j], 0, 0)

    def row_index(ib, j, bt, pos_s):
        return (ib, 0, 0)

    in_specs = [
        pl.BlockSpec((1, kv, g, hd), lambda ib, j, bt, ps: (ib, 0, 0, 0)),
        pl.BlockSpec((1, bs, kv, hd), pool_index),
        pl.BlockSpec((1, bs, kv, hd), pool_index),
    ]
    if quant:
        # per-row f32 scales ride the same block-table DMA as their rows
        in_specs += [
            pl.BlockSpec((1, bs, kv), scale_index),
            pl.BlockSpec((1, bs, kv), scale_index),
        ]
    in_specs += [
        pl.BlockSpec((1, kv, hd), row_index),
        pl.BlockSpec((1, kv, hd), row_index),
        pl.BlockSpec((1, 1, 1, bs), lambda ib, j, bt, ps: (ib, j, 0, 0)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block_table, pos
        grid=(b, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kv, g, hd), lambda ib, j, bt, ps: (ib, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv, g, 1), jnp.float32),     # running max
            pltpu.VMEM((kv, g, 1), jnp.float32),     # running denominator
            pltpu.VMEM((kv, g, hd), jnp.float32),    # output accumulator
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=scale, softcap=softcap,
                               bs=bs, nb=mb, quant=quant)
    operands = [q, k_pages, v_pages]
    if quant:
        operands += [k_scales, v_scales]
    operands += [k_new, v_new, mask]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="paged_attention",
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), pos.astype(jnp.int32), *operands)
    return out.reshape(b, kv * g * hd)
