"""Pure-jnp oracles for the GQMV/GQMM kernels (paper Algorithm 1).

These are the ground truth the Pallas kernels are validated against. They
follow the paper's arithmetic exactly:

  for each output row i:
    for each group j (of GS columns):
      group_sum = sum_k  xq[j*GS+k] * wq[i, j*GS+k]        # int8*int8 -> int32
      sum      += group_sum * ws[i, j] * xs[j]             # fp32 scaling
    out[i] = sum

i.e. integer accumulation *within* a group, float scale-and-accumulate
*across* groups.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.quant import QuantizedTensor, unpack_int3, unpack_int4


def _sum_groups(scaled: jax.Array) -> jax.Array:
    """Sum (..., G) over its groups one at a time, in order — the order in
    which the Pallas kernel adds them, so its integer formats reproduce the
    GQMV oracles bitwise in interpret mode."""
    acc = scaled[..., 0]
    for g in range(1, scaled.shape[-1]):
        acc = acc + scaled[..., g]
    return acc


@partial(jax.jit, static_argnames=("group_size",))
def gqmv_ref(
    wq: jax.Array,   # int8 (m, n)
    ws: jax.Array,   # float32 (m, n // GS)
    xq: jax.Array,   # int8 (n,)
    xs: jax.Array,   # float32 (n // GS,)
    *,
    group_size: int,
) -> jax.Array:
    """out[m] = GQMV(W, x) per paper Alg. 1. Returns float32 (m,)."""
    m, n = wq.shape
    ng = n // group_size
    wg = wq.reshape(m, ng, group_size).astype(jnp.int32)
    xg = xq.reshape(ng, group_size).astype(jnp.int32)
    group_sums = jnp.einsum("mgk,gk->mg", wg, xg)              # int32 (m, ng)
    scaled = group_sums.astype(jnp.float32) * ws * xs[None, :]  # fp32 (m, ng)
    return _sum_groups(scaled)


@partial(jax.jit, static_argnames=("group_size",))
def gqmm_ref(
    wq: jax.Array,   # int8 (m, n)
    ws: jax.Array,   # float32 (m, n // GS)
    xq: jax.Array,   # int8 (b, n)
    xs: jax.Array,   # float32 (b, n // GS)
    *,
    group_size: int,
) -> jax.Array:
    """Batched GQMV: out[b, m]. The paper runs batch=1; this is the natural
    batched generalization (same per-row math for every batch element)."""
    m, n = wq.shape
    b = xq.shape[0]
    ng = n // group_size
    wg = wq.reshape(m, ng, group_size).astype(jnp.int32)
    xg = xq.reshape(b, ng, group_size).astype(jnp.int32)
    group_sums = jnp.einsum("mgk,bgk->bmg", wg, xg)             # int32
    scaled = group_sums.astype(jnp.float32) * ws[None] * xs[:, None, :]
    return jnp.sum(scaled, axis=-1)


@partial(jax.jit, static_argnames=("group_size",))
def gqmv_int4_ref(
    wp: jax.Array,   # int8 packed (m, n // 2) — two nibbles per byte
    ws: jax.Array,   # float32 (m, n // GS)
    xq: jax.Array,   # int8 (n,) — activations stay int8 (W4A8)
    xs: jax.Array,   # float32 (n // GS,)
    *,
    group_size: int,
) -> jax.Array:
    """Packed-int4 GQMV oracle: unpack nibbles to int8, then Alg. 1 math.

    The group sums are exact integers either way; the fp32 stage uses the
    COMBINED scale ``group_sums * (ws * xs)`` and adds the groups in order
    — the association and order of the Pallas kernel — so the
    interpret-mode kernel reproduces this oracle bit-for-bit.
    """
    wq = unpack_int4(wp, group_size)
    m, n = wq.shape
    ng = n // group_size
    wg = wq.reshape(m, ng, group_size).astype(jnp.int32)
    xg = xq.reshape(ng, group_size).astype(jnp.int32)
    group_sums = jnp.einsum("mgk,gk->mg", wg, xg)               # int32 (m, ng)
    scaled = group_sums.astype(jnp.float32) * (ws * xs[None, :])
    return _sum_groups(scaled)


@partial(jax.jit, static_argnames=("group_size",))
def gqmm_int4_ref(
    wp: jax.Array,   # int8 packed (m, n // 2)
    ws: jax.Array,   # float32 (m, n // GS)
    xq: jax.Array,   # int8 (b, n)
    xs: jax.Array,   # float32 (b, n // GS)
    *,
    group_size: int,
) -> jax.Array:
    """Batched packed-int4 GQMV oracle (see gqmv_int4_ref)."""
    wq = unpack_int4(wp, group_size)
    m, n = wq.shape
    b = xq.shape[0]
    ng = n // group_size
    wg = wq.reshape(m, ng, group_size).astype(jnp.int32)
    xg = xq.reshape(b, ng, group_size).astype(jnp.int32)
    group_sums = jnp.einsum("mgk,bgk->bmg", wg, xg)             # int32
    # same association as the Pallas kernel: (sums * xs) * ws
    scaled = (group_sums.astype(jnp.float32) * xs[:, None, :]) * ws[None]
    return jnp.sum(scaled, axis=-1)


@partial(jax.jit, static_argnames=("group_size",))
def gqmv_int3_ref(
    wp: jax.Array,   # uint8 packed (m, n // 8 * 3) — eight 3-bit fields per 3 bytes
    ws: jax.Array,   # float32 (m, n // GS)
    xq: jax.Array,   # int8 (n,) — activations stay int8 (W3A8)
    xs: jax.Array,   # float32 (n // GS,)
    *,
    group_size: int,
) -> jax.Array:
    """Packed-int3 GQMV oracle: unpack the 3-bit fields to int8, then Alg. 1
    math with the same combined-scale association as the Pallas kernel (see
    gqmv_int4_ref for the bit-exactness argument)."""
    wq = unpack_int3(wp, group_size)
    m, n = wq.shape
    ng = n // group_size
    wg = wq.reshape(m, ng, group_size).astype(jnp.int32)
    xg = xq.reshape(ng, group_size).astype(jnp.int32)
    group_sums = jnp.einsum("mgk,gk->mg", wg, xg)               # int32 (m, ng)
    scaled = group_sums.astype(jnp.float32) * (ws * xs[None, :])
    return _sum_groups(scaled)


@partial(jax.jit, static_argnames=("group_size",))
def gqmm_int3_ref(
    wp: jax.Array,   # uint8 packed (m, n // 8 * 3)
    ws: jax.Array,   # float32 (m, n // GS)
    xq: jax.Array,   # int8 (b, n)
    xs: jax.Array,   # float32 (b, n // GS)
    *,
    group_size: int,
) -> jax.Array:
    """Batched packed-int3 GQMV oracle (see gqmv_int3_ref)."""
    wq = unpack_int3(wp, group_size)
    m, n = wq.shape
    b = xq.shape[0]
    ng = n // group_size
    wg = wq.reshape(m, ng, group_size).astype(jnp.int32)
    xg = xq.reshape(b, ng, group_size).astype(jnp.int32)
    group_sums = jnp.einsum("mgk,bgk->bmg", wg, xg)             # int32
    scaled = (group_sums.astype(jnp.float32) * xs[:, None, :]) * ws[None]
    return jnp.sum(scaled, axis=-1)


@partial(jax.jit, static_argnames=("group_size",))
def gqmv_fp8_ref(
    wq: jax.Array,   # float8_e4m3fn (m, n)
    ws: jax.Array,   # float32 (m, n // GS)
    xq: jax.Array,   # int8 (n,) — activations stay int8 (W8A8, float weights)
    xs: jax.Array,   # float32 (n // GS,)
    *,
    group_size: int,
) -> jax.Array:
    """fp8-weight GQMV oracle: the group dot runs in f32 (no exact integer
    stage), so kernel-vs-oracle comparisons are tolerance-based — f32 dot
    reassociation across lanes is allowed to differ."""
    m, n = wq.shape
    ng = n // group_size
    wg = wq.reshape(m, ng, group_size).astype(jnp.float32)
    xg = xq.reshape(ng, group_size).astype(jnp.float32)
    group_sums = jnp.einsum("mgk,gk->mg", wg, xg)               # f32 (m, ng)
    scaled = group_sums * (ws * xs[None, :])
    return _sum_groups(scaled)


@partial(jax.jit, static_argnames=("group_size",))
def gqmm_fp8_ref(
    wq: jax.Array,   # float8_e4m3fn (m, n)
    ws: jax.Array,   # float32 (m, n // GS)
    xq: jax.Array,   # int8 (b, n)
    xs: jax.Array,   # float32 (b, n // GS)
    *,
    group_size: int,
) -> jax.Array:
    """Batched fp8-weight GQMV oracle (see gqmv_fp8_ref)."""
    m, n = wq.shape
    b = xq.shape[0]
    ng = n // group_size
    wg = wq.reshape(m, ng, group_size).astype(jnp.float32)
    xg = xq.reshape(b, ng, group_size).astype(jnp.float32)
    group_sums = jnp.einsum("mgk,bgk->bmg", wg, xg)             # f32
    scaled = (group_sums * xs[:, None, :]) * ws[None]
    return jnp.sum(scaled, axis=-1)


def paged_attention_ref(
    q: jax.Array,            # (b, KV, G, hd) decode-step queries, grouped
    k_pages: jax.Array,      # (NB, BS, KV, hd) one layer's block pool
    v_pages: jax.Array,      # (NB, BS, KV, hd)
    block_table: jax.Array,  # (b, MB) int32 physical block per virtual block
    pos: jax.Array,          # (b,) int32 current decode position per row
    k_new: jax.Array,        # (b, KV, hd) current token's K (not yet committed)
    v_new: jax.Array,        # (b, KV, hd)
    mask: jax.Array,         # (b, T) additive decode mask, T = MB * BS
    *,
    scale: float,
    softcap: float | None = None,
    k_scales: jax.Array | None = None,   # (NB, BS, KV) quantized-pool scales
    v_scales: jax.Array | None = None,
) -> jax.Array:
    """Block-table gather attention oracle for one decode step.

    Mirrors ``gqa_decode_deferred``'s arithmetic exactly — same einsums, same
    operation order — over a gathered virtual sequence: row i's keys live in
    pool blocks ``block_table[i]``, virtual position t maps to physical slot
    ``(block_table[i, t // BS], t % BS)``. The current token is handled
    explicitly (its score overwrites column ``pos``; its value is added after
    zeroing the attention weight at ``pos``), so STALE data in recycled or
    sink blocks is harmless: every unwritten column is either masked
    (``k > pos``) or overwritten. With an identity block table over a
    reshaped contiguous cache this is bit-exact against the contiguous
    deferred decode path (tests/test_paged.py).

    With ``k_scales``/``v_scales`` the pool rows are quantized (int8/fp8,
    one scale per (block row, kv head), group = head_dim) and the scales are
    factored OUTSIDE the dots — ``(q . k_q) * k_s`` and
    ``(attn * v_s) . v_q`` — the exact association of the contiguous
    quantized decode path (models/attention.py::gqa_decode_deferred_quant),
    so paged and contiguous quantized decode agree on identity tables.

    Returns ctx (b, KV * G * hd) in the contiguous path's head order.
    """
    b, kv, g, hd = q.shape
    nb, bs = k_pages.shape[:2]
    mb = block_table.shape[1]
    # gather (b, MB, BS, KV, hd) -> virtual (b, T, KV, hd)
    k = k_pages[block_table].reshape(b, mb * bs, kv, hd)
    v = v_pages[block_table].reshape(b, mb * bs, kv, hd)
    quant = k_scales is not None
    if quant:
        k = k.astype(q.dtype)
        ks = k_scales[block_table].reshape(b, mb * bs, kv)       # (b,T,KV)
        vs = v_scales[block_table].reshape(b, mb * bs, kv)
    scores = jnp.einsum("bkgh,btkh->bkgt", q, k).astype(jnp.float32)
    if quant:
        scores = scores * ks.transpose(0, 2, 1)[:, :, None, :]   # (b,KV,1,T)
    cur = jnp.einsum("bkgh,bkh->bkg", q, k_new).astype(jnp.float32)
    barng = jnp.arange(b)
    scores = scores.at[barng, :, :, pos].set(cur)
    scores = scores * scale
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    scores = scores + mask[:, None, None, :]
    attn = jax.nn.softmax(scores, axis=-1)
    # zero the current column before the value gather: the pool slot at pos
    # holds stale data (it is committed AFTER attention); the real
    # contribution is the explicit k_new/v_new term
    attn_cur = attn[barng, :, :, pos][..., None].astype(q.dtype)  # (b,KV,G,1)
    attn_z = attn.at[barng, :, :, pos].set(0.0)
    if quant:
        attn_z = attn_z * vs.transpose(0, 2, 1)[:, :, None, :]
        v = v.astype(q.dtype)
    ctx = jnp.einsum("bkgt,btkh->bkgh", attn_z.astype(q.dtype), v)
    ctx = ctx + attn_cur * v_new[:, :, None, :]
    return ctx.reshape(b, kv * g * hd)


def paged_poison_counts(
    k_pages: jax.Array,      # (L, NB, BS, KV, hd) full block pool, all layers
    v_pages: jax.Array,      # (L, NB, BS, KV, hd)
    block_table: jax.Array,  # (b, MB) int32 physical block per virtual block
    pos: jax.Array,          # (b,) int32 current decode position per row
    poison: float,
) -> jax.Array:
    """repro-san's use-after-free detector: per (layer, slot, virtual block)
    counts of COMMITTED positions whose gathered K or V contains the poison
    fill value (analysis/shadow.py POISON, written over freed blocks).

    Mirrors :func:`paged_attention_ref`'s gather exactly — the same
    ``pages[block_table]`` indirection attention reads through — so a hit
    means poisoned (freed) data is REACHABLE by a live slot at a position
    the mask does not exclude: a freed block its table still maps. Only
    positions ``t < pos[slot]`` count; lookahead blocks (allocated ahead of
    the write frontier, possibly recycled-and-poisoned) and finished slots'
    sink-mapped rows sit at ``t >= pos`` or block 0 and stay clean.

    Returns int32 (L, b, MB). Runs under jit inside the sanitizer's single
    per-round check program (one host sync for all tripwires).
    """
    ell, nb, bs = k_pages.shape[:3]
    b, mb = block_table.shape
    t = jnp.arange(mb * bs, dtype=jnp.int32)
    committed = (t[None, :] < pos[:, None]).reshape(b, mb, bs)
    out = jnp.zeros((ell, b, mb), jnp.int32)
    for pages in (k_pages, v_pages):
        g = pages[:, block_table]                # (L, b, MB, BS, KV, hd)
        bad = (g == jnp.asarray(poison, g.dtype)).reshape(
            ell, b, mb, bs, -1).any(-1)
        out = out + jnp.sum(bad & committed[None], axis=-1).astype(jnp.int32)
    return out


def verify_attend(
    scores: jax.Array,       # (b, KV, G, S, T) chunk queries vs the sequence
    cur: jax.Array,          # (b, KV, G, S, M) intra-chunk q.k products
    chunk_v: jax.Array,      # (b, M, KV, hd) the chunk's own V rows
    v_source: jax.Array,     # (b, T, KV, hd) committed sequence values
    pos: jax.Array,          # (b,) int32 virtual position of chunk row 0
    mask: jax.Array,         # (b, S, T) additive verify mask
    *,
    scale: float,
    softcap: float | None = None,
) -> jax.Array:
    """The speculative-verify score arrangement, shared by the contiguous
    path (models/attention.py::gqa_verify_deferred) and the paged gather
    path (:func:`paged_verify_ref`) so the two cannot drift.

    The intra-chunk scores are SCATTERED into columns ``pos + m`` of the T
    axis — the exact layout m successive single-token decode steps would
    produce — so softmax sums in the same column order as vanilla decode
    and greedy speculative output stays token-identical. The chunk
    columns' attention weights are then pulled out, zeroed in place (the
    sequence source may hold zeros there — contiguous deferred cache — or
    stale recycled data — paged pool; either way unreachable), and their
    value contribution is added explicitly from ``chunk_v``.

    Returns ctx (b, S, KV * G * hd) in the contiguous path's head order.
    """
    b, kv, g, s, t = scores.shape
    m = cur.shape[-1]
    hd = chunk_v.shape[-1]
    rows = jnp.arange(b)[:, None]
    cols = pos[:, None] + jnp.arange(m, dtype=jnp.int32)[None, :]    # (b, m)
    # advanced-index layout: [rows, :, :, :, cols] -> (b, m, kv, g, s)
    scores = scores.at[rows, :, :, :, cols].set(cur.transpose(0, 4, 1, 2, 3))
    scores = scores * scale
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    scores = scores + mask[:, None, None, :, :]
    attn = jax.nn.softmax(scores, axis=-1).astype(chunk_v.dtype)
    attn_chunk = attn[rows, :, :, :, cols].transpose(0, 2, 3, 4, 1)  # (b,kv,g,s,m)
    attn_z = attn.at[rows, :, :, :, cols].set(0.0)
    ctx = jnp.einsum("bkgst,btkh->bkgsh", attn_z, v_source)
    ctx = ctx + jnp.einsum("bkgsm,bmkh->bkgsh", attn_chunk, chunk_v)
    return ctx.transpose(0, 3, 1, 2, 4).reshape(b, s, kv * g * hd)


def paged_verify_ref(
    q: jax.Array,            # (b, S, KV, G, hd) verify-chunk queries, grouped
    k_pages: jax.Array,      # (NB, BS, KV, hd) one layer's block pool
    v_pages: jax.Array,
    block_table: jax.Array,  # (b, MB) int32
    pos: jax.Array,          # (b,) int32 virtual position of chunk row 0
    k_new: jax.Array,        # (b, S, KV, hd) the chunk's own K rows
    v_new: jax.Array,
    mask: jax.Array,         # (b, S, T) additive verify mask, T = MB * BS
    *,
    scale: float,
    softcap: float | None = None,
) -> jax.Array:
    """Block-table gather attention for a k-token speculative-verify chunk:
    gather row i's keys/values through its block table into a virtual
    (b, T, KV, hd) sequence, then run the shared :func:`verify_attend`
    arrangement — identical math to the contiguous verify path on identity
    tables (tests/test_spec.py).

    Returns ctx (b, S, KV * G * hd) in the contiguous path's head order.
    """
    b, s, kv, g, hd = q.shape
    nb, bs = k_pages.shape[:2]
    mb = block_table.shape[1]
    k = k_pages[block_table].reshape(b, mb * bs, kv, hd)
    v = v_pages[block_table].reshape(b, mb * bs, kv, hd)
    qg = q.transpose(0, 2, 3, 1, 4)                              # (b,kv,g,s,hd)
    scores = jnp.einsum("bkgsh,btkh->bkgst", qg, k).astype(jnp.float32)
    cur = jnp.einsum("bkgsh,bmkh->bkgsm", qg, k_new).astype(jnp.float32)
    return verify_attend(scores, cur, v_new, v, pos, mask,
                         scale=scale, softcap=softcap)


def gqmv_from_qt(w: QuantizedTensor, x: QuantizedTensor) -> jax.Array:
    assert w.group_size == x.group_size
    return gqmv_ref(w.qvalues, w.scales, x.qvalues, x.scales, group_size=w.group_size)


def gqmm_from_qt(w: QuantizedTensor, x: QuantizedTensor) -> jax.Array:
    assert w.group_size == x.group_size
    return gqmm_ref(w.qvalues, w.scales, x.qvalues, x.scales, group_size=w.group_size)
