"""Attention blocks: GQA (llama-family, gemma2 local/global+softcap) and
MLA (multi-head latent attention: minicpm3, deepseek-v2).

Each block exposes:
  init_*          -> param dict (weights in (out, in) layout, quantizable)
  *_forward       -> full-sequence self-attention (training / naive prefill)
  *_prefill       -> forward + returns the cache tensors for decode
  *_decode        -> single-token step against the cache

Projections go through ``linear`` so the same code runs fp32/bf16 (training,
"PS baseline") or W8A8 GQMV (paper path) depending on the weight leaf type.
QKV is one fused projection (paper Alg. 2 line 4).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import flags
from repro.core.qlinear import linear, split_fused
from repro.core.quant import FP8_MAX, LayerView, QuantizedTensor
from repro.dist import logical
from repro.models.common import (
    NEG_INF,
    apply_rope,
    causal_mask,
    decode_mask,
    dense_init,
    length_mask,
    rmsnorm,
    softcap,
)

# ---------------------------------------------------------------------------
# per-request decode positions
#
# Every decode entry point accepts ``pos`` as either a scalar (uniform batch,
# the original contract) or a (b,) vector of per-request positions (ragged
# continuous batching). The helpers below keep one code path for RoPE rows,
# score updates at the current column, and per-row cache commits.
# ---------------------------------------------------------------------------

def _pos_rows(pos, b: int):
    """(b, 1) int32 RoPE position rows from scalar or (b,) ``pos``."""
    pos = jnp.asarray(pos, jnp.int32)
    return pos.reshape(b, 1) if pos.ndim else jnp.full((b, 1), pos, jnp.int32)


def _commit_bt(cache, rows, pos):
    """Write rows (b, 1, ...) into cache (b, T, ...) at time ``pos``."""
    if jnp.asarray(pos).ndim:
        return cache.at[jnp.arange(cache.shape[0]), pos].set(rows[:, 0])
    return jax.lax.dynamic_update_slice_in_dim(cache, rows, pos, axis=1)


def _commit_bkt(cache, rows, pos):
    """Write rows (b, KV, 1, ...) into cache (b, KV, T, ...) at ``pos``."""
    if jnp.asarray(pos).ndim:
        b, kv = cache.shape[:2]
        return cache.at[
            jnp.arange(b)[:, None], jnp.arange(kv)[None, :], pos[:, None]
        ].set(rows[:, :, 0])
    start = (0, 0, pos) + (0,) * (cache.ndim - 3)
    return jax.lax.dynamic_update_slice(cache, rows, start)


def _col_update(scores, cur, pos):
    """scores (b, ..., t): overwrite column ``pos`` (per-row when vector)
    with cur (b, ...)."""
    if jnp.asarray(pos).ndim:
        idx = (jnp.arange(scores.shape[0]),) + (slice(None),) * (scores.ndim - 2) + (pos,)
        return scores.at[idx].set(cur)
    return jax.lax.dynamic_update_slice(
        scores, cur[..., None], (0,) * (scores.ndim - 1) + (pos,)
    )


def _col_at(attn, pos):
    """attn (b, ..., t) -> (b, ..., 1) column at ``pos`` (per-row when vector)."""
    if jnp.asarray(pos).ndim:
        idx = (jnp.arange(attn.shape[0]),) + (slice(None),) * (attn.ndim - 2) + (pos,)
        return attn[idx][..., None]
    return jax.lax.dynamic_slice(
        attn, (0,) * (attn.ndim - 1) + (pos,), attn.shape[:-1] + (1,)
    )


def _bcast_decode_mask(m):
    """decode mask (t,) or (b, t) -> broadcastable over (b, s=1, t) scores."""
    return m[None, None, :] if m.ndim == 1 else m[:, None, :]


def commit_layers_bt(cache, rows, pos):
    """Deferred-decode commit, (L, b, T, ...) layout: write rows (L, b, 1, ...)
    at time ``pos`` — one donated dynamic-update-slice (scalar pos) or one
    per-row scatter (vector pos, ragged batches)."""
    if jnp.asarray(pos).ndim:
        return cache.at[:, jnp.arange(cache.shape[1]), pos].set(rows[:, :, 0])
    return jax.lax.dynamic_update_slice(
        cache, rows, (0, 0, pos) + (0,) * (cache.ndim - 3)
    )


def commit_layers_paged(pages, rows, block_table, pos):
    """Deferred paged commit: write rows (L, b, KV, hd) into the block pool
    (L, NB, BS, KV, hd) at each row's (physical block, offset) for virtual
    position ``pos`` (b,). One scatter for all layers. The block index is
    clamped to the table width so a frozen/overflowed position can never
    escape its own table row (live positions are host-asserted in range)."""
    bs = pages.shape[2]
    b = rows.shape[1]
    idx = jnp.minimum(pos // bs, block_table.shape[1] - 1)
    phys = block_table[jnp.arange(b), idx]                    # (b,)
    return pages.at[:, phys, pos % bs].set(rows)


def commit_layers_verify(cache, rows, pos, n_commit):
    """Speculative-verify commit, (L, b, T, KV, hd) layout: write the chunk's
    K/V rows (L, b, k, KV, hd) at times ``pos + j`` for the ACCEPTED prefix
    ``j < n_commit[b]`` only. Rejected rows are redirected to column ``T``
    (out of bounds — scatter updates there are dropped), so the cache after a
    partial accept is bit-identical to one that never saw the rejected
    drafts: rollback is a position rewind, no zeroing pass (DESIGN.md §10)."""
    b, k = rows.shape[1], rows.shape[2]
    t = cache.shape[2]
    j = jnp.arange(k, dtype=jnp.int32)[None, :]
    cols = jnp.where(j < n_commit[:, None], pos[:, None] + j, t)      # (b, k)
    return cache.at[:, jnp.arange(b)[:, None], cols].set(rows)


def commit_layers_paged_verify(pages, rows, block_table, pos, n_commit):
    """Speculative-verify commit into the block pool (L, NB, BS, KV, hd):
    row j of the chunk lands at virtual position ``pos + j``'s (physical
    block, offset); rejected rows (``j >= n_commit``) are redirected past
    the pool's block axis, where the scatter drops them — NOT to the
    scheduler's sink block 0, which under the engine's identity tables is
    a live block. The pool after a partial accept is therefore
    bit-identical to one that never saw the rejected drafts."""
    nb, bs = pages.shape[1], pages.shape[2]
    b, k = rows.shape[1], rows.shape[2]
    j = jnp.arange(k, dtype=jnp.int32)[None, :]
    vpos = pos[:, None] + j                                           # (b, k)
    idx = jnp.minimum(vpos // bs, block_table.shape[1] - 1)
    phys = jnp.take_along_axis(block_table, idx, axis=1)              # (b, k)
    phys = jnp.where(j < n_commit[:, None], phys, nb)                 # dropped
    return pages.at[:, phys, vpos % bs].set(rows)


def commit_layers_bkt(cache, rows, pos):
    """Deferred-decode commit, (L, b, KV, T, ...) layout (kvt / int8 caches)."""
    if jnp.asarray(pos).ndim:
        b, kv = cache.shape[1], cache.shape[2]
        return cache.at[
            :, jnp.arange(b)[:, None], jnp.arange(kv)[None, :], pos[:, None]
        ].set(rows[:, :, :, 0])
    return jax.lax.dynamic_update_slice(
        cache, rows, (0, 0, 0, pos) + (0,) * (cache.ndim - 4)
    )


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def init_gqa(key, cfg: ModelConfig) -> dict:
    kq, ko = jax.random.split(key)
    dt = cfg.pdtype()
    return {
        "wqkv": dense_init(kq, cfg.q_dim + 2 * cfg.kv_dim, cfg.d_model, dt),
        "wo": dense_init(ko, cfg.d_model, cfg.q_dim, dt),
    }


def _gqa_scale(cfg: ModelConfig) -> float:
    base = cfg.query_scale if cfg.query_scale is not None else cfg.resolved_head_dim
    return base ** -0.5


def _qkv(p, x, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    qkv = linear(p["wqkv"], x)
    q, k, v = split_fused(qkv, (cfg.q_dim, cfg.kv_dim, cfg.kv_dim))
    q = q.reshape(b, s, cfg.num_heads, hd)
    k = k.reshape(b, s, cfg.num_kv_heads, hd)
    v = v.reshape(b, s, cfg.num_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mha_blockwise(q, k, v, cfg: ModelConfig, *, causal=True, window=None,
                   use_window=None, lengths=None):
    """Chunked online-softmax attention (flash-style), XLA fallback of
    kernels/flash_attn.py. Streams K/V in chunks of flags.attention_chunk;
    never materializes the (b,kv,g,s,t) score tensor. Used for train/prefill
    under flags.blockwise_attention; TPU deployment uses the Pallas kernel."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    kv = k.shape[2]
    g = h // kv
    tp = logical.size("tp")
    if kv % tp == 0:
        q = logical.constrain(q, "dp", None, "tp", None)
        k = logical.constrain(k, "dp", None, "tp", None)
        v = logical.constrain(v, "dp", None, "tp", None)
        cspec = ("dp", "tp", None, None, None)
    else:
        cspec = ("dp", None, None, None, None)
    chunk = int(flags.get("attention_chunk"))
    chunk = min(chunk, t)
    while t % chunk:
        chunk //= 2
    nchunks = t // chunk
    qg = q.reshape(b, s, kv, g, hd)
    scale = _gqa_scale(cfg)
    q_pos = jnp.arange(s)

    def body(carry, ic):
        m_prev, l_prev, acc = carry
        ks = jax.lax.dynamic_slice_in_dim(k, ic * chunk, chunk, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, ic * chunk, chunk, axis=1)
        sc = jnp.einsum("bskgh,btkh->bkgst", qg, ks).astype(jnp.float32) * scale
        sc = logical.constrain(sc, *cspec)
        if cfg.attn_logit_softcap:
            sc = softcap(sc, cfg.attn_logit_softcap)
        k_pos = ic * chunk + jnp.arange(chunk)
        ok = jnp.ones((s, chunk), bool)
        if causal:
            ok &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            okw = ok & ((q_pos[:, None] - k_pos[None, :]) < window)
            ok = okw if use_window is None else jnp.where(use_window, okw, ok)
        if lengths is not None:
            # ragged prefill: hide right-pad keys per row -> (b, s, chunk)
            okb = ok[None] & (k_pos[None, None, :] < lengths[:, None, None])
            sc = jnp.where(okb[:, None, None], sc, NEG_INF)
        else:
            sc = jnp.where(ok[None, None, None], sc, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new[..., None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkh->bkgsh", p.astype(q.dtype), vs
        ).astype(jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, kv, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv, g, s), jnp.float32)
    acc0 = jnp.zeros((b, kv, g, s, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), jnp.arange(nchunks))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, h * hd)  # (b,s,kv,g,hd)->(b,s,h*hd)
    return logical.constrain(out, "dp", None, "tp" if kv % tp == 0 else None)


def _mha(q, k, v, mask, cfg: ModelConfig):
    """q: (b,s,H,hd); k,v: (b,t,KV,hd); mask additive (s,t) or (b,s,t).

    Logical sharding: kv-head-parallel when KV divides the model axis, else
    q-sequence-parallel (train/prefill) or cache-sequence-parallel (decode).
    Without annotations XLA SPMD replicates the (b,kv,g,s,t) score buffer
    (measured: 120 GB/layer on deepseek-coder train_4k).
    """
    b, s, h, hd = q.shape
    t = k.shape[1]
    kv = k.shape[2]
    g = h // kv
    tp = logical.size("tp")
    # batch-1 decode: shard the cache length over the FULL mesh (seq axes)
    seq_ax = "seq" if (b == 1 and s == 1 and t % max(logical.size("seq"), 1) == 0
                       and logical.size("seq") > 1) else "tp"
    mode = "head" if kv % tp == 0 else ("seq" if s % tp == 0 else
                                        ("cache" if t % tp == 0 else "none"))
    if b == 1 and s == 1 and seq_ax == "seq":
        mode = "cache"
    if mode == "head":
        q = logical.constrain(q, "dp", None, "tp", None)
        k = logical.constrain(k, "dp", None, "tp", None)
        v = logical.constrain(v, "dp", None, "tp", None)
    elif mode == "seq":
        q = logical.constrain(q, "dp", "tp", None, None)
        k = logical.constrain(k, "dp", None, None, None)
        v = logical.constrain(v, "dp", None, None, None)
    elif mode == "cache":
        k = logical.constrain(k, None if seq_ax == "seq" else "dp", seq_ax, None, None)
        v = logical.constrain(v, None if seq_ax == "seq" else "dp", seq_ax, None, None)
    qg = q.reshape(b, s, kv, g, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32)
    score_spec = {
        "head": ("dp", "tp", None, None, None),
        "seq": ("dp", None, None, "tp", None),
        "cache": (None if seq_ax == "seq" else "dp", None, None, None, seq_ax),
        "none": ("dp", None, None, None, None),
    }[mode]
    scores = logical.constrain(scores, *score_spec)
    scores *= _gqa_scale(cfg)
    if cfg.attn_logit_softcap:
        scores = softcap(scores, cfg.attn_logit_softcap)
    scores = scores + mask[..., None, None, :, :] if mask.ndim == 2 else scores + mask[:, None, None]
    attn = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    attn = logical.constrain(attn, *score_spec)
    out = jnp.einsum("bkgst,btkh->bskgh", attn, v)
    out = out.reshape(b, s, h, hd).reshape(b, s, h * hd)
    return logical.constrain(
        out, "dp", "tp" if mode == "seq" else None, "tp" if mode == "head" else None
    )


def _flag_mask(s: int, window, use_window):
    """(s, s) additive mask; ``use_window`` may be a traced bool selecting the
    sliding-window variant per layer (gemma2 L/G alternation inside scan)."""
    full = causal_mask(s, None)
    if window is None:
        return full
    local = causal_mask(s, window)
    if use_window is None:
        return local
    return jnp.where(use_window, local, full)


def _flag_decode_mask(cache_len: int, pos, window, use_window):
    full = decode_mask(cache_len, pos, None)
    if window is None:
        return full
    local = decode_mask(cache_len, pos, window)
    if use_window is None:
        return local
    return jnp.where(use_window, local, full)


def gqa_forward(p, x, cfg: ModelConfig, *, window=None, use_window=None, causal=True):
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    q, k, v = _qkv(p, x, cfg, positions)
    if flags.get("blockwise_attention") and s > 1:
        ctx = _mha_blockwise(q, k, v, cfg, causal=causal, window=window,
                             use_window=use_window)
    else:
        mask = _flag_mask(s, window, use_window) if causal else jnp.zeros((s, s), jnp.float32)
        ctx = _mha(q, k, v, mask, cfg)
    return linear(p["wo"], ctx)


def gqa_prefill(p, x, cfg: ModelConfig, cache_len: int, *, window=None, use_window=None,
                lengths=None):
    """Returns (y, (k_cache, v_cache)) with caches padded to cache_len.

    ``lengths`` (b,) marks each row's true prompt length in a right-padded
    ragged batch: keys at positions >= lengths[i] are masked out so pad
    tokens never leak into valid positions' attention, and pad K/V rows are
    zeroed before caching — decode's `k <= pos` mask hides them until the
    per-request decode positions overwrite them in order, and the deferred
    decode paths' cache-slot-at-pos-is-zero invariant keeps holding."""
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    q, k, v = _qkv(p, x, cfg, positions)
    if lengths is not None:
        valid = (jnp.arange(s)[None, :] < lengths[:, None])[..., None, None]
        k = jnp.where(valid, k, 0)
        v = jnp.where(valid, v, 0)
    if flags.get("blockwise_attention") and s > 1:
        ctx = _mha_blockwise(q, k, v, cfg, window=window, use_window=use_window,
                             lengths=lengths)
    else:
        mask = _flag_mask(s, window, use_window)
        if lengths is not None:
            mask = mask[None] + length_mask(lengths, s)[:, None, :]   # (b, s, s)
        ctx = _mha(q, k, v, mask, cfg)
    kvq = kv_quant_format(cfg)
    if kvq:
        pad = [(0, 0), (0, 0), (0, cache_len - s), (0, 0)]
        pad_s = [(0, 0), (0, 0), (0, cache_len - s)]
        kq, ks = _quantize_rows(k.transpose(0, 2, 1, 3), kvq)  # (b,KV,s,hd)/(b,KV,s)
        vq, vs = _quantize_rows(v.transpose(0, 2, 1, 3), kvq)
        return linear(p["wo"], ctx), (jnp.pad(kq, pad), jnp.pad(ks, pad_s),
                                      jnp.pad(vq, pad), jnp.pad(vs, pad_s))
    if flags.get("kvt_cache_layout"):
        pad = [(0, 0), (0, 0), (0, cache_len - s), (0, 0)]
        kc = jnp.pad(k.transpose(0, 2, 1, 3), pad)       # (b,KV,T,hd)
        vc = jnp.pad(v.transpose(0, 2, 1, 3), pad)
        return linear(p["wo"], ctx), (kc, vc)
    pad = [(0, 0), (0, cache_len - s), (0, 0), (0, 0)]
    return linear(p["wo"], ctx), (jnp.pad(k, pad), jnp.pad(v, pad))


def gqa_decode(p, x, cache, pos, cfg: ModelConfig, *, window=None, use_window=None):
    """x: (b, d_model) single token; cache: (k, v) each (b, T, KV, hd);
    pos: scalar int32 or (b,) per-request positions. Returns (y, new_cache)."""
    k_cache, v_cache = cache
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    q, k, v = _qkv(p, x[:, None, :], cfg, _pos_rows(pos, b))
    k_cache = _commit_bt(k_cache, k, pos)
    v_cache = _commit_bt(v_cache, v, pos)
    mask = _bcast_decode_mask(_flag_decode_mask(k_cache.shape[1], pos, window, use_window))
    ctx = _mha(q, k_cache, v_cache, mask, cfg)                        # (b,1,q_dim)
    return linear(p["wo"], ctx[:, 0, :]), (k_cache, v_cache)


# KV-cache quantization storage dtypes (cfg.kv_quant / serve --kv-quant).
# One scale per (position, kv head) row, group = head_dim — the paper's
# group-wise symmetric scheme (Eq. 1) applied to the cache stream.
KV_STORE_DTYPES = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}


def kv_quant_format(cfg: ModelConfig) -> str | None:
    """Active KV-cache quantization format for the GQA layouts: the
    engine-threaded ``cfg.kv_quant`` or the legacy int8_kv_cache flag."""
    kvq = cfg.kv_quant or ("int8" if flags.get("int8_kv_cache") else None)
    if kvq is not None and kvq not in KV_STORE_DTYPES:
        raise ValueError(
            f"unknown kv_quant format {kvq!r}; supported: "
            f"{sorted(KV_STORE_DTYPES)}")
    return kvq


def _quantize_rows(t: jax.Array, fmt: str = "int8"):
    """Symmetric quantization over the last axis (head_dim = one group),
    Eq. 1. t: (..., hd) -> (storage rows, f32 scales (...)). int8 rounds to
    the integer grid; fp8 casts onto the e4m3 float grid after normalizing
    the row absmax to FP8_MAX."""
    absmax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
    if fmt == "fp8":
        scales = absmax / FP8_MAX
        safe = jnp.where(scales > 0, scales, 1.0)
        q = (t.astype(jnp.float32) / safe[..., None]).astype(jnp.float8_e4m3fn)
        return q, scales
    scales = absmax * (2.0 / 255.0)
    safe = jnp.where(scales > 0, scales, 1.0)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / safe[..., None]), -127, 127)
    return q.astype(jnp.int8), scales


def gqa_decode_deferred_quant(p, x, cache, pos, cfg: ModelConfig, *, window=None,
                              use_window=None):
    """Quantized-KV-cache decode (paper's group-wise quantization applied to
    the cache, kvt layout, int8 or fp8 storage):
    scores = (q . k_q) * k_s; ctx = (attn * v_s) . v_q.
    The per-position scales factor out of the sums exactly like the GQMV
    group scales factor out of Alg. 1's group sums."""
    kq_c, ks_c, vq_c, vs_c = cache      # (b,KV,T,hd) int8, (b,KV,T) f32
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    kv_heads = cfg.num_kv_heads
    h = cfg.num_heads
    g = h // kv_heads
    t = kq_c.shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    q, k_new, v_new = _qkv(p, x[:, None, :], cfg, _pos_rows(pos, b))

    tp = logical.size("tp")
    tp_t = t % tp == 0
    cspec = ("dp", None, "tp" if tp_t else None, None)
    kq_c = logical.constrain(kq_c, *cspec)
    vq_c = logical.constrain(vq_c, *cspec)
    qg = q.reshape(b, kv_heads, g, hd)
    scores = jnp.einsum("bkgh,bkth->bkgt", qg, kq_c.astype(x.dtype)).astype(jnp.float32)
    scores = scores * ks_c[:, :, None, :]
    cur = jnp.einsum("bkgh,bkh->bkg", qg, k_new[:, 0]).astype(jnp.float32)
    scores = _col_update(scores, cur, pos)
    scores = logical.constrain(scores, "dp", None, None, "tp" if tp_t else None)
    scores *= _gqa_scale(cfg)
    if cfg.attn_logit_softcap:
        scores = softcap(scores, cfg.attn_logit_softcap)
    dm = _flag_decode_mask(t, pos, window, use_window)
    scores = scores + (dm[None, None, None, :] if dm.ndim == 1 else dm[:, None, None, :])
    attn = jax.nn.softmax(scores, axis=-1)                    # f32 (b,kv,g,t)
    ctx = jnp.einsum("bkgt,bkth->bkgh",
                     (attn * vs_c[:, :, None, :]).astype(x.dtype),
                     vq_c.astype(x.dtype))
    attn_cur = _col_at(attn, pos)
    ctx = ctx + attn_cur.astype(x.dtype) * v_new[:, 0][:, :, None, :]
    ctx = ctx.reshape(b, h * hd)
    kvq = kv_quant_format(cfg) or "int8"
    kq_n, ks_n = _quantize_rows(k_new[:, 0], kvq)             # (b,kv,hd)/(b,kv)
    vq_n, vs_n = _quantize_rows(v_new[:, 0], kvq)
    rows = (kq_n[:, :, None, :], ks_n[:, :, None],
            vq_n[:, :, None, :], vs_n[:, :, None])
    return linear(p["wo"], ctx), rows


# Backwards-compat alias (the int8_kv_cache flag path predates cfg.kv_quant).
gqa_decode_deferred_int8 = gqa_decode_deferred_quant


def gqa_decode_deferred(p, x, cache, pos, cfg: ModelConfig, *, window=None,
                        use_window=None):
    """Decode WITHOUT writing the cache: attends over the read-only cache
    (whose slot at ``pos`` is still zero) plus the freshly-computed K/V row,
    and returns that row for the caller to commit with ONE donated
    dynamic-update-slice after the layer scan.

    The baseline path funnels the full per-layer cache through the scan's
    ys stack — a full cache read+write per step. This variant's per-layer
    cache traffic is the attention read only (hillclimb: decode cells).

    Supports both cache layouts: (b,T,KV,hd) baseline and (b,KV,T,hd)
    attention-native (flags.kvt_cache_layout — the dots then contract the
    trailing axis of both operands, no transpose materialization).
    """
    k_cache, v_cache = cache
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    kv_heads = cfg.num_kv_heads
    kvt = bool(flags.get("kvt_cache_layout"))
    pos = jnp.asarray(pos, jnp.int32)
    q, k_new, v_new = _qkv(p, x[:, None, :], cfg, _pos_rows(pos, b))  # (b,1,H/KV,hd)

    h = cfg.num_heads
    g = h // kv_heads
    t = k_cache.shape[2] if kvt else k_cache.shape[1]
    # batch-1: shard the cache length over the FULL mesh ("seq"); else model
    seq_sz = logical.size("seq")
    if b == 1 and seq_sz > 1 and t % seq_sz == 0:
        t_ax, b_ax, tp_t = "seq", None, True
    else:
        tp_t = t % logical.size("tp") == 0
        t_ax, b_ax = ("tp" if tp_t else None), "dp"
    cache_spec = (b_ax, None, t_ax, None) if kvt else (b_ax, t_ax, None, None)
    k_cache = logical.constrain(k_cache, *cache_spec)
    v_cache = logical.constrain(v_cache, *cache_spec)
    qg = q.reshape(b, kv_heads, g, hd)
    if kvt:
        scores = jnp.einsum("bkgh,bkth->bkgt", qg, k_cache).astype(jnp.float32)
    else:
        scores = jnp.einsum("bkgh,btkh->bkgt", qg, k_cache).astype(jnp.float32)
    cur = jnp.einsum("bkgh,bkh->bkg", qg, k_new[:, 0]).astype(jnp.float32)
    # overwrite the (zero-keyed) slot at pos with the current-token score
    scores = _col_update(scores, cur, pos)
    scores = logical.constrain(scores, b_ax, None, None, t_ax if tp_t else None)
    scores *= _gqa_scale(cfg)
    if cfg.attn_logit_softcap:
        scores = softcap(scores, cfg.attn_logit_softcap)
    mask = _flag_decode_mask(t, pos, window, use_window)
    scores = scores + (mask[None, None, None, :] if mask.ndim == 1 else mask[:, None, None, :])
    attn = jax.nn.softmax(scores, axis=-1).astype(x.dtype)   # (b,kv,g,t)
    # v_cache slot at pos is zero, so its contribution is exactly the
    # explicit current-token term below
    if kvt:
        ctx = jnp.einsum("bkgt,bkth->bkgh", attn, v_cache)
    else:
        ctx = jnp.einsum("bkgt,btkh->bkgh", attn, v_cache)
    attn_cur = _col_at(attn, pos)
    ctx = ctx + attn_cur * v_new[:, 0][:, :, None, :]   # (b,kv,g,1)x(b,kv,1,hd)
    ctx = ctx.reshape(b, h * hd)
    if kvt:
        rows = (k_new[:, 0][:, :, None, :], v_new[:, 0][:, :, None, :])  # (b,kv,1,hd)
    else:
        rows = (k_new, v_new)                                            # (b,1,kv,hd)
    return linear(p["wo"], ctx), rows


def _verify_mask(t: int, pos, k: int, window, use_window):
    """(b, k, t) additive mask for a k-token verify chunk starting at
    ``pos`` (b,): query j (virtual position pos+j) sees columns <= pos+j —
    exactly ``decode_mask`` row-by-row, so each chunk row reproduces the
    single-token decode step's mask arrangement bit-for-bit."""
    qpos = pos[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]     # (b, k)
    col = jnp.arange(t)[None, None, :]
    ok_full = col <= qpos[..., None]
    if window is None:
        ok = ok_full
    else:
        ok_local = ok_full & ((qpos[..., None] - col) < window)
        ok = ok_local if use_window is None else jnp.where(use_window, ok_local, ok_full)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def gqa_verify_deferred(p, x, cache, pos, cfg: ModelConfig, *, window=None,
                        use_window=None):
    """Speculative-verify attention: k chunk tokens x (b, k, d_model) attend
    over the read-only contiguous cache (slots >= pos still zero) plus the
    chunk's own K/V rows with an intra-chunk causal mask, WITHOUT writing
    the cache. Returns (y (b, k, q_dim), (k_rows, v_rows) (b, k, KV, hd))
    for the caller to commit the accepted prefix via commit_layers_verify.

    Row j of the chunk reproduces the arithmetic of the single-token decode
    step that would run after committing rows 0..j-1 (same score columns,
    same mask, same softmax arrangement), which is what makes greedy
    speculative decoding token-identical to vanilla decode."""
    k_cache, v_cache = cache
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    kv_heads = cfg.num_kv_heads
    g = cfg.num_heads // kv_heads
    t = k_cache.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    if not pos.ndim:
        pos = jnp.full((b,), pos, jnp.int32)
    positions = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    q, k_new, v_new = _qkv(p, x, cfg, positions)        # (b,s,H,hd)/(b,s,KV,hd)

    tp_t = t % max(logical.size("tp"), 1) == 0
    k_cache = logical.constrain(k_cache, "dp", "tp" if tp_t else None, None, None)
    v_cache = logical.constrain(v_cache, "dp", "tp" if tp_t else None, None, None)
    qg = q.reshape(b, s, kv_heads, g, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k_cache).astype(jnp.float32)
    cur = jnp.einsum("bskgh,bmkh->bkgsm", qg, k_new).astype(jnp.float32)
    mask = _verify_mask(t, pos, s, window, use_window)
    # the scatter/zero/explicit-chunk-V arrangement is shared with the
    # paged gather path so the two verify flavors cannot drift
    from repro.kernels import ref as _kref

    ctx = _kref.verify_attend(scores, cur, v_new, v_cache, pos, mask,
                              scale=_gqa_scale(cfg),
                              softcap=cfg.attn_logit_softcap or None)
    return linear(p["wo"], ctx), (k_new, v_new)


def gqa_verify_paged(p, x, pages, block_table, pos, cfg: ModelConfig, *,
                     window=None, use_window=None):
    """Paged speculative-verify attention: the chunk attends over the block
    pool through each row's block table (kernels/ops.py::paged_verify).
    Same contract as gqa_verify_deferred; rows are committed by the caller
    via commit_layers_paged_verify (rejected rows -> sink block)."""
    from repro.kernels import ops as _kops

    k_pages, v_pages = pages
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    kv_heads = cfg.num_kv_heads
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    q, k_new, v_new = _qkv(p, x, cfg, positions)
    g = cfg.num_heads // kv_heads
    t = block_table.shape[1] * k_pages.shape[1]
    tp_kv = kv_heads % max(logical.size("tp"), 1) == 0
    pspec = (None, None, "tp" if tp_kv else None, None)
    k_pages = logical.constrain(k_pages, *pspec)
    v_pages = logical.constrain(v_pages, *pspec)
    qg = q.reshape(b, s, kv_heads, g, hd)
    mask = _verify_mask(t, pos, s, window, use_window)
    ctx = _kops.paged_verify(
        qg, k_pages, v_pages, block_table, pos, k_new, v_new, mask,
        scale=_gqa_scale(cfg), softcap=cfg.attn_logit_softcap or None,
    )
    ctx = logical.constrain(ctx, "dp", None, None)
    return linear(p["wo"], ctx), (k_new, v_new)


def gqa_decode_paged(p, x, pages, block_table, pos, cfg: ModelConfig, *,
                     window=None, use_window=None, scales=None):
    """Paged decode step: attention over the block pool through each row's
    block table (kernels/ops.py::paged_attention), current token handled
    explicitly so the pool is read-only here. x: (b, d_model); pages:
    (k_pages, v_pages) each (NB, BS, KV, hd); block_table (b, MB);
    pos (b,) int32 virtual positions. Returns (y, (k_new, v_new)) — the
    caller commits the rows with commit_layers_paged after the layer scan.

    With cfg.kv_quant the pool rows are int8/fp8 storage and ``scales`` is
    the (k_scales, v_scales) pool leaves (NB, BS, KV); dequantization is
    fused into the attention read and the returned rows are quantized —
    (k_q, k_s, v_q, v_s) — ready for the pool commit."""
    from repro.kernels import ops as _kops

    k_pages, v_pages = pages
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    kv_heads = cfg.num_kv_heads
    pos = jnp.asarray(pos, jnp.int32)
    q, k_new, v_new = _qkv(p, x[:, None, :], cfg, _pos_rows(pos, b))
    g = cfg.num_heads // kv_heads
    t = block_table.shape[1] * k_pages.shape[1]
    # pool sharding: kv heads -> model axis; the block axis is NEVER sharded
    # (blocks migrate between requests; dist/sharding.py `_pages` rule)
    tp_kv = kv_heads % max(logical.size("tp"), 1) == 0
    pspec = (None, None, "tp" if tp_kv else None, None)
    k_pages = logical.constrain(k_pages, *pspec)
    v_pages = logical.constrain(v_pages, *pspec)
    k_scales = v_scales = None
    if scales is not None:
        k_scales, v_scales = scales
        k_scales = logical.constrain(k_scales, *pspec[:-1])
        v_scales = logical.constrain(v_scales, *pspec[:-1])
    qg = q.reshape(b, kv_heads, g, hd)
    mask = _flag_decode_mask(t, pos, window, use_window)       # (b, t)
    ctx = _kops.paged_attention(
        qg, k_pages, v_pages, block_table, pos, k_new[:, 0], v_new[:, 0],
        mask, scale=_gqa_scale(cfg), softcap=cfg.attn_logit_softcap or None,
        k_scales=k_scales, v_scales=v_scales,
    )
    ctx = logical.constrain(ctx, "dp", None)
    kvq = cfg.kv_quant
    if kvq:
        kq, ks = _quantize_rows(k_new[:, 0], kvq)              # (b,KV,hd)/(b,KV)
        vq, vs = _quantize_rows(v_new[:, 0], kvq)
        return linear(p["wo"], ctx), (kq, ks, vq, vs)
    return linear(p["wo"], ctx), (k_new[:, 0], v_new[:, 0])


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig) -> dict:
    m = cfg.mla
    dt = cfg.pdtype()
    keys = jax.random.split(key, 5)
    h = cfg.num_heads
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    p = {
        # fused latent-kv + rope-k projection (paper C4 fusion style)
        "wdkv": dense_init(keys[0], m.kv_lora_rank + m.qk_rope_dim, cfg.d_model, dt),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dt),
        "wukv": dense_init(keys[1], h * (m.qk_nope_dim + m.v_head_dim), m.kv_lora_rank, dt),
        "wo": dense_init(keys[2], cfg.d_model, h * m.v_head_dim, dt),
    }
    if m.q_lora_rank:
        p["wdq"] = dense_init(keys[3], m.q_lora_rank, cfg.d_model, dt)
        p["q_norm"] = jnp.ones((m.q_lora_rank,), dt)
        p["wuq"] = dense_init(keys[4], h * qk_dim, m.q_lora_rank, dt)
    else:
        p["wq"] = dense_init(keys[3], h * qk_dim, cfg.d_model, dt)
    return p


def _mla_q(p, x, cfg: ModelConfig, positions):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    if m.q_lora_rank:
        q = linear(p["wuq"], rmsnorm(linear(p["wdq"], x), p["q_norm"], cfg.norm_eps))
    else:
        q = linear(p["wq"], x)
    q = q.reshape(b, s, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(p, x, cfg: ModelConfig, positions):
    m = cfg.mla
    c = linear(p["wdkv"], x)
    c_kv, k_rope = c[..., : m.kv_lora_rank], c[..., m.kv_lora_rank:]
    c_kv = rmsnorm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_rope


def _mla_scale(m) -> float:
    return (m.qk_nope_dim + m.qk_rope_dim) ** -0.5


def mla_forward(p, x, cfg: ModelConfig, *, window=None, lengths=None):
    """Naive (materialized) MLA for training/prefill. ``lengths`` (b,) masks
    right-pad keys per row (ragged prefill)."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    positions = jnp.arange(s)[None, :]
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c_kv, k_rope = _mla_latent(p, x, cfg, positions)
    kv = linear(p["wukv"], c_kv).reshape(b, s, h, m.qk_nope_dim + m.v_head_dim)
    k_nope, v = kv[..., : m.qk_nope_dim], kv[..., m.qk_nope_dim:]
    tp = logical.size("tp")
    mode = "head" if h % tp == 0 else ("seq" if s % tp == 0 else "none")
    hspec = ("dp", None, "tp" if mode == "head" else None, None)
    q_nope = logical.constrain(q_nope, *hspec)
    k_nope = logical.constrain(k_nope, *hspec)
    v = logical.constrain(v, *hspec)
    scores = (
        jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
        + jnp.einsum("bshd,btd->bhst", q_rope, k_rope)
    ).astype(jnp.float32) * _mla_scale(m)
    sspec = {"head": ("dp", "tp", None, None), "seq": ("dp", None, "tp", None),
             "none": ("dp", None, None, None)}[mode]
    scores = logical.constrain(scores, *sspec)
    mask = causal_mask(s, window)
    if lengths is not None:
        mask = mask[None] + length_mask(lengths, s)[:, None, :]       # (b, s, s)
        mask = mask[:, None]                                          # (b, 1, s, s)
    scores = scores + mask
    attn = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    attn = logical.constrain(attn, *sspec)
    ctx = jnp.einsum("bhst,bthd->bshd", attn, v).reshape(b, s, h * m.v_head_dim)
    ctx = logical.constrain(ctx, "dp", None, "tp" if mode == "head" else None)
    return linear(p["wo"], ctx)


def mla_prefill(p, x, cfg: ModelConfig, cache_len: int, *, window=None, lengths=None):
    """Cache = (c_kv, k_rope): the low-rank latent (MLA's memory saving).
    ``lengths`` (b,): mask + zero right-pad latent rows (see gqa_prefill)."""
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    y = mla_forward(p, x, cfg, window=window, lengths=lengths)
    c_kv, k_rope = _mla_latent(p, x, cfg, positions)
    if lengths is not None:
        valid = (jnp.arange(s)[None, :] < lengths[:, None])[..., None]
        c_kv = jnp.where(valid, c_kv, 0)
        k_rope = jnp.where(valid, k_rope, 0)
    pad = [(0, 0), (0, cache_len - s), (0, 0)]
    return y, (jnp.pad(c_kv, pad), jnp.pad(k_rope, pad))


def _maybe_dequant(w):
    return w.dequantize() if isinstance(w, (QuantizedTensor, LayerView)) else w


def mla_decode_deferred(p, x, cache, pos, cfg: ModelConfig, *, window=None):
    """Absorbed MLA decode WITHOUT writing the latent cache: attends over the
    read-only cache (slot ``pos`` still zero) plus the current latent row and
    returns (c_new, r_new) for a single donated commit after the layer scan
    (same dataflow as gqa_decode_deferred)."""
    m = cfg.mla
    b = x.shape[0]
    h = cfg.num_heads
    c_cache, r_cache = cache                        # (b,T,kvr) / (b,T,rope)
    t = c_cache.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    positions = _pos_rows(pos, b)
    q_nope, q_rope = _mla_q(p, x[:, None, :], cfg, positions)
    c_new, r_new = _mla_latent(p, x[:, None, :], cfg, positions)   # (b,1,.)

    seq_sz = logical.size("seq")
    if b == 1 and seq_sz > 1 and t % seq_sz == 0:
        t_ax, b_ax = "seq", None
    else:
        t_ax = "tp" if t % max(logical.size("tp"), 1) == 0 else None
        b_ax = "dp"
    c_cache = logical.constrain(c_cache, b_ax, t_ax, None)
    r_cache = logical.constrain(r_cache, b_ax, t_ax, None)

    wukv = _maybe_dequant(p["wukv"]).reshape(h, m.qk_nope_dim + m.v_head_dim, m.kv_lora_rank)
    wuk, wuv = wukv[:, : m.qk_nope_dim, :], wukv[:, m.qk_nope_dim :, :]
    q_abs = jnp.einsum("bhd,hdc->bhc", q_nope[:, 0], wuk.astype(x.dtype))
    scores = (
        jnp.einsum("bhc,btc->bht", q_abs, c_cache)
        + jnp.einsum("bhd,btd->bht", q_rope[:, 0], r_cache)
    ).astype(jnp.float32)
    cur = (
        jnp.einsum("bhc,bc->bh", q_abs, c_new[:, 0])
        + jnp.einsum("bhd,bd->bh", q_rope[:, 0], r_new[:, 0])
    ).astype(jnp.float32)
    scores = _col_update(scores, cur, pos)
    scores = logical.constrain(scores, b_ax, None, t_ax)
    dm = decode_mask(t, pos, window)
    scores = scores * _mla_scale(m) + (dm[None, None, :] if dm.ndim == 1 else dm[:, None, :])
    attn = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    # cache slot at pos is zero -> its contribution is the explicit term
    ctx = jnp.einsum("bht,btc->bhc", attn, c_cache)
    attn_cur = _col_at(attn, pos)
    ctx = ctx + attn_cur * c_new[:, 0][:, None, :]
    out = jnp.einsum("bhc,hvc->bhv", ctx, wuv.astype(x.dtype)).reshape(b, h * m.v_head_dim)
    return linear(p["wo"], out), (c_new, r_new)


def mla_decode(p, x, cache, pos, cfg: ModelConfig, *, window=None):
    """Absorbed-matrix decode: attends directly over the latent cache without
    materializing per-position K/V (beyond-paper efficiency; the on-the-fly
    dequantization of wukv mirrors what the GQMV kernel does in VMEM)."""
    m = cfg.mla
    b = x.shape[0]
    h = cfg.num_heads
    c_cache, r_cache = cache                       # (b,T,kvr), (b,T,rope)
    pos = jnp.asarray(pos, jnp.int32)
    positions = _pos_rows(pos, b)
    q_nope, q_rope = _mla_q(p, x[:, None, :], cfg, positions)
    c_kv, k_rope = _mla_latent(p, x[:, None, :], cfg, positions)
    c_cache = _commit_bt(c_cache, c_kv, pos)
    r_cache = _commit_bt(r_cache, k_rope, pos)

    wukv = _maybe_dequant(p["wukv"]).reshape(h, m.qk_nope_dim + m.v_head_dim, m.kv_lora_rank)
    wuk, wuv = wukv[:, : m.qk_nope_dim, :], wukv[:, m.qk_nope_dim :, :]
    c_cache = logical.constrain(c_cache, "dp", "tp", None)   # latent cache: seq-parallel
    r_cache = logical.constrain(r_cache, "dp", "tp", None)
    q_abs = jnp.einsum("bhd,hdc->bhc", q_nope[:, 0], wuk.astype(x.dtype))
    scores = (
        jnp.einsum("bhc,btc->bht", q_abs, c_cache)
        + jnp.einsum("bhd,btd->bht", q_rope[:, 0], r_cache)
    ).astype(jnp.float32) * _mla_scale(m)
    scores = logical.constrain(scores, "dp", None, "tp")
    dm = decode_mask(c_cache.shape[1], pos, window)
    scores = scores + (dm[None, None, :] if dm.ndim == 1 else dm[:, None, :])
    attn = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    attn = logical.constrain(attn, "dp", None, "tp")
    ctx = jnp.einsum("bht,btc->bhc", attn, c_cache)
    out = jnp.einsum("bhc,hvc->bhv", ctx, wuv.astype(x.dtype)).reshape(b, h * m.v_head_dim)
    return linear(p["wo"], out), (c_cache, r_cache)
