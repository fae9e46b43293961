"""Decoder-only transformer LM covering the dense / MoE / MLA / VLM archs:
internlm2, deepseek-coder-33b, pixtral-12b (backbone), gemma2-2b,
minicpm3-4b, dbrx-132b, deepseek-v2-lite-16b, plus the paper's TinyLlama.

Layers are homogeneous and stacked: init via vmap, forward via lax.scan
(keeps HLO size O(1) in depth — essential for the 62-layer dry-runs).
Per-layer local/global alternation (gemma2) is a scanned boolean driving the
mask, not a structural branch. The serving forwards walk the stack by layer
index (``_walk_layers``) so the GQMM kernel reads each layer's quantized
weights in place.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import flags
from repro.core.qlinear import embedding_lookup, linear
from repro.core.quant import LayerView, QuantizedTensor, layer_of
from repro.dist import logical
from repro.models import attention as attn
from repro.models import mlp as mlpmod
from repro.models.common import dense_init, embed_init, rmsnorm, softcap


def _layer_windows(cfg: ModelConfig) -> jax.Array:
    """(L,) bool: True where the layer uses the sliding window (gemma2 'L')."""
    if not cfg.layer_pattern or not cfg.sliding_window:
        return jnp.zeros((cfg.num_layers,), jnp.bool_)
    pat = (cfg.layer_pattern * cfg.num_layers)[: cfg.num_layers]
    return jnp.asarray([c == "L" for c in pat])


def _layer_view(layers, l):
    """Layer ``l`` of the stacked ``layers`` tree. A quantized (L, m, n)
    matrix becomes a LayerView that the GQMM kernel reads in place; every
    other leaf (norm gains, float weights, MoE expert stacks) is indexed,
    as scanning over it would slice it."""
    def one(leaf):
        if isinstance(leaf, QuantizedTensor) and leaf.qvalues.ndim == 3:
            return LayerView(leaf, l)
        return jax.tree.map(lambda a: layer_of(a, l), leaf)

    return jax.tree.map(one, layers,
                        is_leaf=lambda x: isinstance(x, QuantizedTensor))


def _walk_layers(body, x, params, cfg: ModelConfig, *per_layer):
    """Scan ``body(x, lp, use_window, *layer_slices) -> (x, out)`` over the
    layers by index: ``lp`` is ``_layer_view`` of ``params["layers"]``,
    which stays whole, while ``per_layer`` arrays (a cache or pool) are
    scanned and arrive sliced. Returns (x, stacked outs).

    The training forward keeps scanning the stack itself: the gradient of
    an indexed read would scatter into the whole stack at every layer."""
    layers = params["layers"]

    def step(carry, scanned):
        x, l = carry
        use_window, *rest = scanned
        x, out = body(x, _layer_view(layers, l), use_window, *rest)
        return (x, l + 1), out

    (x, _), outs = jax.lax.scan(step, (x, jnp.int32(0)),
                                (_layer_windows(cfg), *per_layer))
    return x, outs


def init_layer(key, cfg: ModelConfig) -> dict:
    ka, km = jax.random.split(key)
    dt = cfg.pdtype()
    p = {
        "att_norm": jnp.ones((cfg.d_model,), dt) * (0.0 if cfg.gemma_norms else 1.0),
        "attn": attn.init_mla(ka, cfg) if cfg.mla else attn.init_gqa(ka, cfg),
        "ffn_norm": jnp.ones((cfg.d_model,), dt) * (0.0 if cfg.gemma_norms else 1.0),
        "mlp": mlpmod.init_moe(km, cfg) if cfg.moe else mlpmod.init_mlp(km, cfg),
    }
    if cfg.gemma_norms:
        p["post_att_norm"] = jnp.zeros((cfg.d_model,), dt)
        p["post_ffn_norm"] = jnp.zeros((cfg.d_model,), dt)
    return p


def init_lm(key, cfg: ModelConfig) -> dict:
    ke, kl, kc = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.num_layers)
    params = {
        "embed": embed_init(ke, cfg.vocab_padded, cfg.d_model, cfg.pdtype()),
        "layers": jax.vmap(lambda k: init_layer(k, cfg))(layer_keys),
        "final_norm": jnp.ones((cfg.d_model,), cfg.pdtype()) * (0.0 if cfg.gemma_norms else 1.0),
    }
    if not cfg.tie_embeddings:
        params["classifier"] = dense_init(kc, cfg.vocab_padded, cfg.d_model, cfg.pdtype())
    return params


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

def _embed(params, tokens, cfg: ModelConfig, frontend_embeds=None):
    x = embedding_lookup(params["embed"], tokens, cfg.cdtype())
    if cfg.gemma_norms:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
    if frontend_embeds is not None:
        # VLM stub (pixtral): precomputed patch embeddings replace the first
        # P positions of the sequence (input_specs supplies them).
        pfx = frontend_embeds.astype(x.dtype)
        x = jnp.concatenate([pfx, x[:, pfx.shape[1] :, :]], axis=1)
    return logical.constrain(x, *(["dp"] + [None] * (x.ndim - 1)))


def _logits(params, x, cfg: ModelConfig):
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps, plus_one=cfg.gemma_norms)
    w = params["embed"] if cfg.tie_embeddings else params["classifier"]
    logits = linear(w, x)
    logits = logical.constrain(logits, *(["dp"] + [None] * (logits.ndim - 2) + ["tp"]))
    if cfg.final_logit_softcap:
        logits = softcap(logits, cfg.final_logit_softcap)
    return logits


def _block(lp, x, cfg: ModelConfig, attn_fn):
    """One residual block given an attention closure; shared by all paths."""
    g = cfg.gemma_norms
    h = rmsnorm(x, lp["att_norm"], cfg.norm_eps, plus_one=g)
    a = attn_fn(h)
    if g:
        a = rmsnorm(a, lp["post_att_norm"], cfg.norm_eps, plus_one=True)
    x = x + a
    h = rmsnorm(x, lp["ffn_norm"], cfg.norm_eps, plus_one=g)
    f = mlpmod.moe_forward(lp["mlp"], h, cfg) if cfg.moe else mlpmod.mlp_forward(lp["mlp"], h)
    if g:
        f = rmsnorm(f, lp["post_ffn_norm"], cfg.norm_eps, plus_one=True)
    return x + f


# ---------------------------------------------------------------------------
# training / scoring forward
# ---------------------------------------------------------------------------

def lm_forward(params, tokens, cfg: ModelConfig, frontend_embeds=None, *, remat=True):
    """tokens (b, s) -> logits (b, s, vocab_padded)."""
    x = _embed(params, tokens, cfg, frontend_embeds)
    windows = _layer_windows(cfg)

    def body(x, scanned):
        lp, use_window = scanned

        def attn_fn(h):
            if cfg.mla:
                return attn.mla_forward(lp["attn"], h, cfg)
            return attn.gqa_forward(
                lp["attn"], h, cfg, window=cfg.sliding_window, use_window=use_window
            )

        return _block(lp, x, cfg, attn_fn), None

    body_fn = jax.checkpoint(body) if remat else body
    x, _ = jax.lax.scan(body_fn, x, (params["layers"], windows))
    return _logits(params, x, cfg)


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def lm_init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype):
    if cfg.mla:
        return {
            "ckv": jnp.zeros((cfg.num_layers, batch, cache_len, cfg.mla.kv_lora_rank), dtype),
            "krope": jnp.zeros((cfg.num_layers, batch, cache_len, cfg.mla.qk_rope_dim), dtype),
        }
    hd = cfg.resolved_head_dim
    kvq = attn.kv_quant_format(cfg)
    if kvq:
        sdt = attn.KV_STORE_DTYPES[kvq]
        qshape = (cfg.num_layers, batch, cfg.num_kv_heads, cache_len, hd)
        sshape = (cfg.num_layers, batch, cfg.num_kv_heads, cache_len)
        return {"k_q": jnp.zeros(qshape, sdt), "k_s": jnp.zeros(sshape, jnp.float32),
                "v_q": jnp.zeros(qshape, sdt), "v_s": jnp.zeros(sshape, jnp.float32)}
    if flags.get("kvt_cache_layout"):
        shape = (cfg.num_layers, batch, cfg.num_kv_heads, cache_len, hd)
    else:
        shape = (cfg.num_layers, batch, cache_len, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def lm_insert_slots(cache, rows, slots):
    """Scatter per-request prefill cache ``rows`` into decode ``slots`` of a
    batched contiguous cache. Every decoder_lm cache layout — base, MLA,
    kvt, int8 quantized — keeps batch on axis 1 of each (layers, b, ...)
    leaf, so one axis-1 scatter covers them all (the serving core's
    slot-admission contract, serving/core.py)."""
    return jax.tree.map(
        lambda big, small: big.at[:, slots].set(small), cache, rows
    )


def lm_gather_slots(cache, slots):
    """Inverse of ``lm_insert_slots``: the per-slot cache rows for ``slots``
    (snapshot/preemption path)."""
    return jax.tree.map(lambda big: big[:, slots], cache)


def lm_init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int, dtype):
    """Block-pool KV cache: (L, NB, BS, KV, hd) leaves named ``*_pages`` so
    the sharding policy can keep the block axis whole (dist/sharding.py).
    Block 0 is conventionally the allocator's write-off sink
    (serving/paged.py); the paged attention path never reads an unmasked
    stale slot, so pool memory is recycled without zeroing."""
    if cfg.mla:
        raise ValueError(
            f"{cfg.arch_id}: paged KV cache covers the GQA layouts; the MLA "
            "latent cache keeps the contiguous path (supports_paged=False)"
        )
    hd = cfg.resolved_head_dim
    shape = (cfg.num_layers, num_blocks, block_size, cfg.num_kv_heads, hd)
    kvq = attn.kv_quant_format(cfg)
    if kvq:
        # quantized pool: blocks at storage width plus per-row f32 scale
        # leaves named ``*_scales`` (group = head_dim; dist/sharding.py keeps
        # the block axis whole and puts KV heads on the model axis)
        sdt = attn.KV_STORE_DTYPES[kvq]
        sshape = shape[:-1]
        return {"k_pages": jnp.zeros(shape, sdt),
                "k_scales": jnp.zeros(sshape, jnp.float32),
                "v_pages": jnp.zeros(shape, sdt),
                "v_scales": jnp.zeros(sshape, jnp.float32)}
    return {"k_pages": jnp.zeros(shape, dtype), "v_pages": jnp.zeros(shape, dtype)}


def lm_decode_paged(params, token, cache, block_table, pos, cfg: ModelConfig):
    """One paged decode step. token (b,) int32; cache the ``*_pages`` block
    pool; block_table (b, MB) int32 physical block ids per virtual block;
    pos (b,) int32 virtual positions. Returns (logits, new cache).

    Always deferred: the layer scan emits only the new K/V rows, committed
    after the scan with one scatter at each row's (physical block, offset)
    (attention.commit_layers_paged). The attention reads the pool through
    the block table (Pallas kernel on TPU, gather oracle elsewhere)."""
    if flags.get("kvt_cache_layout") or flags.get("int8_kv_cache"):
        raise ValueError("paged KV cache supports the base float KV layout "
                         "(kvt_cache_layout / int8_kv_cache flags off)")
    kvq = attn.kv_quant_format(cfg)
    pos = jnp.asarray(pos, jnp.int32)
    if not pos.ndim:
        pos = jnp.full((token.shape[0],), pos, jnp.int32)
    x = embedding_lookup(params["embed"], token, cfg.cdtype())
    if cfg.gemma_norms:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)

    def body(x, lp, use_window, layer_cache):
        new_cache = {}

        def attn_fn(h):
            scales = ((layer_cache["k_scales"], layer_cache["v_scales"])
                      if kvq else None)
            y, rows = attn.gqa_decode_paged(
                lp["attn"], h, (layer_cache["k_pages"], layer_cache["v_pages"]),
                block_table, pos, cfg,
                window=cfg.sliding_window, use_window=use_window,
                scales=scales,
            )
            if kvq:
                (new_cache["k"], new_cache["k_s"],
                 new_cache["v"], new_cache["v_s"]) = rows
            else:
                new_cache["k"], new_cache["v"] = rows
            return y

        g = cfg.gemma_norms
        h = rmsnorm(x, lp["att_norm"], cfg.norm_eps, plus_one=g)
        a = attn_fn(h)
        if g:
            a = rmsnorm(a, lp["post_att_norm"], cfg.norm_eps, plus_one=True)
        x = x + a
        h = rmsnorm(x, lp["ffn_norm"], cfg.norm_eps, plus_one=g)
        if cfg.moe:
            f = mlpmod.moe_forward(lp["mlp"], h[:, None, :], cfg)[:, 0, :]
        else:
            f = mlpmod.mlp_forward(lp["mlp"], h)
        if g:
            f = rmsnorm(f, lp["post_ffn_norm"], cfg.norm_eps, plus_one=True)
        return x + f, new_cache

    x, new_rows = _walk_layers(body, x, params, cfg, cache)
    new_cache = {
        "k_pages": attn.commit_layers_paged(cache["k_pages"], new_rows["k"],
                                            block_table, pos),
        "v_pages": attn.commit_layers_paged(cache["v_pages"], new_rows["v"],
                                            block_table, pos),
    }
    if kvq:
        # scale rows (L, b, KV) land in the (L, NB, BS, KV) scale pool at the
        # same (physical block, offset) as their quantized rows
        new_cache["k_scales"] = attn.commit_layers_paged(
            cache["k_scales"], new_rows["k_s"], block_table, pos)
        new_cache["v_scales"] = attn.commit_layers_paged(
            cache["v_scales"], new_rows["v_s"], block_table, pos)
    return _logits(params, x, cfg), new_cache


# ---------------------------------------------------------------------------
# speculative-verify: k-token chunked decode (DESIGN.md §10)
# ---------------------------------------------------------------------------

def _verify_embed(params, tokens, cfg: ModelConfig):
    x = embedding_lookup(params["embed"], tokens, cfg.cdtype())
    if cfg.gemma_norms:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)
    return x


def _check_verify_layout(cfg: ModelConfig):
    if cfg.mla:
        raise ValueError(
            f"{cfg.arch_id}: speculative verify covers the GQA layouts; the "
            "MLA latent cache keeps the single-token path (supports_spec=False)"
        )
    if flags.get("kvt_cache_layout") or attn.kv_quant_format(cfg):
        raise ValueError("speculative verify supports the base float KV "
                         "layout (kvt_cache_layout / int8_kv_cache flags and "
                         "kv_quant off)")


def lm_verify(params, tokens, cache, pos, cfg: ModelConfig):
    """Chunked multi-token decode for speculative verification. tokens
    (b, k) int32 — the current token followed by k-1 drafted candidates;
    cache the contiguous {k, v} layout (slots >= pos zero); pos (b,) or
    scalar int32 virtual position of tokens[:, 0]. Returns
    (logits (b, k, vocab_padded), rows {k, v} (L, b, k, KV, hd)).

    The cache is NOT written: row j attends over committed history plus
    chunk rows 0..j (intra-chunk causal, scattered into the columns the
    sequential decode would occupy), and the caller commits only the
    accepted prefix with :func:`lm_commit_verify` — one forward pass
    streams each weight block once for up to k tokens (the GQMM
    amortization LlamaF §II-B prices per token)."""
    _check_verify_layout(cfg)
    pos = jnp.asarray(pos, jnp.int32)
    if not pos.ndim:
        pos = jnp.full((tokens.shape[0],), pos, jnp.int32)
    x = _verify_embed(params, tokens, cfg)

    def body(x, lp, use_window, layer_cache):
        rows = {}

        def attn_fn(h):
            y, (k, v) = attn.gqa_verify_deferred(
                lp["attn"], h, (layer_cache["k"], layer_cache["v"]), pos, cfg,
                window=cfg.sliding_window, use_window=use_window,
            )
            rows["k"], rows["v"] = k, v
            return y

        return _block(lp, x, cfg, attn_fn), rows

    x, rows = _walk_layers(body, x, params, cfg, cache)
    return _logits(params, x, cfg), rows


def lm_commit_verify(cache, rows, pos, n_commit):
    """Commit the accepted prefix of a verify chunk: rows[:, :, :n_commit[b]]
    land at positions pos[b]..pos[b]+n_commit[b]-1; rejected rows are
    DROPPED (redirected out of bounds), so the cache is bit-identical to a
    trajectory that never drafted them — rollback is ``pos + n_commit``."""
    return {
        "k": attn.commit_layers_verify(cache["k"], rows["k"], pos, n_commit),
        "v": attn.commit_layers_verify(cache["v"], rows["v"], pos, n_commit),
    }


def lm_verify_paged(params, tokens, cache, block_table, pos, cfg: ModelConfig):
    """Paged sibling of :func:`lm_verify`: the chunk attends through each
    row's block table over the ``*_pages`` pool (kernels/ops.py
    ``paged_verify``). Same return contract; commit via
    :func:`lm_commit_verify_paged` (rejected rows dropped out of bounds)."""
    _check_verify_layout(cfg)
    pos = jnp.asarray(pos, jnp.int32)
    if not pos.ndim:
        pos = jnp.full((tokens.shape[0],), pos, jnp.int32)
    x = _verify_embed(params, tokens, cfg)

    def body(x, lp, use_window, layer_cache):
        rows = {}

        def attn_fn(h):
            y, (k, v) = attn.gqa_verify_paged(
                lp["attn"], h, (layer_cache["k_pages"], layer_cache["v_pages"]),
                block_table, pos, cfg,
                window=cfg.sliding_window, use_window=use_window,
            )
            rows["k"], rows["v"] = k, v
            return y

        return _block(lp, x, cfg, attn_fn), rows

    x, rows = _walk_layers(body, x, params, cfg, cache)
    return _logits(params, x, cfg), rows


def lm_commit_verify_paged(cache, rows, block_table, pos, n_commit):
    return {
        "k_pages": attn.commit_layers_paged_verify(
            cache["k_pages"], rows["k"], block_table, pos, n_commit),
        "v_pages": attn.commit_layers_paged_verify(
            cache["v_pages"], rows["v"], block_table, pos, n_commit),
    }


def contiguous_to_paged(cache, block_size: int):
    """Reshape a contiguous (L, b, T, KV, hd) cache into a block pool plus
    the identity block tables: row i owns blocks [i*MB, (i+1)*MB). T must be
    a multiple of ``block_size``. The paged decode over this pool is
    bit-exact against the contiguous deferred path (tests/test_paged.py).

    A quantized contiguous cache ({k_q, k_s, v_q, v_s}, kvt layout
    (L, b, KV, T, ...)) maps to the quantized pool layout
    ({k_pages, k_scales, v_pages, v_scales}, time-major blocks)."""
    if "k_q" in cache:
        kq = cache["k_q"]                                 # (L, b, KV, T, hd)
        L, b, _, t = kq.shape[:4]
        if t % block_size:
            raise ValueError(f"cache_len {t} not a multiple of block_size {block_size}")
        mb = t // block_size

        def pool_kvt(leaf):                               # (L,b,KV,T,...) -> blocks
            x = jnp.moveaxis(leaf, 3, 2)                  # (L,b,T,KV,...)
            return x.reshape(L, b * mb, block_size, *x.shape[3:])

        table = jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb)
        return {"k_pages": pool_kvt(kq), "k_scales": pool_kvt(cache["k_s"]),
                "v_pages": pool_kvt(cache["v_q"]),
                "v_scales": pool_kvt(cache["v_s"])}, table
    k = cache["k"]
    L, b, t = k.shape[:3]
    if t % block_size:
        raise ValueError(f"cache_len {t} not a multiple of block_size {block_size}")
    mb = t // block_size
    def pool(leaf):
        return leaf.reshape(L, b * mb, block_size, *leaf.shape[3:])
    table = jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb)
    return {"k_pages": pool(k), "v_pages": pool(cache["v"])}, table


def lm_prefill(params, tokens, cfg: ModelConfig, cache_len: int, frontend_embeds=None,
               lengths=None):
    """Prompt pass: returns (last-position logits, populated cache).

    ``lengths`` (b,) enables ragged right-padded prompts: attention masks pad
    keys (and zeroes their cached K/V rows), and the returned logits are
    gathered per row at position lengths[i]-1 instead of the shared last
    column — the fix for sampling the first token from pad-position logits."""
    x = _embed(params, tokens, cfg, frontend_embeds)

    def body(x, lp, use_window):
        cache_out = {}

        def attn_fn(h):
            if cfg.mla:
                y, (ckv, krope) = attn.mla_prefill(lp["attn"], h, cfg, cache_len,
                                                   lengths=lengths)
                cache_out["ckv"], cache_out["krope"] = ckv, krope
                return y
            out = attn.gqa_prefill(
                lp["attn"], h, cfg, cache_len,
                window=cfg.sliding_window, use_window=use_window, lengths=lengths,
            )
            if attn.kv_quant_format(cfg):
                y, (cache_out["k_q"], cache_out["k_s"],
                    cache_out["v_q"], cache_out["v_s"]) = out
            else:
                y, (cache_out["k"], cache_out["v"]) = out
            return y

        x = _block(lp, x, cfg, attn_fn)
        return x, cache_out

    x, cache = _walk_layers(body, x, params, cfg)
    if lengths is None:
        last = x[:, -1, :]
    else:
        last = x[jnp.arange(x.shape[0]), lengths - 1]
    return _logits(params, last, cfg), cache


def lm_decode(params, token, cache, pos, cfg: ModelConfig):
    """One decode step. token (b,) int32; pos scalar int32 OR (b,) int32
    per-request positions (ragged continuous batching: each row's RoPE
    angle, decode mask, and cache-commit slot follow its own counter).
    Returns (logits (b, vocab_padded), new cache).

    With flags.deferred_decode_cache the layer scan emits only the new K/V
    rows; they are committed with one donated dynamic-update-slice (scalar
    pos) or one per-row scatter (vector pos) at the end (§Perf decode
    optimization)."""
    int8kv = attn.kv_quant_format(cfg) is not None and not cfg.mla
    kvt = (bool(flags.get("kvt_cache_layout")) or int8kv) and not cfg.mla
    deferred = bool(flags.get("deferred_decode_cache")) or kvt or (
        cfg.mla and (flags.get("deferred_decode_cache") or flags.get("kvt_cache_layout")
                     or flags.get("int8_kv_cache"))
    )
    x = embedding_lookup(params["embed"], token, cfg.cdtype())
    if cfg.gemma_norms:
        x = x * jnp.asarray(cfg.d_model**0.5, x.dtype)

    def body(x, lp, use_window, layer_cache):
        new_cache = {}

        def attn_fn(h):
            if cfg.mla:
                mla_fn = attn.mla_decode_deferred if deferred else attn.mla_decode
                y, (ckv, krope) = mla_fn(
                    lp["attn"], h, (layer_cache["ckv"], layer_cache["krope"]), pos, cfg
                )
                new_cache["ckv"], new_cache["krope"] = ckv, krope
                return y
            if int8kv:
                c = (layer_cache["k_q"], layer_cache["k_s"],
                     layer_cache["v_q"], layer_cache["v_s"])
                y, rows = attn.gqa_decode_deferred_quant(
                    lp["attn"], h, c, pos, cfg,
                    window=cfg.sliding_window, use_window=use_window,
                )
                (new_cache["k_q"], new_cache["k_s"],
                 new_cache["v_q"], new_cache["v_s"]) = rows
                return y
            c = (layer_cache["k"], layer_cache["v"])
            decode_fn = attn.gqa_decode_deferred if deferred else attn.gqa_decode
            y, (k, v) = decode_fn(
                lp["attn"], h, c, pos, cfg,
                window=cfg.sliding_window, use_window=use_window,
            )
            new_cache["k"], new_cache["v"] = k, v
            return y

        # decode blocks operate on (b, d): reuse _block via a 1-seq view
        g = cfg.gemma_norms
        h = rmsnorm(x, lp["att_norm"], cfg.norm_eps, plus_one=g)
        a = attn_fn(h)
        if g:
            a = rmsnorm(a, lp["post_att_norm"], cfg.norm_eps, plus_one=True)
        x = x + a
        h = rmsnorm(x, lp["ffn_norm"], cfg.norm_eps, plus_one=g)
        if cfg.moe:
            f = mlpmod.moe_forward(lp["mlp"], h[:, None, :], cfg)[:, 0, :]
        else:
            f = mlpmod.mlp_forward(lp["mlp"], h)
        if g:
            f = rmsnorm(f, lp["post_ffn_norm"], cfg.norm_eps, plus_one=True)
        return x + f, new_cache

    x, new_cache = _walk_layers(body, x, params, cfg, cache)
    if deferred and cfg.mla:
        new_cache = {
            "ckv": attn.commit_layers_bt(cache["ckv"], new_cache["ckv"], pos),
            "krope": attn.commit_layers_bt(cache["krope"], new_cache["krope"], pos),
        }
    elif deferred:
        # commit all layers' new rows with one in-place (donated) update
        if int8kv:
            new_cache = {
                "k_q": attn.commit_layers_bkt(cache["k_q"], new_cache["k_q"], pos),
                "k_s": attn.commit_layers_bkt(cache["k_s"], new_cache["k_s"], pos),
                "v_q": attn.commit_layers_bkt(cache["v_q"], new_cache["v_q"], pos),
                "v_s": attn.commit_layers_bkt(cache["v_s"], new_cache["v_s"], pos),
            }
        else:
            commit = attn.commit_layers_bkt if kvt else attn.commit_layers_bt
            new_cache = {
                "k": commit(cache["k"], new_cache["k"], pos),
                "v": commit(cache["v"], new_cache["v"], pos),
            }
    return _logits(params, x, cfg), new_cache
