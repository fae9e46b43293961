"""Plain float32 reference of the dense decoder, for the ``correct`` check.

The dense layout's reference hook (``bench/arch/dense.py``); a
configuration of another layout gets its reference from its own layout
file, which may reuse the helpers here. ``teacher_forced`` and ``gaps``
serve every layout.

A Llama-style GQA decoder written straight from its equations: token
embedding, then per layer RMSNorm -> q/k/v projections -> rotary position
embedding (rotate-half, base ``rope_theta``) -> causal attention where each
group of ``num_attention_heads / num_key_value_heads`` query heads shares
one key/value head -> output projection -> residual add; RMSNorm -> SwiGLU
MLP (``silu(gate) * up`` -> down) -> residual add; then a final RMSNorm and
the output head. Every matrix product runs in float32 at
``Precision.HIGHEST``.

Weights are the dequantized int8 weights of ``bench/weights.py``, made
again here from the run's seed, one layer at a time, so that the reference
fits on the chip beside nothing else. It imports nothing of the program
and takes nothing the program made.

Departures from the published models, followed on purpose because the
served program makes them too (each configuration file lists them):
no RoPE scaling (deepseek-coder-33b publishes linear scaling by 4); the
weights are quantized (int8, group 256), which is the served format.

``weight_bits=4`` gives the control: every weight matrix re-quantized to
int4 (groups of 256, symmetric, [-7, 7]) before use, the step below the
int8 the configuration states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

_HI = jax.lax.Precision.HIGHEST


def _mm(x, w):
    """x (..., in) @ w (out, in)^T in float32 at full precision."""
    return jnp.einsum("...i,oi->...o", x, w, precision=_HI,
                      preferred_element_type=jnp.float32)


def _rmsnorm(x, w, eps):
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * inv * w.astype(jnp.float32)


def _requantize(w, group_size: int, qmax: int):
    """Symmetric group-wise re-quantization of an f32 matrix to
    [-qmax, qmax] (the control's lower precision)."""
    m, n = w.shape
    g = w.reshape(m, n // group_size, group_size)
    s = jnp.max(jnp.abs(g), axis=-1, keepdims=True) * (2.0 / (2 * qmax + 1))
    s = jnp.where(s > 0, s, 1.0)
    return (jnp.clip(jnp.round(g / s), -qmax, qmax) * s).reshape(m, n)


def _rope(x, theta: float):
    """Rotate-half rotary embedding. x (T, heads, hd) at positions 0..T-1."""
    t, _, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = np.arange(t, dtype=np.float32)[:, None] * inv[None, :]
    ang = jnp.asarray(np.concatenate([ang, ang], axis=-1))[:, None, :]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


class Reference:
    """Layer-by-layer f32 forward over teacher-forced sequences.

    ``logits(seed, tokens, rows)`` runs ``tokens`` (n, T) through the
    model and returns the logits (M, vocab) at the (sequence, position)
    pairs ``rows`` (M, 2). Sequences are right-padded to T; the causal mask
    keeps the padding out of every position that is read."""

    def __init__(self, cfg: dict, *, weight_bits: int = 8):
        if weight_bits not in (8, 4):
            raise ValueError(f"weight_bits must be 8 or 4, got {weight_bits}")
        self.cfg = cfg
        self.qmax = None if weight_bits == 8 else 7
        self._embed = jax.jit(self._embed_fn)
        self._layer = jax.jit(self._layer_fn)
        self._head = jax.jit(self._head_fn)

    def _weight(self, q, s):
        w = W.dequantize(q, s)
        if self.qmax is not None:
            w = _requantize(w, self.cfg["group_size"], self.qmax)
        return w

    def _embed_fn(self, wkey, tokens):
        q, s = W.embedding(wkey, self.cfg)
        if self.qmax is None:
            # gather rows first: the whole table in f32 is not needed
            rows_q, rows_s = q[tokens], s[tokens]
            gs = self.cfg["group_size"]
            g = rows_q.reshape(*tokens.shape, -1, gs).astype(jnp.float32)
            return (g * rows_s[..., None]).reshape(*tokens.shape, -1)
        return self._weight(q, s)[tokens]

    def _attention(self, q, k, v):
        """One sequence: q (T, H, hd), k/v (T, KV, hd) -> (T, H * hd)."""
        t, h, hd = q.shape
        group = h // k.shape[1]
        k = jnp.repeat(k, group, axis=1)          # query head i -> kv head i // group
        v = jnp.repeat(v, group, axis=1)
        scores = jnp.einsum("shd,thd->hst", q, k, precision=_HI) * hd ** -0.5
        causal = np.tril(np.ones((t, t), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hst,thd->shd", p, v, precision=_HI).reshape(t, h * hd)

    def _layer_fn(self, wkey, layer, x):
        cfg = self.cfg
        lw = W.layer_weights(wkey, layer, cfg)
        w = {name: self._weight(*lw[name]) for name in W.PROJECTIONS}
        eps, hd = cfg["rms_norm_eps"], cfg["head_dim"]
        n, t, _ = x.shape

        h = _rmsnorm(x, lw["att_norm"], eps)
        q = _mm(h, w["wq"]).reshape(n, t, -1, hd)
        k = _mm(h, w["wk"]).reshape(n, t, -1, hd)
        v = _mm(h, w["wv"]).reshape(n, t, -1, hd)

        def one(args):
            qi, ki, vi = args
            theta = cfg["rope_theta"]
            return self._attention(_rope(qi, theta), _rope(ki, theta), vi)

        ctx = jax.lax.map(one, (q, k, v))         # one sequence at a time
        x = x + _mm(ctx, w["wo"])
        h = _rmsnorm(x, lw["ffn_norm"], eps)
        return x + _mm(jax.nn.silu(_mm(h, w["w_gate"])) * _mm(h, w["w_up"]),
                       w["w_down"])

    def _head_fn(self, wkey, x, rows):
        h = x[rows[:, 0], rows[:, 1]]
        h = _rmsnorm(h, W.final_norm(wkey, self.cfg), self.cfg["rms_norm_eps"])
        return _mm(h, self._weight(*W.classifier(wkey, self.cfg)))

    def logits(self, seed: int, tokens: np.ndarray, rows: np.ndarray) -> jax.Array:
        wkey = W.weight_key(seed)
        x = self._embed(wkey, jnp.asarray(tokens, jnp.int32))
        for layer in range(self.cfg["num_hidden_layers"]):
            x = self._layer(wkey, jnp.int32(layer), x)
        return self._head(wkey, x, jnp.asarray(rows, jnp.int32))


def teacher_forced(prompts, served, *, sequences: int = 0, length: int = 0,
                   multiple: int = 512, row_multiple: int = 1024):
    """Inputs for scoring served tokens: each prompt followed by all but its
    last served token, right-padded to at least ``sequences`` rows of at
    least ``length`` tokens, a multiple of ``multiple``; the (sequence,
    position) rows whose logits chose each served token, with the served
    tokens themselves in the same order, both padded to a multiple of
    ``row_multiple`` with copies of the first (which leave every maximum
    as it is); and the number of real rows. Fixed sizes give the reference
    one compiled program per cell, not one per sample."""
    lengths = [len(p) + len(s) - 1 for p, s in zip(prompts, served)]
    t = -(-max(max(lengths), length) // multiple) * multiple
    tokens = np.zeros((max(len(prompts), sequences), t), np.int32)
    rows, targets = [], []
    for i, (p, s) in enumerate(zip(prompts, served)):
        seq = list(p) + list(s[:-1])
        tokens[i, : len(seq)] = seq
        rows += [(i, len(p) - 1 + j) for j in range(len(s))]
        targets += list(s)
    n = len(rows)
    pad = -(-n // row_multiple) * row_multiple - n
    rows, targets = rows + rows[:1] * pad, targets + targets[:1] * pad
    return tokens, np.asarray(rows, np.int32), np.asarray(targets, np.int32), n


@jax.jit
def gaps(ref_logits, tokens):
    """How far each chosen token's reference logit lies below the
    reference's best at its position (0 where they agree)."""
    chosen = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return jnp.max(ref_logits, axis=-1) - chosen
