"""Seeded model weights at storage width: int8 values with f32 group scales.

Weights are made here, from the run's seed, and never by the program under
test. Each weight matrix (out, in) is drawn directly as int8 values
uniform on [-127, 127] with one f32 scale per group of ``group_size``
inputs, so no float copy of the model ever exists. The dequantized weight
``q * s`` has the standard deviation a ``1/sqrt(in)`` init would give
(embedding: 0.02), with each group's scale jittered by +-25%.

Every leaf of every layer has its own key, ``fold_in(fold_in(key, leaf),
layer)``, so one layer can be made alone (the reference does that, layer
by layer) and gives bit for bit what the whole-model call gives. Plain
JAX; nothing here imports the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# std of int8 values uniform on [-127, 127]: sqrt((255**2 - 1) / 12)
_INT8_STD = 73.6103
EMBED_STD = 0.02

# projection name -> leaf id used in its key; the order is part of the
# weights' definition and must not change
PROJECTIONS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
NORMS = ("att_norm", "ffn_norm")
_LEAF_ID = {n: i for i, n in enumerate(PROJECTIONS + NORMS)}
_EMBED, _CLASSIFIER, _FINAL_NORM = 100, 101, 102


def run_key(seed: int) -> jax.Array:
    """The key every draw of one run derives from. ``seed`` may exceed 32
    bits: its high and low words are folded in separately."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, seed & 0xFFFFFFFF)


def weight_key(seed: int) -> jax.Array:
    return jax.random.fold_in(run_key(seed), 1)


def projection_shapes(cfg: dict) -> dict[str, tuple[int, int]]:
    """(out, in) of each projection of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    f = cfg["intermediate_size"]
    return {"wq": (q, d), "wk": (kv, d), "wv": (kv, d), "wo": (d, q),
            "w_gate": (f, d), "w_up": (f, d), "w_down": (d, f)}


def quantized_matrix(key, shape, std: float, group_size: int):
    """(q int8 (m, n), s f32 (m, n // group_size)) whose dequantized value
    has standard deviation ``std``."""
    m, n = shape
    if n % group_size:
        raise ValueError(f"in-dim {n} not a multiple of group size {group_size}")
    kq, ks = jax.random.split(key)
    bits = jax.random.bits(kq, (m, n), jnp.uint8)
    q = jnp.maximum(jax.lax.bitcast_convert_type(bits, jnp.int8), -127)
    jitter = jax.random.uniform(ks, (m, n // group_size), jnp.float32, 0.75, 1.25)
    return q, jitter * (std / _INT8_STD)


def norm_weight(key, d: int, dtype) -> jax.Array:
    """RMSNorm gains around 1, rounded to bfloat16 and held in the
    configuration's dtype, so the reference reads the very values the
    program does."""
    w = 1.0 + 0.25 * jax.random.uniform(key, (d,), jnp.float32, -1.0, 1.0)
    return w.astype(jnp.bfloat16).astype(dtype)


def layer_weights(wkey, layer, cfg: dict) -> dict:
    """One layer: ``{proj: (q, s)}`` for each projection plus its two norm
    gains. ``layer`` may be traced."""
    gs = cfg["group_size"]
    out = {}
    for name, shape in projection_shapes(cfg).items():
        k = jax.random.fold_in(jax.random.fold_in(wkey, _LEAF_ID[name]), layer)
        out[name] = quantized_matrix(k, shape, shape[1] ** -0.5, gs)
    for name in NORMS:
        k = jax.random.fold_in(jax.random.fold_in(wkey, _LEAF_ID[name]), layer)
        out[name] = norm_weight(k, cfg["hidden_size"], cfg["dtype"])
    return out


def embedding(wkey, cfg: dict):
    shape = (cfg["vocab_size"], cfg["hidden_size"])
    return quantized_matrix(jax.random.fold_in(wkey, _EMBED), shape,
                            EMBED_STD, cfg["group_size"])


def classifier(wkey, cfg: dict):
    shape = (cfg["vocab_size"], cfg["hidden_size"])
    return quantized_matrix(jax.random.fold_in(wkey, _CLASSIFIER), shape,
                            cfg["hidden_size"] ** -0.5, cfg["group_size"])


def final_norm(wkey, cfg: dict) -> jax.Array:
    return norm_weight(jax.random.fold_in(wkey, _FINAL_NORM), cfg["hidden_size"],
                       cfg["dtype"])


def dequantize(q, s) -> jax.Array:
    """f32 (m, n) from int8 values and per-group scales."""
    m, n = q.shape
    g = q.reshape(m, s.shape[1], n // s.shape[1]).astype(jnp.float32)
    return (g * s[..., None]).reshape(m, n)
