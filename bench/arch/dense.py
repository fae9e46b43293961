"""The dense decoder layout: a Llama-style GQA decoder with a SwiGLU MLP in
every layer, served by the program's ``family="dense"`` transformer.

A configuration file names its layout with ``"arch"``; this one is the
default where the key is absent. A layout file provides the hooks that
``bench/run.py`` checks for (``run.LAYOUT_HOOKS``), and nothing about the
traffic, the trace or the spans:

- ``model_config(cfg)``: the program's ``ModelConfig``;
- ``served_params(model, cfg, seed)``: the seeded int8 parameter tree,
  made on the device and checked against the program's own tree;
- ``Reference(cfg, weight_bits=8|4)``: the float32 reference and its int4
  control, ``.logits(seed, tokens, rows)``;
- ``decode_step(cfg, positions)``, ``prefill(cfg, lengths)``: the ops and
  bytes one decode step over live rows at ``positions`` and one prefill of
  prompts of ``lengths`` tokens require;
- ``gqmm_calls(cfg, rows, head_rows)``: the (ops, bytes) of each GQMM call
  of one forward step over ``rows`` token rows, the output head's over
  ``head_rows`` rows among them;
- ``attention_calls(cfg, positions)``: the (ops, bytes) of each paged
  attention call of one decode step, one entry per layer that attends.

Here each hook names the dense code that already exists: the weights of
``bench/weights.py``, the reference of ``bench/reference.py`` and the
counts of ``bench/counts.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import counts
from bench import weights as W
from bench.reference import Reference  # noqa: F401  (the reference hook)

decode_step = counts.decode_step
prefill = counts.prefill


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    if cfg["vocab_size"] % 32:
        raise ValueError("vocab_size must be a multiple of 32 (no padded rows)")
    return ModelConfig(
        arch_id=cfg["name"], family="dense", model_type="decoder_lm",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        group_size=cfg["group_size"], param_dtype=cfg["dtype"],
        compute_dtype=cfg["dtype"])


def _qt(q, s, cfg: dict):
    from repro.core.quant import QuantizedTensor

    return QuantizedTensor(qvalues=q, scales=s, group_size=cfg["group_size"],
                           fmt="int8")


def _cat(*parts):
    return (jnp.concatenate([p[0] for p in parts]),
            jnp.concatenate([p[1] for p in parts]))


def served_layer(wkey, layer, cfg: dict) -> dict:
    """One layer of the program's tree, from the weights of ``layer``
    alone (``layer`` may be traced)."""
    lw = W.layer_weights(wkey, layer, cfg)
    # the program fuses q|k|v and gate|up into one matrix each
    return {"att_norm": lw["att_norm"],
            "attn": {"wqkv": _qt(*_cat(lw["wq"], lw["wk"], lw["wv"]), cfg),
                     "wo": _qt(*lw["wo"], cfg)},
            "ffn_norm": lw["ffn_norm"],
            "mlp": {"w13": _qt(*_cat(lw["w_gate"], lw["w_up"]), cfg),
                    "w2": _qt(*lw["w_down"], cfg)}}


def served_params(model, cfg: dict, seed: int):
    """The program's int8 parameter tree, made on the device in one jitted
    call from the seed: the tree ``quantize_params(model.init(...))`` has,
    with no float copy of any weight."""
    from repro.core.policy import quantize_params

    want = jax.eval_shape(lambda: quantize_params(
        model.init(jax.random.PRNGKey(0)), cfg["group_size"]))

    @jax.jit
    def make(wkey):
        return {
            "embed": _qt(*W.embedding(wkey, cfg), cfg),
            "layers": jax.lax.map(lambda i: served_layer(wkey, i, cfg),
                                  jnp.arange(cfg["num_hidden_layers"])),
            "final_norm": W.final_norm(wkey, cfg),
            "classifier": _qt(*W.classifier(wkey, cfg), cfg),
        }

    params = make(W.weight_key(seed))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    exp = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != exp:
        raise AssertionError("seeded weights do not match the program's "
                             f"parameter tree:\n{got}\n!=\n{exp}")
    return params


def gqmm_calls(cfg: dict, rows: int, head_rows: int) -> list[tuple[float, float]]:
    """Four GQMM calls a layer (q|k|v, output, gate|up, down), then the
    output head."""
    return counts.gqmm_calls(cfg, rows) + [counts.head_call(cfg, head_rows)]


def attention_calls(cfg: dict, positions) -> list[tuple[float, float]]:
    """Every layer attends over all positions up to its own."""
    return [counts.paged_attention(cfg, positions)] * cfg["num_hidden_layers"]
