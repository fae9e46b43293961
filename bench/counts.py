"""Operations and bytes the served work requires, counted from shapes.

The counts are of what the work needs, not of what a kernel happens to
move: each weight byte once per call at storage width (int8 values plus
f32 group scales), the K/V rows of live positions only, the activations
in and out. A kernel or step that moves or computes more than this shows
as a lower share of its roofline. Per-leaf storage bytes follow the
``nbytes`` arithmetic of the program's quantized tensors (qvalues +
scales), copied here rather than imported.

The per-layer arithmetic (``matrices`` and what builds on it) is the dense
decoder's, and reaches the readers through ``bench/arch/dense.py``;
``gqmm``, ``paged_attention``, ``weight_bytes`` and ``least_time`` price
the calls of any layout.
"""

from __future__ import annotations

KV_BYTES = 2          # the paged pool holds bfloat16 K/V rows
ACT_BYTES = 2         # bfloat16 activations between kernels


def matrices(cfg: dict) -> list[tuple[int, int]]:
    """(out, in) of the quantized matmuls of one layer, as the program
    fuses them: q|k|v, output, gate|up, down."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    f = cfg["intermediate_size"]
    return [(q + 2 * kv, d), (d, q), (2 * f, d), (d, f)]


def weight_bytes(m: int, n: int, gs: int) -> int:
    return m * n + m * (n // gs) * 4


def gqmm(rows: int, m: int, n: int, gs: int) -> tuple[float, float]:
    """One GQMM call: int8 activations (rows, n) with f32 group scales
    against int8 weights (m, n) -> f32 (rows, m)."""
    ops = 2.0 * rows * m * n
    nbytes = weight_bytes(m, n, gs) + rows * n + rows * (n // gs) * 4 + rows * m * 4
    return ops, float(nbytes)


def gqmm_calls(cfg: dict, rows: int) -> list[tuple[float, float]]:
    """The GQMM calls of the layers in one forward step over ``rows``
    token rows: four per layer (the output head is ``head_call``)."""
    gs = cfg["group_size"]
    per_layer = [gqmm(rows, m, n, gs) for m, n in matrices(cfg)]
    return per_layer * cfg["num_hidden_layers"]


def head_call(cfg: dict, rows: int) -> tuple[float, float]:
    return gqmm(rows, cfg["vocab_size"], cfg["hidden_size"], cfg["group_size"])


def paged_attention(cfg: dict, positions) -> tuple[float, float]:
    """One layer's paged decode attention for live rows at ``positions``
    (each attends to positions 0..p): QK and PV products, the live K/V
    rows read once, the queries read and the context written."""
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    ctx = sum(int(p) + 1 for p in positions)
    ops = 4.0 * h * hd * ctx
    nbytes = 2 * kv * hd * KV_BYTES * ctx + 2 * len(positions) * h * hd * ACT_BYTES
    return ops, float(nbytes)


def model_weight_bytes(cfg: dict) -> float:
    """Every weight streamed once: the layers' matmuls, the output head
    and the two norm gains per layer (the embedding is gathered by rows)."""
    gs, d = cfg["group_size"], cfg["hidden_size"]
    per_layer = sum(weight_bytes(m, n, gs) for m, n in matrices(cfg)) + 2 * d * 2
    return float(per_layer * cfg["num_hidden_layers"]
                 + weight_bytes(cfg["vocab_size"], d, gs) + d * 2)


def matmul_params(cfg: dict) -> float:
    return float(sum(m * n for m, n in matrices(cfg)) * cfg["num_hidden_layers"]
                 + cfg["vocab_size"] * cfg["hidden_size"])


def decode_step(cfg: dict, positions) -> tuple[float, float]:
    """One decode step for live rows at ``positions``: the model's
    operations for those tokens and the bytes they require (weights once,
    live K/V rows read and the new row written, the embedding rows,
    activations and logits)."""
    n = len(positions)
    d, v, layers = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    att_ops, att_bytes = paged_attention(cfg, positions)
    kv_row = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * KV_BYTES
    ops = 2.0 * n * matmul_params(cfg) + layers * att_ops
    nbytes = (model_weight_bytes(cfg) + layers * (att_bytes + n * kv_row)
              + n * (d + d // cfg["group_size"] * 4)        # embedding rows
              + layers * n * 4 * d * ACT_BYTES + n * v * 4)
    return ops, float(nbytes)


def prefill(cfg: dict, lengths) -> tuple[float, float]:
    """One prefill of prompts of ``lengths`` tokens (real tokens, not the
    padded bucket): every token through every matmul, causal attention,
    the K/V rows written, one row of logits per prompt."""
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    d, v, layers = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    tokens = sum(lengths)
    pairs = sum(n * (n + 1) / 2 for n in lengths)
    lin = sum(m * n for m, n in matrices(cfg)) * layers
    ops = 2.0 * tokens * lin + 2.0 * len(lengths) * v * d + layers * 4.0 * h * hd * pairs
    kv_row = 2 * kv * hd * KV_BYTES
    nbytes = (model_weight_bytes(cfg) + layers * tokens * kv_row
              + tokens * (d + d // cfg["group_size"] * 4)
              + layers * tokens * 4 * d * ACT_BYTES + len(lengths) * v * 4)
    return ops, float(nbytes)


def least_time(ops: float, nbytes: float, peak_ops: float, peak_bw: float):
    """The roofline's least time and which bound sets it."""
    t_ops, t_mem = ops / peak_ops, nbytes / peak_bw
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
