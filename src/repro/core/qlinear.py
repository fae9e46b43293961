"""Quantization-aware linear / embedding primitives.

Every weight-bearing matmul in the model zoo goes through ``linear``: when
the weight leaf is a plain array it is an ordinary (bf16/f32) matmul; when
it is a :class:`QuantizedTensor` the call becomes the paper's GQMV/GQMM
(run-time int8 activation quantization + the group-wise kernel of the
weight's registered format — W8A8 for int8 storage, W4A8 for packed int4;
see core/quant.py and DESIGN.md §8).

Weights follow the paper's (out, in) row-major layout with quantization
groups along the *in* (contraction) axis.

Kernel-launch fusion (paper C4: concatenated Wq+Wk+Wv, W1+W3) is expressed
by storing the concatenated matrix as one leaf and splitting the output.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import flags
from repro.core.quant import QuantizedTensor
from repro.kernels import ops

__all__ = ["linear", "embedding_lookup", "split_fused"]


def linear(w, x: jax.Array, *, impl: str = "auto") -> jax.Array:
    """y = x @ W^T for W (out, in); quantized-kernel path when W is a
    QuantizedTensor (any registered format)."""
    if isinstance(w, QuantizedTensor):
        if flags.get("prefill_dequant"):
            # compute-bound many-token passes: one dequant + bf16 MXU matmul
            # beats GQMV's int32 group-sum buffers (flags.py rationale)
            return jnp.einsum("...i,oi->...o", x, w.dequantize(x.dtype))
        return ops.quantized_matmul(x, w, impl=impl).astype(x.dtype)
    return jnp.einsum("...i,oi->...o", x, w.astype(x.dtype))


def embedding_lookup(w, ids: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Row gather from a (vocab, d) table; dequantizes gathered rows when
    the table is quantized (paper quantizes W_embeddings, Table I).

    Only the gathered rows leave HBM: packed formats gather their (smaller)
    storage rows and unpack to nibble values on-chip before scaling.
    """
    if isinstance(w, QuantizedTensor):
        q = jnp.take(w.qvalues, ids, axis=0)        # (..., d/pack) storage
        s = jnp.take(w.scales, ids, axis=0)         # (..., d/GS)
        v = w.format.unpack_values(q, w.group_size)   # (..., d) int8 values
        g = v.reshape(*v.shape[:-1], w.num_groups, w.group_size).astype(dtype)
        return (g * s[..., None].astype(dtype)).reshape(v.shape)
    return jnp.take(w, ids, axis=0).astype(dtype)


def split_fused(y: jax.Array, sizes: tuple[int, ...]):
    """Split the output of a fused projection (paper Alg. 2 lines 4, 12)."""
    outs, off = [], 0
    for s in sizes:
        outs.append(y[..., off:off + s])
        off += s
    if off != y.shape[-1]:
        raise ValueError(
            f"split_fused sizes {tuple(sizes)} sum to {off} but the fused "
            f"output has trailing dim {y.shape[-1]} (shape {y.shape})"
        )
    return outs
