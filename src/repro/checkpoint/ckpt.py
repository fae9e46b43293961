"""Atomic, restartable checkpoints (fault-tolerance substrate).

Format: one directory per step containing a flat .npz of all leaves plus a
JSON manifest (treedef paths, shapes, dtypes, step, data-iterator state).
Writes go to ``<dir>/tmp.<step>`` then os.replace() -> crash-safe: a partial
write can never be mistaken for a complete checkpoint.

Restore is resharding-friendly: leaves come back as host numpy arrays; the
caller device_puts them with whatever sharding the *current* mesh dictates
(elastic restart after losing a pod re-lays-out automatically).

Quantized leaves (QuantizedTensor) flatten to their ``.../qvalues`` and
``.../scales`` children, so the array format is format-agnostic; the
manifest additionally records each leaf's quantization format name and
group size (``quant`` key) and restore refuses a tree whose declared
formats disagree — a packed-int4 qvalues array silently reinterpreted as
int8 rows would be shape-valid but numerically garbage. For the same reason
restore refuses int4/int3 leaves from a manifest older than format 2, which
packed them in an order the current unpack would misread.
"""

from __future__ import annotations

import json
import os
import shutil

import jax
import numpy as np

from repro.core.quant import QuantizedTensor
from repro.core.treepath import path_str

MANIFEST = "manifest.json"
ARRAYS = "arrays.npz"
# manifest format: 2 = int4/int3 qvalues in the group-local storage orders
# of core/quant.py (format 1 packed runs of consecutive elements)
FORMAT = 2
_REORDERED_FORMATS = ("int4", "int3")


def _flatten_with_paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = path_str(path)
        out[key] = np.asarray(jax.device_get(leaf))
    return out


def _quant_meta(tree) -> dict:
    """{tree path: {"fmt", "group_size"}} for every QuantizedTensor leaf."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    )
    return {
        path_str(p): {"fmt": leaf.fmt, "group_size": leaf.group_size}
        for p, leaf in flat
        if isinstance(leaf, QuantizedTensor)
    }


def save(directory: str, step: int, tree, extra: dict | None = None) -> str:
    """Atomically write checkpoint for ``step``. Returns the final path."""
    os.makedirs(directory, exist_ok=True)
    final = os.path.join(directory, f"step_{step:08d}")
    tmp = os.path.join(directory, f"tmp.{step}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    arrays = _flatten_with_paths(tree)
    np.savez(os.path.join(tmp, ARRAYS), **arrays)
    manifest = {
        "step": step,
        "keys": sorted(arrays.keys()),
        "extra": extra or {},
        "quant": _quant_meta(tree),
        "format": FORMAT,
    }
    with open(os.path.join(tmp, MANIFEST), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.replace(tmp, final)
    return final


def latest_step(directory: str) -> int | None:
    if not os.path.isdir(directory):
        return None
    steps = []
    for name in os.listdir(directory):
        if name.startswith("step_") and os.path.exists(
            os.path.join(directory, name, MANIFEST)
        ):
            steps.append(int(name.split("_")[1]))
    return max(steps) if steps else None


def restore(directory: str, like, step: int | None = None):
    """Restore into the structure of ``like`` (a pytree of arrays or
    ShapeDtypeStructs). Returns (tree, step, extra)."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {directory}")
    path = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(path, MANIFEST)) as f:
        manifest = json.load(f)
    arrays = np.load(os.path.join(path, ARRAYS))

    saved_q = manifest.get("quant")
    if saved_q is not None:
        old_order = manifest.get("format", 1) < FORMAT
        for key, meta in _quant_meta(like).items():
            got = saved_q.get(key)
            if got is not None and got != meta:
                raise ValueError(
                    f"quantization mismatch for {key}: checkpoint has "
                    f"{got}, restore target expects {meta} — requantize "
                    "instead of reinterpreting packed qvalues"
                )
            if got is not None and old_order and got["fmt"] in _REORDERED_FORMATS:
                raise ValueError(
                    f"checkpoint format {manifest.get('format', 1)} packs "
                    f"{key} in an older int4/int3 storage order — "
                    "requantize from float weights")

    flat, treedef = jax.tree_util.tree_flatten_with_path(like)
    leaves = []
    for p, leaf in flat:
        key = path_str(p)
        arr = arrays[key]
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"shape mismatch for {key}: ckpt {arr.shape} vs {leaf.shape}")
        leaves.append(arr.astype(leaf.dtype))
    tree = jax.tree_util.tree_unflatten(treedef, leaves)
    return tree, manifest["step"], manifest["extra"]


def retain(directory: str, keep: int = 3) -> None:
    """Delete all but the newest ``keep`` complete checkpoints."""
    if not os.path.isdir(directory):
        return
    steps = sorted(
        int(n.split("_")[1]) for n in os.listdir(directory) if n.startswith("step_")
    )
    for s in steps[:-keep]:
        shutil.rmtree(os.path.join(directory, f"step_{s:08d}"), ignore_errors=True)
