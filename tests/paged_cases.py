"""Inputs for the paged-attention kernel parity tests (tests/test_paged.py,
tests/test_kvquant.py).

The kernel walks 128-token compute blocks (P = 16 pool blocks of BS = 8)
and only as many of them as each row's position needs. The geometries put
one row on each edge of that walk: the first token, the last and first
token of a pool block, the last and first token of a compute block, and
the last slot of the table.
"""

import functools

import jax
import numpy as np
import pytest

from repro.kernels.paged_attn import paged_attention_pallas

BS = 8
# geometry -> (MB, per-row positions)
GEOMETRIES = {
    "shuffled": (3, [3, 10, 21]),
    "ragged": (32, [0, BS - 1, BS, 16 * BS - 1, 16 * BS, 32 * BS - 1]),
    # MB not a multiple of P: the last compute block is ragged
    "ragged-mb": (20, [0, BS - 1, BS, 16 * BS - 1, 16 * BS, 20 * BS - 1]),
}
# (window, softcap)
ATTENTION = [(None, None), (6, None), (None, 30.0)]

# the kernel in interpret mode, compiled once per shape and softcap: the
# cases of one geometry differ in values only
interpret_kernel = jax.jit(
    functools.partial(paged_attention_pallas, interpret=True),
    static_argnames=("scale", "softcap"))


def parity_params(prefix: str = "", base: str | None = None):
    """(geometry, window, softcap, poison) cases; each ragged geometry also
    runs with its dead blocks poisoned. Ids are ``prefix`` plus the case;
    ``base``, when given, is the id of the shuffled case without window
    or softcap."""
    out = []
    for geometry in GEOMETRIES:
        for poison in ((False,) if geometry == "shuffled" else (False, True)):
            for window, softcap in ATTENTION:
                cid = f"{window}-{softcap}"
                if geometry != "shuffled":
                    cid = f"{geometry}{'-poisoned' if poison else ''}-{cid}"
                if base and geometry == "shuffled" and not (window or softcap):
                    cid = base
                else:
                    cid = prefix + cid
                out.append(pytest.param(geometry, window, softcap, poison,
                                        id=cid))
    return out


def paged_case(rng, geometry: str, kv: int, g: int, hd: int):
    """q, K/V pool, block table, positions and current-token rows for one
    geometry, as float32 numpy arrays (ids as int32), plus the ids of the
    blocks that only dead table entries name: past each row's block that
    holds its position."""
    mb, pos = GEOMETRIES[geometry]
    b = len(pos)
    nb = b * mb + 1
    q = rng.normal(size=(b, kv, g, hd)).astype(np.float32)
    kp = rng.normal(size=(nb, BS, kv, hd)).astype(np.float32)
    vp = rng.normal(size=(nb, BS, kv, hd)).astype(np.float32)
    kn = rng.normal(size=(b, kv, hd)).astype(np.float32)
    vn = rng.normal(size=(b, kv, hd)).astype(np.float32)
    table = rng.permutation(np.arange(1, nb)).reshape(b, mb).astype(np.int32)
    dead = np.concatenate([table[r, p // BS + 1:] for r, p in enumerate(pos)])
    return (q, kp, vp, table, np.asarray(pos, np.int32), kn, vn,
            dead.astype(np.int32))
