"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<mix>.json``) gives prompt and output lengths as
clipped lognormals (median, sigma, min, max) and a block size. Every
request is due when the window opens (``"arrivals": "all_at_start"``, an
offline queue that stays full); a mix that asks for any other arrival
process is refused, as the generator has none.

Lengths are stratified: every block of ``block`` requests holds the same
lengths, the quantiles (i + 0.5) / block of their distributions, in an
order drawn from the seed. Seeds therefore differ in the order of requests
and in their token ids, never in the amount of work, which keeps runs of
different seeds comparable.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class TrafficRequest:
    id: int
    tokens: np.ndarray      # prompt token ids, int32
    max_new: int            # output tokens to generate (no EOS: exactly this)


def lengths(spec: dict, block: int) -> np.ndarray:
    """The ``block`` stratified lengths of one block, in quantile order."""
    u = (np.arange(block) + 0.5) / block
    z = np.asarray([NormalDist().inv_cdf(x) for x in u])
    raw = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(raw, spec["min"], spec["max"]).astype(np.int64)


def generate(mix: dict, seed: int, n_requests: int, vocab: int) -> list[TrafficRequest]:
    """``n_requests`` requests of ``mix`` from ``seed``, rounded up to whole
    blocks."""
    if mix.get("arrivals") != "all_at_start":
        raise ValueError(f"unknown arrivals {mix.get('arrivals')!r}: the generator "
                         "makes offline queues only ('all_at_start')")
    block = mix["block"]
    rng = np.random.default_rng(int(seed))
    p_len = lengths(mix["prompt_tokens"], block)
    o_len = lengths(mix["output_tokens"], block)
    out = []
    for _ in range(math.ceil(n_requests / block)):
        for p, o in zip(rng.permutation(p_len), rng.permutation(o_len)):
            toks = rng.integers(0, vocab, size=int(p), dtype=np.int32)
            out.append(TrafficRequest(len(out), toks, int(o)))
    return out


def mean_output(mix: dict) -> float:
    return float(lengths(mix["output_tokens"], mix["block"]).mean())
