"""The trace reduction on a small trace recorded on a TPU v5e
(``record_trace.py``: two layers, 8 slots, the real kernels), checked
against the host's own counts of that run and against a brute-force
timeline."""

import json
import os

import numpy as np
import pytest

from bench import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(DATA, "small.counts.json")) as f:
        counts = json.load(f)
    return tr.load(os.path.join(DATA, "small.xplane.pb")), counts


def test_op_names_are_the_ops_own():
    assert tr.op_name("%gqmm_int8.34 = f32[32,38400]{1,0} custom-call(s8[32,7168] "
                      "%fusion.67)") == "gqmm_int8"
    assert tr.op_name("%convert_bitcast_fusion.3 = bf16[8,1,1024] fusion(f32[8,1024] "
                      "%gqmm_int8.32)") == "convert_bitcast_fusion"
    assert tr.op_name("%paged_attention = bf16[8] custom-call()") == "paged_attention"


def test_kernel_events_match_the_calls_the_host_made(small):
    t, c = small
    assert c["device"]["kind"] == "TPU v5 lite"
    layers = c["layers"]
    assert tr.op_count(t, "paged_attention") == c["decode_steps"] * layers
    # four GQMM calls per layer and the output head, per decode step and
    # per prefill group
    assert tr.op_count(t, "gqmm_") == (c["decode_steps"] + c["prefill_groups"]) * (4 * layers + 1)


def test_busy_matches_a_brute_force_timeline(small):
    t, _ = small
    o = t.running[0]
    step = 1000.0                                      # 1 us bins
    n = int((t.t1 - t.t0) / step) + 1
    line = np.zeros(n, bool)
    for a, b in zip(o.start, o.end):
        lo, hi = max(a, t.t0), min(b, t.t1)
        if hi > lo:
            line[int((lo - t.t0) // step): int(np.ceil((hi - t.t0) / step))] = True
    brute = line.sum() * step * 1e-9
    busy = tr.busy_s(t)
    assert 0 < busy < t.window_s
    assert abs(brute - busy) <= 2e-6 * len(o.start) + 1e-3 * busy
    idle = sum(s for _, s in tr.idle_gaps(t))
    assert abs(idle + busy - t.window_s) < 1e-6 * t.window_s + 1e-9


def test_kernel_and_program_times_fit_inside_busy(small):
    t, _ = small
    busy = tr.busy_s(t)
    pa, gq = tr.op_time(t, "paged_attention"), tr.op_time(t, "gqmm_")
    assert pa > 0 and gq > 0 and pa + gq <= busy
    kpa, kgq = tr.kernel_time(t, "paged_attention"), tr.kernel_time(t, "gqmm_")
    assert kpa >= pa and kgq >= gq and kpa + kgq <= busy
    pre, dec = tr.program_time(t, "prefill_group"), tr.program_time(t, "decode_until")
    assert pre > 0 and dec > 0 and pre + dec <= busy + 1e-9
    names = dict(tr.top_ops(t))
    assert abs(names["paged_attention"] - pa) < 1e-9
    assert not any(n.startswith("while") for n in names)   # loops are not leaves
    labels = {k for k, _ in tr.idle_by_span(t)}
    assert labels <= set(tr.PHASES) | {"outside_spans"}
