"""The control, the reference at the next precision down (int4 weights),
fails the committed limit where the program passes it, at a tiny size on
the CPU. The chip readings at each cell's own size are in PERF.md."""

import gc

from bench import control
from bench import run as R
from bench.tests.record_trace import tiny_cell
from bench.tests.test_faults import limit

SEED = 2**31 + 777


def test_control_fails_the_limit_the_program_passes():
    cell = tiny_cell(check_requests=12)
    run, _, _ = R.serve_window(cell, SEED, 3.0, False, require_chip=False)
    gc.collect()
    got = control.readings(run, SEED)
    assert got["tokens"] >= 100
    assert got["program"] <= limit() < got["control"], got
