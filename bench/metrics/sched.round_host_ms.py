"""Host time per decode round: the median, over the whole decode rounds of
the traced window, of the summed durations of the program's
``serve.round_prepare``, ``serve.round_dispatch`` and
``serve.round_commit`` spans of one round (``bench/spans.py``): the host
work a synchronous loop adds to every round, the wait in
``serve.round_sync`` left out. None when the program writes no such
spans."""

import numpy as np

from bench import spans


def read(run):
    whole = spans.rounds(spans.of(run), run.trace.t0, run.trace.t1)
    if not whole:
        return None
    return float(np.median([spans.round_host_s(r) for r in whole])) * 1e3
