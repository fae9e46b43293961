"""GQMM kernel (``gqmm_int8``) roofline share in the traced window: the
least time of every GQMM call's work (the layout's ``gqmm_calls``, priced
by ``counts.least_time``: int8 ops over the int8 peak, or weight +
activation bytes over HBM bandwidth, whichever is larger) over the device
time of the ``gqmm_*`` calls and of the ops feeding them (the per-layer
weight slices XLA copies out of the stacked weights, the activation
quantization; ``trace.kernel_time``). Decode calls carry every slot's row;
prefill calls the padded prompt rows, the output head one row a prompt.
The run's notes give the kernel's own time and share beside it
(``trace.kernel_report``)."""

import sys

from bench import counts
from bench import trace as tr

KERNEL = "gqmm_"


def read(run):
    cfg, pk, layout = run.cfg, run.peaks, run.layout
    peak, bw = pk["int8_ops"], pk["hbm_bytes_per_s"]
    calls = []
    for w in run.waves:
        if w.traced:
            groups = {}
            for s, rid, glen in w.members:
                groups.setdefault(glen, []).append(rid)
            for glen, rids in groups.items():
                calls += layout.gqmm_calls(cfg, len(rids) * glen, len(rids))
    for r in run.rounds:
        if r.traced:
            calls += layout.gqmm_calls(cfg, run.slots, run.slots) * r.n_steps
    t = tr.kernel_time(run.trace, KERNEL)
    if not calls or t <= 0:
        return None
    bounds = [counts.least_time(o, b, peak, bw) for o, b in calls]
    least = sum(x for x, _ in bounds)
    mem = sum(x for x, kind in bounds if kind == "memory")
    run.notes.append(f"gqmm: {len(calls)} calls counted, {100 * mem / least:.1f}% of "
                     f"their least time {least:.4f} s memory-bound; traced "
                     + tr.describe(tr.kernel_report(run.trace, KERNEL), least))
    seen = tr.op_count(run.trace, KERNEL)
    if seen != len(calls):
        print(f"gqmm_roofline: traced {seen} events for {len(calls)} counted calls",
              file=sys.stderr)
    return 100.0 * least / t
