"""Paged-attention kernel (``paged_attention``) roofline share in the
traced window: per layer and decode step, the least time of the work the
live rows need (their K/V rows up to the current position, read once;
bench/counts.py) over the device time of the ``paged_attention`` calls
and of the ops feeding them (the per-layer slices of the KV pool XLA
copies out, the mask; ``trace.kernel_time``). The kernel's grid runs over
every slot and the whole block table; what it does beyond the live rows
shows as a lower share. The run's notes give the kernel's own time and
share beside it (``trace.kernel_report``)."""

import sys

from bench import counts
from bench import trace as tr

KERNEL = "paged_attention"


def read(run):
    cfg, pk = run.cfg, run.peaks
    peak, bw = pk["bf16_flops"], pk["hbm_bytes_per_s"]
    least, n = 0.0, 0
    for r in run.rounds:
        if r.traced:
            pos = r.pos[r.live]
            for j in range(r.n_steps):
                least += cfg["num_hidden_layers"] * counts.least_time(
                    *counts.paged_attention(cfg, pos + j), peak, bw)[0]
                n += cfg["num_hidden_layers"]
    t = tr.kernel_time(run.trace, KERNEL)
    if not n or t <= 0:
        return None
    run.notes.append(f"paged_attention: {n} calls counted, least time {least:.4f} s; "
                     "traced " + tr.describe(tr.kernel_report(run.trace, KERNEL), least))
    seen = tr.op_count(run.trace, KERNEL)
    if seen != n:
        print(f"paged_attn_roofline: traced {seen} events for {n} counted calls",
              file=sys.stderr)
    return 100.0 * least / t
