"""Whole-step roofline share: for each prefill and decode step in the
traced window, the larger of its model operations over the int8 peak and
its required bytes over the HBM bandwidth (the layout's ``prefill`` and
``decode_step`` counts); summed, over the traced window's wall time.
Counted from the configuration's shapes and the tokens the scheduler hooks
saw, not from any kernel."""

from bench import counts


def read(run):
    cfg, pk, layout = run.cfg, run.peaks, run.layout
    peak, bw = pk["int8_ops"], pk["hbm_bytes_per_s"]
    least = 0.0
    for w in run.waves:
        if not w.traced:
            continue
        groups = {}
        for s, rid, glen in w.members:
            groups.setdefault(glen, []).append(len(run.requests[rid].tokens))
        for lens in groups.values():
            least += counts.least_time(*layout.prefill(cfg, lens), peak, bw)[0]
    for r in run.rounds:
        if not r.traced:
            continue
        pos = r.pos[r.live]
        for j in range(r.n_steps):
            least += counts.least_time(*layout.decode_step(cfg, pos + j), peak, bw)[0]
    return 100.0 * least / run.trace.window_s if least > 0 else None
