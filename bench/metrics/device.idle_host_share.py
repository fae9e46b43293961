"""Share of the traced window in which no op ran on chip 0 while the host
was inside one of the program's ``serve.*`` spans other than the two
syncs (``serve.round_sync``, ``serve.admit_sync``): the idle the host's own
work costs. Appends one line to the run's notes: the window's idle seconds
by innermost program span, "in sync" and "outside spans"
(``spans.idle_split``), which add up to ``device.idle_share``'s idle. None
when the program writes no such spans."""

from bench import spans
from bench import trace as tr


def read(run):
    t = run.trace
    mine = spans.of(run)
    if not mine:
        return None
    split = spans.idle_split(mine, tr._union(t.running[0].start, t.running[0].end,
                                             t.t0, t.t1), t.t0, t.t1)
    run.notes.append(
        f"idle by program span, {sum(split.values()):.4f} s of the "
        f"{t.window_s:.4f} s traced window: " + ", ".join(
            f"{k} {v:.4f} s" for k, v in sorted(split.items(), key=lambda kv: -kv[1])))
    return spans.host_idle_share(split, t.window_s)
