"""Share of the traced window in which no op ran on the device."""

from bench import trace as tr


def read(run):
    return 100.0 * (1.0 - tr.busy_s(run.trace) / run.trace.window_s)
