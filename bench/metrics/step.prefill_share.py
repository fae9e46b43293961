"""Device time inside the prefill program (``prefill_group``) over device
busy time, in the traced window (0 when no request was admitted in it)."""

from bench import trace as tr


def read(run):
    busy = tr.busy_s(run.trace)
    return 100.0 * tr.program_time(run.trace, "prefill_group") / busy if busy > 0 else None
