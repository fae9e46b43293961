"""Scheduler occupancy: live slots over slots, averaged over the decode
steps of the window (each round weighted by its steps)."""


def read(run):
    steps = sum(r.n_steps for r in run.rounds)
    if not steps:
        return None
    live = sum(r.n_steps * int(r.live.sum()) for r in run.rounds)
    return 100.0 * live / (steps * run.slots)
