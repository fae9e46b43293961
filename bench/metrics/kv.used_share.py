"""KV pool in use: ``BlockPool.live_blocks`` over the pool's blocks,
averaged over the decode steps of the window."""


def read(run):
    steps = sum(r.n_steps for r in run.rounds)
    if not steps:
        return None
    used = sum(r.n_steps * r.blocks for r in run.rounds)
    return 100.0 * used / (steps * run.pool_blocks)
