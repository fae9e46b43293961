"""90th percentile, over every request that received two or more tokens in
the window, of (time its latest tokens reached the host - time of its
first token) / (tokens received - 1). Finished and unfinished requests
alike: a request's time per output token is known once it has two."""

import numpy as np


def read(run):
    got, last = {}, {}
    for r in run.rounds:
        for s in np.flatnonzero(r.live):
            rid = r.rids[s]
            got[rid] = got.get(rid, 0) + r.n_steps
            last[rid] = r.t_done
    tpot = [(last[rid] - run.t_first[rid]) / n for rid, n in got.items() if n > 0]
    return float(np.percentile(tpot, 90)) * 1e3 if tpot else None
