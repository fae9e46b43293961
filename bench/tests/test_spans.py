"""The reductions of the program's spans (``bench/spans.py``) on synthetic
spans and device intervals, checked against a brute-force timeline."""

import numpy as np

from bench import spans as S
from bench import trace as tr

US = 1000.0                      # ns


def rnd(k, t, prep=30, grow=(5, 12), dispatch=4, sync=200, commit=9, live=3):
    """One round's spans from ``t`` (us): prepare with kv_grow inside it,
    dispatch, sync, commit."""
    out, a = [], t
    out.append(S.Span("serve.round_prepare", a * US, (a + prep) * US,
                      {"round": k, "live": live}))
    out.append(S.Span("serve.kv_grow", (a + grow[0]) * US, (a + grow[1]) * US,
                      {"blocks_live": 10}))
    a += prep
    for name, d in (("serve.round_dispatch", dispatch), ("serve.round_sync", sync)):
        out.append(S.Span(name, a * US, (a + d) * US, {"round": k}))
        a += d
    out.append(S.Span("serve.round_commit", a * US, (a + commit) * US,
                      {"round": k, "steps": 4, "live": live,
                       "prompt_tokens": 100 * (k + 1), "prefill_slots": 128 * (k + 1)}))
    return out, a + commit


def timeline():
    """Rounds 0..5 back to back, an admission between rounds 2 and 3, and
    the device busy from each dispatch's end to a little before its sync
    ends."""
    spans, busy, t = [], [], 0.0
    for k in range(6):
        if k == 3:
            spans.append(S.Span("serve.admit", t * US, (t + 6) * US, {"admitted": 1}))
            spans.append(S.Span("serve.prefill", (t + 6) * US, (t + 20) * US, {"rows": 1}))
            spans.append(S.Span("serve.admit_sync", (t + 20) * US, (t + 60) * US, {}))
            busy.append(((t + 22) * US, (t + 55) * US))
            t += 65                                   # 5 us outside spans
        sp, end = rnd(k, t)
        spans += sp
        d0 = t + 30 + 4
        busy.append(((d0 + 1) * US, (d0 + 190) * US))
        t = end
    return spans, busy, t


def brute_idle(spans, busy, t0, t1):
    """Idle seconds by innermost span on a 0.1 us grid."""
    step = 0.1 * US
    grid = np.arange(t0, t1, step) + step / 2
    on = np.zeros(len(grid), bool)
    for a, b in busy:
        on |= (grid >= a) & (grid < b)
    out = {}
    for t, b in zip(grid, on):
        if b:
            continue
        inner = [s for s in spans if s.start <= t < s.end]
        name = max(inner, key=lambda s: s.start).name if inner else S.OUTSIDE
        name = S.IN_SYNC if name in S.SYNCS else name
        out[name] = out.get(name, 0.0) + step * 1e-9
    return out


def test_rounds_drop_the_round_cut_by_the_trace_start_and_end():
    spans, _, t_end = timeline()
    prepares = [s for s in spans if s.name == "serve.round_prepare"]
    # a trace started inside round 1's prepare records none of that span;
    # the window ends inside round 5's sync
    kept = [s for s in spans if s is not prepares[1]]
    t0 = prepares[1].start + 2 * US
    t1 = [s for s in spans if s.name == "serve.round_sync"][5].start + 5 * US
    got = S.rounds(kept, t0, t1)
    assert [r["serve.round_prepare"].args["round"] for r in got] == [2, 3, 4]
    assert all(abs(S.round_host_s(r) - 43e-6) < 1e-12 for r in got)
    # every round whole
    assert len(S.rounds(spans, 0.0, t_end * US)) == 6


def test_idle_split_matches_a_brute_force_timeline():
    spans, busy, t_end = timeline()
    t0, t1 = 17 * US, (t_end - 3) * US            # cuts round 0 and round 5
    merged = tr._union(np.asarray([a for a, _ in busy]), np.asarray([b for _, b in busy]),
                       t0, t1)
    split = S.idle_split(spans, merged, t0, t1)
    brute = brute_idle(spans, busy, t0, t1)
    assert set(split) == set(brute)
    for k in split:
        assert abs(split[k] - brute[k]) < 2e-7, (k, split[k], brute[k])
    busy_s = sum(b - a for a, b in merged) * 1e-9
    assert abs(sum(split.values()) + busy_s - (t1 - t0) * 1e-9) < 1e-12
    assert split["serve.kv_grow"] > 0 and split[S.OUTSIDE] > 0 and split[S.IN_SYNC] > 0
    share = S.host_idle_share(split, (t1 - t0) * 1e-9)
    host = sum(v for k, v in brute.items() if k not in (S.IN_SYNC, S.OUTSIDE))
    assert abs(share - 100 * host / ((t1 - t0) * 1e-9)) < 1e-3


def test_last_commit_is_the_windows_last():
    spans, _, t_end = timeline()
    commits = [s for s in spans if s.name == "serve.round_commit"]
    assert S.last_commit(spans, 0.0, t_end * US)["prompt_tokens"] == 600
    assert S.last_commit(spans, 0.0, commits[3].start + 1)["prefill_slots"] == 512
    assert S.last_commit(spans, 0.0, commits[0].start) is None
