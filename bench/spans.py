"""The program's own spans in a traced run: reductions of the ``serve.*``
host events that ``SchedulerCore.serve`` writes (README.md "Tracing a
server"), with their args, on the trace's clock.

- ``load``: every ``serve.*`` event of an ``.xplane.pb`` (cached per path);
- ``of(run)``: those of a run's trace (``.bench_runs/trace-<cell>``, as
  ``run.py`` names it); empty where the program writes no such spans.
  Each reduction below keeps to the traced window
  ``run.trace.t0..t1``;
- ``rounds``: the whole decode rounds of the window; a round whose spans
  began before the trace started, or end after the window, is dropped;
- ``idle_split``: the device's idle seconds by the innermost program span
  the host was in, with the two syncs as one part and "outside spans";
- ``last_commit``: the args of the window's last ``serve.round_commit``,
  which carry ``SchedulerCore.counts`` as they stood.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import numpy as np

from bench import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREFIX = "serve."
SYNCS = ("serve.round_sync", "serve.admit_sync")
IN_SYNC, OUTSIDE = "in sync", "outside spans"
ROUND = ("serve.round_prepare", "serve.round_dispatch", "serve.round_sync",
         "serve.round_commit")


@dataclasses.dataclass
class Span:
    name: str
    start: float           # ns, the trace's clock
    end: float
    args: dict


@functools.lru_cache(maxsize=4)
def load(path: str) -> tuple:
    """Every ``serve.*`` host event of the trace, in start order."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(PREFIX):
                    out.append(Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    return tuple(sorted(out, key=lambda s: (s.start, -s.end)))


def of(run) -> tuple:
    """The program's spans in the run's trace (empty when none)."""
    return load(tr.find(os.path.join(ROOT, ".bench_runs", f"trace-{run.cell['name']}")))


def rounds(spans, t0: float, t1: float) -> list[dict]:
    """Whole decode rounds inside ``t0..t1``: {span name: Span} holding a
    prepare, a dispatch, a sync and a commit of one ``round``, in that
    order, each wholly inside the window. The spans are those of one
    ``serve`` (``round`` counts from 0 in each)."""
    out, cur = [], None
    for s in sorted(spans, key=lambda s: s.start):
        if s.name not in ROUND:
            continue
        k = ROUND.index(s.name)
        if k == 0:
            cur = [s]
        elif cur and len(cur) == k and s.args.get("round") == cur[0].args.get("round"):
            cur.append(s)
            if k == len(ROUND) - 1:
                if all(t0 <= x.start and x.end <= t1 for x in cur):
                    out.append(dict(zip(ROUND, cur)))
                cur = None
        else:
            cur = None
    return out


def round_host_s(rnd: dict) -> float:
    """Host seconds a synchronous loop adds to a round: prepare, dispatch
    and commit (the sync is the wait on the device)."""
    return sum(rnd[n].end - rnd[n].start for n in ROUND if n != "serve.round_sync") * 1e-9


def _busy_before(busy: list):
    """A function giving the busy ns in ``[t0, t]`` for merged intervals."""
    if not busy:
        return lambda t: 0.0
    a = np.asarray([x for x, _ in busy], np.float64)
    b = np.asarray([y for _, y in busy], np.float64)
    before = np.concatenate([[0.0], np.cumsum(b - a)])

    def f(t):
        i = int(np.searchsorted(a, t, side="right"))   # intervals starting <= t
        if i == 0:
            return 0.0
        return float(before[i - 1] + min(t, b[i - 1]) - a[i - 1])

    return f


def idle_split(spans, busy: list, t0: float, t1: float) -> dict[str, float]:
    """Idle seconds of ``t0..t1`` (the complement of the merged ``busy``
    intervals, ns) by the innermost span the host was in: a span's name, or
    ``IN_SYNC`` for the two syncs, or ``OUTSIDE``. The parts add up to the
    window's idle time."""
    spans = [dataclasses.replace(s, start=max(s.start, t0), end=min(s.end, t1))
             for s in spans if s.end > t0 and s.start < t1]
    edges = sorted({t0, t1} | {s.start for s in spans} | {s.end for s in spans})
    busy_to = _busy_before(busy)
    out: dict[str, float] = {}
    for a, b in zip(edges, edges[1:]):
        idle = (b - a) - (busy_to(b) - busy_to(a))
        if idle <= 0:
            continue
        mid = (a + b) / 2
        inner = None
        for s in spans:                       # innermost: the latest to open
            if s.start <= mid < s.end and (inner is None or s.start >= inner.start):
                inner = s
        label = OUTSIDE if inner is None else (
            IN_SYNC if inner.name in SYNCS else inner.name)
        out[label] = out.get(label, 0.0) + idle * 1e-9
    return out


def host_idle_share(split: dict[str, float], window_s: float) -> float:
    """% of the window idle while the host worked inside a program span."""
    return 100.0 * sum(v for k, v in split.items() if k not in (IN_SYNC, OUTSIDE)) / window_s


def last_commit(spans, t0: float, t1: float) -> dict | None:
    """Args of the last ``serve.round_commit`` begun inside ``t0..t1``."""
    commits = [s for s in spans if s.name == "serve.round_commit" and t0 <= s.start < t1]
    return max(commits, key=lambda s: s.start).args if commits else None
