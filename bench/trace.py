"""Reduction of a profiler trace to the numbers the per-layer metrics read.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with nothing but
JAX. From the device planes it takes the op events (the ``XLA Ops`` line)
and the program executions (``XLA Modules``); from the host plane the
harness's phase spans. All times are nanoseconds on the trace's clock.

- window: from the start of the first harness span to the end of the last
  one (the trace starts at a decode round and ends when the window closes);
- busy: the union of op intervals inside the window (a loop's op counts
  while its body runs), averaged over chips;
- idle gaps: the complement, each labelled by the harness span open at
  its midpoint;
- op time: summed durations of the op events of a given name (an op's
  own name, ``gqmm_int8`` in ``%gqmm_int8.34 = ...``; ops that enclose
  others, such as ``while`` loops, are dropped); kernel time adds the ops
  that produce the kernel's operands in the same program, because XLA
  stages a scanned layer's weights (sometimes into on-chip memory) in a
  copy of its own before the kernel reads them;
- program time: op time inside the executions of a named program.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

PHASES = ("admission", "prefill", "decode_round")


_OPERAND = re.compile(r"%([\w\-.]+)")


def op_name(event_name: str) -> str:
    """An op event is named by its HLO text, ``%gqmm_int8.34 = f32[..]
    custom-call(...)``; its own name is ``gqmm_int8`` (operands named in
    the text are not it)."""
    head = op_id(event_name)
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def op_id(event_name: str) -> str:
    """The instruction's id within its program: ``gqmm_int8.34``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def operands(event_name: str) -> tuple:
    """Ids of the instructions whose results the op reads."""
    rest = event_name.split(" = ", 1)
    return tuple(_OPERAND.findall(rest[1])) if len(rest) == 2 else ()


@dataclasses.dataclass
class Events:
    names: list            # op names (or span / program names)
    start: np.ndarray      # ns
    end: np.ndarray        # ns
    ids: list = None       # instruction ids, ops only
    reads: list = None     # operand ids, ops only
    program: list = None   # the program execution each op ran in, ops only
    texts: list = None     # the op's HLO text, ops only

    @classmethod
    def of(cls, evs):
        evs = sorted(evs, key=lambda e: e[1])
        return cls([e[0] for e in evs],
                   np.asarray([e[1] for e in evs], np.float64),
                   np.asarray([e[1] + e[2] for e in evs], np.float64))

    @classmethod
    def of_ops(cls, evs, modules: "Events"):
        evs = sorted(evs, key=lambda e: e.start_ns)
        out = cls.of([(op_name(e.name), e.start_ns, e.duration_ns) for e in evs])
        out.ids = [op_id(e.name) for e in evs]
        out.reads = [operands(e.name) for e in evs]
        out.texts = [e.name for e in evs]
        i = np.searchsorted(modules.start, out.start, side="right") - 1
        out.program = [modules.names[k] if k >= 0 and modules.end[k] >= t else ""
                       for k, t in zip(i, out.start)]
        return out

    def take(self, keep):
        pick = lambda xs: None if xs is None else [xs[i] for i in keep]
        return Events(pick(self.names), self.start[keep], self.end[keep],
                      pick(self.ids), pick(self.reads), pick(self.program),
                      pick(self.texts))

    def select(self, pred):
        return self.take([i for i, n in enumerate(self.names) if pred(n)])

    def leaves(self):
        """Without the ops that enclose others (a ``while`` loop's event
        spans every op of its body)."""
        nxt = np.append(self.start[1:], np.inf)
        return self.take(np.flatnonzero(nxt >= self.end))


@dataclasses.dataclass
class Trace:
    ops: list            # per chip: Events of device ops (leaves)
    running: list        # per chip: Events of every op, loops included
    modules: list        # per chip: Events of program executions
    spans: Events        # harness phase spans on the host
    t0: float
    t1: float

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, running, modules, spans = [], [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mods = lines.get("XLA Modules")
            modules.append(Events.of([] if mods is None else [
                (e.name, e.start_ns, e.duration_ns) for e in mods.events]))
            every = Events.of_ops(list(lines["XLA Ops"].events), modules[-1])
            running.append(every)
            ops.append(every.leaves())
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [(e.name, e.start_ns, e.duration_ns)
                          for e in ln.events if e.name in PHASES]
    if not ops:
        raise ValueError(f"{path}: no device plane with an 'XLA Ops' line")
    spans = Events.of(spans)
    if not spans.names:
        raise ValueError(f"{path}: no harness spans")
    return Trace(ops, running, modules, spans, float(spans.start[0]),
                 float(spans.end.max()))


def _union(start, end, t0, t1):
    """Merged, clipped intervals."""
    s, e = np.clip(start, t0, t1), np.clip(end, t0, t1)
    keep = e > s
    s, e = s[keep], e[keep]
    merged = []
    for a, b in zip(s, e):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_s(tr: Trace) -> float:
    """Seconds in which an op ran, averaged over the chips."""
    tot = [sum(b - a for a, b in _union(o.start, o.end, tr.t0, tr.t1))
           for o in tr.running]
    return float(np.mean(tot)) * 1e-9


def idle_gaps(tr: Trace, chip: int = 0) -> list[tuple[str, float]]:
    """(harness span at the gap's midpoint, seconds) for every idle gap."""
    busy = _union(tr.running[chip].start, tr.running[chip].end, tr.t0, tr.t1)
    edges = [tr.t0] + [x for iv in busy for x in iv] + [tr.t1]
    out = []
    sp = tr.spans
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        i = np.searchsorted(sp.start, mid, side="right") - 1
        label = sp.names[i] if i >= 0 and sp.end[i] >= mid else "outside_spans"
        out.append((label, (b - a) * 1e-9))
    return out


def op_time(tr: Trace, prefix: str) -> float:
    """Summed device seconds of ops whose name starts with ``prefix``,
    averaged over chips."""
    tot = []
    for o in tr.ops:
        sel = o.select(lambda n: n.startswith(prefix))
        tot.append(float(np.sum(np.clip(sel.end, tr.t0, tr.t1)
                                - np.clip(sel.start, tr.t0, tr.t1))))
    return float(np.mean(tot)) * 1e-9


def kernel_time(tr: Trace, prefix: str) -> float:
    """Device seconds of a kernel's calls (ops named ``prefix``...) and of
    the ops in the same program that produce what they read: the per-layer
    slices XLA copies out of stacked weights or the KV pool, the quantized
    activations. Averaged over chips."""
    tot = []
    for o in tr.ops:
        calls = [i for i, n in enumerate(o.names) if n.startswith(prefix)]
        feeds = {(o.program[i], r) for i in calls for r in o.reads[i]}
        mine = set(calls) | {i for i in range(len(o.names))
                             if (o.program[i], o.ids[i]) in feeds}
        sel = o.take(sorted(mine))
        tot.append(float(np.sum(np.clip(sel.end, tr.t0, tr.t1)
                                - np.clip(sel.start, tr.t0, tr.t1))))
    return float(np.mean(tot)) * 1e-9


_SHAPE = re.compile(r"(pred|[a-z]+\d*(?:e\d+m\d+\w*)?)\[([\d,]*)\]\{([^}]*)\}")
_BITS = {"pred": 8, "s4": 4, "u4": 4, "s8": 8, "u8": 8, "bf16": 16, "f16": 16,
         "s16": 16, "u16": 16, "f32": 32, "s32": 32, "u32": 32, "f64": 64,
         "s64": 64, "u64": 64}


def shape_bytes(text: str) -> tuple[float, float]:
    """(bytes in device memory, bytes in on-chip memory) of the arrays
    typed in ``text``; a layout with ``S(1)`` lives in on-chip memory."""
    hbm = vmem = 0.0
    for dtype, dims, layout in _SHAPE.findall(text):
        bits = _BITS.get(dtype, 8 if dtype.startswith("f8") else 32)
        n = float(np.prod([int(x) for x in dims.split(",") if x] or [1]))
        if "S(1)" in layout:
            vmem += n * bits / 8
        else:
            hbm += n * bits / 8
    return hbm, vmem


def _result_text(event_name: str) -> str:
    """The result type(s) of an op: between ``=`` and the op's own name."""
    rest = event_name.split(" = ", 1)[-1]
    m = re.search(r"[})]\s+[a-z][\w\-]*\(", rest)
    return rest[: m.start() + 1] if m else ""


def _operand_text(event_name: str) -> str:
    """The typed operand list of an op, without its attributes."""
    rest = event_name.split(" = ", 1)[-1]
    i = rest.find("(", len(_result_text(event_name)))
    depth = 0
    for j in range(max(i, 0), len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[j], 0)
        if depth == 0:
            return rest[i: j + 1]
    return rest[max(i, 0):]


def kernel_report(tr: Trace, prefix: str, chip: int = 0) -> dict:
    """What ``kernel_time`` is made of, for reading a roofline share: the
    kernel's own calls and device seconds, with the bytes of their operands
    in device and in on-chip memory; the feeding ops' count and seconds,
    with the bytes they write in each memory."""
    o = tr.ops[chip]
    inside = (o.start >= tr.t0) & (o.end <= tr.t1)
    calls = [i for i, n in enumerate(o.names) if n.startswith(prefix) and inside[i]]
    want = {(o.program[i], r) for i in calls for r in o.reads[i]}
    feeds = [i for i in range(len(o.names))
             if inside[i] and (o.program[i], o.ids[i]) in want]

    def total(idx, text):
        b = [shape_bytes(text(o.texts[i])) for i in idx]
        return (sum(x for x, _ in b), sum(y for _, y in b))

    out = {"calls": len(calls), "feeds": len(feeds),
           "kernel_s": float(np.sum(o.end[calls] - o.start[calls])) * 1e-9,
           "feed_s": float(np.sum(o.end[feeds] - o.start[feeds])) * 1e-9}
    out["operand_hbm_bytes"], out["operand_vmem_bytes"] = total(calls, _operand_text)
    out["feed_hbm_bytes"], out["feed_vmem_bytes"] = total(feeds, _result_text)
    return out


def describe(rep: dict, least_s: float) -> str:
    """One line of ``kernel_report`` beside the calls' least time."""
    gb = 1e-9
    k, f = rep["kernel_s"], rep["feed_s"]
    return (f"{rep['calls']} calls, {k:.4f} s alone (share {100 * least_s / k:.1f}%) "
            if k > 0 else f"{rep['calls']} calls, ") + (
        f"operands {rep['operand_hbm_bytes'] * gb:.3f} GB in HBM, "
        f"{rep['operand_vmem_bytes'] * gb:.3f} GB on chip; "
        f"{rep['feeds']} feeding ops {f:.4f} s writing "
        f"{rep['feed_hbm_bytes'] * gb:.3f} GB to HBM, "
        f"{rep['feed_vmem_bytes'] * gb:.3f} GB on chip"
        + (f" ({(rep['feed_hbm_bytes'] + rep['feed_vmem_bytes']) * gb / f:.1f} GB/s)"
           if f > 0 else "")
        + (f"; with them share {100 * least_s / (k + f):.1f}%" if k + f > 0 else ""))


def op_count(tr: Trace, prefix: str, chip: int = 0) -> int:
    o = tr.ops[chip]
    inside = (o.start >= tr.t0) & (o.end <= tr.t1)
    return int(sum(1 for n, k in zip(o.names, inside) if k and n.startswith(prefix)))


def program_time(tr: Trace, substring: str) -> float:
    """Busy device seconds inside executions of programs whose name
    contains ``substring``, averaged over chips."""
    tot = []
    for o, m in zip(tr.running, tr.modules):
        sel = m.select(lambda n: substring in n)
        t = 0.0
        for a, b in _union(sel.start, sel.end, tr.t0, tr.t1):
            t += sum(y - x for x, y in _union(o.start, o.end, a, b))
        tot.append(t)
    return float(np.mean(tot)) * 1e-9


def top_ops(tr: Trace, k: int = 10, chip: int = 0) -> list[tuple[str, float]]:
    """The ``k`` op names with the most device time."""
    o = tr.ops[chip]
    acc: dict[str, float] = {}
    dur = np.clip(o.end, tr.t0, tr.t1) - np.clip(o.start, tr.t0, tr.t1)
    for n, d in zip(o.names, dur):
        if d > 0:
            acc[n] = acc.get(n, 0.0) + d * 1e-9
    return sorted(acc.items(), key=lambda kv: -kv[1])[:k]


def idle_by_span(tr: Trace, k: int = 10) -> list[tuple[str, float]]:
    acc: dict[str, float] = {}
    for label, s in idle_gaps(tr):
        acc[label] = acc.get(label, 0.0) + s
    return sorted(acc.items(), key=lambda kv: -kv[1])[:k]
