"""The serving loop's spans and counters (``serve.*``, ``SchedulerCore.counts``).

A tiny paged model serves a queue longer than its slots under
``jax.profiler``; the ``serve.*`` host events are read back from the
``.xplane.pb`` with their args and checked against what the loop did: the
order of each round's phases, the prefill groups ``pad_bucket`` built, the
pool's live blocks, the tokens the responses hold, and the counters the
core keeps.
"""

import glob
import os
import sys

import jax
import numpy as np
import pytest

from repro.models.registry import build, load_config
from repro.serving import core as core_mod
from repro.serving.core import Request, SchedulerCore
from repro.serving.engine import InferenceEngine
from repro.serving.paged import PagedAdapter

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROUND = ("serve.round_prepare", "serve.round_dispatch", "serve.round_sync",
         "serve.round_commit")


class RecordingAdapter(PagedAdapter):
    """Records ``pool.live_blocks`` after each round's block growth."""

    def begin_serve(self):
        self.grown = []
        return super().begin_serve()

    def before_round(self, pos, live):
        super().before_round(pos, live)
        self.grown.append(self.pool.live_blocks)


def read_spans(trace_dir):
    """Every ``serve.*`` host event: (name, start ns, end ns, args), in
    start order."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                        for e in ln.events if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def serve_traced(tmp_dir, monkeypatch, *, spec_k=None):
    cfg = load_config("tinyllama-1.1b").reduced()
    model = build(cfg)
    engine = InferenceEngine(model, model.init(jax.random.PRNGKey(0)), cache_len=64)
    adapter = RecordingAdapter(engine, block_size=8, max_len=64)
    core = SchedulerCore(engine, adapter, slots=3, chunk=4, spec_k=spec_k)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(1, 200, int(n)).tolist(), max_new=int(m))
            for i, (n, m) in enumerate(zip([5, 13, 3, 20, 9, 17, 2, 11],
                                           [6, 3, 9, 5, 1, 7, 4, 8]))]
    core.serve(reqs, 8)                      # compile outside the trace
    built, pad = [], core_mod.pad_bucket

    def pad_bucket(rs, length, pad_id=0):
        toks, lens = pad(rs, length, pad_id)
        built.append((toks.shape, int(lens.sum())))
        return toks, lens

    monkeypatch.setattr(core_mod, "pad_bucket", pad_bucket)
    jax.profiler.start_trace(str(tmp_dir))
    try:
        resp = core.serve(reqs, 8)
    finally:
        jax.profiler.stop_trace()
    return {"spans": read_spans(str(tmp_dir)), "resp": resp, "reqs": reqs,
            "core": core, "adapter": adapter, "built": built}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        return serve_traced(tmp_path_factory.mktemp("trace"), mp)


def by_name(spans, name):
    return [s for s in spans if s[0] == name]


def test_round_phases_in_order_with_kv_grow_inside_prepare(served):
    spans = served["spans"]
    rounds = {}
    for s in spans:
        if s[0] in ROUND:
            rounds.setdefault(s[3]["round"], []).append(s)
    assert rounds and sorted(rounds) == list(range(len(rounds)))
    grows = by_name(spans, "serve.kv_grow")
    assert len(grows) == len(rounds)
    for k, phases in rounds.items():
        assert [s[0] for s in phases] == list(ROUND), k
        for a, b in zip(phases, phases[1:]):
            assert a[2] <= b[1]
        prep = phases[0]
        assert prep[1] <= grows[k][1] and grows[k][2] <= prep[2]
        assert prep[3]["live"] == phases[-1][3]["live"] >= 1


def test_prefill_args_match_pad_bucket(served):
    pre = by_name(served["spans"], "serve.prefill")
    assert len(pre) == len(served["built"]) >= 3
    for (_, _, _, args), ((rows, length), tokens) in zip(pre, served["built"]):
        assert (args["rows"], args["length"], args["tokens"]) == (rows, length, tokens)
        assert length % 8 == 0 and tokens <= rows * length
    admits = by_name(served["spans"], "serve.admit")
    assert sum(a[3]["admitted"] for a in admits) == len(served["reqs"])
    assert admits[0][3]["pending"] == len(served["reqs"]) - 3
    waves = by_name(served["spans"], "serve.admit_sync")
    assert sum(w[3]["groups"] for w in waves) == len(pre)


def test_kv_grow_reports_the_pool(served):
    grows = by_name(served["spans"], "serve.kv_grow")
    pool = served["adapter"].pool
    assert [g[3]["blocks_live"] for g in grows] == served["adapter"].grown
    for g in grows:
        a = g[3]
        assert a["blocks_live"] + a["blocks_free"] == pool.num_blocks - 1
        assert a["backlog"] >= 0


def test_live_slot_steps_are_the_decoded_tokens(served):
    commits = by_name(served["spans"], "serve.round_commit")
    decoded = sum(int(r.length) - 1 for r in served["resp"])
    assert sum(c[3]["steps"] * c[3]["live"] for c in commits) == decoded
    assert sum(c[3]["finished"] for c in commits) + sum(
        r.max_new == 1 for r in served["reqs"]) == len(served["reqs"])


def test_counts_are_the_last_commits_args(served):
    counts = served["core"].counts
    last = by_name(served["spans"], "serve.round_commit")[-1][3]
    assert counts == {k: last[k] for k in counts}
    assert counts["prompt_tokens"] == sum(len(r.tokens) for r in served["reqs"])
    assert counts["prefill_slots"] == sum(np.prod(s) for s, _ in served["built"])
    assert 0 < counts["live_slot_steps"] <= counts["slot_steps"]
    assert counts["slot_steps"] % 3 == 0


def test_no_span_takes_a_harness_phase_name(served):
    sys.path.insert(0, ROOT)
    try:
        from bench.trace import PHASES
    finally:
        sys.path.remove(ROOT)
    names = {s[0] for s in served["spans"]}
    assert names == set(ROUND) | {"serve.admit", "serve.prefill", "serve.admit_sync",
                                  "serve.kv_grow"}
    assert not names & set(PHASES)


def test_verify_rounds_write_the_same_spans(tmp_path, monkeypatch):
    got = serve_traced(tmp_path, monkeypatch, spec_k=3)
    commits = by_name(got["spans"], "serve.round_commit")
    assert commits and all(c[3]["steps"] == 1 for c in commits)
    assert [c[3]["round"] for c in commits] == list(range(len(commits)))
    assert got["core"].counts["slot_steps"] == 3 * len(commits)
    assert got["core"].counts["live_slot_steps"] == sum(c[3]["live"] for c in commits)
