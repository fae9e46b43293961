"""Layout files (``bench/arch/<layout>.py``): what a configuration's
``"arch"`` names, and what reaches each hook.

The recorded values under ``data/dense_recorded.*`` were made with the
benchmark's code as it stood before layouts were files of their own
(weights and model from ``harness.py``, ``reference.Reference`` and the
``counts`` functions called by name, the attention reader multiplying one
layer's call by the number of layers). The dense layout has to read the
same, bit for bit: the weights, the reference's logits, the counts at the
published shapes, and the three count readers on a chip-recorded trace.
"""

import collections
import hashlib
import json
import os
import re
import sys
import types

import jax
import numpy as np
import pytest

from bench import control
from bench import run as R
from bench import trace as tr
from bench.harness import Round, Wave
from bench.tests.record_trace import TINY, tiny_cell
from bench.traffic import TrafficRequest

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CONFIGS = ("dscoder33b-s16", "internlm2-1.8b")
# tiny widths in place of the published ones; every other key is the file's
TINY_WIDTHS = {
    "dscoder33b-s16": dict(hidden_size=512, intermediate_size=1024, num_attention_heads=14,
                           num_key_value_heads=2, head_dim=128, num_hidden_layers=2,
                           vocab_size=1024),
    "internlm2-1.8b": dict(hidden_size=512, intermediate_size=1024, num_attention_heads=4,
                           num_key_value_heads=2, head_dim=128, num_hidden_layers=2,
                           vocab_size=1024),
}
SEED = 2**33 + 4321
POSITIONS = np.asarray([0, 5, 127, 128, 1000, 2047], np.int32)
LENGTHS = [16, 200, 1403, 2048]
GQMM_ROWS = [(32, 32), (8, 8), (3 * 1024, 3)]          # (rows, head rows)
READERS = ("step.mfu", "gqmm_roofline", "paged_attn_roofline")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "dense_recorded.json")) as f:
        rec = json.load(f)
    with np.load(os.path.join(DATA, "dense_recorded.npz")) as z:
        rec["logits"] = dict(z)
    return rec


def config(name: str) -> dict:
    return R.load_json(R.BENCH, "configs", f"{name}.json")


def leaf_digests(tree) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        out[jax.tree_util.keystr(path)] = [list(a.shape), str(a.dtype),
                                           hashlib.sha256(a.tobytes()).hexdigest()]
    return out


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_weights_as_recorded(name, recorded):
    from repro.models.registry import build

    cfg = dict(config(name), **TINY_WIDTHS[name])
    layout = R.layout_of(cfg)
    params = layout.served_params(build(layout.model_config(cfg)), cfg, SEED)
    assert leaf_digests(params) == recorded["configs"][name]["params"]


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_reference_as_recorded(name, recorded):
    cfg = dict(config(name), **TINY_WIDTHS[name])
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 1024, (2, 40)).astype(np.int32)
    rows = np.asarray([[0, 0], [0, 17], [0, 39], [1, 3], [1, 20], [1, 39], [0, 5], [1, 11]],
                      np.int32)
    for bits in (8, 4):
        got = np.asarray(R.layout_of(cfg).Reference(cfg, weight_bits=bits)
                         .logits(SEED, tokens, rows))
        np.testing.assert_array_equal(got, recorded["logits"][f"{name}.logits{bits}"])


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_counts_at_published_shapes_as_recorded(name, recorded):
    cfg = config(name)
    layout, want = R.layout_of(cfg), recorded["configs"][name]
    assert list(layout.decode_step(cfg, POSITIONS)) == want["decode_step"]
    assert list(layout.prefill(cfg, LENGTHS)) == want["prefill"]
    assert [[list(c) for c in layout.gqmm_calls(cfg, r, h)]
            for r, h in GQMM_ROWS] == want["gqmm_calls"]
    assert [list(c) for c in layout.attention_calls(cfg, POSITIONS)] == want["attention_calls"]


def synthetic_run(cfg, layout, trace, peaks) -> R.Run:
    """A schedule drawn from a fixed generator, as many prefill groups and
    decode steps as the recorded trace holds, every wave and round traced."""
    rng = np.random.default_rng(7)
    requests, waves, rounds, rid = {}, [], [], 0
    for _ in range(56):
        glen = int(rng.choice([16, 32, 64]))
        members = []
        for s in range(int(rng.integers(1, 4))):
            n = int(rng.integers(4, glen + 1))
            requests[rid] = TrafficRequest(rid, np.zeros(n, np.int32), 8)
            members.append((s, rid, glen))
            rid += 1
        waves.append(Wave(members, traced=True))
    for k in range(57):
        live = rng.random(8) < 0.7
        live[k % 8] = True
        rounds.append(Round(live, list(range(8)), rng.integers(8, 100, 8).astype(np.int32),
                            10, traced=True, n_steps=3 if k < 21 else 2, t_done=1.0))
    return R.Run(cell={}, cfg=cfg, layout=layout, peaks=peaks, slots=8, pool_blocks=100,
                 setup_s=0.0, t0=0.0, t_close=1.0, requests=requests, waves=waves,
                 rounds=rounds, t_admit={}, t_first={}, t_last={}, served={}, failed=0,
                 trace=trace)


def test_count_readers_on_a_chip_trace_as_recorded(recorded):
    trace = tr.load(os.path.join(DATA, "small.xplane.pb"))
    peaks = R.load_json(R.BENCH, "peaks.json")["devices"]["TPU v5 lite"]
    run = synthetic_run(dict(TINY), R.layout_of(TINY), trace, peaks)
    assert {m: R.reader(m)(run) for m in READERS} == recorded["readers"]
    assert run.notes == recorded["reader_notes"]


def recording_layout(dense):
    """A layout that delegates every hook to ``dense`` and records, for each
    hook, the files it was called from."""
    calls = collections.defaultdict(set)

    def caller(depth=2):
        return os.path.basename(sys._getframe(depth).f_code.co_filename)

    def wrap(hook):
        def call(*args, **kw):
            calls[hook].add(caller())
            return getattr(dense, hook)(*args, **kw)
        return call

    class Reference(dense.Reference):
        def __init__(self, cfg, *, weight_bits=8):
            calls[f"Reference{weight_bits}"].add(caller())
            super().__init__(cfg, weight_bits=weight_bits)

    hooks = {h: wrap(h) for h in R.LAYOUT_HOOKS if h != "Reference"}
    return types.SimpleNamespace(Reference=Reference, calls=calls, **hooks)


def test_every_hook_is_called_by_its_consumer(monkeypatch):
    dense = R.load_layout("dense")
    rec = recording_layout(dense)
    monkeypatch.setattr(R, "load_layout",
                        lambda name: rec if name == "recording" else dense)
    metrics = R.load_json(R.ROOT, "BENCHMARK.json")["per_layer"]
    cell = tiny_cell(per_layer=[m for m in metrics if m["name"] in READERS])
    cell["cfg"]["arch"] = "recording"
    run, device, peak = R.serve_window(cell, SEED, 2.0, False, require_chip=False)
    assert run.layout is rec
    # read the count readers over the chip-recorded trace, every step traced
    run.trace = tr.load(os.path.join(DATA, "small.xplane.pb"))
    for x in run.waves + run.rounds:
        x.traced = True
    out = R.result(run, R.check(run, SEED), device, peak, True)
    assert set(out["metrics"]) == set(READERS)
    control.readings(run, SEED)
    assert dict(rec.calls) == {
        "model_config": {"run.py"}, "served_params": {"run.py"},
        "Reference8": {"run.py", "control.py"}, "Reference4": {"control.py"},
        "prefill": {"step.mfu.py"}, "decode_step": {"step.mfu.py"},
        "gqmm_calls": {"gqmm_roofline.py"},
        "attention_calls": {"paged_attn_roofline.py"}}


def test_unknown_layout_fails_in_load_cell(monkeypatch):
    load = R.load_json

    def with_arch(*parts):
        out = load(*parts)
        return dict(out, arch="no_such_layout") if "configs" in parts else out

    monkeypatch.setattr(R, "load_json", with_arch)
    want = os.path.join(R.BENCH, "arch", "no_such_layout.py")
    with pytest.raises(ValueError, match=re.escape(f"looked for {want}")):
        R.load_cell("internlm2-1.8b.longctx")


def test_layout_lacking_a_hook_is_refused(tmp_path, monkeypatch):
    (tmp_path / "arch").mkdir()
    (tmp_path / "arch" / "partial.py").write_text("def model_config(cfg):\n    pass\n")
    monkeypatch.setattr(R, "BENCH", str(tmp_path))
    with pytest.raises(ValueError, match="lacks served_params, Reference"):
        R.load_layout("partial")

