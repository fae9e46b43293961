"""A run whose timed path is broken underneath comes out not ``correct``.

Each test drives the whole of a run (set-up, warm-up, the window through
``SchedulerCore.serve``, the read-back of served tokens and the reference
check) at a tiny size on the CPU, skipping only the look for a chip, with
one fault planted in the program's decode step. The limit is the one the
decode cell commits."""

import dataclasses
import gc
import json
import os

import jax.numpy as jnp
import pytest

from bench import run as R
from bench.tests.record_trace import tiny_cell

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**31 + 12345


def limit():
    with open(os.path.join(HERE, "..", "workloads", "dscoder33b-s16.decode.json")) as f:
        return json.load(f)["limits"]["max_logit_gap"]


def _wrap(engine, fn):
    model = engine.model
    engine.model = dataclasses.replace(model, decode_paged=fn(model.decode_paged))


def token_altered(engine):
    """Every 16th position's token is the best token's neighbour id."""
    def wrap(dp):
        def decode_paged(params, tok, cache, table, pos):
            logits, cache = dp(params, tok, cache, table, pos)
            hit = (pos % 16 == 0)[:, None]
            return jnp.where(hit, jnp.roll(logits, 1, axis=-1), logits), cache
        return decode_paged
    _wrap(engine, wrap)


def state_unchanged(engine):
    """The decode step returns the KV pool it was given: no new rows."""
    def wrap(dp):
        def decode_paged(params, tok, cache, table, pos):
            logits, _ = dp(params, tok, cache, table, pos)
            return logits, cache
        return decode_paged
    _wrap(engine, wrap)


def half_batch(engine):
    """Only the first half of the slots is computed; the other half gets
    their logits."""
    def wrap(dp):
        def decode_paged(params, tok, cache, table, pos):
            logits, cache = dp(params, tok, cache, table, pos)
            h = logits.shape[0] // 2
            return jnp.concatenate([logits[:h], logits[:h]]), cache
        return decode_paged
    _wrap(engine, wrap)


def run_tiny(fault):
    cell = tiny_cell(limits={"max_logit_gap": limit()}, check_requests=8)
    run, device, peak = R.serve_window(cell, SEED, 2.0, False,
                                       require_chip=False, fault=fault)
    gc.collect()
    out = R.result(run, R.check(run, SEED), device, peak, False)
    return out


def test_sound_run_is_correct():
    out = run_tiny(None)
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_checked"]["value"] >= 50


def test_window_uses_only_warmed_shapes():
    """The program's own admission makes only prefill shapes the host
    replay predicted, so nothing is traced or compiled inside the window."""
    from bench import harness
    from repro.serving.core import bucket_length

    cell = tiny_cell()
    run, _, _ = R.serve_window(cell, SEED + 1, 2.0, False, require_chip=False)
    used = set()
    for w in run.waves:
        groups = {}
        for _, _, glen in w.members:
            groups[glen] = groups.get(glen, 0) + 1
        used |= {(g, n) for n, g in groups.items()}

    class Pad:                       # the adapter's padding: bucket, whole blocks
        group_len = staticmethod(lambda n: -(-bucket_length(n) // 8) * 8)

    predicted = harness.admission_shapes(Pad, list(run.requests.values()),
                                         cell["slots"], harness.CHUNK)
    assert len(run.waves) > 3 and used <= predicted
    assert any(g > 1 for g, _ in used)
    assert run.traced_in_window == 0 and run.compiled_in_window == 0


@pytest.mark.parametrize("fault", [token_altered, state_unchanged, half_batch])
def test_fault_is_caught(fault):
    out = run_tiny(fault)
    assert not out["correct"], out["checks"]
