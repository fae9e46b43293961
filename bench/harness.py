"""The system under test, driven through its own serving path.

Builds the program's ``InferenceEngine`` over seeded int8 weights (made on
the device at storage width, ``bench/weights.py``) and serves a cell's
offline queue through ``SchedulerCore.serve`` on a ``PagedAdapter``
subclass. Admission, prefill grouping and decode rounds are the
program's own: the subclass changes nothing the program computes or
decides. It only

- records host timestamps, live masks, positions and ``pool.live_blocks``
  at every admission and decode round, and keeps each round's step count
  (a device scalar, read back after the window);
- writes host spans (``jax.profiler.TraceAnnotation``) named after the
  phase the host is in, ``admission``, ``prefill`` and ``decode_round``,
  so a trace can say what the host did in each idle gap;
- closes the window: the first hook after the deadline raises
  ``WindowClosed`` out of ``serve``.

``recorded_responses`` keeps the ``Response`` the core itself builds for
each request it finishes: the tokens the ``correct`` check scores.
``admission_shapes`` replays the core's schedule on the host to find every
prefill shape the window can use, and ``warm_up`` compiles those (or loads
them from the compile cache) before the window.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import resource
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from repro.serving.paged import PagedAdapter

BLOCK_SIZE = 8      # the program's default KV block
CHUNK = 4           # the program's default decode round


class WindowClosed(Exception):
    """Raised from a scheduler hook once the measured window has ended."""


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    if cfg["vocab_size"] % 32:
        raise ValueError("vocab_size must be a multiple of 32 (no padded rows)")
    return ModelConfig(
        arch_id=cfg["name"], family="dense", model_type="decoder_lm",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        group_size=cfg["group_size"], param_dtype=cfg["dtype"],
        compute_dtype=cfg["dtype"])


def served_params(model, cfg: dict, seed: int):
    """The program's int8 parameter tree, made on the device in one jitted
    call from the seed: the tree ``quantize_params(model.init(...))`` has,
    with no float copy of any weight."""
    from repro.core.policy import quantize_params
    from repro.core.quant import QuantizedTensor

    want = jax.eval_shape(lambda: quantize_params(
        model.init(jax.random.PRNGKey(0)), cfg["group_size"]))

    def qt(q, s):
        return QuantizedTensor(qvalues=q, scales=s,
                               group_size=cfg["group_size"], fmt="int8")

    def cat(a, b_or_list):
        parts = [a] + list(b_or_list)
        return (jnp.concatenate([p[0] for p in parts]),
                jnp.concatenate([p[1] for p in parts]))

    @jax.jit
    def make(wkey):
        def layer(i):
            lw = W.layer_weights(wkey, i, cfg)
            # the program fuses q|k|v and gate|up into one matrix each
            return {"att_norm": lw["att_norm"], "ffn_norm": lw["ffn_norm"],
                    "wqkv": cat(lw["wq"], [lw["wk"], lw["wv"]]),
                    "wo": lw["wo"], "w13": cat(lw["w_gate"], [lw["w_up"]]),
                    "w2": lw["w_down"]}

        ls = jax.lax.map(layer, jnp.arange(cfg["num_hidden_layers"]))
        return {
            "embed": qt(*W.embedding(wkey, cfg)),
            "layers": {"att_norm": ls["att_norm"],
                       "attn": {"wqkv": qt(*ls["wqkv"]), "wo": qt(*ls["wo"])},
                       "ffn_norm": ls["ffn_norm"],
                       "mlp": {"w13": qt(*ls["w13"]), "w2": qt(*ls["w2"])}},
            "final_norm": W.final_norm(wkey, cfg),
            "classifier": qt(*W.classifier(wkey, cfg)),
        }

    params = make(W.weight_key(seed))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    exp = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != exp:
        raise AssertionError("seeded weights do not match the program's "
                             f"parameter tree:\n{got}\n!=\n{exp}")
    return params


@dataclasses.dataclass
class Wave:
    """One admission wave: (slot, request id, padded prompt length) of each
    request it admitted."""
    members: list
    traced: bool = False


@dataclasses.dataclass
class Round:
    """One decode round, as the host saw it before dispatching it."""
    live: np.ndarray
    rids: list
    pos: np.ndarray
    blocks: int
    steps: object = None               # device scalar
    traced: bool = False
    n_steps: int = 0                   # read back after the window
    t_done: float | None = None        # its tokens on the host


class BenchAdapter(PagedAdapter):
    """``PagedAdapter`` with the harness's hooks (see the module doc)."""

    def __init__(self, engine, *, max_len: int):
        super().__init__(engine, block_size=BLOCK_SIZE, max_len=max_len)

    def arm(self, *, t0: float, deadline: float, trace_at: float | None = None,
            trace_dir: str | None = None):
        self.t0, self.deadline, self.t_close = t0, deadline, None
        self.trace_at, self.trace_dir, self.tracing = trace_at, trace_dir, False
        self.waves: list[Wave] = []
        self.rounds: list[Round] = []
        self.t_admit, self.t_first, self.t_last = {}, {}, {}
        self._wave = None
        self._rid = [None] * self.core.slots
        self._span = self._span_name = None
        # the host's own record of the window: every hook's time, the
        # process's CPU time and preemptions, and garbage-collector pauses
        self.stamps: list[tuple[float, str]] = [(t0, "open")]
        self.gc_pause = collections.Counter()
        self.gc_count = collections.Counter()
        self._gc_t = None
        self._usage0 = resource.getrusage(resource.RUSAGE_SELF)
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            self.gc_pause[info["generation"]] += time.perf_counter() - self._gc_t
            self.gc_count[info["generation"]] += 1

    def _stamp(self, name: str, now: float | None = None):
        self.stamps.append((time.perf_counter() if now is None else now, name))

    def host_report(self) -> str:
        """One line on what the host did in the window: its CPU time,
        involuntary context switches, garbage-collector pauses by
        generation, and the three longest gaps between hook calls."""
        u0, u1 = self._usage0, self._usage1
        cpu = (u1.ru_utime + u1.ru_stime) - (u0.ru_utime + u0.ru_stime)
        gaps = sorted(((b[0] - a[0], a[1], b[1], a[0] - self.t0)
                       for a, b in zip(self.stamps, self.stamps[1:])), reverse=True)
        return (f"host in window: cpu {cpu:.2f} s, "
                f"{u1.ru_nivcsw - u0.ru_nivcsw} involuntary switches, gc pauses "
                + " ".join(f"gen{g} {self.gc_count[g]}x {self.gc_pause[g]:.3f} s"
                           for g in range(3))
                + "; longest gaps: " + ", ".join(
                    f"{d:.3f} s {a}->{b} at {t:.1f} s" for d, a, b, t in gaps[:3]))

    # -- host spans -----------------------------------------------------------
    def _phase(self, name: str | None):
        if name == self._span_name:
            return
        if self._span is not None:
            self._span.__exit__(None, None, None)
        self._span, self._span_name = None, name
        if name is not None:
            self._span = jax.profiler.TraceAnnotation(name)
            self._span.__enter__()

    def close(self):
        self._phase(None)
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
            self._usage1 = resource.getrusage(resource.RUSAGE_SELF)

    def _delivered(self, now):
        """Stamp the last round's tokens as delivered: every hook after a
        dispatched round runs after the core read its tokens back."""
        if self.rounds and self.rounds[-1].steps is not None and self.rounds[-1].t_done is None:
            self.rounds[-1].t_done = now

    def _check_deadline(self, now):
        if now >= self.deadline:
            self._stamp("close", now)
            self.t_close = now
            self.close()
            raise WindowClosed

    # -- admission ------------------------------------------------------------
    def can_admit(self, r, budget):
        now = time.perf_counter()
        self._stamp("can_admit", now)
        self._delivered(now)
        self._check_deadline(now)
        if self._wave is None:
            self._wave = Wave([], traced=self.tracing)
            self._phase("admission")
        return super().can_admit(r, budget)

    def on_admit(self, s, r, budget):
        super().on_admit(s, r, budget)
        self.t_admit[r.id] = time.perf_counter()
        self._rid[s] = r.id
        self._wave.members.append((s, r.id, self.group_len(len(r.tokens))))

    def prefill(self, length):
        fn = super().prefill(length)

        def call(*args):
            self._phase("prefill")
            out = fn(*args)
            self._stamp("prefill")
            return out

        return call

    # -- decode rounds ----------------------------------------------------------
    def before_round(self, pos, live):
        now = time.perf_counter()
        self._stamp("before_round", now)
        self._delivered(now)
        if self._wave is not None:
            for _, rid, _ in self._wave.members:   # first tokens on the host
                self.t_first[rid] = now
            if self._wave.members:
                self.waves.append(self._wave)
            self._wave = None
        if (self.trace_at is not None and not self.tracing
                and now >= self.trace_at):
            self._phase(None)          # spans begun before the trace are lost
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # a traced Python call per call: far too slow
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.tracing = True
        self._check_deadline(now)
        self._phase("decode_round")
        super().before_round(pos, live)
        self.rounds.append(Round(live.copy(), list(self._rid), pos.copy(),
                                 self.pool.live_blocks, traced=self.tracing))

    def decode_round(self, params, tok, cache, pos, live, remaining, keys):
        toks, steps, cache, pos = super().decode_round(
            params, tok, cache, pos, live, remaining, keys)
        self.rounds[-1].steps = steps
        self._stamp("decode_sent")
        return toks, steps, cache, pos

    def on_finish(self, s):
        super().on_finish(s)
        now = time.perf_counter()
        self._stamp("on_finish", now)
        self._delivered(now)
        self.t_last[self._rid[s]] = now
        self._rid[s] = None

    def read_back(self) -> list[Round]:
        """The rounds whose tokens reached the host, with their step counts
        (one read-back after the window)."""
        done = [r for r in self.rounds if r.t_done is not None]
        for r, n in zip(done, jax.device_get([r.steps for r in done])):
            r.n_steps = int(n)
        return done


@contextlib.contextmanager
def recorded_responses():
    """Yields {request id: Response} of every request the core finishes
    meanwhile, as the core built it (its ``make_response``), so that the
    check reads the tokens the program assembled and delivered."""
    from repro.serving import core

    made = {}
    build = core.make_response

    def record(req, *args, **kw):
        resp = build(req, *args, **kw)
        made[req.id] = resp
        return resp

    core.make_response = record
    try:
        yield made
    finally:
        core.make_response = build


def admission_shapes(adapter, requests, slots: int, chunk: int) -> set:
    """Every (group size, padded prompt length) prefill the program's
    admission makes when it serves ``requests`` in order to the end, with
    no EOS. Replays ``SchedulerCore.serve`` on the host: at each round
    boundary the free slots take the next requests in order, one prefill
    group per padded length (the default pool always admits to a free
    slot); a decode round runs up to ``chunk`` steps and stops at the step
    any slot finishes."""
    pending = collections.deque(requests)
    left = np.zeros(slots, np.int64)       # tokens still to decode
    busy = np.zeros(slots, bool)
    shapes = set()
    while pending or busy.any():
        groups = collections.Counter()
        for s in np.flatnonzero(~busy)[: len(pending)]:
            r = pending.popleft()
            groups[adapter.group_len(len(r.tokens))] += 1
            left[s], busy[s] = r.max_new - 1, True
        shapes |= {(g, n) for n, g in groups.items()}
        busy &= left > 0                   # a one-token budget ends at prefill
        if busy.any():
            left[busy] -= min(chunk, int(left[busy].min()))
            busy &= left > 0
    return shapes


def warm_up(core, adapter, shapes) -> None:
    """Compile (or load from the compile cache) every program the window
    runs: the prefill and the insert of each (group size, padded length)
    in ``shapes``, and the decode round, called with the argument types
    ``SchedulerCore.serve`` passes. Rows go to the pool's sink block."""
    params, slots = core.engine.params, core.slots
    cache = adapter.begin_serve()
    key = jax.random.PRNGKey(0)
    # largest first, while the device holds least: a prefill's scratch is
    # reserved at the bottom of device memory when it runs (5 x 4096 tokens
    # of internlm2-1.8b takes 10.2 GB), and no earlier output may sit there
    for g, n in sorted(shapes, key=lambda gn: (-gn[0] * gn[1], gn)):
        key, kp = jax.random.split(key)
        toks, lens = np.zeros((g, n), np.int32), np.full((g,), n, np.int32)
        first, rows = PagedAdapter.prefill(adapter, n)(
            params, jnp.asarray(toks), jnp.asarray(lens), kp)
        cache = adapter.insert(cache, rows, [(s, None) for s in range(g)], n)
        jax.device_get([first])
        del first, rows
    key, kc = jax.random.split(key)
    zero = np.zeros((slots,), np.int32)
    toks, steps, cache, pos = PagedAdapter.decode_round(
        adapter, params, jnp.asarray(zero), cache, jnp.asarray(zero),
        jnp.asarray(np.zeros((slots,), bool)), jnp.asarray(zero),
        jax.random.split(kc, core.chunk))
    jax.device_get((steps, toks, pos))
    adapter.end_serve()
