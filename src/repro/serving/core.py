"""The scheduling core: ONE serving loop, pluggable per-slot cache adapters.

Every continuous-batching mode is the same host loop — validate, admit
pending requests into fixed decode slots (one batched prefill per admission
group), decode in jitted rounds, finish slots at EOS/budget, finalize
Responses in arrival order. What differs between modes is only HOW a slot's
persistent decode state is laid out and addressed:

- ``ContiguousAdapter`` — one ``cache_len``-wide KV row per slot (the
  original ``SlotScheduler`` cache), batch on axis 1 of every leaf.
- ``PagedAdapter`` (serving/paged.py) — a ``BlockPool`` of fixed-size KV
  blocks behind per-slot block tables; admission is reservation-gated and
  blocks are allocated on demand / reclaimed the step a slot finishes.
- ``RecurrentAdapter`` — O(1) per-slot recurrent state (rwkv6, zamba2's SSM
  backbone): continuous batching is a state gather/scatter, no paging and —
  for fully O(1) families — no cache capacity to validate at all.

``SchedulerCore`` owns the queue, the slots, the budgets, the speculative
draft/accept bookkeeping and the Response finalization; adapters own the
jitted device programs (prefill/insert/decode/verify). Adapters return
DEVICE arrays; the core performs the single host sync per admission wave and
per round, so the host-sync round-trip budget (DESIGN.md §7,
analysis/host_sync.py) is enforced lexically on one loop instead of one copy
per scheduler (DESIGN.md §12).

Each phase of the loop runs inside a ``jax.profiler.TraceAnnotation`` named
``serve.*`` whose args carry the phase's counts (README.md "Tracing a
server"); with no profiler running a span costs about a microsecond.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict, deque
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.serving.sampling import make_sampler

__all__ = [
    "CacheAdapter",
    "ContiguousAdapter",
    "RecurrentAdapter",
    "Request",
    "Response",
    "SchedulerCore",
    "bucket_length",
    "finalize_tokens",
    "make_response",
    "pad_bucket",
]


@dataclasses.dataclass
class Request:
    id: int
    tokens: list[int]
    # per-request decode budget; None falls back to the serve call's
    # max_new_tokens. Mixed budgets are where continuous batching pays off:
    # bucketed decode drags every row to its bucket's longest budget, the
    # slot schedulers free and refill each slot at its own.
    max_new: int | None = None


@dataclasses.dataclass
class Response:
    id: int
    tokens: np.ndarray
    # true generated length: tokens[:length] are real, the rest is padding
    # (EOS, or 0 when the engine has no eos_id — indistinguishable from a
    # real vocab-0 token, which is exactly why the length rides along).
    length: int | None = None


def finalize_tokens(toks: list[int], budget: int, eos: int | None):
    """Trim at EOS, pad to ``budget``; returns (tokens (budget,), true length).

    ``length`` counts the real generated tokens (including the EOS itself);
    callers must not infer it from the pad value — with ``eos None`` the pad
    token 0 is a legal vocab id."""
    t = toks[:budget]
    if eos is not None and eos in t:
        t = t[: t.index(eos) + 1]
    length = len(t)
    t = t + [eos if eos is not None else 0] * (budget - length)
    return np.asarray(t, np.int32), length


def make_response(req: Request, toks: list[int], budget: int,
                  eos: int | None) -> Response:
    """The one Response construction path for every serving mode (bucketed,
    continuous, recurrent, paged): trim at EOS, pad to the request's budget,
    carry the true generated length. Keeping EOS/length semantics in a
    single call site is what makes the cross-mode parity tests meaningful."""
    tokens, length = finalize_tokens(toks, budget, eos)
    return Response(id=req.id, tokens=tokens, length=length)


def bucket_length(n: int, *, minimum: int = 8) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def pad_bucket(reqs: Sequence[Request], length: int, pad_id: int = 0):
    """Right-pad to ``length``; returns (tokens (b, length), true lengths)."""
    toks = np.full((len(reqs), length), pad_id, np.int32)
    lens = np.zeros((len(reqs),), np.int32)
    for i, r in enumerate(reqs):
        toks[i, : len(r.tokens)] = r.tokens
        lens[i] = len(r.tokens)
    return toks, lens


# ---------------------------------------------------------------------------
# cache adapters
# ---------------------------------------------------------------------------

class CacheAdapter:
    """Per-slot cache policy behind ``SchedulerCore``: alloc / insert /
    commit / free / snapshot. The protocol verbs map onto the loop as:

      alloc    ``can_admit`` / ``on_admit``  (paged: reservation-gated block
               allocation; contiguous/recurrent: a free slot IS the alloc)
      insert   ``prefill`` + ``insert``      (batched prefill rows scattered
               into the admitted slots)
      commit   ``decode_round`` / ``verify_round``  (jitted programs that
               advance the cache in place — buffers donated)
      free     ``on_finish``                 (paged: blocks back to the pool,
               table row sunk; others: freeing the slot index is enough)
      snapshot ``snapshot``                  (host copy of per-slot state,
               for preemption/debug)

    Adapters must return DEVICE values from prefill/decode/verify — the core
    owns the one host sync per admission wave and per round."""

    kind: str = "abstract"
    spec_capable: bool = False

    def bind(self, core, *, sampler: str, sampler_kw) -> None:
        """Attach to a core and build the jitted device programs."""
        raise NotImplementedError

    def validate(self, requests, budget, slack: int) -> None:
        """Reject requests that could never be served (capacity/layout)."""

    def begin_serve(self):
        """Fresh per-serve device cache (plus any host-side pool state)."""
        raise NotImplementedError

    def can_admit(self, r: Request, budget: int) -> bool:
        return True

    def on_admit(self, s: int, r: Request, budget: int) -> None:
        """Per-slot allocation at admission (paged: prompt blocks + table)."""

    def group_len(self, n: int) -> int:
        """Padded prefill length for an ``n``-token prompt; admission groups
        share one batched prefill per distinct value."""
        raise NotImplementedError

    def prefill(self, length: int):
        """Jitted (params, toks, lens, key) -> (first tokens, cache rows)."""
        raise NotImplementedError

    def insert(self, cache, rows, group, length: int):
        """Scatter prefill ``rows`` into ``group``'s slots; returns cache."""
        raise NotImplementedError

    def before_round(self, pos, live) -> None:
        """Pre-round host bookkeeping (paged: on-demand block growth)."""

    def check_positions(self, pos, live) -> None:
        """Assert live positions are addressable (cache edge, table edge)."""

    def decode_round(self, params, tok, cache, pos, live, remaining, keys):
        """One jitted decode round -> device (toks (steps, b), steps, cache,
        pos). ``steps`` may be a device scalar (paged early exit) or a plain
        int; the core resolves it inside its single round sync."""
        raise NotImplementedError

    def verify_round(self, params, chunk, cache, pos, live, remaining, key):
        """One jitted speculative verify round -> device (out (b, k), n_out
        (b,), cache, pos). Only ``spec_capable`` adapters implement this."""
        raise NotImplementedError(f"{self.kind}: no speculative verify path")

    def on_finish(self, s: int) -> None:
        """Free slot ``s``'s allocation (the core froze its tok/pos)."""

    def end_serve(self) -> None:
        """Post-serve bookkeeping (paged: pool high-water accounting)."""

    def snapshot(self, cache, slots):
        """Host copy of the per-slot cache state for ``slots``."""
        raise NotImplementedError

    def san_state(self) -> dict:
        """repro-san registration (analysis/sanitizer.py): the adapter's
        host allocator state as ``{"pool": BlockPool | None, "table":
        block-table ndarray | None}``. Every concrete adapter must define
        this (the ``adapter-lifecycle`` checker enforces it) so the shadow
        tracker can mirror whatever the adapter allocates."""
        raise NotImplementedError(f"{self.kind}: adapter registers no "
                                  "sanitizer state (san_state)")


class ContiguousAdapter(CacheAdapter):
    """The original ``SlotScheduler`` cache: one ``cache_len``-wide cache row
    per slot, batch on axis 1 of every leaf (``Model.insert_slots`` /
    ``Model.gather_slots``), live positions bounded by ``cache_len``."""

    kind = "contiguous"
    spec_capable = True

    def __init__(self, engine):
        if not engine.model.supports_lengths:
            raise ValueError(
                f"{engine.cfg.arch_id}: continuous batching needs length-aware "
                "prefill and per-request decode positions (decoder_lm families)"
            )
        self.engine = engine

    def bind(self, core, *, sampler, sampler_kw):
        engine = self.engine
        self.core = core
        self._prefill_jit: dict[int, callable] = {}
        if core.spec_k is not None:
            from repro.serving.spec import build_verify_step

            # verify -> accept -> commit-accepted-prefix in one jitted
            # program; per-slot budgets and the live mask clamp the commit
            self._verify_step = build_verify_step(
                engine.model, sampler=sampler, sampler_kw=sampler_kw,
                paged=False)

        model, sample = engine.model, core._sampler

        # the cache is donated: the core always rebinds it to the result,
        # and without donation XLA keeps both buffers live across every
        # chunk — a full extra cache of device memory
        @partial(jax.jit, donate_argnums=(2,))
        def decode_chunk(params, tok, cache, pos, live, keys):
            # ``live`` (b,) freezes finished/empty slots: their token and
            # position stop advancing, so a slot idling to the chunk
            # boundary keeps committing the SAME in-bounds cache slot of its
            # own (dead) row instead of drifting past cache_len, where the
            # commit would clamp/drop against the cache edge.
            def step(carry, k):
                tok, cache, pos = carry
                logits, cache = model.decode(params, tok, cache, pos)
                nxt = sample(logits, k)
                nxt = jnp.where(live, nxt, tok)
                pos = jnp.where(live, pos + 1, pos)
                return (nxt, cache, pos), nxt

            (tok, cache, pos), toks = jax.lax.scan(step, (tok, cache, pos), keys)
            return toks, cache, pos

        @partial(jax.jit, donate_argnums=(0,))
        def insert_slots(cache, rows, slots):
            return model.insert_slots(cache, rows, slots)

        self._decode_chunk = decode_chunk
        self._insert = insert_slots

    def validate(self, requests, budget, slack):
        cache_len = self.engine.cache_len
        for r in requests:
            need = max(bucket_length(len(r.tokens)),
                       len(r.tokens) + budget(r) + slack)
            if need > cache_len:
                raise ValueError(
                    f"request {r.id}: len={len(r.tokens)} + "
                    f"max_new={budget(r)}"
                    + (f" + spec_k={slack}" if slack else "")
                    + f" needs {need} cache slots "
                    f"but cache_len={cache_len}"
                )

    def begin_serve(self):
        engine = self.engine
        return engine.model.init_cache(
            self.core.slots, engine.cache_len, engine.cfg.cdtype())

    def group_len(self, n):
        return bucket_length(n)

    def prefill(self, length):
        """Jitted batched prefill+sample, cached per padded group length
        (retraces per admission-group size via jit's shape cache)."""
        if length not in self._prefill_jit:
            model, cache_len = self.engine.model, self.engine.cache_len
            sample = self.core._sampler

            @jax.jit
            def prefill_group(params, toks, lens, key):
                logits, cache = model.prefill(
                    params, {"tokens": toks, "lengths": lens}, cache_len
                )
                return sample(logits, key), cache

            self._prefill_jit[length] = prefill_group
        return self._prefill_jit[length]

    def insert(self, cache, rows, group, length):
        del length
        slots_g = jnp.asarray([s for s, _ in group], jnp.int32)
        return self._insert(cache, rows, slots_g)

    def check_positions(self, pos, live):
        cache_len = self.engine.cache_len
        assert not live.any() or int(pos[live].max()) < cache_len, (
            f"live slot position escaped the cache: {pos[live]} "
            f">= cache_len={cache_len}")

    def decode_round(self, params, tok, cache, pos, live, remaining, keys):
        del remaining   # chunk rounds run full length; budgets live on host
        toks, cache, pos = self._decode_chunk(params, tok, cache, pos, live,
                                              keys)
        return toks, keys.shape[0], cache, pos

    def verify_round(self, params, chunk, cache, pos, live, remaining, key):
        out, n_out, cache, pos, _ = self._verify_step(
            params, chunk, cache, pos, live, remaining, key)
        return out, n_out, cache, pos

    def snapshot(self, cache, slots):
        san = getattr(self.core, "sanitizer", None)
        if san is not None:
            san.on_snapshot(slots)
        rows = self.engine.model.gather_slots(
            cache, jnp.asarray(slots, jnp.int32))
        return jax.device_get(rows)

    def san_state(self):
        # slot rows are the allocation: no pool, no table to shadow
        return {"pool": None, "table": None}


class RecurrentAdapter(ContiguousAdapter):
    """Slot-state continuous batching for recurrent families (rwkv6, zamba2's
    SSM backbone): the per-slot "cache" is O(1) recurrent state, so admission
    is a state gather/scatter — no paging, no per-slot KV rows to size. Two
    deltas from the contiguous adapter:

    - a recurrent prefill cannot mask pads out of the recurrence, so
      admission groups by EXACT prompt length and the batched prefill sees
      no pad tokens;
    - position bounds only exist where the state still carries a bounded
      cache axis (zamba2's shared-attention KV rows); a fully O(1) family
      (rwkv6) has nothing to overflow and serves any budget
      (``engine.unbounded_state``)."""

    kind = "recurrent"
    spec_capable = False

    def __init__(self, engine):
        if engine.model.cache_kind != "state":
            raise ValueError(
                f"{engine.cfg.arch_id}: the recurrent adapter serves "
                "cache_kind='state' families only"
            )
        # deliberately no supports_lengths gate: exact-length groups make
        # per-row lengths unnecessary
        self.engine = engine

    def validate(self, requests, budget, slack):
        engine = self.engine
        if engine.unbounded_state:
            return
        for r in requests:
            need = len(r.tokens) + budget(r) + slack
            if need > engine.cache_len:
                raise ValueError(
                    f"request {r.id}: len={len(r.tokens)} + "
                    f"max_new={budget(r)} needs {need} cache slots "
                    f"but cache_len={engine.cache_len}"
                )

    def group_len(self, n):
        # exact length: no pad token may enter the recurrence
        return n

    def prefill(self, length):
        """Jitted batched prefill+sample, cached per EXACT prompt length
        (retraces per admission-group size via jit's shape cache)."""
        if length not in self._prefill_jit:
            model, cache_len = self.engine.model, self.engine.cache_len
            sample = self.core._sampler

            @jax.jit
            def prefill_group(params, toks, lens, key):
                del lens   # exact-length groups: every row IS its length
                logits, state = model.prefill(
                    params, {"tokens": toks}, cache_len)
                return sample(logits, key), state

            self._prefill_jit[length] = prefill_group
        return self._prefill_jit[length]

    def check_positions(self, pos, live):
        if self.engine.unbounded_state:
            return
        ContiguousAdapter.check_positions(self, pos, live)

    def san_state(self):
        # explicit (not just inherited): the shadow-coverage contract is
        # that every concrete adapter declares its sanitizer state in its
        # own body, so the adapter-lifecycle checker can verify it
        return {"pool": None, "table": None}


# ---------------------------------------------------------------------------
# the scheduling core
# ---------------------------------------------------------------------------

COUNTS = ("prompt_tokens", "prefill_slots", "slot_steps", "live_slot_steps")


class SchedulerCore:
    """The one serving loop: admission -> grouped prefill -> jitted
    decode/verify rounds -> finish -> finalize, over any ``CacheAdapter``.

    Responses always contain exactly the request's budget of tokens;
    sequences that hit EOS early are padded with EOS (``make_response`` —
    parity across every mode). The adapter's jitted programs live for the
    core's lifetime, so a long-lived core serves successive traces with no
    recompilation.

    Host-sync budget (pinned lexically by analysis/host_sync.py): ONE
    ``jax.device_get`` per admission wave and ONE per decode/verify round.

    ``counts`` holds what the last (or current) ``serve`` did, for an
    operator to read after it returns:

      prompt_tokens    real prompt tokens prefilled
      prefill_slots    rows x padded length over every prefill group, so
                       ``1 - prompt_tokens / prefill_slots`` is the share of
                       prefill rows that was padding
      slot_steps       steps x slots over every decode/verify round
      live_slot_steps  steps x live slots, so ``live_slot_steps /
                       slot_steps`` is the batch occupancy; in decode rounds
                       it is also the tokens decoded after the first

    A verify round counts as one step. Each ``serve.round_commit`` span
    carries the running values.
    """

    def __init__(self, engine, adapter: CacheAdapter, *, slots: int = 4,
                 chunk: int = 4, sampler: str = "greedy", sampler_kw=None,
                 spec_k: int | None = None, drafter=None,
                 sanitize: bool | None = None):
        if spec_k is not None:
            if spec_k < 2:
                raise ValueError(f"spec_k must be >= 2, got {spec_k}")
            if not adapter.spec_capable or not engine.model.supports_spec:
                raise ValueError(
                    f"{engine.cfg.arch_id}: model family has no speculative "
                    "verify path (GQA decoder_lm families only)"
                )
        self.engine = engine
        self.adapter = adapter
        self.slots = slots
        self.chunk = chunk
        self.spec_k = spec_k
        self._sampler = make_sampler(sampler, **dict(sampler_kw or {}))
        self.last_positions = None     # final per-slot positions (debug)
        self.last_spec_stats = None    # per-serve speculative accounting
        self.counts = dict.fromkeys(COUNTS, 0)
        if spec_k is not None:
            from repro.serving.spec import NgramDrafter

            self._drafter = drafter if drafter is not None else NgramDrafter()
        # repro-san (DESIGN.md §13): None inherits the engine's setting, so
        # every scheduler built over a sanitized engine is sanitized too
        san_on = (getattr(engine, "sanitize", False) if sanitize is None
                  else bool(sanitize))
        self.sanitizer = None
        if san_on:
            from repro.analysis.sanitizer import Sanitizer

            self.sanitizer = Sanitizer(self)
        adapter.bind(self, sampler=sampler, sampler_kw=sampler_kw)

    def serve(self, requests: Sequence[Request], max_new_tokens: int,
              *, key=None) -> list[Response]:
        engine, adapter, B = self.engine, self.adapter, self.slots
        eos = engine.eos_id

        def budget(r: Request) -> int:
            return r.max_new if r.max_new is not None else max_new_tokens

        # a verify chunk touches score columns up to pos + spec_k - 1, so
        # speculative serving needs spec_k slots of slack past the vanilla
        # requirement (frozen slots included: their chunks still index)
        slack = self.spec_k or 0
        adapter.validate(requests, budget, slack)

        cache = adapter.begin_serve()
        san = self.sanitizer
        if san is not None:
            cache = san.begin_serve(adapter, cache)
        pending = deque(requests)
        slot_req: list[Request | None] = [None] * B
        slot_toks: list[list[int]] = [[] for _ in range(B)]
        tok = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        live = np.zeros((B,), bool)
        remaining = np.zeros((B,), np.int32)
        out: dict[int, Response] = {}
        key = key if key is not None else jax.random.PRNGKey(0)
        self.last_spec_stats = (
            {"verify_steps": 0, "generated": 0, "drafted": 0, "accepted": 0}
            if self.spec_k is not None else None)
        counts = self.counts = dict.fromkeys(COUNTS, 0)
        rnd = 0

        def finish(s: int):
            nonlocal cache
            r = slot_req[s]
            out[r.id] = make_response(r, slot_toks[s], budget(r), eos)
            slot_req[s], slot_toks[s] = None, []
            remaining[s] = 0
            live[s] = False                # token and position stay frozen
            adapter.on_finish(s)
            if san is not None:
                # freeze the slot shadow, audit the request's blocks, and
                # poison its frees NOW — before any re-allocation can write
                cache = san.on_request_finish(cache, s, r.id, pos[s])

        while pending or live.any():
            # admission: pop pending in arrival order while a slot (and, for
            # gated adapters, worst-case capacity) is available; one batched
            # prefill per distinct group length, one scatter-insert per group
            free_slots = [s for s in range(B) if slot_req[s] is None]
            admitted: dict[int, list[tuple[int, Request]]] = defaultdict(list)
            with TraceAnnotation("serve.admit") as span:
                while free_slots and pending:
                    r = pending[0]
                    if not adapter.can_admit(r, budget(r)):
                        break              # backpressure: decode frees space
                    pending.popleft()
                    s = free_slots.pop(0)
                    slot_req[s], slot_toks[s] = r, []
                    live[s] = True
                    if san is not None:
                        san.on_admit(s, r)
                    adapter.on_admit(s, r, budget(r))
                    admitted[adapter.group_len(len(r.tokens))].append((s, r))
                span.set_metadata(
                    admitted=sum(map(len, admitted.values())),
                    pending=len(pending))
            staged: list[tuple[list[tuple[int, Request]], jax.Array]] = []
            for length, group in admitted.items():
                n_tokens = sum(len(r.tokens) for _, r in group)
                with TraceAnnotation("serve.prefill", rows=len(group),
                                     length=length, tokens=n_tokens):
                    if san is not None:
                        san.on_prefill_group(group, length)
                    toks_np, lens_np = pad_bucket([r for _, r in group], length)
                    key, kp = jax.random.split(key)
                    t0_d, rows = adapter.prefill(length)(
                        engine.params, jnp.asarray(toks_np),
                        jnp.asarray(lens_np), kp)
                    cache = adapter.insert(cache, rows, group, length)
                counts["prompt_tokens"] += n_tokens
                counts["prefill_slots"] += len(group) * length
                staged.append((group, t0_d))
            if staged:
                # ONE host round-trip for the whole admission wave, not one
                # per group (host-sync round-trip budget: admission + round)
                with TraceAnnotation("serve.admit_sync", groups=len(staged)):
                    first_toks = jax.device_get([t for _, t in staged])
                for (group, _), t0 in zip(staged, first_toks):
                    for (s, r), t in zip(group, t0):
                        slot_toks[s] = [int(t)]
                        tok[s], pos[s] = int(t), len(r.tokens)
                        remaining[s] = budget(r) - 1
                        if self.last_spec_stats is not None:
                            # the prefill-sampled token is delivered work too
                            # — keeps 'generated' comparable with engine
                            # spec_stats
                            self.last_spec_stats["generated"] += 1
                        if budget(r) <= 1 or (eos is not None and int(t) == eos):
                            finish(s)

            if not live.any():
                if pending:
                    continue
                break

            n_live = int(live.sum())
            with TraceAnnotation("serve.round_prepare", round=rnd, live=n_live):
                adapter.before_round(pos, live)
                adapter.check_positions(pos, live)
                if san is not None:
                    cache = san.pre_round(cache)
                key, kc = jax.random.split(key)
                pos_in, live_in = jnp.asarray(pos), jnp.asarray(live)
                remaining_in = jnp.asarray(remaining)
                if self.spec_k is not None:
                    # speculative round: draft on the host (per-slot token
                    # history), verify the chunk in one forward pass, keep
                    # the accepted prefix — 1..spec_k tokens per weight
                    # stream
                    from repro.serving.spec import draft_chunk, take_accepted

                    K = self.spec_k
                    chunk_in = jnp.asarray(draft_chunk(
                        self._drafter, tok, live,
                        lambda s: slot_req[s].tokens + slot_toks[s], K))
                else:
                    tok_in = jnp.asarray(tok)
                    keys_in = jax.random.split(kc, self.chunk)
            if self.spec_k is not None:
                with TraceAnnotation("serve.round_dispatch", round=rnd):
                    out_d, n_out_d, cache, pos_d = adapter.verify_round(
                        engine.params, chunk_in, cache, pos_in, live_in,
                        remaining_in, kc)
                with TraceAnnotation("serve.round_sync", round=rnd):
                    out_np, n_out, pos = jax.device_get((out_d, n_out_d, pos_d))
                with TraceAnnotation("serve.round_commit", round=rnd) as span:
                    pos = pos.copy()
                    st = self.last_spec_stats
                    st["verify_steps"] += 1
                    finished = 0
                    for s in np.flatnonzero(live):
                        slot_toks[s].extend(take_accepted(
                            out_np[s], n_out[s], remaining[s], eos, st, K))
                        tok[s] = slot_toks[s][-1]
                        n = budget(slot_req[s])
                        remaining[s] = n - len(slot_toks[s])
                        if len(slot_toks[s]) >= n or (
                                eos is not None and eos in slot_toks[s][:n]):
                            finish(s)
                            finished += 1
                    if san is not None:
                        san.check_round(cache, pos, live)
                    counts["slot_steps"] += B
                    counts["live_slot_steps"] += n_live
                    span.set_metadata(steps=1, live=n_live, finished=finished,
                                      **counts)
                rnd += 1
                continue
            with TraceAnnotation("serve.round_dispatch", round=rnd):
                toks_d, steps_d, cache, pos_d = adapter.decode_round(
                    engine.params, tok_in, cache, pos_in, live_in,
                    remaining_in, keys_in)
            # ONE host sync per round: separate transfers for the step
            # count, the chunk tokens and the positions would each force
            # their own device round-trip on the hot loop
            with TraceAnnotation("serve.round_sync", round=rnd):
                steps, toks_all, pos = jax.device_get((steps_d, toks_d, pos_d))
            with TraceAnnotation("serve.round_commit", round=rnd) as span:
                steps = int(steps)
                toks_np = toks_all[:steps]                # (steps, B)
                pos = pos.copy()
                finished = 0
                for s in range(B):
                    if not live[s]:
                        continue
                    n = budget(slot_req[s])
                    slot_toks[s].extend(int(t) for t in toks_np[:, s])
                    tok[s] = slot_toks[s][-1]
                    remaining[s] = n - len(slot_toks[s])
                    done = len(slot_toks[s]) >= n
                    if eos is not None and eos in slot_toks[s][:n]:
                        done = True
                    if done:
                        finish(s)
                        finished += 1
                if san is not None:
                    san.check_round(cache, pos, live)
                counts["slot_steps"] += steps * B
                counts["live_slot_steps"] += steps * n_live
                span.set_metadata(steps=steps, live=n_live, finished=finished,
                                  **counts)
            rnd += 1

        self.last_positions = pos.copy()
        if san is not None:
            san.finalize()
        adapter.end_serve()
        return [out[r.id] for r in requests]
