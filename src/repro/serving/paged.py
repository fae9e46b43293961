"""Paged KV-cache serving: block-pool allocator + the paged cache adapter.

The contiguous slot path (serving/core.py ``ContiguousAdapter``) reserves a
full ``slots x cache_len`` KV region up front and lets finished slots idle
until the next chunk boundary — the capacity/utilization gap LlamaF's weight
streaming attacks on the FPGA, replayed on the serving side. Here the cache
is a POOL of fixed-size KV blocks:

- ``BlockPool`` — host-side allocator over ``num_blocks`` blocks of
  ``block_size`` token slots. Block 0 is the reserved write-off SINK:
  unallocated block-table entries point at it, so stray writes (prompt pad
  tail, frozen slots) land somewhere harmless instead of clobbering live
  data. Blocks are recycled WITHOUT zeroing — the paged attention path
  overwrites the current column's score/value explicitly and masks
  everything beyond ``pos``, so stale block contents are unreachable.
- ``PagedAdapter`` — the block pool behind the scheduling core's one
  admission/refill/finish loop (serving/core.py). Requests admit into fixed
  decode slots (one batched prefill per bucket, scattered into their
  blocks), blocks are allocated ON DEMAND as positions advance (a round's
  worth ahead), and the jitted decode loop is a ``while_loop`` that EXITS
  the moment any live slot finishes — blocks are freed and the queue
  re-admitted at that exact step, not at the next chunk boundary. Resident
  KV memory therefore scales with live tokens (+ block slack), not with
  ``slots x cache_len`` (``benchmarks/run.py paged``).
- ``PagedScheduler`` — the historical front: picks the adapter, exposes
  pool sizing and the residency high-water mark.

Admission is reservation-gated (``can_admit``): a request is admitted only
when the pool can cover every live request's worst-case remaining need plus
its own, so allocation for live slots never fails and no preemption path is
needed (DESIGN.md §9 allocator invariants).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import flags
from repro.serving.core import (
    CacheAdapter,
    Request,
    Response,
    SchedulerCore,
    bucket_length,
)
from repro.serving.sampling import sampler_sig

__all__ = ["BlockPool", "PagedAdapter", "PagedScheduler", "serve_paged"]


class BlockPool:
    """Fixed-size KV block allocator. Block ids are indices into the device
    pool's block axis; block 0 is the reserved sink and is never handed out.
    Tracks ``peak_live`` (high-water mark of allocated blocks) for the
    residency benchmark."""

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("BlockPool needs >= 2 blocks (block 0 is the sink)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free = list(range(num_blocks - 1, 0, -1))   # LIFO reuse
        self._free_set = set(self._free)
        self.peak_live = 0
        # repro-san hook (analysis/shadow.py ShadowBlockTracker): when set,
        # every alloc/free is mirrored — ownership, generations, poison queue
        self.shadow = None

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def live_blocks(self) -> int:
        return (self.num_blocks - 1) - len(self._free)

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"block pool exhausted: want {n}, free {len(self._free)} "
                f"of {self.num_blocks - 1}"
            )
        out = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(out)
        self.peak_live = max(self.peak_live, self.live_blocks)
        if self.shadow is not None:
            self.shadow.on_alloc(out)
        return out

    def free(self, blocks: Sequence[int]) -> None:
        if self.shadow is not None:
            # first: the shadow's unowned-free diagnosis (double-free with
            # generation attribution) beats the bare ValueError below
            self.shadow.on_free(blocks)
        for b in blocks:
            # a double-free would hand one physical block to two requests —
            # silent KV corruption — so this must not be a strippable assert
            if not 0 < b < self.num_blocks or b in self._free_set:
                raise ValueError(f"bad free of block {b}: out of range, "
                                 "double-freed, or the sink")
            self._free.append(b)
            self._free_set.add(b)


class PagedAdapter(CacheAdapter):
    """Block-pool cache behind the scheduling core: per-slot block tables
    over a ``BlockPool``, reservation-gated admission, on-demand block
    growth before each round, blocks reclaimed the step a slot finishes."""

    kind = "paged"
    spec_capable = True

    def __init__(self, engine, *, block_size: int = 8,
                 num_blocks: int | None = None, max_len: int | None = None):
        if not engine.model.supports_paged:
            raise ValueError(
                f"{engine.cfg.arch_id}: paged serving needs a block-pool cache "
                "(GQA decoder_lm families; MLA/recurrent keep the contiguous "
                "and slot-state paths)"
            )
        self.engine = engine
        self.block_size = block_size
        self.max_len = max_len if max_len is not None else engine.cache_len
        self.blocks_per_req = math.ceil(self.max_len / block_size)
        self._num_blocks_arg = num_blocks
        self.num_blocks: int | None = None   # resolved at bind (needs slots)
        self.pool: BlockPool | None = None   # per-serve allocator

    def bind(self, core, *, sampler, sampler_kw):
        engine = self.engine
        self.core = core
        # default pool matches the contiguous footprint (worst case for every
        # slot); benchmarks/tests hand in smaller pools to exercise
        # backpressure — correctness never depends on pool size
        self.num_blocks = (self._num_blocks_arg
                           if self._num_blocks_arg is not None
                           else core.slots * self.blocks_per_req + 1)
        # block lookahead per decode round: a verify chunk commits up to
        # spec_k rows per slot in one step
        self._ahead = (core.chunk if core.spec_k is None
                       else max(core.chunk, core.spec_k))
        self._prefill_jit = None
        if core.spec_k is not None:
            from repro.serving.spec import build_verify_step

            self._verify_step = build_verify_step(
                engine.model, sampler=sampler, sampler_kw=sampler_kw,
                paged=True)

        model, sample, eos = engine.model, core._sampler, engine.eos_id
        block_size = self.block_size

        # pool buffers are donated: the core always rebinds the cache to
        # each round's result, and an undonated pool would transiently
        # double the very footprint this subsystem exists to shrink
        @partial(jax.jit, donate_argnums=(2,))
        def decode_until(params, tok, cache, table, pos, live, remaining, keys):
            """Decode up to ``chunk`` steps, but stop at the step ANY live
            slot finishes (EOS or budget) — the host frees/refills there."""
            nsteps, b = keys.shape[0], tok.shape[0]

            def cond(c):
                i, _, _, _, _, stop, _ = c
                return (i < nsteps) & ~stop

            def body(c):
                i, tok, cache, pos, remaining, stop, toks = c
                logits, cache = model.decode_paged(params, tok, cache, table, pos)
                nxt = sample(logits, keys[i])
                nxt = jnp.where(live, nxt, tok)        # frozen slots keep tok
                toks = toks.at[i].set(nxt)
                pos = jnp.where(live, pos + 1, pos)    # ...and their position
                remaining = jnp.where(live, remaining - 1, remaining)
                fin = live & (remaining <= 0)
                if eos is not None:
                    fin = fin | (live & (nxt == eos))
                return (i + 1, nxt, cache, pos, remaining, jnp.any(fin), toks)

            toks0 = jnp.zeros((nsteps, b), jnp.int32)
            i, tok, cache, pos, remaining, _, toks = jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), tok, cache, pos, remaining, jnp.bool_(False), toks0))
            return toks, i, cache, pos

        @partial(jax.jit, donate_argnums=(0,))
        def insert(cache, rows, tables):
            # rows: contiguous prefill cache (L, bg, S, KV, hd); tables
            # (bg, S // block_size) physical block per prompt block (0=sink)
            def put(pages, r):
                ell, bg = r.shape[:2]
                rr = r.reshape(ell, bg, tables.shape[1], block_size, *r.shape[3:])
                return pages.at[:, tables].set(rr)
            if "k_q" in rows:
                # quantized prefill rows arrive kvt-major (L, bg, KV, S[, hd]);
                # swing the time axis forward so the same block reshape applies
                # to storage rows and their per-row scale leaves alike
                tm = lambda leaf: jnp.moveaxis(leaf, 3, 2)
                return {"k_pages": put(cache["k_pages"], tm(rows["k_q"])),
                        "k_scales": put(cache["k_scales"], tm(rows["k_s"])),
                        "v_pages": put(cache["v_pages"], tm(rows["v_q"])),
                        "v_scales": put(cache["v_scales"], tm(rows["v_s"]))}
            return {"k_pages": put(cache["k_pages"], rows["k"]),
                    "v_pages": put(cache["v_pages"], rows["v"])}

        self._decode_until = decode_until
        self._insert = insert

    # -- sizing helpers -----------------------------------------------------

    def _prompt_pad(self, n: int) -> int:
        """Padded prefill length: the power-of-two bucket, rounded up to a
        whole number of blocks."""
        b = bucket_length(n)
        return math.ceil(b / self.block_size) * self.block_size

    def _blocks_needed(self, r: Request, budget: int) -> int:
        # decode commits positions len .. len+budget-2 (the first generated
        # token comes from prefill); prompt occupies 0 .. len-1
        last = len(r.tokens) + max(budget - 1, 0)
        return math.ceil(max(last, 1) / self.block_size)

    def _reserved_backlog(self) -> int:
        """Blocks the live slots may still demand beyond what they hold."""
        return sum(self._slot_need[s] - len(self._slot_blocks[s])
                   for s in range(len(self._slot_need)) if self._slot_live[s])

    def _ensure_blocks(self, s: int, p: int) -> None:
        """Grow slot ``s`` to cover the next round of decode commits
        (``chunk`` single-token steps, or one spec_k-row verify chunk) —
        reservation-gated admission guarantees this never fails."""
        bs = self.block_size
        target = min(math.ceil((p + self._ahead) / bs), self._slot_need[s])
        delta = target - len(self._slot_blocks[s])
        if delta > 0:
            if self.pool.shadow is not None:
                self.pool.shadow.set_context(s)   # attribute growth allocs
            new = self.pool.alloc(delta)
            start = len(self._slot_blocks[s])
            self._slot_blocks[s].extend(new)
            self.table[s, start:start + len(new)] = new

    # -- CacheAdapter surface ------------------------------------------------

    def validate(self, requests, budget, slack):
        if flags.get("kvt_cache_layout") or flags.get("int8_kv_cache"):
            raise ValueError("paged serving supports the base float KV layout "
                             "(kvt_cache_layout / int8_kv_cache flags off)")
        mb, bs = self.blocks_per_req, self.block_size
        for r in requests:
            need = max(self._prompt_pad(len(r.tokens)),
                       len(r.tokens) + budget(r) + slack)
            if need > mb * bs:
                raise ValueError(
                    f"request {r.id}: len={len(r.tokens)} + max_new={budget(r)}"
                    + (f" + spec_k={slack}" if slack else "")
                    + f" needs {need} cache slots but the paged table covers "
                    f"{mb} blocks x {bs} = {mb * bs}"
                )
            if self._blocks_needed(r, budget(r)) > self.num_blocks - 1:
                raise ValueError(
                    f"request {r.id}: needs {self._blocks_needed(r, budget(r))} "
                    f"blocks but the pool has {self.num_blocks - 1}"
                )

    def begin_serve(self):
        B, bs = self.core.slots, self.block_size
        self.pool = BlockPool(self.num_blocks, bs)
        self.table = np.zeros((B, self.blocks_per_req), np.int32)  # 0 = sink
        self._slot_blocks: list[list[int]] = [[] for _ in range(B)]
        self._slot_need = [0] * B              # worst-case total blocks
        self._slot_live = np.zeros((B,), bool)
        return self.engine.model.init_paged_cache(
            self.num_blocks, bs, self.engine.cfg.cdtype())

    def can_admit(self, r, budget):
        # reservation-gated: admit only when the pool covers every live
        # slot's worst-case remaining growth plus this request's whole need
        return (self._blocks_needed(r, budget)
                <= self.pool.free_blocks - self._reserved_backlog())

    def on_admit(self, s, r, budget):
        prompt_blocks = self.pool.alloc(
            math.ceil(len(r.tokens) / self.block_size))
        self._slot_blocks[s] = prompt_blocks
        self._slot_need[s] = self._blocks_needed(r, budget)
        self.table[s, :] = 0
        self.table[s, : len(prompt_blocks)] = prompt_blocks
        self._slot_live[s] = True

    def group_len(self, n):
        return self._prompt_pad(n)

    def prefill(self, length):
        del length   # pad target rides in via toks.shape: one cached program
        if self._prefill_jit is None:
            model, sample = self.engine.model, self.core._sampler

            @jax.jit
            def prefill_group(params, toks, lens, key):
                # pad target == the padded prompt length: the paged pool is
                # the only persistent cache, so no cache_len-wide row exists
                logits, cache = model.prefill(
                    params, {"tokens": toks, "lengths": lens}, toks.shape[1]
                )
                return sample(logits, key), cache

            self._prefill_jit = prefill_group
        return self._prefill_jit

    def insert(self, cache, rows, group, length):
        tables_g = jnp.asarray(
            np.stack([self.table[s, : length // self.block_size]
                      for s, _ in group]))
        return self._insert(cache, rows, tables_g)

    def before_round(self, pos, live):
        with jax.profiler.TraceAnnotation("serve.kv_grow") as span:
            for s in range(len(live)):
                if live[s]:
                    self._ensure_blocks(s, int(pos[s]))
            span.set_metadata(blocks_live=self.pool.live_blocks,
                              blocks_free=self.pool.free_blocks,
                              backlog=self._reserved_backlog())

    def check_positions(self, pos, live):
        mb, bs = self.blocks_per_req, self.block_size
        assert not live.any() or int(pos[live].max()) < mb * bs, (
            f"live slot position escaped the block table: {pos[live]}")

    def decode_round(self, params, tok, cache, pos, live, remaining, keys):
        toks, steps, cache, pos = self._decode_until(
            params, tok, cache, jnp.asarray(self.table), pos, live,
            remaining, keys)
        return toks, steps, cache, pos

    def verify_round(self, params, chunk, cache, pos, live, remaining, key):
        out, n_out, cache, pos, _ = self._verify_step(
            params, chunk, cache, jnp.asarray(self.table), pos, live,
            remaining, key)
        return out, n_out, cache, pos

    def on_finish(self, s):
        self.pool.free(self._slot_blocks[s])
        self._slot_blocks[s], self._slot_need[s] = [], 0
        self.table[s, :] = 0                   # stray writes go to the sink
        self._slot_live[s] = False

    def snapshot(self, cache, slots):
        """Pool-level snapshot: the pages plus each slot's block-table row —
        pool rows are unaddressable without the table (engine.snapshot
        carries the same pair for the uniform paged path)."""
        san = getattr(self.core, "sanitizer", None)
        if san is not None:
            san.on_snapshot(slots)
        return {"cache": jax.device_get(cache),
                "table": self.table[np.asarray(slots)].copy()}

    def san_state(self):
        return {"pool": self.pool, "table": self.table}


class PagedScheduler:
    """Paged continuous batching over one engine (see module docstring).

    Produces token-identical greedy outputs to the contiguous
    ``SlotScheduler`` / ``serve_ragged(mode="continuous")`` on any trace —
    the paged attention path is parity-tested bit-exact against the
    contiguous deferred decode (tests/test_paged.py).
    """

    def __init__(self, engine, *, slots: int = 4, chunk: int = 4,
                 block_size: int = 8, num_blocks: int | None = None,
                 max_len: int | None = None, sampler: str = "greedy",
                 sampler_kw=None, spec_k: int | None = None, drafter=None):
        self.adapter = PagedAdapter(engine, block_size=block_size,
                                    num_blocks=num_blocks, max_len=max_len)
        self._core = SchedulerCore(engine, self.adapter, slots=slots,
                                   chunk=chunk, sampler=sampler,
                                   sampler_kw=sampler_kw, spec_k=spec_k,
                                   drafter=drafter)
        self.engine = engine
        self.slots = slots
        self.chunk = chunk
        self.spec_k = spec_k
        self.block_size = block_size
        self.max_len = self.adapter.max_len
        self.blocks_per_req = self.adapter.blocks_per_req
        self.num_blocks = self.adapter.num_blocks
        self.last_peak_blocks = 0          # residency high-water of last serve
        self.last_positions: np.ndarray | None = None   # debug/introspection
        self.last_spec_stats = None        # per-serve speculative accounting

    def serve(self, requests: Sequence[Request], max_new_tokens: int,
              *, key=None) -> list[Response]:
        out = self._core.serve(requests, max_new_tokens, key=key)
        self.last_positions = self._core.last_positions
        self.last_spec_stats = self._core.last_spec_stats
        # the allocator's exact high-water mark (sampling pool.live_blocks at
        # loop points would miss peaks freed before the sample, e.g. prompt
        # blocks of budget<=1 requests finished at admission)
        self.last_peak_blocks = max(self.last_peak_blocks,
                                    self.adapter.pool.peak_live)
        return out


def serve_paged(engine, requests: Sequence[Request], max_new_tokens: int,
                *, sampler: str = "greedy", sampler_kw=None, key=None,
                slots: int = 4, chunk: int = 4, block_size: int = 8,
                num_blocks: int | None = None, spec_k: int | None = None,
                drafter=None) -> list[Response]:
    """Paged continuous batching through a per-engine cached scheduler."""
    cache = getattr(engine, "_paged_schedulers", None)
    if cache is None:
        cache = engine._paged_schedulers = {}
    sig = (slots, chunk, block_size, num_blocks, sampler,
           sampler_sig(sampler_kw), spec_k,
           id(drafter) if drafter is not None else None)
    if sig not in cache:
        cache[sig] = PagedScheduler(engine, slots=slots, chunk=chunk,
                                    block_size=block_size, num_blocks=num_blocks,
                                    sampler=sampler, sampler_kw=sampler_kw,
                                    spec_k=spec_k, drafter=drafter)
    sched = cache[sig]
    sched.last_peak_blocks = 0
    return sched.serve(requests, max_new_tokens, key=key)
