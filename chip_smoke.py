#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU: the quickest proof that the
system still starts on the chip.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # four chips: sharded training only

One chip runs five phases, in order:

  device   the platform must be ``tpu``; nothing runs anywhere else
  kernels  GQMV and GQMM in int8/int4/int3/fp8 at tinyllama-1.1b widths,
           and paged attention over a float pool and an int8 pool (ragged
           positions; then with every block only dead table entries name
           poisoned with NaN), each against its XLA oracle (kernels/ref.py);
           GQMM int8/int4 on one layer of a stacked weight, read in place
           through its layer index, equal to the call on the copied slice
  serve    ``repro.launch.serve.main`` at the full width and depth of
           tinyllama-1.1b: int8 W8A8 weights, the default --ragged
           scheduler (paged), 8 requests of up to 128 prompt tokens, 32 new
           tokens each, 8 slots; every request must come back whole
  parity   from one full-width prefill, one paged decode step (Pallas
           paged attention) against one contiguous decode step (XLA
           attention): logits finite and within tolerance
  pallas   the compiled paged decode program holds the Pallas kernels

``--four-chips`` runs only the sharded-training phase: a few steps of
tinyllama at its published widths, cut from 22 to 4 layers so that the
same steps fit one chip, on a mesh of all four chips and on a one-chip
mesh, in this one process, with the same seed and data. Their losses must
agree.

Weights are random, made from a seed. Every phase raises on failure, so
the script exits non-zero at the first one, and only a run whose phases
all passed prints the last line: one JSON object naming the device.
Times printed are smoke timings, not benchmark results.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "tinyllama-1.1b"
GS = 256


def check_close(name, got, want, *, rtol, atol):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        raise AssertionError(f"{name}: non-finite output")
    err = float(np.max(np.abs(got - want) - rtol * np.abs(want)))
    print(f"  {name}: max|got-want| {np.max(np.abs(got - want)):.3g} "
          f"(rtol {rtol:g}, atol {atol:g})", flush=True)
    if err > atol:
        raise AssertionError(f"{name}: outside rtol={rtol} atol={atol}")


def phase_device(count: int):
    devices = jax.devices()
    d = devices[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{d.platform!r}); nothing is run in its place")
    if len(devices) != count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chip(s), found "
                         f"{len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def phase_kernels():
    from repro.core.quant import get_format, quantize, quantize_activation
    from repro.kernels import ops
    from repro.models.attention import _quantize_rows
    from repro.models.common import decode_mask

    key = jax.random.PRNGKey(0)
    # the interpret-mode tolerances of tests/test_kernels.py
    for fmt in ("int8", "int4", "int3", "fp8"):
        hook = get_format(fmt).kernel
        for m, n in ((5632, 2048), (2048, 5632)):
            key, kw, kx = jax.random.split(key, 3)
            w = quantize(jax.random.normal(kw, (m, n), jnp.float32), GS, fmt)
            for shape, fn in (((n,), ops.gqmv), ((8, n), ops.gqmm)):
                x = quantize_activation(jax.random.normal(kx, shape), GS)
                args = (w.qvalues, w.scales, x.qvalues, x.scales)
                got = fn(*args, group_size=GS, impl="pallas", kernel=hook)
                want = fn(*args, group_size=GS, impl="xla", kernel=hook)
                name = f"{'gqmv' if len(shape) == 1 else 'gqmm b=8'} {fmt} {m}x{n}"
                check_close(name, got, want, rtol=5e-4, atol=1e-4)

    # one layer of a stacked weight read in place through its index: the
    # same blocks and arithmetic as the call on the copied slice, so equal
    # (keys of their own: the paged attention below keeps its inputs)
    m, n, L = 5632, 2048, 3
    for i, fmt in enumerate(("int8", "int4")):
        hook = get_format(fmt).kernel
        kw, kx = jax.random.split(jax.random.PRNGKey(1 + i))
        ws = [quantize(w, GS, fmt) for w in
              jax.random.normal(kw, (L, m, n), jnp.float32)]
        wq = jnp.stack([w.qvalues for w in ws])
        wsc = jnp.stack([w.scales for w in ws])
        x = quantize_activation(jax.random.normal(kx, (8, n)), GS)
        for layer in (0, L - 1):
            got = ops.gqmm(wq, wsc, x.qvalues, x.scales, jnp.int32(layer),
                           group_size=GS, impl="pallas", kernel=hook)
            want = ops.gqmm(wq[layer], wsc[layer], x.qvalues, x.scales,
                            group_size=GS, impl="pallas", kernel=hook)
            same = bool(jnp.array_equal(got, want))
            print(f"  gqmm b=8 {fmt} {m}x{n}, layer {layer} of a {L}-layer "
                  f"stack read in place: equal to the copied slice: {same}",
                  flush=True)
            if not same:
                raise AssertionError(f"stacked gqmm {fmt} layer {layer}: "
                                     "differs from the call on its slice")

    # paged attention at tinyllama's head geometry over a shuffled pool:
    # rows at the edges of the kernel's 16-block compute blocks (first
    # token, pool-block and compute-block boundaries, last slot; MB = 20 is
    # not a multiple of 16), and every block that only dead table entries
    # name poisoned for the kernel and zeroed for the oracle
    b, kv, g, hd, bs, mb = 8, 4, 8, 64, 8, 20
    nb = b * mb + 1
    rng = np.random.default_rng(0)
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, kv, g, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (nb, bs, kv, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (nb, bs, kv, hd), jnp.float32)
    kn = jax.random.normal(ks[3], (b, kv, hd), jnp.float32)
    vn = jax.random.normal(ks[4], (b, kv, hd), jnp.float32)
    table_np = rng.permutation(np.arange(1, nb)).reshape(b, mb)
    pos_np = np.array([0, bs - 1, bs, 16 * bs - 1, 16 * bs, mb * bs - 1,
                       *rng.integers(0, mb * bs, size=2)])
    dead = np.concatenate([table_np[r, p // bs + 1:]
                           for r, p in enumerate(pos_np)])
    table = jnp.asarray(table_np, jnp.int32)
    pos = jnp.asarray(pos_np, jnp.int32)
    mask = decode_mask(mb * bs, pos)
    kq, k_s = _quantize_rows(kp, "int8")
    vq, v_s = _quantize_rows(vp, "int8")
    pools = {"float": [kp, vp], "int8": [kq, vq, k_s, v_s]}
    for pool, leaves in pools.items():
        for poisoned in (False, True):
            given = want_in = leaves
            if poisoned:
                # int8 rows cannot hold NaN: their scales carry it
                given = [x.at[dead].set(jnp.nan)
                         if jnp.issubdtype(x.dtype, jnp.floating) else x
                         for x in leaves]
                want_in = [x.at[dead].set(0) for x in leaves]

            def attend(leaves, impl):
                scales = dict(zip(("k_scales", "v_scales"), leaves[2:]))
                return ops.paged_attention(
                    q, *leaves[:2], table, pos, kn, vn, mask,
                    scale=hd ** -0.5, impl=impl, **scales)

            got = attend(given, "pallas")
            with jax.default_matmul_precision("highest"):
                want = attend(want_in, "xla")
            # wider than the interpret-mode 2e-5: on the chip both sides run
            # their f32 dots as multi-pass bf16 products on the MXU, which
            # round differently from the CPU's f32 dots
            name = f"paged attention, {pool} pool" + (
                ", dead blocks poisoned" if poisoned else "")
            check_close(name, got, want, rtol=1e-4, atol=1e-4)


def phase_serve():
    from repro.launch import serve

    t0 = time.perf_counter()
    out = serve.main(["--arch", ARCH, "--ragged", "--batch", "8",
                      "--prompt-len", "128", "--steps", "32", "--slots", "8"])
    wall = time.perf_counter() - t0
    if len(out) != 8 or sorted(r.id for r in out) != list(range(8)):
        raise AssertionError(f"serve: {len(out)} responses for 8 requests")
    for r in out:
        if r.length != 32 or r.tokens.shape != (32,):
            raise AssertionError(f"serve: request {r.id} came back with "
                                 f"{r.length} of 32 tokens")
    print(f"smoke timing, not a benchmark: serve.main took {wall:.1f} s "
          f"wall for 256 tokens ({256 / wall:.1f} tokens/s), including "
          "init, quantization and compilation", flush=True)


def phase_parity_and_pallas():
    from repro.models.registry import build, load_config
    from repro.models.transformer import contiguous_to_paged
    from repro.serving.batching import resolve_mode
    from repro.serving.engine import InferenceEngine

    cfg = load_config(ARCH)
    model = build(cfg)
    prompt, block = 128, 8
    cache_len = prompt + block
    engine = InferenceEngine(model, model.init(jax.random.PRNGKey(1)),
                             cache_len=cache_len, quantize=True)
    if resolve_mode(engine, "auto") != "paged":
        raise AssertionError("the default --ragged scheduler is not paged")
    params = engine.params
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, prompt), 0,
                              cfg.vocab_size)
    logits, cache = jax.jit(
        lambda p, t: model.prefill(p, {"tokens": t}, cache_len))(params, toks)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    pos = jnp.full((2,), prompt, jnp.int32)
    want, _ = jax.jit(model.decode)(params, tok, cache, pos)
    pool, table = contiguous_to_paged(cache, block)
    decode_paged = jax.jit(model.decode_paged).lower(
        params, tok, pool, table, pos).compile()
    got, _ = decode_paged(params, tok, pool, table, pos)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise AssertionError("decode parity: non-finite logits")
    rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    # bf16 activations (a step of 2^-8 relative): the kernel attends in
    # f32, the XLA path in bf16, and 22 residual layers carry the rounding
    print(f"  decode parity: max|paged-contiguous| / max|contiguous| "
          f"{rel:.3g} (limit 5e-2)", flush=True)
    if rel > 5e-2:
        raise AssertionError("decode parity: paged and contiguous logits differ")

    text = decode_paged.as_text()
    names = [k for k in ("paged_attention", "gqmm_int8") if k in text]
    print(f"  pallas: {text.count('tpu_custom_call')} tpu_custom_call "
          f"ops in the paged decode program, kernels {names}", flush=True)
    if "tpu_custom_call" not in text or len(names) != 2:
        raise AssertionError("the paged decode program does not run the "
                             "Pallas kernels")


def phase_four_chips():
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.dist import logical
    from repro.dist.sharding import param_specs, shardings
    from repro.ft.elastic import elastic_mesh
    from repro.models.registry import build, load_config
    from repro.optim import adamw
    from repro.train.loop import make_train_step

    # published widths; depth cut to 4 of 22 layers so one chip holds the
    # same steps (parameters, AdamW moments and gradients) for comparison
    cfg = dataclasses.replace(load_config(ARCH), num_layers=4)
    model = build(cfg)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=128,
                                  global_batch=8, seed=0))
    opt_cfg = adamw.AdamWConfig(lr=3e-4, warmup_steps=1, total_steps=3)
    batches = [jax.tree.map(jnp.asarray, data.batch_at(s)) for s in range(3)]

    def train(devices):
        mesh = elastic_mesh(devices, model_parallel=len(devices))
        params = model.init(jax.random.PRNGKey(0))
        params = jax.device_put(
            params, shardings(param_specs(params, mesh, "train"), mesh))
        opt = adamw.init(params)
        with mesh, logical.use_mesh_rules(mesh):
            step = jax.jit(make_train_step(model, opt_cfg)).lower(
                params, opt, batches[0]).compile()
            losses = []
            for batch in batches:
                params, opt, metrics = step(params, opt, batch)
                losses.append(float(metrics["loss"]))
        text = step.as_text()
        coll = {op: text.count(f" {op}(") for op in (
            "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
            "collective-permute") if f" {op}(" in text}
        mem = step.memory_analysis()
        peak = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) / 2**30
                for d in devices]
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        print(f"  mesh {shape}: losses {losses}", flush=True)
        print(f"    collectives in the compiled step: {coll}", flush=True)
        print(f"    per device: arguments {mem.argument_size_in_bytes / 2**30:.2f}"
              f" GiB, temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB;"
              f" peak in use {[round(p, 2) for p in peak]} GiB", flush=True)
        return losses, coll

    devices = jax.devices()
    four, coll = train(devices)
    one, _ = train(devices[:1])
    if not coll:
        raise AssertionError("the four-chip step has no collectives")
    # bf16 activations: the sharded step reduces in another order
    rel = max(abs(a - b) / abs(b) for a, b in zip(four, one))
    print(f"  four chips vs one: max relative loss difference {rel:.3g} "
          "(limit 1e-2)", flush=True)
    if not np.isfinite(four + one).all() or rel > 1e-2:
        raise AssertionError("sharded and one-chip losses disagree")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-training phase, on 4 chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache

    device = phase_device(4 if args.four_chips else 1)
    print(f"compile cache: {use_compile_cache()}", flush=True)
    phases = ([("four chips", phase_four_chips)] if args.four_chips else
              [("kernels", phase_kernels), ("serve", phase_serve),
               ("parity + pallas", phase_parity_and_pallas)])
    for name, fn in phases:
        t0 = time.perf_counter()
        print(f"[{name}]", flush=True)
        fn()
        print(f"[{name}] passed; smoke timing {time.perf_counter() - t0:.1f} s",
              flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
