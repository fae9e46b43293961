"""Run one benchmark cell once, on the chip, and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``bench/workloads/<cell>.json``) names a model configuration
(``bench/configs/``), a traffic mix (``bench/traffic/``) and the serving
shape (slots, max_len). The run

1. finds the chip (a TPU it has peaks for; anything else exits non-zero);
2. sets up: weights from the seed, made on the device at storage width;
   the program's engine, scheduler and paged pool; every prefill shape
   the program's admission makes of this traffic, and the decode round,
   compiled or loaded from ``<checkout>/.jax_cache``;
3. serves the seed's traffic for ``--seconds`` through
   ``SchedulerCore.serve`` (bench/harness.py), tracing the last seconds of
   the window when ``--trace 1``;
4. reads the metrics ``BENCHMARK.json`` gives this cell, one reader each
   under ``bench/metrics/``: end-to-end with ``--trace 0``, per-layer with
   ``--trace 1``;
5. frees the program and checks what it served (the tokens of the
   responses the core built) against the float32 reference
   (bench/reference.py): the widest gap by which a served token's
   reference logit lies below the reference's best, over a sample of
   finished requests that includes the longest, against the cell's limit;
6. prints the compared numbers with their limits as the last lines of
   standard error, and one JSON object as the last line of standard output.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402


class NoChip(SystemExit):
    pass


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell's file with its configuration and mix loaded, and the
    metrics ``BENCHMARK.json`` gives it."""
    cell = load_json(BENCH, "workloads", f"{name}.json")
    cell["name"] = name
    cell["cfg"] = load_json(BENCH, "configs", f"{cell['config']}.json")
    cell["mix"] = load_json(BENCH, "traffic", f"{cell['traffic']}.json")
    spec = load_json(ROOT, "BENCHMARK.json")

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    cell["end_to_end"] = [m for m in spec["end_to_end"] if mine(m)]
    cell["per_layer"] = [m for m in spec["per_layer"] if mine(m)]
    return cell


def reader(metric: str):
    path = os.path.join(BENCH, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def find_chip(chips: int) -> tuple[dict, dict]:
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"bench: no TPU found (JAX platform {d.platform!r}); "
                     "nothing is measured in its place")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chip(s), found {len(devs)}")
    peaks = load_json(BENCH, "peaks.json")["devices"]
    if d.device_kind not in peaks:
        raise NoChip(f"bench: no peaks for device kind {d.device_kind!r} "
                     "in bench/peaks.json")
    return ({"platform": d.platform, "kind": d.device_kind, "count": len(devs)},
            peaks[d.device_kind])


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: dict
    cfg: dict
    peaks: dict
    slots: int
    pool_blocks: int
    setup_s: float
    t0: float
    t_close: float
    requests: dict
    waves: list
    rounds: list
    t_admit: dict
    t_first: dict
    t_last: dict
    served: dict                     # request id -> the tokens it was served
    failed: int                      # finished with another number of tokens
    traced_in_window: int = 0        # programs traced (jit cache misses) in it
    compiled_in_window: int = 0
    trace: object = None
    notes: list = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.t_close - self.t0


def request_count(cell: dict, seconds: float) -> int:
    from bench import traffic

    mix = cell["mix"]
    n = seconds * cell["max_tokens_per_s"] / traffic.mean_output(mix)
    return math.ceil(n) + cell["slots"] + mix["block"]


def serve_window(cell: dict, seed: int, seconds: float, trace: bool,
                 *, require_chip: bool = True, fault=None):
    """Set up the program, serve the window, read back what it served.
    Returns (Run, device dict, memory peak bytes). ``fault`` (tests only)
    is applied to the engine before the scheduler is built."""
    import jax

    from bench import harness, traffic
    from repro.launch.compile_cache import use_compile_cache
    from repro.models.registry import build
    from repro.serving.core import Request, SchedulerCore
    from repro.serving.engine import InferenceEngine

    stamps = [("imports", time.perf_counter())]
    if require_chip:
        device, peaks = find_chip(cell["chips"])
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
        peaks = next(iter(load_json(BENCH, "peaks.json")["devices"].values()))
    stamps.append(("chip", time.perf_counter()))

    # programs traced (a jit cache miss) and compiled while the window is open
    inside = {"on": False, "/jax/core/compile/jaxpr_trace_duration": 0,
              "/jax/core/compile/backend_compile_duration": 0}

    def on_event(event, duration, **kw):
        if inside["on"] and event in inside:
            inside[event] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)

    cfg, mix = cell["cfg"], cell["mix"]
    model = build(harness.model_config(cfg))
    params = jax.block_until_ready(harness.served_params(model, cfg, seed))
    stamps.append(("weights", time.perf_counter()))
    engine = InferenceEngine(model, params, cache_len=cell["max_len"],
                             quantize=False, eos_id=None, sanitize=False)
    if fault is not None:
        fault(engine)
    adapter = harness.BenchAdapter(engine, max_len=cell["max_len"])
    core = SchedulerCore(engine, adapter, slots=cell["slots"],
                         chunk=harness.CHUNK, sampler="greedy", sanitize=False)
    reqs = traffic.generate(mix, seed, request_count(cell, seconds),
                            cfg["vocab_size"])
    by_id = {r.id: r for r in reqs}
    shapes = harness.admission_shapes(adapter, reqs, cell["slots"], harness.CHUNK)
    stamps.append(("traffic", time.perf_counter()))
    harness.warm_up(core, adapter, shapes)
    stamps.append(("warm-up", time.perf_counter()))

    program_reqs = [Request(r.id, r.tokens.tolist(), max_new=r.max_new)
                    for r in reqs]
    trace_dir = os.path.join(ROOT, ".bench_runs", f"trace-{cell['name']}")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    trace_s = min(cell.get("trace_seconds", 4.0), seconds / 2)
    t0 = time.perf_counter()
    adapter.arm(t0=t0, deadline=t0 + seconds,
                trace_at=(t0 + seconds - trace_s) if trace else None,
                trace_dir=trace_dir)
    inside["on"] = True
    with harness.recorded_responses() as responses:
        try:
            core.serve(program_reqs, max_new_tokens=max(r.max_new for r in reqs))
        except harness.WindowClosed:
            pass
        else:
            raise RuntimeError("the traffic ran out before the window closed")
    inside["on"] = False
    if adapter.tracing:
        jax.profiler.stop_trace()

    rounds = adapter.read_back()
    served = {rid: [int(t) for t in resp.tokens[: resp.length]]
              for rid, resp in responses.items()}
    failed = sum(len(t) != by_id[rid].max_new for rid, t in served.items())
    peak = max((dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for dv in jax.devices())
    run = Run(cell=cell, cfg=cfg, peaks=peaks, slots=cell["slots"],
              pool_blocks=adapter.num_blocks - 1, setup_s=t0 - T_START, t0=t0,
              t_close=adapter.t_close, requests=by_id, waves=adapter.waves,
              rounds=rounds, t_admit=adapter.t_admit, t_first=adapter.t_first,
              t_last=adapter.t_last, served=served, failed=failed,
              traced_in_window=inside["/jax/core/compile/jaxpr_trace_duration"],
              compiled_in_window=inside["/jax/core/compile/backend_compile_duration"])
    prev, parts = T_START, []
    for name, t in stamps:
        parts.append(f"{name} {t - prev:.2f}")
        prev = t
    run.notes.append(f"set-up {t0 - T_START:.2f} s: " + ", ".join(parts)
                     + f"; {len(shapes)} prefill shapes warmed: "
                     + " ".join(f"{g}x{n}" for g, n in sorted(shapes)))
    run.notes.append(
        f"window {run.window_s:.3f} s: {run.traced_in_window} programs traced, "
        f"{run.compiled_in_window} compiled inside it; "
        f"{len(run.t_admit)} requests admitted, {len(served)} finished")
    run.notes.append(adapter.host_report())
    if trace:
        from bench import trace as tr

        run.trace = tr.load(tr.find(trace_dir))
    return run, device, peak


def check_sample(run: Run, seed: int):
    """Teacher-forced reference inputs for a sample, drawn from the seed,
    of the requests finished in the window, the longest among them; None
    when none finished."""
    from bench import reference

    done = list(run.served)
    if not done:
        return None
    rng = np.random.default_rng([int(seed), 11])
    longest = max(done, key=lambda r: len(run.requests[r].tokens) + run.requests[r].max_new)
    rest = [r for r in done if r != longest]
    k = min(run.cell["check_requests"] - 1, len(rest))
    sample = [longest] + [rest[i] for i in rng.choice(len(rest), k, replace=False)]
    return reference.teacher_forced([run.requests[r].tokens.tolist() for r in sample],
                                    [run.served[r] for r in sample],
                                    sequences=run.cell["check_requests"],
                                    length=run.cell["max_len"])


# checks that hold as value <= limit; every other check holds as value >= limit
AT_MOST = ("max_logit_gap", "failed_responses")


def check(run: Run, seed: int) -> dict:
    """The reference comparison: {name: (value, limit)}."""
    from bench import reference

    limit = run.cell["limits"]["max_logit_gap"]
    out = {"failed_responses": (run.failed, 0)}
    inputs = check_sample(run, seed)
    if inputs is None:
        return {"max_logit_gap": (math.inf, limit), **out, "requests_checked": (0, 1)}
    tokens, rows, targets, n = inputs
    gap = reference.gaps(reference.Reference(run.cfg).logits(seed, tokens, rows), targets)
    return {"max_logit_gap": (float(gap.max()), limit), **out,
            "requests_checked": (len({int(i) for i in rows[:, 0]}), 1),
            "tokens_checked": (n, 1)}


def result(run: Run, checks: dict, device: dict, peak: int, trace: bool) -> dict:
    from bench import trace as tr

    metrics = {}
    for m in run.cell["per_layer" if trace else "end_to_end"]:
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    correct = all(v <= lim if k in AT_MOST else v >= lim
                  for k, (v, lim) in checks.items())
    dev = dict(device, memory_peak_bytes=int(peak))
    out = {"correct": bool(correct), "attempted": len(run.t_admit), "failed": run.failed,
           "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = tr.busy_s(run.trace)
        dev["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in tr.top_ops(run.trace)],
                            "idle_gaps": [list(x) for x in tr.idle_by_span(run.trace)]}
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    run, device, peak = serve_window(cell, args.seed, args.seconds, bool(args.trace))
    gc.collect()                         # the program's state is gone: free it
    checks = check(run, args.seed)
    out = result(run, checks, device, peak, bool(args.trace))
    for line in run.notes:
        print(line, file=sys.stderr)
    for k, c in out["checks"].items():
        rel = "<=" if k in AT_MOST else ">="
        print(f"check {k} {c['value']} (limit {rel} {c['limit']})", file=sys.stderr)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
