"""Benchmark harness: one module per paper table. CSV: name,us_per_call,derived.

  table4 -> quant_error      (paper Table IV: quantization error stats)
  table5 -> quality          (paper Table V: PPL fp32 vs W8A8)
  table6 -> throughput       (paper Table VI: tok/s, GOPS, scheduling)
  ragged -> throughput       (ragged trace: bucket-serial vs continuous slots;
                              exits non-zero if a sanitize=False scheduler
                              loses more than 2% tok/s vs the default run —
                              repro-san's disabled-mode overhead gate)
  quant -> quant_bench       (per-format bytes/weight, decode us/call, errors;
                              writes BENCH_quant.json; exits non-zero if the
                              mixed3 preset's weight bytes/step exceed 0.8x
                              int4's)
  kvquant -> kvquant_bench   (quantized KV pool: bytes/token per kv_quant
                              format + paged/contiguous parity; writes
                              BENCH_kvquant.json; exits non-zero below the
                              1.8x-vs-float or above the 0.55x-vs-fp16
                              pool-bytes gates)
  paged -> throughput        (paged vs contiguous slots: tok/s + resident KV
                              bytes; exits non-zero if paged residency does
                              not beat the contiguous footprint)
  spec -> throughput         (speculative decode: forward passes + weight
                              bytes per token, acceptance rate; writes
                              BENCH_spec.json; exits non-zero if greedy
                              speculative output diverges from vanilla or
                              the repetitive trace misses the 1.5x gate)
  recurrent -> throughput    (rwkv6 slot-state continuous batching vs
                              exact-length bucket-serial; exits non-zero
                              below the 1.3x tok/s gate)
  xray -> xray_bench         (bytes-per-decode-step contract: compiled-HLO
                              HBM traffic vs the registry nbytes model for
                              tinyllama int8/int4/mixed; exits non-zero on
                              >15% discrepancy — DESIGN.md §14)

A suite returning False marks the run failed (exit 1).
"""

import os
import sys

# Allow both `python benchmarks/run.py` and `python -m benchmarks.run`.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main() -> int:
    from benchmarks import (
        kvquant_bench,
        quant_bench,
        quant_error,
        quality,
        throughput,
        xray_bench,
    )

    only = sys.argv[1] if len(sys.argv) > 1 else None
    suites = {
        "table4": quant_error.run,
        "table5": quality.run,
        "table6": throughput.run,
        "ragged": throughput.run_ragged,
        "quant": quant_bench.run,
        "kvquant": kvquant_bench.run,
        "paged": throughput.run_paged,
        "spec": throughput.run_spec,
        "recurrent": throughput.run_recurrent,
        "xray": xray_bench.run,
    }
    if only is not None and only not in suites:
        print(f"unknown suite {only!r}; valid: {', '.join(suites)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        if only and only != name:
            continue
        if fn() is False:
            failed.append(name)
    if failed:
        print(f"failed suites: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
