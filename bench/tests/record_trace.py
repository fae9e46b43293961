"""Record the small chip trace that tests/test_trace.py reduces.

    python3 bench/tests/record_trace.py      # on a TPU

Serves a two-layer model (tiny widths, the real serving path and kernels)
an offline queue for two seconds, traces the last half second, and copies the ``.xplane.pb`` to
``bench/tests/data/small.xplane.pb`` together with the host-side counts
(decode steps, GQMM calls) the reduction is checked against.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

TINY = {"name": "tiny", "hidden_size": 512, "intermediate_size": 1024,
        "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 64,
        "num_hidden_layers": 2, "vocab_size": 1024, "rms_norm_eps": 1e-6,
        "rope_theta": 10000, "group_size": 256, "dtype": "bfloat16"}
MIX = {"arrivals": "all_at_start",
       "prompt_tokens": {"median": 24, "sigma": 0.6, "min": 8, "max": 64},
       "output_tokens": {"median": 16, "sigma": 0.6, "min": 4, "max": 48},
       "block": 8}


def tiny_cell(**kw) -> dict:
    cell = {"name": "tiny.cell", "cfg": dict(TINY), "mix": json.loads(json.dumps(MIX)),
            "chips": 1, "slots": 8, "max_len": 128, "max_tokens_per_s": 4000,
            "check_requests": 3, "trace_seconds": 0.5,
            "limits": {"max_logit_gap": 1.0}, "end_to_end": [], "per_layer": []}
    cell.update(kw)
    return cell


def main():
    from bench import run as R
    from bench import trace as tr

    cell = tiny_cell()
    run, device, _ = R.serve_window(cell, 5, 2.0, True)
    gc.collect()
    src = tr.find(os.path.join(ROOT, ".bench_runs", f"trace-{cell['name']}"))
    os.makedirs(os.path.join(HERE, "data"), exist_ok=True)
    shutil.copy(src, os.path.join(HERE, "data", "small.xplane.pb"))
    traced = [r for r in run.rounds if r.traced]
    waves = [w for w in run.waves if w.traced]
    counts = {"device": device, "decode_steps": sum(r.n_steps for r in traced),
              "rounds": len(traced), "prefill_groups": sum(
                  len({g for _, _, g in w.members}) for w in waves),
              "layers": TINY["num_hidden_layers"]}
    with open(os.path.join(HERE, "data", "small.counts.json"), "w") as f:
        json.dump(counts, f, indent=1)
    print(json.dumps(counts))


if __name__ == "__main__":
    main()
