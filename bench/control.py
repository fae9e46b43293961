"""Readings that set a cell's ``max_logit_gap`` limit, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed, in this one process: set up and serve the window exactly as
``bench/run.py`` does, free the program, then on the same sample of
finished requests read

- ``program``: the widest gap by which a served token's f32-reference
  logit lies below the reference's best (the number ``correct`` compares);
- ``control``: the widest gap of the token that the reference computed at
  the next precision down (int4 weights, ``Reference(weight_bits=4)``)
  puts first, at the same positions of the same prompts and tokens.

The limit lies above the largest ``program`` reading and well below the
smallest ``control`` reading (PERF.md gives both). One JSON line per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

import numpy as np  # noqa: E402

from bench import run as R  # noqa: E402


def readings(run, seed: int) -> dict:
    import jax.numpy as jnp

    from bench import reference

    tokens, rows, targets, n = R.check_sample(run, seed)
    ref = reference.Reference(run.cfg).logits(seed, tokens, rows)
    program = reference.gaps(ref, jnp.asarray(targets))
    ctl = reference.Reference(run.cfg, weight_bits=4).logits(seed, tokens, rows)
    control = np.asarray(reference.gaps(ref, jnp.argmax(ctl, axis=-1)))[:n]
    return {"seed": seed, "program": float(program.max()),
            "control": float(control.max()), "tokens": n,
            "control_disagrees": int((control > 0).sum())}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        run, _, _ = R.serve_window(cell, seed, args.seconds, False)
        gc.collect()
        print(json.dumps(readings(run, seed)), flush=True)
        del run
        gc.collect()


if __name__ == "__main__":
    main()
