"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs on whatever devices exist (laptop CPU -> full pod): the mesh is built
elastically, sharding rules key off axis names, and --resume auto restores
the newest complete checkpoint (fault-tolerant restart path).
"""

from __future__ import annotations

import argparse
import os

import jax

from repro.data.pipeline import DataConfig, SyntheticLM
from repro.dist import logical
from repro.dist import sharding as shd
from repro.ft.elastic import elastic_mesh
from repro.launch.compile_cache import CHECKOUT, use_compile_cache
from repro.models.registry import build, load_config
from repro.optim import adamw
from repro.train.loop import LoopConfig, make_train_step, run_loop


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-scale smoke/e2e runs)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(CHECKOUT, "experiments", "train_ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = load_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    mesh = elastic_mesh(model_parallel=min(16, len(jax.devices())))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}  arch: {cfg.arch_id}")

    params = model.init(jax.random.PRNGKey(args.seed))
    p_specs = shd.param_specs(params, mesh, "train")
    params = jax.device_put(params, shd.shardings(p_specs, mesh))

    data = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed,
    ))
    opt_cfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps,
                                warmup_steps=max(1, args.steps // 20))
    loop_cfg = LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                          ckpt_dir=args.ckpt_dir)

    with mesh, logical.use_mesh_rules(mesh):
        step_fn = jax.jit(make_train_step(model, opt_cfg))
        params, _, history = run_loop(
            model, params, data, opt_cfg, loop_cfg,
            train_step=step_fn, resume=not args.no_resume,
        )
    print(f"final loss: {history[-1]['loss']:.4f}")
    return history


if __name__ == "__main__":
    main()
