"""Fault-tolerance integration: train on an 8-device mesh, checkpoint, lose
half the fleet, resume on a 4-device mesh — the checkpoint reshards onto the
surviving devices and the loss curve continues (subprocess because device
count is fixed at first jax init)."""

import os
import subprocess
import sys
import textwrap

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={sys.argv[1]}"
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.dist import logical
    from repro.dist.sharding import param_specs, shardings
    from repro.ft.elastic import elastic_mesh
    from repro.models.registry import build, load_config
    from repro.optim import adamw
    from repro.train.loop import LoopConfig, make_train_step, run_loop

    steps, ckdir = int(sys.argv[2]), sys.argv[3]
    cfg = load_config("internlm2-1.8b").reduced()
    model = build(cfg)
    mesh = elastic_mesh(model_parallel=4)
    assert mesh.devices.size == int(sys.argv[1]), mesh.devices.shape
    params = model.init(jax.random.PRNGKey(0))
    params = jax.device_put(params, shardings(param_specs(params, mesh, "train"), mesh))
    data = SyntheticLM(DataConfig(cfg.vocab_size, 16, 4))
    opt_cfg = adamw.AdamWConfig(lr=2e-3, warmup_steps=2, total_steps=12)
    with mesh, logical.use_mesh_rules(mesh):
        step = jax.jit(make_train_step(model, opt_cfg))
        _, _, hist = run_loop(model, params, data, opt_cfg,
                              LoopConfig(total_steps=steps, ckpt_every=4,
                                         ckpt_dir=ckdir, log_every=100),
                              train_step=step, log=lambda s: None)
        # fresh-init loss on the first batch this run trained on: the
        # reset-detection baseline (params above were never updated here)
        from repro.train.loop import make_loss_fn
        first_batch = jax.tree.map(jnp.asarray, data.batch_at(hist[0]["step"] - 1))
        fresh = float(make_loss_fn(model)(params, first_batch)[0])
    print(json.dumps({"hist": [(h["step"], h["loss"]) for h in hist],
                      "fresh_first_loss": fresh}))
""")


def _run(devices: int, steps: int, ckdir: str):
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(devices), str(steps), ckdir],
        capture_output=True, text=True, timeout=900, cwd=REPO_ROOT,
        # a CPU test: the child must never reach for an accelerator
        env={"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
             "PATH": os.environ.get("PATH", "/usr/bin:/bin")},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    import json
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_elastic_restart_reshards(tmp_path):
    ck = str(tmp_path / "elastic")
    res1 = _run(8, 8, ck)             # 2x4 mesh, checkpoints at steps 4, 8
    hist1 = res1["hist"]
    assert hist1[-1][0] == 8
    res2 = _run(4, 12, ck)            # "pod loss": resume on 1x4 mesh
    hist2 = res2["hist"]
    assert hist2[0][0] == 9           # resumed, not restarted
    # restored params beat a fresh re-init ON THE SAME BATCH: the checkpoint
    # trajectory continued rather than resetting to ~ln(V) (same-batch
    # comparison — per-batch difficulty varies more than 8 steps of progress,
    # so any cross-batch loss comparison here would be unreliable)
    assert hist2[0][1] < res2["fresh_first_loss"], (res2["fresh_first_loss"], hist2[0])
