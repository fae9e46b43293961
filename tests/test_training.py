"""Training substrate tests: optimizer, loss descent, checkpoint/restart,
gradient compression, data pipeline determinism."""

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.ft.elastic import plan_mesh
from repro.models.registry import build, load_config
from repro.optim import adamw
from repro.optim.compress import compress_leaf, decompress_leaf
from repro.train.loop import LoopConfig, lm_loss, make_train_step, run_loop


def _setup(arch="tinyllama-1.1b"):
    cfg = load_config(arch).reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=4))
    return cfg, model, params, data


def test_lm_loss_basics():
    logits = jnp.zeros((2, 3, 8))
    labels = jnp.array([[1, 2, 3], [4, -1, -1]])
    loss = lm_loss(logits, labels)
    np.testing.assert_allclose(float(loss), np.log(8), rtol=1e-5)


def test_loss_decreases():
    cfg, model, params, data = _setup()
    opt_cfg = adamw.AdamWConfig(lr=5e-3, warmup_steps=2, total_steps=30)
    step = jax.jit(make_train_step(model, opt_cfg))
    opt_state = adamw.init(params)
    losses = []
    for i in range(12):
        batch = jax.tree.map(jnp.asarray, data.batch_at(i % 2))
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.9, losses


def test_schedule_shape():
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_ratio=0.1)
    lrs = [float(adamw.schedule(cfg, jnp.int32(s))) for s in [0, 5, 10, 50, 100]]
    assert lrs[0] == 0.0
    assert abs(lrs[2] - 1.0) < 1e-6
    assert lrs[3] < 1.0
    assert abs(lrs[4] - 0.1) < 1e-3


def test_grad_clip():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = adamw.clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(adamw.global_norm(clipped)), 1.0, rtol=1e-5)
    np.testing.assert_allclose(float(norm), 20.0, rtol=1e-5)


def test_checkpoint_roundtrip(tmp_path):
    tree = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.int32)}}
    d = str(tmp_path / "ck")
    ckpt.save(d, 7, tree, extra={"foo": 1})
    out, step, extra = ckpt.restore(d, jax.tree.map(np.asarray, tree))
    assert step == 7 and extra == {"foo": 1}
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))


def test_checkpoint_retention_and_latest(tmp_path):
    d = str(tmp_path / "ck")
    for s in (1, 2, 3, 4):
        ckpt.save(d, s, {"x": jnp.ones(2) * s})
    ckpt.retain(d, keep=2)
    assert ckpt.latest_step(d) == 4
    assert sorted(os.listdir(d)) == ["step_00000003", "step_00000004"]


def test_run_loop_resume(tmp_path):
    cfg, model, params, data = _setup()
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    lc = LoopConfig(total_steps=4, ckpt_every=2, ckpt_dir=str(tmp_path / "run"),
                    log_every=100)
    p1, _, hist1 = run_loop(model, params, data, opt_cfg, lc, log=lambda s: None)
    # simulate preemption + restart: same call resumes from step 4 checkpoint
    lc2 = LoopConfig(total_steps=6, ckpt_every=2, ckpt_dir=str(tmp_path / "run"),
                     log_every=100)
    p2, _, hist2 = run_loop(model, params, data, opt_cfg, lc2, log=lambda s: None)
    assert hist2[0]["step"] == 5  # continued, not restarted
    assert len(hist2) == 2


def test_compress_roundtrip_error_feedback():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(8, 256)).astype(np.float32))
    q, s = compress_leaf(g, 64)
    rec = decompress_leaf(q, s, 64)
    err = np.abs(np.asarray(rec - g))
    half = np.repeat(np.asarray(s), 64, axis=-1) / 2
    assert np.all(err <= half + 1e-6)


def test_compressed_psum_unbiased():
    """shard_map over a 1-device axis: compressed psum == plain mean."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.optim.compress import compressed_psum

    mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
    g = {"w": jnp.asarray(np.random.default_rng(1).normal(size=(4, 128)).astype(np.float32))}

    def f(grads):
        out, res = compressed_psum(grads, "pod")
        return out, res

    out, res = shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=(P(), P()))(g)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]), atol=0.02)
    # residual = quantization error, bounded by half-step
    assert float(jnp.max(jnp.abs(res["w"]))) < 0.02


def test_data_determinism_and_sharding():
    c1 = DataConfig(vocab_size=100, seq_len=8, global_batch=4, seed=3)
    a = SyntheticLM(c1).batch_at(5)
    b = SyntheticLM(c1).batch_at(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    np.testing.assert_array_equal(a["labels"][:, :-1], a["tokens"][:, 1:])
    # host sharding splits the global batch
    h0 = SyntheticLM(DataConfig(100, 8, 4, seed=3, num_hosts=2, host_index=0)).batch_at(5)
    assert h0["tokens"].shape == (2, 8)


def test_plan_mesh_elasticity():
    assert plan_mesh(512).shape == (2, 16, 16)
    assert plan_mesh(256).shape == (16, 16)
    assert plan_mesh(8).shape == (1, 8)
    assert plan_mesh(1).shape == (1, 1)
    # losing a pod: 256 devices -> single-pod plan, same axis names trailing
    assert plan_mesh(256).axes == ("data", "model")
