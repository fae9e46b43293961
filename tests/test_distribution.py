"""Distribution-layer tests: partition rules, HLO analyzer, mesh planning,
plus one real (tiny-mesh) sharded train step for end-to-end validity."""

import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import batch_specs, cache_spec, param_spec, param_specs
from repro.launch import hlo_analysis

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MESH16 = SimpleNamespace(shape={"data": 16, "model": 16},
                         axis_names=("data", "model"))


def _spec(path, shape, mode="train"):
    return param_spec(path, shape, mesh=MESH16, mode=mode)


def test_column_parallel_rules():
    # (L, out, in): out -> model, in -> data (FSDP, train only)
    assert _spec("layers/attn/wqkv", (24, 4096, 2048)) == P(None, "model", "data")
    assert _spec("layers/attn/wqkv", (24, 4096, 2048), "serve") == P(None, "model", None)
    assert _spec("layers/mlp/w13", (24, 16384, 2048)) == P(None, "model", "data")


def test_row_parallel_rules():
    assert _spec("layers/attn/wo", (24, 2048, 2048)) == P(None, "data", "model")
    assert _spec("layers/mlp/w2", (24, 2048, 8192), "serve") == P(None, None, "model")


def test_quantized_leaf_rules():
    # scales of a row-parallel int8 weight: groups axis follows the model axis
    assert _spec("layers/mlp/w2/qvalues", (24, 2048, 8192), "serve") == P(None, None, "model")
    assert _spec("layers/mlp/w2/scales", (24, 2048, 64), "serve") == P(None, None, "model")
    # col-parallel scales shard the out dim, never get FSDP on the group axis
    assert _spec("layers/attn/wqkv/scales", (24, 4096, 8), "serve") == P(None, "model", None)


def test_moe_expert_parallel():
    assert _spec("layers/mlp/experts/w13", (40, 16, 21504, 6144)) == \
        P(None, "model", None, "data")
    # within-expert contraction never sharded (groups stay whole)
    assert _spec("layers/mlp/experts/w2", (40, 16, 6144, 10752), "serve") == \
        P(None, "model", None, None)


def test_embed_and_small_leaves():
    assert _spec("embed", (92544, 2048)) == P("model", "data")
    assert _spec("layers/att_norm", (24, 2048)) == P(None, None)
    assert _spec("layers/mlp/router_w", (40, 16, 6144)) == P(None, None, None)
    # indivisible dims stay unsharded rather than erroring
    assert _spec("layers/attn/wo", (24, 2048, 2047)) == P(None, "data", None)


def test_cache_rules():
    # (L,B,T,KV,hd): batch -> data, seq -> model
    assert cache_spec("k", (24, 128, 32768, 8, 128), mesh=MESH16, batch=128) == \
        P(None, "data", "model", None, None)
    # batch=1 long context: T over both axes
    assert cache_spec("shared_k", (13, 1, 524288, 32, 112), mesh=MESH16, batch=1) == \
        P(None, None, ("data", "model"), None, None)
    # rwkv state: heads -> model
    assert cache_spec("wkv", (32, 128, 64, 64, 64), mesh=MESH16, batch=128) == \
        P(None, "data", "model", None, None)


def test_batch_specs_divisibility():
    mesh = SimpleNamespace(shape={"data": 16, "model": 16}, axis_names=("data", "model"))
    specs = batch_specs({"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32),
                         "odd": jax.ShapeDtypeStruct((3, 5), jnp.int32)}, mesh)
    assert specs["tokens"] == P(("data",), None)
    assert specs["odd"] == P(None, None)


# ---------------------------------------------------------------------------
# HLO analyzer
# ---------------------------------------------------------------------------

HLO_SAMPLE = """
%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %g = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %ar = f32[8,8]{1,0} all-reduce(%g), channel_id=1
  %d = f32[8,8]{1,0} dot(%ar, %g), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[8,8]) tuple(%p, %d)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p2 = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p2), index=0
  %c = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %init = (s32[], f32[8,8]) tuple(%a, %a)
  %w = (s32[], f32[8,8]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_analyzer_trip_count_expansion():
    rep = hlo_analysis.analyze(HLO_SAMPLE)
    # dot: 2*8*8*8 flops, x10 trips
    assert rep.flops == 10 * 2 * 8 * 8 * 8
    assert rep.bytes_by_kind["all-reduce"] == 10 * 8 * 8 * 4
    assert rep.num_collectives["all-reduce"] == 10


def test_analyzer_on_real_compiled_module():
    def f(w, x):
        return jnp.tanh(x @ w)

    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 32), jnp.float32),
        jax.ShapeDtypeStruct((16, 64), jnp.float32)).compile()
    rep = hlo_analysis.analyze(compiled.as_text())
    assert rep.flops == 2 * 16 * 64 * 32
    assert rep.collective_bytes == 0


def test_roofline_terms():
    rl = hlo_analysis.Roofline(flops=197e12, hbm_bytes=819e9 * 2,
                               collective_bytes=50e9 * 3, chips=256,
                               model_flops=197e12 * 256 * 0.5)
    assert abs(rl.compute_s - 1.0) < 1e-9
    assert abs(rl.memory_s - 2.0) < 1e-9
    assert abs(rl.collective_s - 3.0) < 1e-9
    assert rl.dominant == "collective"
    assert abs(rl.mfu - 0.5 / 3.0) < 1e-9


# ---------------------------------------------------------------------------
# end-to-end sharded step on the host devices (1-device mesh)
# ---------------------------------------------------------------------------

def test_sharded_train_step_runs():
    from repro.ft.elastic import elastic_mesh
    from repro.models.registry import build, load_config, smoke_batch
    from repro.optim import adamw
    from repro.train.loop import make_train_step
    from repro.dist.sharding import shardings

    cfg = load_config("internlm2-1.8b").reduced()
    model = build(cfg)
    mesh = elastic_mesh()
    params = model.init(jax.random.PRNGKey(0))
    specs = param_specs(params, mesh, "train")
    params = jax.device_put(params, shardings(specs, mesh))
    opt = adamw.init(params)
    step = jax.jit(make_train_step(model, adamw.AdamWConfig(total_steps=10)))
    batch = smoke_batch(cfg, batch=2, seq=8)
    with mesh:
        params, opt, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_dryrun_cli_single_cell(tmp_path):
    """Full dry-run path in a subprocess (needs its own XLA_FLAGS=512)."""
    out = tmp_path / "res.json"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "internlm2-1.8b",
         "--shape", "prefill_32k", "--mesh", "single", "--out", str(out)],
        capture_output=True, text=True, timeout=1200,
        # a CPU test: the child must never reach for an accelerator
        env={"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
             "PATH": os.environ.get("PATH", "/usr/bin:/bin")},
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    import json
    res = json.loads(out.read_text())
    rec = res["internlm2-1.8b|prefill_32k|single"]
    assert rec["status"] == "ok"
    assert rec["roofline"]["chips"] == 256
    assert rec["roofline"]["step_s"] > 0
