"""Edge cases for repro.dist beyond the seed rule table: no-mesh/CPU
fallback, indivisible-dim degradation, quantized leaves on MoE expert
weights, pod meshes — plus kernels/gqmv._row_block block-size selection."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.quant import quantize_groupwise
from repro.dist import logical
from repro.dist.sharding import (
    batch_specs,
    cache_spec,
    dp_axes,
    logits_spec,
    param_spec,
    param_specs,
)
from repro.kernels.gqmv import _row_block

MESH16 = SimpleNamespace(shape={"data": 16, "model": 16},
                         axis_names=("data", "model"))
POD = SimpleNamespace(shape={"pod": 2, "data": 8, "model": 16},
                      axis_names=("pod", "data", "model"))


# ---------------------------------------------------------------------------
# no-mesh / CPU fallback
# ---------------------------------------------------------------------------

def test_no_mesh_sizes_are_one():
    assert logical.size("dp") == 1
    assert logical.size("tp") == 1
    assert logical.size("seq") == 1
    assert logical.active_mesh() is None


def test_no_mesh_constrain_is_identity():
    x = jnp.arange(12).reshape(3, 4)
    assert logical.constrain(x, "dp", "tp") is x


def test_mesh_rules_bind_and_restore():
    with logical.use_mesh_rules(MESH16):
        assert logical.size("dp") == 16
        assert logical.size("tp") == 16
        assert logical.size("seq") == 256
        assert logical.active_mesh() is MESH16
    assert logical.size("seq") == 1
    assert logical.active_mesh() is None


def test_constrain_runs_on_single_device_mesh():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with logical.use_mesh_rules(mesh):
        assert logical.size("tp") == 1
        y = logical.constrain(jnp.ones((4, 4)), "dp", "tp")
    np.testing.assert_array_equal(np.asarray(y), np.ones((4, 4)))


def test_constrain_rejects_too_many_axes():
    mesh = jax.make_mesh((1,), ("data",))
    with logical.use_mesh_rules(mesh):
        with pytest.raises(ValueError):
            logical.constrain(jnp.ones((4,)), "dp", "tp")


# ---------------------------------------------------------------------------
# indivisible-dim degradation
# ---------------------------------------------------------------------------

def test_logical_spec_drops_indivisible_and_reused_axes():
    with logical.use_mesh_rules(MESH16):
        # 7 % 16 != 0 -> dropped; second "tp" would reuse the model axis
        assert logical.spec((32, 7, 64), "dp", "tp", "tp") == P("data", None, "model")
        assert logical.spec((1, 512), None, "seq") == P(None, ("data", "model"))
        # 8 % 256 != 0 -> seq dropped
        assert logical.spec((8,), "seq") == P(None)


def test_param_spec_fully_indivisible_degrades_to_replicated():
    assert param_spec("layers/attn/wqkv", (24, 4095, 2047),
                      mesh=MESH16, mode="train") == P(None, None, None)


def test_cache_spec_layer_count_equal_to_batch():
    # 16 layers, batch 16: the leading stack axis must NOT be taken for the
    # batch — batch -> data at axis 1, sequence -> model at axis 2.
    assert cache_spec("k", (16, 16, 32768, 8, 128), mesh=MESH16, batch=16) == \
        P(None, "data", "model", None, None)
    # zamba-style (groups, per, batch, ...) still finds batch at axis 2
    assert cache_spec("conv", (4, 6, 32, 3, 288), mesh=MESH16, batch=32) == \
        P(None, None, "data", None, None)


def test_cache_spec_indivisible_dims():
    assert cache_spec("k", (2, 6, 10, 2, 8), mesh=MESH16, batch=6) == \
        P(None, None, None, None, None)
    # batch=1 but T only divides the model axis -> model, not the full mesh
    assert cache_spec("k", (2, 1, 32, 2, 8), mesh=MESH16, batch=1) == \
        P(None, None, "model", None, None)


# ---------------------------------------------------------------------------
# quantized leaves on MoE expert weights
# ---------------------------------------------------------------------------

def test_moe_expert_quantized_leaves():
    # qvalues inherit the expert rule (E -> model, in -> train FSDP)
    assert param_spec("layers/mlp/experts/w13/qvalues", (40, 16, 21504, 6144),
                      mesh=MESH16, mode="train") == P(None, "model", None, "data")
    # scales: group axis NEVER takes FSDP or the (consumed) model axis
    assert param_spec("layers/mlp/experts/w13/scales", (40, 16, 21504, 24),
                      mesh=MESH16, mode="train") == P(None, "model", None, None)
    # row-parallel expert: within-expert contraction whole -> groups whole too
    assert param_spec("layers/mlp/experts/w2/scales", (40, 16, 6144, 48),
                      mesh=MESH16, mode="serve") == P(None, "model", None, None)


def test_param_specs_descends_into_quantized_tensors():
    params = {"layers": {"mlp": {"w2": quantize_groupwise(jnp.ones((4, 64)), 32)}}}
    specs = param_specs(params, MESH16, "serve")
    qt = specs["layers"]["mlp"]["w2"]
    assert qt.qvalues == P(None, "model")   # out 4 indivisible; in -> model
    assert qt.scales == P(None, None)       # 2 groups % 16 -> whole


# ---------------------------------------------------------------------------
# pod meshes / outputs
# ---------------------------------------------------------------------------

def test_pod_mesh_dp_axes_and_batch_specs():
    assert dp_axes(POD) == ("pod", "data")
    specs = batch_specs({"tokens": jax.ShapeDtypeStruct((32, 8), jnp.int32),
                         "odd": jax.ShapeDtypeStruct((10, 8), jnp.int32)}, POD)
    assert specs["tokens"] == P(("pod", "data"), None)
    assert specs["odd"] == P(None, None)    # 10 % 16 != 0


def test_logits_spec():
    assert logits_spec(MESH16, 2, 256) == P(("data",), "model")
    assert logits_spec(MESH16, 3, 3) == P(None, None, "model")


# ---------------------------------------------------------------------------
# kernels/gqmv._row_block: output-row blocks the TPU compiler accepts
# ---------------------------------------------------------------------------

def test_row_block_prefers_default_bm():
    assert _row_block(2048) == 256
    assert _row_block(32000) == 256


def test_row_block_falls_to_one_lane_tile():
    assert _row_block(384) == 128
    assert _row_block(640) == 128


def test_row_block_full_dim_when_not_lane_multiple():
    # a block equal to the whole dim is legal at any size
    assert _row_block(96) == 96
    assert _row_block(13) == 13


def test_row_block_divides_every_tinyllama_projection():
    for m in (256, 2048, 2560, 5632, 11264, 32000):   # kv, o, qkv, ffn, lm head
        bm = _row_block(m)
        assert m % bm == 0 and bm % 128 == 0
