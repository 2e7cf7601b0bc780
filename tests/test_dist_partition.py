"""Fast-lane unit tests for repro.dist: spec inference and the mesh
registry.  No subprocesses, no multi-device requirement — spec functions
only read ``mesh.shape``, so a duck-typed stand-in exercises every
divisibility branch on the single real CPU device."""
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import compression, partition
from repro.launch.mesh import make_mesh


class FakeMesh:
    """Spec inference touches only ``.shape`` (a name->size mapping)."""

    def __init__(self, **axes):
        self.shape = dict(axes)


MESH_2D = FakeMesh(data=2, model=4)
MESH_3D = FakeMesh(pod=2, data=2, model=2)
MESH_1D = FakeMesh(data=1, model=1)


# ---------------------------------------------------------------------------
# Mesh registry + shard_named
# ---------------------------------------------------------------------------
def test_no_mesh_is_identity():
    partition.set_mesh(None)
    x = jnp.ones((4, 8))
    assert partition.shard_named(x, ("D", "T")) is x
    assert partition.shard_activation(x) is x


def test_registry_roundtrip():
    partition.set_mesh(MESH_2D)
    assert partition.get_mesh() is MESH_2D
    partition.set_mesh(None)
    assert partition.get_mesh() is None


def test_unknown_tag_raises():
    mesh = make_mesh((1,), ("data",))
    partition.set_mesh(mesh)
    with pytest.raises(ValueError, match="unknown shard tag"):
        partition.shard_named(jnp.ones((4,)), ("X",))


def test_tag_arity_must_match_rank():
    mesh = make_mesh((1,), ("data",))
    partition.set_mesh(mesh)
    with pytest.raises(AssertionError):
        partition.shard_named(jnp.ones((4, 4)), ("D",))


def test_shard_named_on_real_single_device_mesh():
    """On a trivial concrete mesh every tag resolves to replicated and the
    constraint is still applied (values unchanged)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    partition.set_mesh(mesh)
    x = jnp.arange(32.0).reshape(4, 8)
    y = partition.shard_named(x, ("D", "T"))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


# ---------------------------------------------------------------------------
# Axis resolution
# ---------------------------------------------------------------------------
def test_data_axes_folds_pod_and_data():
    assert partition._data_axes(MESH_3D, 8) == ("pod", "data")
    # batch=2 cannot take pod*data=4 -> single data axis
    assert partition._data_axes(MESH_3D, 2) == ("data",)
    # indivisible stays replicated
    assert partition._data_axes(MESH_3D, 3) is None
    # degenerate mesh never shards
    assert partition._data_axes(MESH_1D, 64) is None


# ---------------------------------------------------------------------------
# param_specs
# ---------------------------------------------------------------------------
def test_small_leaves_replicate():
    params = {"layers": {"norm": jnp.ones((4, 64))},
              "bias": jnp.ones((256,))}
    specs = partition.param_specs(params, MESH_2D)
    assert specs == {"layers": {"norm": P()}, "bias": P()}


def test_stacked_layer_dim_never_sharded():
    params = {"layers": {"wq": jnp.ones((4, 256, 512))}}
    specs = partition.param_specs(params, MESH_2D)
    # column-parallel: last dim over model, layer dim untouched
    assert specs["layers"]["wq"] == P(None, None, "model")


def test_row_parallel_shards_input_dim():
    params = {"layers": {"wo": jnp.ones((4, 256, 512))}}
    specs = partition.param_specs(params, MESH_2D)
    assert specs["layers"]["wo"] == P(None, "model", None)


def test_indivisible_tp_dim_falls_back_to_other_dim():
    # last dim 255 % model=4 != 0, but 256 divides -> shard the other dim
    params = {"w_up": jnp.ones((256, 255))}
    specs = partition.param_specs(params, MESH_2D)
    assert specs["w_up"] == P("model", None)


def test_fully_indivisible_replicates():
    params = {"w_up": jnp.ones((255, 129))}
    assert partition.param_specs(params, MESH_2D)["w_up"] == P()


def test_fsdp_only_for_large_train_leaves():
    big = jnp.ones((2048, 2048))       # 4M elems >= FSDP_MIN_ELEMS
    small = jnp.ones((128, 512))       # 64K elems: TP only
    specs = partition.param_specs({"wq": big, "wk": small}, MESH_2D)
    assert specs["wq"] == P("data", "model")
    assert specs["wk"] == P(None, "model")
    serve = partition.param_specs({"wq": big}, MESH_2D, mode="serve")
    # serve folds (data, model) onto the TP dim instead of FSDP
    assert serve["wq"] == P(None, ("data", "model"))


def test_moe_expert_stack_expert_parallel():
    params = {"layers": {"moe": {"w_up": jnp.ones((2, 8, 64, 128))}}}
    specs = partition.param_specs(params, MESH_2D)
    # (L, E, d, f): E over model, body too small for FSDP
    assert specs["layers"]["moe"]["w_up"] == P(None, "model", None, None)


def test_pod_axis_never_shards_params():
    params = {"wq": jnp.ones((2048, 2048))}
    specs = partition.param_specs(params, MESH_3D)
    for entry in specs["wq"]:
        assert entry != "pod" and entry != ("pod",)


# ---------------------------------------------------------------------------
# batch_specs / cache_specs
# ---------------------------------------------------------------------------
def test_batch_specs_batch_major():
    batch = {"tokens": jnp.ones((8, 64), jnp.int32),
             "positions": jnp.ones((3, 8, 64), jnp.int32),
             "scalar": jnp.float32(1.0)}
    specs = partition.batch_specs(batch, MESH_2D)
    assert specs["tokens"] == P("data", None)
    assert specs["positions"] == P(None, "data", None)
    assert specs["scalar"] == P()


def test_cache_specs_kv_heads_over_model():
    cache = {"k": jnp.ones((2, 8, 64, 4, 32)),
             "len": jnp.ones((8,), jnp.int32)}
    specs = partition.cache_specs(cache, MESH_2D)
    assert specs["k"] == P(None, "data", None, "model", None)
    assert specs["len"] == P("data")


def test_cache_specs_indivisible_heads_replicate():
    cache = {"k": jnp.ones((2, 8, 64, 3, 32))}   # 3 heads % model=4
    specs = partition.cache_specs(cache, MESH_2D)
    assert specs["k"] == P(None, "data", None, None, None)


# ---------------------------------------------------------------------------
# compression (single-pod path: identical numerics, no collective)
# ---------------------------------------------------------------------------
def test_error_feedback_single_pod():
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(16, 64)),
                          jnp.float32)}
    err = compression.init_error_state(g)
    mean, new_err = compression.cross_pod_mean(g, err, MESH_1D)
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    # one-step quantisation error bounded by half a step
    assert float(jnp.max(jnp.abs(mean["w"] - g["w"]))) <= scale / 2 + 1e-7
    # residual carries exactly what the mean dropped
    np.testing.assert_allclose(
        np.asarray(new_err["w"]), np.asarray(g["w"] - mean["w"]), atol=1e-6)


def test_wire_bytes_ratio():
    g = {"w": jnp.ones((256, 256), jnp.float32)}
    stats = compression.wire_bytes(g)
    assert stats["raw"] == 256 * 256 * 4
    assert stats["compressed"] == 256 * 256 + 4
    assert stats["ratio"] > 3.9
