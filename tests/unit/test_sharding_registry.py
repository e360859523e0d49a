"""Sharding-spec registry tests (parallel/sharding_registry.py).

Covers the registry contract the ISSUE names: ordered first-match-wins
resolution, the named failure modes (unmatched path, unknown axis, rank
mismatch), scalar replication, the bitwise shard->gather round-trip on a
multi-device CPU mesh, the mesh factory, and the ``parallel`` ds_config
block validation that feeds it. conftest.py virtualizes 8 CPU devices.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, create_mesh
from deepspeed_tpu.parallel.sharding_registry import (
    SERVING_PARTITION_RULES,
    ShardingRegistry,
    ShardingRegistryError,
    UnknownAxisError,
    UnmatchedPathError,
    create_serving_mesh,
    match_partition_rules,
    normalize_mesh_shape,
    serving_registry,
    serving_sharding,
    train_registry,
    train_spec,
)
from deepspeed_tpu.runtime.config import (
    DeepSpeedConfig,
    get_parallel_config,
)


def _mesh(data=1, model=4):
    return create_mesh(data_parallel_size=data, model_parallel_size=model,
                       devices=jax.devices()[:data * model])


# -- rule resolution ----------------------------------------------------------

def test_ordered_first_match_wins():
    reg = ShardingRegistry({
        r"qkv/kernel$": PartitionSpec(None, MODEL_AXIS),
        r"kernel$": PartitionSpec(MODEL_AXIS, None),
        r".*": PartitionSpec(),
    })
    assert reg.spec_for("layer/qkv/kernel") == PartitionSpec(None, MODEL_AXIS)
    assert reg.spec_for("layer/ff2/kernel") == PartitionSpec(MODEL_AXIS, None)
    assert reg.spec_for("layer/qkv/bias") == PartitionSpec()


def test_unmatched_path_raises_without_replicate_unmatched():
    reg = ShardingRegistry({r"^only/this$": PartitionSpec()})
    with pytest.raises(UnmatchedPathError, match="no rule matches"):
        reg.spec_for("something/else")


def test_replicate_unmatched_defaults_to_replication():
    reg = ShardingRegistry({r"^only/this$": PartitionSpec(MODEL_AXIS)},
                           replicate_unmatched=True)
    assert reg.spec_for("something/else") == PartitionSpec()


def test_scalar_leaves_always_replicate():
    # even when the matching rule names an axis, a 0-d leaf replicates
    reg = ShardingRegistry({r".*": PartitionSpec(MODEL_AXIS)})
    assert reg.spec_for("step", ndim=0) == PartitionSpec()
    specs = reg.specs({"w": np.zeros((4,)), "step": np.float32(0)})
    assert specs["step"] == PartitionSpec()
    assert specs["w"] == PartitionSpec(MODEL_AXIS)


def test_spec_longer_than_leaf_rank_is_an_error():
    reg = ShardingRegistry({r".*": PartitionSpec(None, None, MODEL_AXIS)})
    with pytest.raises(ShardingRegistryError, match="has only"):
        reg.spec_for("w", ndim=2)


def test_validate_axes_names_the_offending_rule():
    reg = ShardingRegistry({r"w$": PartitionSpec("rows")})
    with pytest.raises(UnknownAxisError, match="'rows'"):
        reg.validate_axes(("data", "model"))
    # a Mesh works as the axis source too
    with pytest.raises(UnknownAxisError):
        reg.validate_axes(_mesh())
    ok = ShardingRegistry({r"w$": PartitionSpec(MODEL_AXIS)})
    assert ok.validate_axes(_mesh()) is ok


def test_match_partition_rules_functional_shape():
    tree = {"block": {"qkv": {"kernel": np.zeros((2, 4, 8))},
                      "ln": {"scale": np.zeros((2, 4))}}}
    specs = match_partition_rules(SERVING_PARTITION_RULES, tree)
    assert specs["block"]["qkv"]["kernel"] == \
        PartitionSpec(None, None, MODEL_AXIS)
    # ln/scale falls through to the catch-all
    assert specs["block"]["ln"]["scale"] == PartitionSpec()


def test_serving_registry_extra_rules_take_precedence():
    reg = serving_registry(
        extra_rules=[(r"qkv/kernel$", (None, None, None))])
    assert reg.spec_for("h/qkv/kernel") == PartitionSpec(None, None, None)
    # untouched built-ins still resolve
    assert reg.spec_for("h/ff2/kernel") == PartitionSpec(None, MODEL_AXIS, None)


def test_train_registry_named_placements():
    assert train_spec("zero/flat_shard") == PartitionSpec(DATA_AXIS)
    assert train_spec("zero/gathered") == PartitionSpec()
    with pytest.raises(UnmatchedPathError):
        train_registry().spec_for("zero/unknown")


# -- placement round-trip -----------------------------------------------------

def test_shard_gather_round_trip_is_bitwise():
    mesh = _mesh(data=1, model=4)
    reg = serving_registry()
    rng = np.random.default_rng(0)
    tree = {
        "h": {
            "qkv": {"kernel": rng.standard_normal((2, 8, 24)).astype(
                np.float32), "bias": rng.standard_normal((2, 24)).astype(
                np.float32)},
            "attn_out": {"kernel": rng.standard_normal((2, 8, 8)).astype(
                np.float32)},
            "ln": {"scale": rng.standard_normal((2, 8)).astype(np.float32)},
        },
    }
    sharded = reg.shard(mesh, tree)
    qkv = sharded["h"]["qkv"]["kernel"]
    assert qkv.sharding == NamedSharding(
        mesh, PartitionSpec(None, None, MODEL_AXIS))
    assert len({d.id for d in qkv.sharding.device_set}) == 4
    # per-device shards really split the heads dim
    assert qkv.addressable_shards[0].data.shape == (2, 8, 6)

    gathered = reg.gather(mesh, sharded)
    for path in (("h", "qkv", "kernel"), ("h", "qkv", "bias"),
                 ("h", "attn_out", "kernel"), ("h", "ln", "scale")):
        want = tree
        got = gathered
        for k in path:
            want, got = want[k], got[k]
        assert got.sharding.spec == PartitionSpec()
        np.testing.assert_array_equal(np.asarray(got), want)


def test_make_shard_and_gather_fns_are_per_leaf():
    mesh = _mesh()
    reg = serving_registry()
    tree = {"qkv": {"kernel": np.ones((2, 4, 8), np.float32)}}
    shard_fns = reg.make_shard_fns(mesh, tree)
    gather_fns = reg.make_gather_fns(mesh, tree)
    leaf = shard_fns["qkv"]["kernel"](tree["qkv"]["kernel"])
    assert leaf.sharding.spec == PartitionSpec(None, None, MODEL_AXIS)
    back = gather_fns["qkv"]["kernel"](leaf)
    assert back.sharding.spec == PartitionSpec()
    np.testing.assert_array_equal(np.asarray(back), tree["qkv"]["kernel"])


# -- mesh factory -------------------------------------------------------------

def test_normalize_mesh_shape_forms():
    assert normalize_mesh_shape(None) == (1, 1)
    assert normalize_mesh_shape((1, 4)) == (1, 4)
    assert normalize_mesh_shape([2, 2]) == (2, 2)
    assert normalize_mesh_shape({"model": 4}) == (1, 4)
    assert normalize_mesh_shape({"data": 2, "model": 2}) == (2, 2)
    with pytest.raises(UnknownAxisError, match="unknown axes"):
        normalize_mesh_shape({"rows": 2})
    with pytest.raises(ShardingRegistryError, match="must be"):
        normalize_mesh_shape((1, 2, 3))
    with pytest.raises(ShardingRegistryError, match=">= 1"):
        normalize_mesh_shape((0, 4))


def test_create_serving_mesh_shapes_and_device_floor():
    mesh = create_serving_mesh((1, 4))
    assert mesh.shape[MODEL_AXIS] == 4 and mesh.shape[DATA_AXIS] == 1
    with pytest.raises(ShardingRegistryError, match="needs"):
        create_serving_mesh((4, 4))   # 16 > the 8 virtual devices


def test_serving_sharding_resolves_engine_buffer_paths():
    mesh = _mesh()
    kv = serving_sharding(mesh, "serving/kv_pool")
    assert kv.spec == PartitionSpec(None, None, MODEL_AXIS)
    lane = serving_sharding(mesh, "serving/lane_state")
    assert lane.spec == PartitionSpec()


# -- the `parallel` ds_config block -------------------------------------------

def test_parallel_config_defaults_and_presence_enables():
    off = get_parallel_config({})
    assert not off.enabled and off.mesh_shape == (1, 1)
    assert off.partition_rules is None and off.replicate_unmatched is True
    on = get_parallel_config({"parallel": {}})
    assert on.enabled


def test_parallel_config_mesh_shape_forms_and_errors():
    assert get_parallel_config(
        {"parallel": {"mesh_shape": [1, 4]}}).mesh_shape == (1, 4)
    assert get_parallel_config(
        {"parallel": {"mesh_shape": {"model": 2}}}).mesh_shape == (1, 2)
    with pytest.raises(ValueError, match="unknown axes"):
        get_parallel_config({"parallel": {"mesh_shape": {"rows": 2}}})
    with pytest.raises(ValueError, match="pair"):
        get_parallel_config({"parallel": {"mesh_shape": [1, 2, 3]}})
    with pytest.raises(ValueError, match="int >= 1"):
        get_parallel_config({"parallel": {"mesh_shape": [1, 0]}})
    with pytest.raises(ValueError, match="int >= 1"):
        get_parallel_config({"parallel": {"mesh_shape": [1, True]}})


def test_parallel_config_partition_rules_validation():
    cfg = get_parallel_config({"parallel": {
        "mesh_shape": [1, 2],
        "partition_rules": [["qkv/kernel$", [None, None, "model"]]]}})
    assert cfg.partition_rules == (("qkv/kernel$", (None, None, "model")),)
    with pytest.raises(ValueError, match="not a valid regex"):
        get_parallel_config({"parallel": {
            "partition_rules": [["(", [None]]]}})
    with pytest.raises(ValueError, match="absent from"):
        get_parallel_config({"parallel": {
            "partition_rules": [["x", ["pipe"]]]}})
    with pytest.raises(ValueError, match="pair"):
        get_parallel_config({"parallel": {"partition_rules": ["x"]}})
    with pytest.raises(ValueError, match="bool"):
        get_parallel_config({"parallel": {"replicate_unmatched": "yes"}})


def test_parallel_config_feeds_registry_and_mesh():
    """Config-layer output is directly consumable by the registry layer:
    the end-to-end wiring ServingEngine.from_config performs."""
    cfg = DeepSpeedConfig({"train_batch_size": 8, "parallel": {
        "mesh_shape": {"model": 4},
        "partition_rules": [["ln/scale$", [None, "model"]]]}})
    pc = cfg.parallel_config
    assert pc.enabled
    reg = serving_registry(extra_rules=pc.partition_rules,
                           replicate_unmatched=pc.replicate_unmatched)
    reg.validate_axes(create_serving_mesh(pc.mesh_shape))
    # the override outranks the built-in catch-all
    assert reg.spec_for("h/ln/scale") == PartitionSpec(None, MODEL_AXIS)
