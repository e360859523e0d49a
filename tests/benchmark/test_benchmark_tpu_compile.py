"""What set-up reads back from ZeRO's flat state, compiled at the real size
for four v5e chips that are described and not attached: the chip's compiler
refuses here what it would refuse there, at no chip time. It once did: a
second axis on a slice of the flat vector made XLA re-view all 336M elements
in (8, 128) tiles, 43 GB for an axis of 2."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks.models import bert_pretrain
from benchmarks.refs import bert_pretrain_ref as ref
from benchmarks.refs import weights as weights_mod

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def mesh():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.array(topo.devices).reshape(4), ("data",))


def _program_and_state(mesh):
    with open(os.path.join(
            REPO, "benchmarks/configs/bert_large_pretrain.json")) as f:
        cfg = json.load(f)
    shapes = ref.weight_shapes(cfg)
    program = object.__new__(bert_pretrain.Program)     # no engine needed
    program.cfg = cfg
    program.names = list(weights_mod.flatten(
        weights_mod.nest(dict.fromkeys(shapes))))
    n = sum(int(np.prod(s)) for s in shapes.values())
    flat = jax.ShapeDtypeStruct((n + (-n) % 4,), jnp.float32,
                                sharding=NamedSharding(mesh, P("data")))
    whole = NamedSharding(mesh, P())
    initial = weights_mod.nest({
        k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=whole)
        for k, s in shapes.items()})
    return program, cfg, flat, initial


@pytest.mark.parametrize("reader", ["change_norms", "first_moment_sketch"])
def test_state_readers_fit_four_chips_at_the_real_size(mesh, reader):
    program, cfg, flat, initial = _program_and_state(mesh)
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep it out of one
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        if reader == "change_norms":
            fn = program._per_leaf_fn(
                program._sum_sq_change(ref.change_skip(cfg)))
            compiled = fn.lower(flat, initial).compile()
        else:
            fn = program._per_leaf_fn(lambda _name, m: ref.sketch(m))
            compiled = fn.lower(flat).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 4 * 2 ** 30
