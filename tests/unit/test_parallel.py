"""Tensor-parallel sharding rules + ring attention tests on the 8-device mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

import deepspeed_tpu
from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.parallel.sequence import ring_attention
from deepspeed_tpu.parallel.tp import MEGATRON_RULES, param_specs, shard_params
from deepspeed_tpu.ops.transformer.attention import _attention_reference


def test_tp_rules_transformer_layer():
    from deepspeed_tpu.ops.transformer.transformer import (
        DeepSpeedTransformerConfig,
        DeepSpeedTransformerLayer,
    )

    cfg = DeepSpeedTransformerConfig(
        hidden_size=64, intermediate_size=128, heads=4,
        attn_dropout_ratio=0.0, hidden_dropout_ratio=0.0,
        num_hidden_layers=1, initializer_range=0.02, training=False,
    )
    layer = DeepSpeedTransformerLayer(cfg)
    x = jnp.ones((2, 16, 64))
    params = layer.init(jax.random.PRNGKey(0), x, None, deterministic=True)
    specs = param_specs(params)
    flat = jax.tree_util.tree_leaves_with_path(specs)
    by_name = {"/".join(str(getattr(k, "key", k)) for k in path): spec for path, spec in flat}
    assert any("qkv" in k and v == PartitionSpec(None, "model") for k, v in by_name.items() if k.endswith("kernel"))
    assert any("ff2" in k and v == PartitionSpec("model", None) for k, v in by_name.items() if k.endswith("kernel"))
    assert any("attn_out" in k and v == PartitionSpec("model", None) for k, v in by_name.items() if k.endswith("kernel"))


def test_tp_sharded_forward_matches_replicated():
    """A TP-sharded transformer layer forward must equal the replicated one
    (XLA inserts the collectives)."""
    from deepspeed_tpu.ops.transformer.transformer import (
        DeepSpeedTransformerConfig,
        DeepSpeedTransformerLayer,
    )

    cfg = DeepSpeedTransformerConfig(
        hidden_size=64, intermediate_size=128, heads=4,
        attn_dropout_ratio=0.0, hidden_dropout_ratio=0.0,
        num_hidden_layers=1, initializer_range=0.02, training=False,
    )
    layer = DeepSpeedTransformerLayer(cfg)
    x = jnp.asarray(np.random.RandomState(0).randn(4, 16, 64).astype(np.float32))
    params = layer.init(jax.random.PRNGKey(0), x, None, deterministic=True)

    ref = layer.apply(params, x, None, deterministic=True)

    mesh = mesh_lib.create_mesh(model_parallel_size=2)
    sharded = shard_params(params, mesh)
    fn = jax.jit(lambda p, x: layer.apply(p, x, None, deterministic=True))
    with jax.sharding.use_mesh(mesh) if hasattr(jax.sharding, "use_mesh") else mesh:
        out = fn(sharded, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    B, H, S, D = 2, 2, 64, 16
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.5
    q, k, v = mk(), mk(), mk()
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    out = ring_attention(q, k, v, mesh=mesh, axis_name="data", causal=causal)
    ref = _attention_reference(q, k, v, jnp.zeros((B, S), jnp.float32), None, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_attention_masked():
    B, H, S, D = 2, 2, 64, 16
    rng = np.random.RandomState(1)
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.5
    q, k, v = mk(), mk(), mk()
    bias = jnp.asarray(np.where(rng.rand(B, S) < 0.25, -1e9, 0.0).astype(np.float32))
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    out = ring_attention(q, k, v, mask=bias, mesh=mesh, axis_name="data")
    ref = _attention_reference(q, k, v, bias, None, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_attention_grads():
    B, H, S, D = 1, 2, 64, 8
    rng = np.random.RandomState(2)
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.5
    q, k, v = mk(), mk(), mk()
    mesh = Mesh(np.asarray(jax.devices()), ("data",))

    def loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh=mesh, axis_name="data") ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_attention_reference(
            q, k, v, jnp.zeros((B, S), jnp.float32), None, causal=False) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_dense(causal):
    from deepspeed_tpu.parallel.ulysses import ulysses_attention

    B, H, S, D = 2, 8, 64, 16
    rng = np.random.RandomState(3)
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.5
    q, k, v = mk(), mk(), mk()
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    out = ulysses_attention(q, k, v, mesh=mesh, axis_name="data", causal=causal)
    ref = _attention_reference(q, k, v, jnp.zeros((B, S), jnp.float32), None, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ulysses_attention_masked():
    from deepspeed_tpu.parallel.ulysses import ulysses_attention

    B, H, S, D = 2, 8, 64, 16
    rng = np.random.RandomState(4)
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.5
    q, k, v = mk(), mk(), mk()
    bias = jnp.asarray(np.where(rng.rand(B, S) < 0.25, -1e9, 0.0).astype(np.float32))
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    out = ulysses_attention(q, k, v, mask=bias, mesh=mesh, axis_name="data")
    ref = _attention_reference(q, k, v, bias, None, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("path", ["kernels", "materialised"])
def test_ulysses_pallas_kernel_under_shard_map(monkeypatch, path):
    """Ulysses' local attention goes through ``flash_attention`` on the TPU
    path, whose rule sees the LOCAL call (one head of 1,024 tokens here: 4 MiB
    of scores, the materialised path's plain operations, which need nothing
    inside a shard_map); with the budget at 0 it is the Pallas flash kernel:
    exercise pallas_call (interpret mode) INSIDE shard_map on the virtual
    mesh. Either way the result matches the reference path."""
    import functools

    from deepspeed_tpu.ops.transformer import attention as A
    from deepspeed_tpu.parallel.ulysses import ulysses_attention

    W = len(jax.devices())
    B, H, S, D = 1, 8, 128 * W, 64  # full local seq S is 128-aligned
    rng = np.random.RandomState(7)
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32) * 0.3)
    q, k, v = mk(), mk(), mk()
    mesh = Mesh(np.asarray(jax.devices()), ("data",))

    want = ulysses_attention(q, k, v, mesh=mesh, causal=True)  # reference path

    calls = {"n": 0}
    real = A._attention_pallas

    def spy(*a, **kw):
        calls["n"] += 1
        kw["interpret"] = True
        return real(*a, **kw)

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(A, "_attention_pallas", spy)
    if path == "kernels":
        monkeypatch.setattr(A, "_SCORE_BUDGET", 0)

    before = A.trace_counts()
    got = ulysses_attention(q, k, v, mesh=mesh, causal=True)
    if path == "kernels":
        assert calls["n"] >= 1, "Pallas kernel not exercised under shard_map"
    else:
        assert calls["n"] == 0
        assert A.traced_implementation(since=before) == "dense"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=3e-5)


def test_ulysses_attention_grads():
    """Backward through the all-to-all sequence-parallel path must match the
    dense reference (training, not just inference, runs through Ulysses)."""
    from deepspeed_tpu.parallel.ulysses import ulysses_attention

    B, H, S, D = 2, 8, 64, 16
    rng = np.random.RandomState(5)
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.5
    q, k, v = mk(), mk(), mk()
    mesh = Mesh(np.asarray(jax.devices()), ("data",))

    def loss(q, k, v):
        return jnp.sum(ulysses_attention(q, k, v, mesh=mesh, axis_name="data") ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_attention_reference(
            q, k, v, jnp.zeros((B, S), jnp.float32), None, causal=False) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5)
