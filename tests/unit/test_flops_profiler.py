"""FLOPS profiler tests (reference test_flops_profiler.py: measured flops
within tolerance of the analytic count)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn

import deepspeed_tpu
from deepspeed_tpu.profiling.flops_profiler.profiler import (
    FlopsProfiler,
    flops_to_string,
    get_model_profile,
    params_to_string,
)


def test_matmul_flops_measured():
    M, K, N = 256, 512, 128

    def fn(a, b):
        return a @ b

    a = jnp.ones((M, K), jnp.float32)
    b = jnp.ones((K, N), jnp.float32)
    prof = FlopsProfiler()
    flops = prof.analyze(fn, a, b)
    expected = 2 * M * K * N
    assert 0.5 * expected <= flops <= 2.0 * expected, f"{flops} vs {expected}"


def test_model_profile_dense():
    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(128)(x)
            x = nn.relu(x)
            return nn.Dense(10)(x)

    m = MLP()
    x = jnp.ones((32, 64))
    params = m.init(jax.random.PRNGKey(0), x)
    flops, macs, n_params = get_model_profile(m, args=(params, x), print_profile=False, as_string=False)
    expected_macs = 32 * (64 * 128 + 128 * 10)
    assert 0.5 * expected_macs <= macs <= 3 * expected_macs
    assert n_params == 64 * 128 + 128 + 128 * 10 + 10


def test_engine_profiler_hook(capsys):
    """Engine prints the profile at the configured step."""
    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, y):
            pred = nn.Dense(1)(x)
            return jnp.mean((pred - y) ** 2)

    m = Tiny()
    n_dev = len(jax.devices())
    x = jnp.ones((2 * n_dev, 8))
    y = jnp.zeros((2 * n_dev, 1))
    params = m.init(jax.random.PRNGKey(0), x, y)
    engine, _, _, _ = deepspeed_tpu.initialize(model=m, model_parameters=params, config_params={
        "train_batch_size": 2 * n_dev,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "flops_profiler": {"enabled": True, "profile_step": 1},
    })
    assert engine.flops_profiler is not None
    for _ in range(3):
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()


def test_per_module_profile_two_layer():
    """Per-module attribution for a 2-layer model: every submodule appears
    with its own MACs and params, depth aggregation groups by class
    (reference profiler.py:174-297 per-module tables)."""
    class Block(nn.Module):
        width: int

        @nn.compact
        def __call__(self, x):
            x = nn.Dense(self.width)(x)
            return nn.relu(x)

    class TwoLayer(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = Block(width=128)(x)
            x = Block(width=64)(x)
            return nn.Dense(10)(x)

    m = TwoLayer()
    B, D = 32, 64
    x = jnp.ones((B, D))
    params = m.init(jax.random.PRNGKey(0), x)

    prof = FlopsProfiler()
    prof.analyze_modules(lambda p, a: m.apply(p, a), params, x, params=params)

    # exact-scope flops: each Dense got its dot_general
    scopes = set(prof.module_flops)
    assert any(s.endswith("Block_0/Dense_0") for s in scopes), scopes
    assert any(s.endswith("Block_1/Dense_0") for s in scopes), scopes
    d0 = next(v for s, v in prof.module_flops.items() if s.endswith("Block_0/Dense_0"))
    d1 = next(v for s, v in prof.module_flops.items() if s.endswith("Block_1/Dense_0"))
    assert d0 >= 2 * B * D * 128, (d0, scopes)
    assert d1 >= 2 * B * 128 * 64, d1

    # params mapped onto the same scopes
    p0 = next(v for s, v in prof.module_params.items() if s.endswith("Block_0/Dense_0"))
    assert p0 == 64 * 128 + 128

    # inclusive tree: the Block subtotal contains its Dense
    inc_f, inc_p = prof._inclusive_tree()
    blk = next(v for s, v in inc_f.items() if s.endswith("Block_0") and "Dense" not in s)
    assert blk >= d0
    assert next(v for s, v in inc_p.items() if s.endswith("Block_0") and "Dense" not in s) == p0

    # printed report: aggregated top-k line + per-module tree lines
    prof.set_flops(sum(prof.module_flops.values()))
    prof.set_params(params)
    prof.duration = 0.01
    report = prof.print_model_profile(profile_step=1, module_depth=1, top_modules=2)
    assert "Top 2 modules in MACs at depth 1" in report
    assert "Block" in report and "Dense" in report
    assert "% MACs" in report and "% Params" in report


def test_engine_per_module_profile(capsys):
    """The engine's profile step produces a per-module table for its model."""
    class Inner(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4)(x)

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x, y):
            h = Inner()(x)
            pred = nn.Dense(1)(h)
            return jnp.mean((pred - y) ** 2)

    m = Net()
    n_dev = len(jax.devices())
    x = jnp.ones((2 * n_dev, 8))
    y = jnp.zeros((2 * n_dev, 1))
    params = m.init(jax.random.PRNGKey(0), x, y)
    engine, _, _, _ = deepspeed_tpu.initialize(model=m, model_parameters=params, config_params={
        "train_batch_size": 2 * n_dev,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "flops_profiler": {"enabled": True, "profile_step": 1},
    })
    reports = []
    orig = engine.flops_profiler.print_model_profile

    def capture(**kw):
        reports.append(orig(**kw))
        return reports[-1]

    engine.flops_profiler.print_model_profile = capture
    for _ in range(3):
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
    assert len(reports) == 1
    assert "Inner_0" in reports[0]
    assert "Top" in reports[0] and "MACs at depth" in reports[0]


def test_scan_trip_count_multiplication():
    """Scan-rolled layers (the BERT/GPT-2 encoders) report length x the body's
    FLOPs, not one trip's."""
    D = 32

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x, _):
            return nn.Dense(D)(x), None

    def run(length):
        Scanned = nn.scan(
            Layer, variable_axes={"params": 0}, split_rngs={"params": True},
            length=length,
        )
        m = Scanned()
        x = jnp.ones((4, D))
        params = m.init(jax.random.PRNGKey(0), x, None)
        prof = FlopsProfiler()
        prof.analyze_modules(lambda p, a: m.apply(p, a, None)[0], params, x)
        return sum(prof.module_flops.values())

    f1, f4 = run(1), run(4)
    assert f4 >= 3.5 * f1, (f1, f4)


def test_formatting():
    assert flops_to_string(2e12) == "2.00 TFLOPS"
    assert params_to_string(336e6).endswith("M")


def test_peaks_table_knows_or_raises():
    """One published-peaks table: a known TPU kind gives its peak, a
    non-TPU platform gives None, and a TPU kind the table does not list
    raises instead of borrowing another generation's number."""
    from types import SimpleNamespace as Dev

    from deepspeed_tpu.profiling.flops_profiler.profiler import device_peak_tflops

    assert device_peak_tflops(Dev(platform="tpu", device_kind="TPU v5 lite")) == 197.0
    assert device_peak_tflops(Dev(platform="tpu", device_kind="TPU v5p")) == 459.0
    assert device_peak_tflops(Dev(platform="cpu", device_kind="cpu")) is None
    with pytest.raises(ValueError, match="no published peak"):
        device_peak_tflops(Dev(platform="tpu", device_kind="TPU v5"))
    with pytest.raises(ValueError, match="no published peak"):
        device_peak_tflops(Dev(platform="tpu", device_kind="TPU v9x"))
