"""chip_smoke.py's phase functions at toy size on the CPU mesh, plus the
contracts around it that need no chip: ``main()`` refuses a CPU, the
compile-cache helper places the cache where it says, and importing the
package and the launchers initialises no backend (one process per chip: a
parent that has touched JAX holds it).
"""

import os
import subprocess
import sys

import pytest

import jax

import chip_smoke
from deepspeed_tpu.models.bert import BertConfig
from deepspeed_tpu.models.gpt2 import GPT2Config
from deepspeed_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_kernel_phase_tiny():
    report = chip_smoke.kernel_phase(
        flash_shapes=[(1, 2, 32, 16)], dense_shape=(1, 2, 16, 16),
        flash_block=16, heads=2, head_dim=16, page_tokens=8, native=False)
    assert {"flash_s32_fwd", "flash_sparse_bwd", "flash_dropout_bwd_mask",
            "attention_dense_s16_fwd", "attention_dense_s16_bwd",
            "decode_fp32_step", "decode_bf16_step", "decode_int8_step",
            "decode_fp32_chunk", "band_float32", "band_bfloat16"} <= set(report)


def test_train_phase_tiny():
    """Tiny BERT over the 8-device CPU mesh: ZeRO-2, so the sharding checks
    run too. The attention traced here is the jnp reference, which
    ``native=True`` would refuse."""
    cfg = BertConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=2, intermediate_size=64,
                     max_position_embeddings=32, checkpoint_policy="dots")
    report = chip_smoke.train_phase(cfg, seq_len=16, micro_batch=2, warmup=2,
                                    steps=5, native=False)
    assert report["devices"] == len(jax.devices()) > 1
    assert report["attention"] == "reference"
    assert report["compiles_after_warmup"] == 0
    assert len(report["losses"]) == 7 and len(report["step_ms"]) == 5
    assert report["zero_sharded_vectors"] >= 3      # master + two moments


@pytest.mark.parametrize("mesh_shape", [None, (1, 2)])
def test_serve_phase_tiny(mesh_shape):
    cfg = GPT2Config(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=32,
                     hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    report = chip_smoke.serve_phase(
        cfg, max_seq_len=32, prompt_buckets=(4, 8), max_slots=2,
        requests=[(3, 5), (7, 4), (2, 6), (8, 3)], mesh_shape=mesh_shape,
        timeout_s=120.0)
    assert report["tokens_out"] == 18 and report["decode_compiles"] == 1
    if mesh_shape is not None:
        assert report["kv_pool_bytes_per_device"] == (
            [report["kv_pool_bytes"] // 2] * 2)


def test_main_refuses_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main() != 0
    out = capsys.readouterr()
    assert out.out == ""                    # no phase ran, no result line
    assert "needs a TPU" in out.err


def test_compile_cache_placement(monkeypatch):
    placed = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: placed.append((key, value)))
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert placed == []                     # JAX reads the variable itself
    monkeypatch.delenv(compile_cache.ENV_VAR)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert placed == [("jax_compilation_cache_dir", want)]


def test_imports_initialise_no_backend():
    """The launchers and bench.py's parent start the processes that will
    hold the chip, so they must not have touched a backend themselves."""
    code = (
        "import sys; sys.argv = ['x']\n"
        "import deepspeed_tpu, deepspeed_tpu.launcher.launch\n"
        "import deepspeed_tpu.launcher.runner, bench\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), 'backend is up'\n"
        "print('clean')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and "clean" in r.stdout, r.stderr[-2000:]


def test_bench_without_a_chip_prints_no_number():
    """``python bench.py`` on a host with no TPU: non-zero exit, nothing on
    stdout — no cached, replayed or CPU number in the chip leg's place."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_MODEL", None)
    r = subprocess.run([sys.executable, "bench.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no number printed" in r.stderr
