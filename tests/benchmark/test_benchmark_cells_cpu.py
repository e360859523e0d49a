"""Every cell end to end at tiny size, on the CPU path that only these tests
take (``require_chip=False``); the command itself still fails without a chip.
Also: the timed path broken underneath must come out as not correct, and so
must the reference put in the program's place at the next lower precision.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import tiny_root
from benchmarks import run as run_mod
from benchmarks.harness import costs, serve_cell, spec, train_cell
from benchmarks.tools import limits as limits_tool

REPO = tiny_root.REPO
BENCH = spec.load_benchmark(REPO)
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 2 ** 31 + 1234              # the driver's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root.make(tmp_path_factory.mktemp("tiny_benchmark"))


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    """The peaks table has no row for a CPU (by design); the tiny traced
    runs borrow the v5e row so that the readers run at all."""
    real = costs.peaks_for
    monkeypatch.setattr(
        costs, "peaks_for",
        lambda kind, table_path=None: real(
            "TPU v5 lite" if kind == "cpu" else kind, table_path))


def _run(root, cell, trace, seconds=1.0, seed=SEED):
    return run_mod.run_cell(root, cell, seed, seconds, trace,
                            require_chip=False)


def test_the_command_fails_without_a_chip_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        assert not line.lstrip().startswith("{")


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_runs_end_to_end_at_tiny_size(root, cell_name):
    cell = spec.load_cell(root, cell_name)
    line = _run(root, cell_name, trace=False)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end()}
    for m in cell.end_to_end():
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert line["device"]["count"] == cell.chips
    assert line["device"]["platform"] == "cpu"       # named, never hidden
    json.dumps(line)


@pytest.mark.parametrize("cell_name", ["bert_large_s128_zero2_dp4",
                                       "gpt2_large_chat_steady"])
def test_traced_run_reports_per_layer_metrics_only(root, cell_name):
    cell = spec.load_cell(root, cell_name)
    line = _run(root, cell_name, trace=True)
    assert line["correct"] is True
    names = {m["name"] for m in cell.per_layer()}
    assert set(line["metrics"]) <= names and line["metrics"]
    assert not set(line["metrics"]) & {m["name"] for m in BENCH["end_to_end"]}
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(line["device"])


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        root, monkeypatch):
    from benchmarks.models import bert_pretrain

    real_step = bert_pretrain.Program.step

    def frozen_step(self, batch):
        eng = self.engine
        eng._ensure_opt_state()
        keep = jax.tree_util.tree_map(
            jnp.copy, (eng.params, eng.opt_state, eng.scaler_state))
        loss = real_step(self, batch)
        eng.params, eng.opt_state, eng.scaler_state = keep
        return loss

    monkeypatch.setattr(bert_pretrain.Program, "step", frozen_step)
    line = _run(root, "bert_large_s128_1chip", trace=False)
    assert line["correct"] is False


def test_a_part_of_the_batch_left_out_is_not_correct(root, monkeypatch):
    from benchmarks.models import bert_pretrain

    real_step = bert_pretrain.Program.step

    def half_step(self, batch):
        ids, types, attn, labels, nsp = batch
        labels = labels.copy()
        labels[labels.shape[0] // 2:] = -1       # half the rows teach nothing
        return real_step(self, (ids, types, attn, labels, nsp))

    monkeypatch.setattr(bert_pretrain.Program, "step", half_step)
    line = _run(root, "bert_large_s128_1chip", trace=False)
    assert line["correct"] is False


def test_a_token_altered_where_it_is_produced_is_not_correct(
        root, monkeypatch):
    from deepspeed_tpu.inference.serving import engine as engine_mod

    real_emit = engine_mod.ServingEngine._emit

    def wrong_emit(self, req, token):
        if req.emitted == 1:                      # every request's 2nd token
            token = (token + 1) % self.model_config.vocab_size
        return real_emit(self, req, token)

    monkeypatch.setattr(engine_mod.ServingEngine, "_emit", wrong_emit)
    line = _run(root, "gpt2_large_chat_steady", trace=False)
    assert line["correct"] is False
    assert line["check"]["served_logit_gap_max"] > \
        spec.load_cell(root, "gpt2_large_chat_steady").limits[
            "served_logit_gap"]


def test_a_request_cut_short_counts_as_failed(root, monkeypatch):
    from benchmarks.models import gpt2_serve

    real_submit = gpt2_serve.Program.submit

    def short_submit(self, prompt_ids, max_new_tokens, stream_cb):
        return real_submit(self, prompt_ids, max(1, max_new_tokens - 1),
                           stream_cb)

    monkeypatch.setattr(gpt2_serve.Program, "submit", short_submit)
    line = _run(root, "gpt2_large_longprompt_closed", trace=False)
    assert line["correct"] is False and line["failed"] > 0


@pytest.mark.parametrize("seed", [SEED, 5, 77])
def test_training_control_at_fp8_is_not_correct(root, seed):
    """The reference in the program's place, its products' operands rounded
    to fp8 (the step below the bf16 the configuration states), fails at
    least one of the cell's numbers; in float32 it passes all of them."""
    cell = spec.load_cell(root, "bert_large_s128_1chip")
    devices = jax.devices()[:1]
    limits = cell.limits

    def over(numbers):
        return [k for k, v in numbers.items() if v > (
            limits["loss_gap"][int(k[-1]) - 1] if k.startswith("loss_gap")
            else limits[k])]

    assert over(limits_tool.train_numbers(cell, seed, devices, "f32")) == []
    assert over(limits_tool.train_numbers(cell, seed, devices, "fp8"))


@pytest.mark.parametrize("seed", [SEED, 5, 77])
def test_serving_control_at_fp8_is_not_correct(root, seed):
    """At each position of the same prompts and served tokens, the token an
    fp8 forward pass puts first lies further below the reference's best
    than the limit allows; the served (bf16) tokens stay inside it. A fixed
    set of requests served to the end, so that no clock decides the
    sample."""
    import time

    import jax.numpy as jnp

    from benchmarks.harness import runtime
    from benchmarks.refs import weights as weights_mod

    cell = spec.load_cell(root, "gpt2_large_longprompt_closed")
    cfg = cell.config
    ref = runtime.load_reference(cfg)
    program = runtime.load_adapter(cfg).Program(cfg, weights_mod.make_weights(
        ref.weight_shapes(cfg), seed, jnp.dtype(cfg["serving"]["param_dtype"])))
    gen = serve_cell.Generator(program, cfg, cell.traffic, seed, seconds=0.0)
    program.start()
    try:
        flights = [gen._send(r, time.monotonic())
                   for r in gen.all_requests()[:60]]
        for f in flights:
            assert len(f.future.result(timeout=300)) == f.request.output_len
    finally:
        program.stop()
    program.close()
    cell.traffic["check_requests"] = len(flights)
    gap, control_gap, n_tokens = serve_cell.reference_gaps(
        cell, flights, seed, "fp8")
    limit = cell.limits["served_logit_gap"]
    assert n_tokens > 500
    assert gap <= limit
    assert control_gap > limit
