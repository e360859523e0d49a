"""The Ouro cell end to end at tiny size on the CPU (traced and untraced),
with its own tiny root (``tiny_root.py`` writes tiny files for the first two
configurations only), a token altered where it is produced, the fp8 control
at that size, the readers of the new per-layer metrics on counters made by
hand, that the appended readers give a number for this configuration, and
the byte and FLOP functions of ``costs_ouro.py`` against the issue's numbers
worked by hand. Every entry of ``BENCHMARK.json`` is found by NAME, never by
its place: the next configuration is appended after this one.
"""

import json
import os
import shutil
import time
import types

import jax.numpy as jnp
import pytest

from benchmarks import run as run_mod
from benchmarks.harness import costs, costs_ouro, runtime, serve_cell
from benchmarks.harness import spec
from benchmarks.refs import weights as weights_mod

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO, "benchmarks")
BENCH = spec.load_benchmark(REPO)
CELL = "ouro_2p6b_shortcot_closed8"
CONFIG = "ouro_2p6b_serve"
TRAFFIC = "shortcot_closed8"
SEED = 2 ** 32 + 5                 # the driver's seeds pass 32 signed bits

# toy widths that keep what is published: as many key-value heads as query
# heads, the whole head rotated, four passes; three layers, so that 12
# cache rows are not the 3 layers of weights
TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
            num_attention_heads=4, num_key_value_heads=4, head_dim=16,
            num_hidden_layers=3, max_window_layers=3,
            layer_types=["full_attention"] * 3,
            max_position_embeddings=4096)


def _load(path):
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def make_root(tmp):
    """A tiny copy of the benchmark that holds this cell's files."""
    root = str(tmp)
    bdir = os.path.join(root, "benchmarks")
    os.makedirs(os.path.join(bdir, "configs"))
    os.makedirs(os.path.join(bdir, "traffic"))
    shutil.copytree(os.path.join(BENCH_DIR, "metrics"),
                    os.path.join(bdir, "metrics"))
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    cfg.update(TINY)
    cfg["serving"].update(max_seq_len=256, max_slots=4, max_queue=64,
                          prompt_buckets=[200], kv_page_tokens=16,
                          kv_pool_tokens=1024, prefill_chunk_tokens=64)
    cfg["check"]["limits"] = {"served_logit_gap": TINY_LIMIT}
    with open(os.path.join(bdir, "configs", CONFIG + ".json"), "w") as f:
        json.dump(cfg, f)
    t = _load(f"benchmarks/traffic/{TRAFFIC}.json")
    t.update(clients=4, requests_per_client=200,
             prompt_tokens={"dist": "lognormal", "median": 24, "sigma": 0.9,
                            "min": 4, "max": 190},
             output_tokens={"dist": "lognormal", "median": 20, "sigma": 0.6,
                            "min": 4, "max": 48},
             max_total_tokens=256, warm_seconds=0.5, trace_seconds=0.3,
             check_requests=4)
    with open(os.path.join(bdir, "traffic", TRAFFIC + ".json"), "w") as f:
        json.dump(t, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(BENCH, f)
    return root


# read at THIS size on the CPU (bf16 program, 790 served tokens of the first
# 10 requests of each client; the gap is the reference's 32-token mean or a
# twentieth of the token's own). Over eight seeds (4294967301, 42, 77, 3, 5,
# 11, 2147483655, 123456789012) served tokens read 0.00018 to 0.00037 and
# the fp8 control 0.0245 to 0.090: the two do not overlap, and the limit
# stands a factor of eight from either
TINY_LIMIT = 0.003


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny_ouro_benchmark"))


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    real = costs.peaks_for
    monkeypatch.setattr(
        costs, "peaks_for",
        lambda kind, table_path=None: real(
            "TPU v5 lite" if kind == "cpu" else kind, table_path))


NEW_METRICS = ("ouro_decode_step_ms_p50", "ouro_prefill_time_share",
               "ouro_decode_step_roofline", "ouro_loop_cache_bytes_share")
APPENDED = ("serve_tokens_per_s", "lane_occupancy", "serve_device_idle_share",
            "kimi_prefill_padding_share", "nemotron_prefill_rows_mean")
NOT_APPENDED = ("moe_experts_touched_share", "moe_load_max_over_mean",
                "serve_late_read_share", "serve_decode_dispatch_ms")


def test_the_benchmark_file_holds_the_new_entries():
    """By name, not by place: a later cell is appended after this one."""
    assert spec.validate(BENCH) == []
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": TRAFFIC,
                    "chips": 1, "why": cell["why"]}
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == []
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    metrics = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in APPENDED:
        assert CELL in metrics[name]["workloads"]
    for name in NOT_APPENDED:                   # no experts; PR 37's seven
        assert CELL not in metrics[name]["workloads"]
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "serve_tokens_per_s"
    layers = {metrics[n]["layer"] for n in NEW_METRICS}
    assert layers == {"ouro programs (serving/families/ouro.py jitted steps)"}
    assert metrics["ouro_decode_step_roofline"]["unit"] == "%"
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(json.dumps(BENCH, indent=1)) < 64 * 1024


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's ``config`` under the same name and value:
    nothing is reduced; what no key settles is under ``assumed``."""
    published = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Ouro-2.6B":
                published = row
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    assert cfg["source"] == published["source_url"]
    assert {k: cfg[k] for k in published["config"]} == published["config"]
    assert (cfg["total_ut_steps"], cfg["early_exit_threshold"]) == (4, 1)
    assert cfg["reduced"] == []
    for key in ("sandwich_norms", "norm_between_passes", "shared_weights",
                "no_bias_no_qk_norm", "rope", "exit_gate", "cache_rows",
                "max_seq_len", "weights", "decoding"):
        assert len(cfg["assumed"][key]) > 40, key
    assert (cfg["kind"], cfg["adapter"], cfg["reference"]) == (
        "serve", "ouro_serve", "ouro_ref")
    assert cfg["serving"] == {
        "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
        "max_seq_len": 640, "max_slots": 8, "max_queue": 16,
        "prompt_buckets": [192], "kv_cache_dtype": "bf16",
        "kv_page_tokens": 128, "kv_pool_tokens": 5120,
        "prefill_chunk_tokens": 512}


def test_the_traffic_file_is_the_issues_letter_for_letter():
    t = _load(f"benchmarks/traffic/{TRAFFIC}.json")
    assert (t["loop"], t["clients"], t["requests_per_client"]) == (
        "closed", 8, 32)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 96,
                                  "sigma": 0.5, "min": 32, "max": 192}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 224,
                                  "sigma": 0.55, "min": 48, "max": 448}
    assert t["max_total_tokens"] == 640
    assert (t["warm_seconds"], t["trace_seconds"],
            t["check_requests"]) == (10, 6, 4)
    others = {_load(f"benchmarks/traffic/{n}")["schedule_seed"]
              for n in os.listdir(os.path.join(BENCH_DIR, "traffic"))
              if n != TRAFFIC + ".json"
              and "schedule_seed" in _load(f"benchmarks/traffic/{n}")}
    assert t["schedule_seed"] not in others              # one of its own
    serving = _load(f"benchmarks/configs/{CONFIG}.json")["serving"]
    assert serving["max_slots"] == t["clients"]           # one a lane
    assert serving["max_seq_len"] == t["max_total_tokens"]
    assert serving["prompt_buckets"] == [t["prompt_tokens"]["max"]]
    # full provision: no request waits for pages
    assert serving["kv_pool_tokens"] == (serving["max_slots"]
                                         * serving["max_seq_len"])


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_end_to_end_at_tiny_size(root, trace):
    cell = spec.load_cell(root, CELL)
    line = run_mod.run_cell(root, CELL, 3, 1.5, trace, require_chip=False)
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    if not trace:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        assert line["check"]["compiled_in_window"] == 0
    else:
        names = {m["name"] for m in cell.per_layer()}
        assert set(line["metrics"]) <= names
        # the counter-fed metrics need no device trace: the appended
        # readers give a number for this configuration, and so does the new
        for name in ("lane_occupancy", "kimi_prefill_padding_share",
                     "nemotron_prefill_rows_mean",
                     "ouro_loop_cache_bytes_share"):
            assert line["metrics"][name]["value"] > 0, name
        assert "moe_experts_touched_share" not in line["metrics"]
        assert line["metrics"]["kimi_prefill_padding_share"]["value"] < 100.0
        assert 1.0 <= line["metrics"]["nemotron_prefill_rows_mean"][
            "value"] <= 4.0
        assert line["metrics"]["ouro_loop_cache_bytes_share"]["value"] < 100.0
    json.dumps(line)


def test_the_adapter_lets_the_flat_names_go(root):
    """The layers are stacked by the family and must not be held a second
    time through the dict the harness made them in: the adapter empties it,
    the engine's tree has the stacked leaves and no per-layer ones."""
    cfg = spec.load_cell(root, CELL).config
    ref = runtime.load_reference(cfg)
    flat = weights_mod.make_weights(ref.weight_shapes(cfg), 3, jnp.bfloat16)
    program = runtime.load_adapter(cfg).Program(cfg, flat)
    assert flat == {}
    params = program.engine.params
    assert "layers" not in params
    assert params["stack"]["mlp"]["up_proj"]["kernel"].shape == (3, 64, 96)
    assert program.engine.pool.state["k"].shape[0] == 12   # passes x layers
    program.close()


def _serve(cell, seed, per_client):
    """The first ``per_client`` requests of each client served to the end
    by the program."""
    cfg = cell.config
    ref = runtime.load_reference(cfg)
    dtype = jnp.dtype(cfg["serving"]["param_dtype"])
    program = runtime.load_adapter(cfg).Program(cfg, weights_mod.make_weights(
        ref.weight_shapes(cfg), seed, dtype))
    gen = serve_cell.Generator(program, cfg, cell.traffic, seed, seconds=0.0)
    program.start()
    try:
        flights = [gen._send(r, time.monotonic())
                   for client in gen.schedule for r in client[:per_client]]
        for f in flights:
            assert len(f.future.result(timeout=300)) == f.request.output_len
    finally:
        program.stop()
    program.close()
    cell.traffic["check_requests"] = len(flights)
    return flights


@pytest.mark.parametrize("seed", [SEED, 42, 77])
def test_serving_control_at_fp8_is_not_correct(root, seed):
    """A fixed set of requests served to the end by the bf16 program stays
    inside the tiny limit; the token an fp8 forward pass of the reference
    puts first lies further below the reference's best than it allows."""
    cell = spec.load_cell(root, CELL)
    flights = _serve(cell, seed, 10)
    gap, control_gap, n_tokens = serve_cell.reference_gaps(
        cell, flights, seed, "fp8")
    limit = cell.limits["served_logit_gap"]
    assert n_tokens > 400
    assert gap <= limit, (gap, control_gap)
    assert control_gap > limit, (gap, control_gap)


def test_a_token_altered_where_it_is_produced_is_not_correct(
        root, monkeypatch):
    """Every request's second token replaced by another id where the loop
    hands it out: the cell's own comparison reads it."""
    from deepspeed_tpu.inference.serving import engine as engine_mod

    real_emit = engine_mod.ServingEngine._emit

    def wrong_emit(self, req, token):
        if req.emitted == 1:
            token = (token + 1) % self.model_config.vocab_size
        return real_emit(self, req, token)

    monkeypatch.setattr(engine_mod.ServingEngine, "_emit", wrong_emit)
    line = run_mod.run_cell(root, CELL, 3, 1.5, False, require_chip=False)
    assert line["correct"] is False
    assert line["failed"] == 0
    assert line["check"]["served_logit_gap_max"] > spec.load_cell(
        root, CELL).limits["served_logit_gap"]


# -- readers on counters made by hand ---------------------------------------

# a window of 1,000 decode steps of four passes: 7.8 lanes a step that hold
# 2,080 positions between them; 30 prefill calls of 4 rows of 128, of which
# 100 rows carried the 9,000 tokens of the prompts
COUNTERS = {
    "decode_steps": 1000, "tokens_emitted": 7800, "prefill_chunks": 30,
    "prefill_chunk_rows": 100, "prefill_tokens": 9000,
    "prefill_positions_run": 30 * 512, "decode_context_tokens": 2080000,
    "pool_pages_in_use_steps": 26000, "decode_time_s": 45.0,
    "loop_passes": 4000, "loop_layer_calls": 192 * 1030,
}
STACK = 48 * 51388416                # worked out in the costs test below
HEAD = 2048 * 49152 + 2048
TOKEN = 1572864                      # bytes a token caches


def _run_data(counters, trace=None):
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    return types.SimpleNamespace(
        counters=counters, trace=trace, device_kind="TPU v5 lite",
        host={"max_slots": 8, "mean_live_kv_tokens_per_lane": 260.0},
        cell=types.SimpleNamespace(config=cfg))


def _least_bytes():
    return 4 * STACK * 2 + HEAD * 2 + 2080 * TOKEN


def _expected(name):
    return {
        "kimi_prefill_padding_share": 100.0 * (1 - 9000 / 15360),
        "nemotron_prefill_rows_mean": 100 / 30,
        "lane_occupancy": 100.0 * 7.8 / 8,
        "ouro_loop_cache_bytes_share": 100.0 * 2080 * TOKEN / _least_bytes(),
    }[name]


COUNTER_READERS = {
    "kimi_prefill_padding_share": "prefill_chunks",
    "nemotron_prefill_rows_mean": "prefill_chunks",
    "lane_occupancy": "decode_steps",
    "ouro_loop_cache_bytes_share": "decode_steps",
}


@pytest.mark.parametrize("name", sorted(COUNTER_READERS))
def test_reader_takes_its_number_from_the_counters(name):
    reader = spec.load_reader(BENCH_DIR, name)
    assert reader.read(_run_data(dict(COUNTERS))) == pytest.approx(
        _expected(name))


@pytest.mark.parametrize("name", sorted(COUNTER_READERS))
def test_reader_finds_nothing_where_nothing_was_counted(name):
    reader = spec.load_reader(BENCH_DIR, name)
    assert reader.read(_run_data({})) is None
    assert reader.read(_run_data(
        dict(COUNTERS, **{COUNTER_READERS[name]: 0}))) is None


class _Trace:
    """Programs' executions by name, as ``TraceSummary`` answers."""

    window_s = 6.0

    def __init__(self, durations):
        self.durations = durations

    def program_durations(self, name):
        return self.durations.get(name, [])

    def program_time(self, names):
        return sum(sum(self.durations.get(n, [])) for n in names)


@pytest.mark.parametrize("name", ["ouro_loop_cache_bytes_share",
                                  "ouro_decode_step_roofline"])
def test_new_readers_find_nothing_in_a_program_without_the_counter(name):
    """A program that counts no passes (any other family's, the parent's):
    the readers leave the metrics out and do not raise."""
    reader = spec.load_reader(BENCH_DIR, name)
    parent = {k: v for k, v in COUNTERS.items() if not k.startswith("loop_")}
    trace = _Trace({"jit__ouro_decode_step_jit": [0.045]})
    assert reader.read(_run_data(parent, trace=trace)) is None


TRACE_READERS = ("ouro_decode_step_ms_p50", "ouro_prefill_time_share",
                 "ouro_decode_step_roofline")


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_readers_find_nothing_without_their_programs(name):
    reader = spec.load_reader(BENCH_DIR, name)
    assert reader.read(_run_data(dict(COUNTERS), trace=None)) is None
    others = _Trace({"jit__decode_step_jit": [0.05] * 10,
                     "jit__mimo_decode_step_jit": [0.01] * 10,
                     "jit__mimo_prefill_chunk_jit": [0.04],
                     "jit__zero_slot": [0.001]})
    assert reader.read(_run_data(dict(COUNTERS), trace=others)) is None


def test_trace_readers_on_a_hand_made_trace():
    trace = _Trace({"jit__ouro_decode_step_jit": [0.045, 0.043, 0.050],
                    "jit__ouro_prefill_chunk_jit": [0.07, 0.08],
                    "jit__mimo_decode_step_jit": [0.001]})
    run = _run_data(dict(COUNTERS), trace=trace)
    assert spec.load_reader(BENCH_DIR, "ouro_decode_step_ms_p50").read(
        run) == pytest.approx(45.0)
    assert spec.load_reader(BENCH_DIR, "ouro_prefill_time_share").read(
        run) == pytest.approx(100.0 * 0.15 / 6.0)
    # 7.8 lanes, 2,080 positions: memory binds by far
    least_s = _least_bytes() / 819e9
    got = spec.load_reader(BENCH_DIR, "ouro_decode_step_roofline").read(run)
    assert got == pytest.approx(100.0 * least_s / 0.045)
    assert 0 < got < 100


# -- the byte and FLOP functions against the issue's numbers ----------------

def test_costs_against_hand_worked_numbers():
    """ISSUE 46's arithmetic, by hand."""
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    # a layer: four square projections, three matrices of 2048 x 5632, four
    # norms
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert costs_ouro.layer_params(cfg) == layer == 51388416
    assert costs_ouro.stack_params(cfg) == 48 * layer == STACK == 2466643968
    assert costs_ouro.head_params(cfg) == HEAD
    # embedding and untied head 201,326,592; final norm and gate 4,097
    assert 2 * 49152 * 2048 == 201326592
    assert costs_ouro.total_params(cfg) == (
        STACK + 201326592 + 2048 + 2049) == 2667974657          # 5.34 GB
    assert costs_ouro.cache_rows(cfg) == 192
    assert costs_ouro.cache_bytes_per_token(cfg, 2) == (
        192 * 2 * 2048 * 2) == TOKEN
    # the pool: 40 pages of 128 and the spare page 0
    assert 2 * 192 * 41 * 2048 * 128 * 2 == 8254390272          # 8.25 GB
    # the issue's step: 8 lanes of a mean live context near 260
    ctx = 8 * 260
    got = costs_ouro.decode_step_min_bytes(cfg, context_tokens=ctx,
                                           weight_bytes=2)
    assert got == 4 * STACK * 2 + HEAD * 2 + ctx * TOKEN
    assert 19.9e9 < 4 * STACK * 2 + HEAD * 2 < 20.0e9   # "19.9 GB of weights"
    assert 3.2e9 < ctx * TOKEN < 3.3e9                  # "3.3 GB", "14%"
    assert 0.13 < ctx * TOKEN / got < 0.15
    assert 28.0e-3 < got / 819e9 < 28.7e-3              # "28.7 ms"
    # the same widths run once: "7.3"
    assert 7.0e-3 < (STACK * 2 + HEAD * 2 + ctx * TOKEN / 4) / 819e9 < 7.4e-3
    flops = costs_ouro.decode_step_flops(cfg, lanes=8, context_tokens=ctx)
    assert flops == (2 * 8 * (4 * STACK + HEAD)
                     + 4 * 16 * 128 * ctx * 192)
    # memory binds by far: the step's FLOPs take a thirtieth of its bytes
    assert flops / 197e12 < 0.05 * got / 819e9
    assert costs_ouro.step_means(dict(COUNTERS)) == (7.8, 2080)
    least, step_flops, cache = costs_ouro.step_costs(cfg, dict(COUNTERS))
    assert (least, cache) == (_least_bytes(), 2080 * TOKEN)
    assert step_flops == costs_ouro.decode_step_flops(
        cfg, lanes=7.8, context_tokens=2080)
