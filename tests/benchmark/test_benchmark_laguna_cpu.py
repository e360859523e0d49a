"""The Laguna cell end to end at tiny size on the CPU (traced and untraced),
with its own tiny root (``tiny_root.py`` writes tiny files for the first two
configurations only), a token altered where it is produced, the fp8 control
at that size, the readers of the new per-layer metrics on counters made by
hand, that the appended readers give a number for this configuration, and
the byte and FLOP functions of ``costs_laguna.py`` against hand-worked
numbers.
"""

import json
import os
import shutil
import time
import types

import jax.numpy as jnp
import pytest

from benchmarks import run as run_mod
from benchmarks.harness import costs, costs_laguna, runtime, serve_cell
from benchmarks.harness import spec
from benchmarks.refs import weights as weights_mod

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO, "benchmarks")
BENCH = spec.load_benchmark(REPO)
CELL = "laguna_xs2_pp8_mixedlen_closed64"
CONFIG = "laguna_xs2_33b_serve_pp8"
TRAFFIC = "mixedlen_closed64"
SEED = 2 ** 32 + 5                 # the driver's seeds pass 32 signed bits

# the published layer lists' first five entries at toy widths: 4 query heads
# in a full layer and 6 in a window layer on 2 key-value heads of 16, a
# window of 32 in pages of 16, YaRN with an original length of 64; 16
# experts top-4 and a shared one
TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, sliding_window=32,
            num_attention_heads_per_layer=[4, 6, 6, 6] * 10)
TINY_ROPE = {"factor": 4, "original_max_position_embeddings": 64,
             "beta_fast": 8}


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
    cfg["rope_parameters"]["full_attention"].update(TINY_ROPE)
    # 4 lanes of 256 would be 1,024 tokens: a budget under full provision
    cfg["serving"].update(max_seq_len=256, max_slots=4, max_queue=64,
                          prompt_buckets=[200], kv_page_tokens=16,
                          kv_pool_tokens=640, prefill_chunk_tokens=64)
    cfg["check"]["limits"] = {"served_logit_gap": TINY_LIMIT}
    with open(os.path.join(bdir, "configs", CONFIG + ".json"), "w") as f:
        json.dump(cfg, f)
    t = _load(f"benchmarks/traffic/{TRAFFIC}.json")
    t.update(clients=4, requests_per_client=200,
             prompt_tokens={"dist": "lognormal", "median": 40, "sigma": 0.9,
                            "min": 4, "max": 190},
             output_tokens={"dist": "lognormal", "median": 12, "sigma": 0.6,
                            "min": 4, "max": 40},
             max_total_tokens=256, warm_seconds=0.5, trace_seconds=0.3,
             check_requests=4)
    with open(os.path.join(bdir, "traffic", TRAFFIC + ".json"), "w") as f:
        json.dump(t, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(BENCH, f)
    return root


# read at THIS size on the CPU (bf16 program, 637 served tokens of the first
# 10 requests of each client; the gap is the reference's 32-token mean or a
# twentieth of the token's own, and requests here are 4-40 tokens long).
# Seeds 4294967301, 42 and 77: served tokens at most 0.0007, the fp8 control
# at least 0.0021. Over 11 seeds sound reads 0.0001 to 0.0013 and fp8 0.0015
# to 0.0031: they do not overlap here (Nemotron-H's do at this size), but
# seeds 2147483655 and 3 stand within a tenth of the limit on one side or
# the other, so the tests keep to the three above and the timed run to seed
# 3 (sound 0.0001)
TINY_LIMIT = 0.0014


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny_laguna_benchmark"))


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    real = costs.peaks_for
    monkeypatch.setattr(
        costs, "peaks_for",
        lambda kind, table_path=None: real(
            "TPU v5 lite" if kind == "cpu" else kind, table_path))


NEW_METRICS = ("laguna_decode_step_ms_p50", "laguna_prefill_time_share",
               "laguna_decode_step_roofline", "laguna_full_kv_bytes_share",
               "laguna_pool_pages_share", "laguna_decode_call_ms_mean")
APPENDED = ("serve_tokens_per_s", "lane_occupancy", "serve_device_idle_share",
            "moe_experts_touched_share", "moe_load_max_over_mean",
            "kimi_prefill_padding_share", "nemotron_prefill_rows_mean")


def test_the_benchmark_file_holds_the_new_entries():
    """By name, not by place: a later cell is appended after this one."""
    assert spec.validate(BENCH) == []
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": CONFIG, "traffic": TRAFFIC,
                    "chips": 1, "why": cell["why"]}
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    metrics = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in APPENDED:
        assert CELL in metrics[name]["workloads"]
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "serve_tokens_per_s"
    layers = {metrics[n]["layer"] for n in NEW_METRICS}
    assert len(layers) == 1 and "serving/families/laguna.py" in layers.pop()
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's ``config`` under the same name and value
    but ``num_hidden_layers``, and the file states the published depth and
    the deployment beside it."""
    published = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Laguna-XS.2":
                published = row
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    assert cfg["source"] == published["source_url"]
    differ = sorted(k for k, v in published["config"].items()
                    if cfg.get(k) != v)
    assert differ == cfg["reduced"] == ["num_hidden_layers"]
    share = cfg["share"]
    assert share["num_hidden_layers_published"] == 40
    assert share["chips_sharing_a_layer"] == 1
    # the guide's floors: the leading dense layer, a whole period and four
    # expert layers; every expert and every row of the vocabulary
    L = cfg["num_hidden_layers"]
    assert cfg["layer_types"][:L] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert cfg["mlp_layer_types"][:L] == ["dense"] + ["sparse"] * 4
    assert cfg["num_attention_heads_per_layer"][:L] == [48, 64, 64, 64, 48]
    for key in ("gating", "router", "qk_norm", "window", "weights",
                "decoding"):
        assert key in cfg["assumed"]
    assert (cfg["kind"], cfg["adapter"], cfg["reference"]) == (
        "serve", "laguna_serve", "laguna_ref")


def test_the_traffic_file_is_the_issues_letter_for_letter():
    t = _load(f"benchmarks/traffic/{TRAFFIC}.json")
    assert (t["loop"], t["clients"], t["requests_per_client"]) == (
        "closed", 64, 64)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 2048,
                                  "sigma": 1.4, "min": 64, "max": 14336}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 512,
                                  "sigma": 0.8, "min": 32, "max": 2048}
    assert (t["max_total_tokens"], t["schedule_seed"]) == (16384, 20261001)
    assert (t["warm_seconds"], t["trace_seconds"],
            t["check_requests"]) == (10, 6, 4)
    serving = _load(f"benchmarks/configs/{CONFIG}.json")["serving"]
    assert serving["max_slots"] == t["clients"]           # one a lane
    assert serving["max_seq_len"] == t["max_total_tokens"]
    assert serving["prompt_buckets"] == [t["prompt_tokens"]["max"]]
    # the page budget is under full provision: 28 lanes' worth of 64
    assert serving["kv_pool_tokens"] == 3584 * serving["kv_page_tokens"]
    assert serving["kv_pool_tokens"] < (serving["max_slots"]
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
        # readers give a number for this configuration, and so do the new
        for name in ("lane_occupancy", "moe_experts_touched_share",
                     "moe_load_max_over_mean", "kimi_prefill_padding_share",
                     "nemotron_prefill_rows_mean",
                     "laguna_full_kv_bytes_share",
                     "laguna_pool_pages_share", "laguna_decode_call_ms_mean"):
            assert line["metrics"][name]["value"] > 0, name
        share = line["metrics"]["moe_experts_touched_share"]["value"]
        assert 100.0 / 16 <= share <= 100.0
        assert line["metrics"]["kimi_prefill_padding_share"]["value"] < 100.0
        assert 1.0 <= line["metrics"]["nemotron_prefill_rows_mean"][
            "value"] <= 4.0
        assert line["metrics"]["laguna_full_kv_bytes_share"]["value"] < 100.0
        assert line["metrics"]["laguna_pool_pages_share"]["value"] <= 100.0
    json.dumps(line)


def _serve(cell, seed, per_client, alter=None):
    """The first ``per_client`` requests of each client served to the end
    by the program; ``alter(flights)`` may change what came back."""
    cfg = cell.config
    ref = runtime.load_reference(cfg)
    program = runtime.load_adapter(cfg).Program(cfg, weights_mod.make_weights(
        ref.weight_shapes(cfg), seed, jnp.dtype(cfg["serving"]["param_dtype"])))
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

# a window of 100 decode steps over 4 expert layers of 256 experts: 60 lanes
# a step that hold 264,000 positions between them, 221 experts touched a
# layer a step (88,400), every one of the 192,000 picks lands here, the
# busiest expert 7 tokens a layer a step; 2,500 of the pool's 3,584 pages in
# use on average; 40 prefill calls of 16 rows of 128, of which 600 rows
# carried the 75,000 tokens of the prompts; 3 admission passes found no pages;
# 2.6 s inside the loop's decode calls
COUNTERS = {
    "decode_steps": 100, "tokens_emitted": 6000, "moe_layer_steps": 400,
    "moe_picks_here": 192000, "moe_experts_touched": 88400,
    "moe_expert_load_max": 2800, "prefill_chunks": 40,
    "prefill_chunk_rows": 600, "prefill_tokens": 75000,
    "prefill_positions_run": 40 * 2048,
    "decode_context_tokens": 26400000, "pool_pages_in_use_steps": 250000,
    "page_waits": 3, "decode_time_s": 2.6,
}


def _run_data(counters, trace=None):
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    return types.SimpleNamespace(
        counters=counters, trace=trace, device_kind="TPU v5 lite",
        host={"max_slots": 64, "mean_live_kv_tokens_per_lane": 4400.0},
        cell=types.SimpleNamespace(config=cfg))


def _least_bytes(cfg):
    return costs_laguna.decode_step_min_bytes(
        cfg, lanes=60, experts_touched=884, context_tokens=264000,
        weight_bytes=2)


def _expected(name):
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    return {
        "moe_experts_touched_share": 100.0 * 88400 / (400 * 256),
        "moe_load_max_over_mean": 2800 * 256 / 192000,
        "kimi_prefill_padding_share": 100.0 * (1 - 75000 / 81920),
        "nemotron_prefill_rows_mean": 15.0,
        "lane_occupancy": 100.0 * 60 / 64,
        # two full layers x 264,000 positions x 2,048 values x 2 bytes
        "laguna_full_kv_bytes_share": 100.0 * 2 * 264000 * 4096
        / _least_bytes(cfg),
        "laguna_pool_pages_share": 100.0 * 2500 / 3584,
        "laguna_decode_call_ms_mean": 26.0,
    }[name]


COUNTER_READERS = {
    "moe_experts_touched_share": "moe_layer_steps",
    "moe_load_max_over_mean": "moe_picks_here",
    "kimi_prefill_padding_share": "prefill_chunks",
    "nemotron_prefill_rows_mean": "prefill_chunks",
    "lane_occupancy": "decode_steps",
    "laguna_full_kv_bytes_share": "decode_steps",
    "laguna_pool_pages_share": "decode_steps",
    "laguna_decode_call_ms_mean": "decode_steps",
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


@pytest.mark.parametrize("name", ["laguna_full_kv_bytes_share",
                                  "laguna_pool_pages_share",
                                  "laguna_decode_step_roofline"])
def test_new_readers_find_nothing_in_a_program_without_the_counters(name):
    """The parent counts neither contexts nor pages a step: its traced runs
    leave the metrics out and do not raise (and a configuration without a
    page budget has no share of one)."""
    reader = spec.load_reader(BENCH_DIR, name)
    parent = {k: v for k, v in COUNTERS.items()
              if k not in ("decode_context_tokens", "pool_pages_in_use_steps",
                           "page_waits")}
    trace = _Trace({"jit__laguna_decode_step_jit": [0.016]})
    assert reader.read(_run_data(parent, trace=trace)) is None
    if name == "laguna_pool_pages_share":
        run = _run_data(dict(COUNTERS))
        del run.cell.config["serving"]["kv_pool_tokens"]
        assert reader.read(run) is None


class _Trace:
    """Programs' executions by name, as ``TraceSummary`` answers."""

    window_s = 2.0

    def __init__(self, durations):
        self.durations = durations

    def program_durations(self, name):
        return self.durations.get(name, [])

    def program_time(self, names):
        return sum(sum(self.durations.get(n, [])) for n in names)


TRACE_READERS = ("laguna_decode_step_ms_p50", "laguna_prefill_time_share",
                 "laguna_decode_step_roofline")


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_readers_find_nothing_without_their_programs(name):
    reader = spec.load_reader(BENCH_DIR, name)
    assert reader.read(_run_data(dict(COUNTERS), trace=None)) is None
    others = _Trace({"jit__decode_step_jit": [0.05] * 10,
                     "jit__nemotron_decode_step_jit": [0.01] * 10,
                     "jit__nemotron_prefill_chunk_jit": [0.04],
                     "jit__zero_slot": [0.001]})
    assert reader.read(_run_data(dict(COUNTERS), trace=others)) is None


def test_trace_readers_on_a_hand_made_trace():
    trace = _Trace({"jit__laguna_decode_step_jit": [0.018, 0.016, 0.022],
                    "jit__laguna_prefill_chunk_jit": [0.05, 0.05],
                    "jit__zero_slot": [0.001]})
    run = _run_data(dict(COUNTERS), trace=trace)
    assert spec.load_reader(BENCH_DIR, "laguna_decode_step_ms_p50").read(
        run) == pytest.approx(18.0)
    assert spec.load_reader(BENCH_DIR, "laguna_prefill_time_share").read(
        run) == pytest.approx(100.0 * 0.1 / 2.0)
    # 60 lanes, 884 expert reads a step, 264,000 positions: memory binds
    least_s = _least_bytes(run.cell.config) / 819e9
    got = spec.load_reader(BENCH_DIR, "laguna_decode_step_roofline").read(run)
    assert got == pytest.approx(100.0 * least_s / 0.018)
    assert 0 < got < 100


# -- the byte and FLOP functions against hand-worked numbers ----------------

def test_costs_against_hand_worked_numbers():
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    # a full layer's attention: q and o 2048 x 6144 each, k and v 2048 x
    # 1024 each, the gate 2048 x 48; a window layer's at 64 heads
    full = 2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48
    window = 2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64
    assert costs_laguna.attention_params(cfg, 48) == full == 29458432
    assert costs_laguna.attention_params(cfg, 64) == window == 37879808
    expert = 3 * 2048 * 512
    assert costs_laguna.expert_params(cfg) == expert == 3145728
    assert costs_laguna.shared_expert_params(cfg) == expert
    dense = 3 * 2048 * 8192
    assert costs_laguna.dense_mlp_params(cfg) == dense == 50331648
    # layer 0: full + dense; 1-3: window + router 2048 x 256 + shared; 4:
    # full + router + shared; two norms a layer, the final norm, the head
    router = 2048 * 256
    non_expert = ((full + dense) + 3 * (window + router + expert)
                  + (full + router + expert) + 10 * 2048 + 2048
                  + 2048 * 100352)
    assert costs_laguna.non_expert_params(cfg) == non_expert == 443111424
    # the issue's table: layer 0 is 79,790,080 and a window expert layer
    # 41,549,824 outside its experts, without their norms
    assert full + dense == 79790080
    assert window + router + expert == 41549824
    assert full + router + expert == 33128448
    # all of it: the embedding and 4 x 256 experts too (7.74 GB in bf16)
    total = non_expert + 2048 * 100352 + 4 * 256 * expert
    assert costs_laguna.total_params(cfg) == total == 3869857792
    assert costs_laguna.kv_row_values(cfg) == 2048
    # the issue's step: 64 lanes, 221 of 256 experts a layer touched, a
    # live context of 4,400 tokens a lane
    ctx = 64 * 4400
    got = costs_laguna.decode_step_min_bytes(
        cfg, lanes=64, experts_touched=4 * 221, context_tokens=ctx,
        weight_bytes=2)
    kv = 2 * ctx * 2048 * 2                       # two full layers
    rings = 3 * 64 * 512 * 2048 * 2               # three window layers
    assert costs_laguna.full_kv_bytes(cfg, context_tokens=ctx,
                                      kv_bytes=2) == kv == 2306867200
    assert costs_laguna.ring_bytes(cfg, lanes=64, kv_bytes=2) == (
        rings) == 402653184
    assert got == 2 * non_expert + 884 * expert * 2 + kv + rings
    assert 9.1e9 < got < 9.3e9                    # "9.2 GB, 11.2 ms"
    assert 0.24 < kv / got < 0.26                 # "a quarter"
    flops = costs_laguna.decode_step_flops(cfg, lanes=64, picks=4 * 512,
                                           context_tokens=ctx)
    assert flops == (2 * 64 * non_expert + 2 * 2048 * expert
                     + 4 * 128 * (2 * 48 * ctx + 3 * 64 * 64 * 512))
    # memory binds by far: the step's FLOPs take under a tenth of its bytes
    assert flops / 197e12 < 0.1 * got / 819e9
    assert costs_laguna.step_means(dict(COUNTERS)) == (60, 264000, 884, 1920)
