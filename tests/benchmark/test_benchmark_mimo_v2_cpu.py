"""The MiMo-V2 cell end to end at tiny size on the CPU (traced and untraced),
with its own tiny root (``tiny_root.py`` writes tiny files for the first two
configurations only), a token altered where it is produced, the fp8 control
at that size, the readers of the new per-layer metrics on counters made by
hand, that the appended readers give a number for this configuration, and
the byte and FLOP functions of ``costs_mimo_v2.py`` against hand-worked
numbers. Every entry of ``BENCHMARK.json`` is found by NAME, never by its
place: the next configuration is appended after this one.
"""

import json
import os
import shutil
import time
import types

import jax.numpy as jnp
import pytest

from benchmarks import run as run_mod
from benchmarks.harness import costs, costs_mimo_v2, runtime, serve_cell
from benchmarks.harness import spec
from benchmarks.refs import weights as weights_mod

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO, "benchmarks")
BENCH = spec.load_benchmark(REPO)
CELL = "mimo_v25_ep16_reasoning_closed128"
CONFIG = "mimo_v25_serve_ep16"
TRAFFIC = "reasoning_closed128"
SEED = 2 ** 32 + 5                 # the driver's seeds pass 32 signed bits

# the published pattern's first seven entries at toy widths that keep the
# published inequalities: a key head (24) wider than a value head (16), 8 of
# 24 dimensions rotated, 8 query heads on 2 key-value heads in a full layer
# and 4 in a window layer, a window of 16 in pages of 16 (a ring of one
# block); 4 of 16 experts held, top-4
TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
            num_attention_heads=8, num_key_value_heads=2, head_dim=24,
            v_head_dim=16, swa_num_attention_heads=8,
            swa_num_key_value_heads=4, swa_head_dim=24, swa_v_head_dim=16,
            sliding_window=16, sliding_window_size=16,
            attention_chunk_size=16, n_routed_experts=4, num_experts=4,
            num_experts_per_tok=4, moe_intermediate_size=32)


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
    cfg["share"].update(n_routed_experts_published=16, experts_first=4)
    # 4 lanes of 256 would be 1,024 tokens: a budget under full provision
    cfg["serving"].update(max_seq_len=256, max_slots=4, max_queue=64,
                          prompt_buckets=[200], kv_page_tokens=16,
                          kv_pool_tokens=640, prefill_chunk_tokens=64)
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


# read at THIS size on the CPU (bf16 program, 818 served tokens of the first
# 10 requests of each client; the gap is the reference's 32-token mean or a
# twentieth of the token's own). Over eight seeds (4294967301, 42, 77, 3, 5,
# 11, 2147483655, 123456789012) served tokens read 0.00003 to 0.00017 and
# the fp8 control 0.0026 to 0.0061: the two do not overlap, and the limit
# stands a factor of three and more from either
TINY_LIMIT = 0.0008


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny_mimo_benchmark"))


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    real = costs.peaks_for
    monkeypatch.setattr(
        costs, "peaks_for",
        lambda kind, table_path=None: real(
            "TPU v5 lite" if kind == "cpu" else kind, table_path))


NEW_METRICS = ("mimo_decode_step_ms_p50", "mimo_prefill_time_share",
               "mimo_decode_step_roofline", "mimo_attn_bytes_share")
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
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "num_experts", "vocab_size"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    metrics = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in APPENDED:
        assert CELL in metrics[name]["workloads"]
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "serve_tokens_per_s"
    layers = {metrics[n]["layer"] for n in NEW_METRICS}
    assert len(layers) == 1 and "serving/families/mimo_v2.py" in layers.pop()
    assert metrics["mimo_decode_step_roofline"]["unit"] == "%"
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(json.dumps(BENCH, indent=1)) < 64 * 1024


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's ``config`` under the same name and value
    but the three that count what is held, and the file states the
    published counts, the deployment and what is not served beside them."""
    published = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "MiMo-V2.5":
                published = row
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    assert cfg["source"] == published["source_url"]
    assert set(published["config"]) <= set(cfg)
    differ = sorted(k for k, v in published["config"].items()
                    if cfg[k] != v)
    assert differ == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert sorted(cfg["reduced"]) == sorted(differ + ["num_experts"])
    assert cfg["num_experts"] == cfg["n_routed_experts"] == 16
    share = cfg["share"]
    assert share["num_hidden_layers_published"] == 48
    assert share["n_routed_experts_published"] == 256
    assert share["vocab_size_published"] == 152576
    assert share["chips_sharing_a_layer"] == 16
    assert (share["experts_first"], share["vocab_first"]) == (0, 0)
    assert "towers" in share["not_served"] or "tower" in share["not_served"]
    assert "multi-token-prediction" in share["not_served"]
    # the guide's floors: the leading dense layer, a whole period of six
    # expert layers, 16 >= 8 experts, an eighth of the vocabulary
    L = cfg["num_hidden_layers"]
    assert cfg["hybrid_layer_pattern"][:L] == [0, 1, 1, 1, 1, 0, 1]
    assert cfg["moe_layer_freq"][:L] == [0, 1, 1, 1, 1, 1, 1]
    assert cfg["vocab_size"] * 8 >= share["vocab_size_published"]
    assert cfg["vocab_size"] == 149 * 128
    for key in ("value_scale", "sink", "window", "attention_chunk_size",
                "rope", "attention_projection_layout", "qk_norm", "router",
                "weights", "decoding"):
        assert key in cfg["assumed"]
    assert (cfg["kind"], cfg["adapter"], cfg["reference"]) == (
        "serve", "mimo_v2_serve", "mimo_v2_ref")


def test_the_traffic_file_is_the_issues_letter_for_letter():
    t = _load(f"benchmarks/traffic/{TRAFFIC}.json")
    assert (t["loop"], t["clients"], t["requests_per_client"]) == (
        "closed", 128, 24)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                  "sigma": 1.2, "min": 32, "max": 8192}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 1024,
                                  "sigma": 0.7, "min": 128, "max": 3072}
    assert (t["max_total_tokens"], t["schedule_seed"]) == (12288, 20261003)
    assert (t["warm_seconds"], t["trace_seconds"],
            t["check_requests"]) == (10, 6, 4)
    serving = _load(f"benchmarks/configs/{CONFIG}.json")["serving"]
    assert serving["max_slots"] == t["clients"]           # one a lane
    assert serving["max_seq_len"] == t["max_total_tokens"]
    assert serving["prompt_buckets"] == [t["prompt_tokens"]["max"]]
    # the page budget is under full provision: 42.7 lanes' worth of 128
    assert serving["kv_pool_tokens"] == 4096 * serving["kv_page_tokens"]
    assert serving["kv_pool_tokens"] < (serving["max_slots"]
                                        * serving["max_seq_len"])
    # one page is one window: a ring of one block
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    assert serving["kv_page_tokens"] == cfg["sliding_window"] == 128


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
        for name in ("lane_occupancy", "moe_experts_touched_share",
                     "moe_load_max_over_mean", "kimi_prefill_padding_share",
                     "nemotron_prefill_rows_mean", "mimo_attn_bytes_share"):
            assert line["metrics"][name]["value"] > 0, name
        share = line["metrics"]["moe_experts_touched_share"]["value"]
        assert 100.0 / 4 <= share <= 100.0
        assert line["metrics"]["kimi_prefill_padding_share"]["value"] < 100.0
        assert 1.0 <= line["metrics"]["nemotron_prefill_rows_mean"][
            "value"] <= 4.0
        assert line["metrics"]["mimo_attn_bytes_share"]["value"] < 100.0
    json.dumps(line)


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

# a window of 100 decode steps over 6 expert layers of 16 held experts: 120
# lanes a step that hold 210,000 positions between them in each full layer
# and 72,000 ring positions over the five window layers (120 x 120), 15.5 of
# 16 experts touched a layer a step (9,300), 60 picks a layer a step land
# here (36,000), the busiest expert 9 tokens a layer a step; 40 prefill calls
# of 16 rows of 128, of which 600 rows carried the 75,000 tokens of the prompts
COUNTERS = {
    "decode_steps": 100, "tokens_emitted": 12000, "moe_layer_steps": 600,
    "moe_picks_here": 36000, "moe_experts_touched": 9300,
    "moe_expert_load_max": 5400, "prefill_chunks": 40,
    "prefill_chunk_rows": 600, "prefill_tokens": 75000,
    "prefill_positions_run": 40 * 2048,
    "decode_context_tokens": 21000000, "decode_ring_positions": 7200000,
    "pool_pages_in_use_steps": 250000, "decode_time_s": 2.1,
}


def _run_data(counters, trace=None):
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    return types.SimpleNamespace(
        counters=counters, trace=trace, device_kind="TPU v5 lite",
        host={"max_slots": 128, "mean_live_kv_tokens_per_lane": 1750.0},
        cell=types.SimpleNamespace(config=cfg))


NON_EXPERT = 935917376             # worked out in the costs test below
EXPERT = 3 * 4096 * 2048


def _attn_bytes():
    # two full layers x 210,000 positions x 2,560 B; 72,000 ring positions
    # x 5,120 B
    return 2 * 210000 * 2560 + 72000 * 5120


def _least_bytes():
    return 2 * NON_EXPERT + 93 * EXPERT * 2 + _attn_bytes()


def _expected(name):
    return {
        "moe_experts_touched_share": 100.0 * 9300 / (600 * 16),
        "moe_load_max_over_mean": 5400 * 16 / 36000,
        "kimi_prefill_padding_share": 100.0 * (1 - 75000 / 81920),
        "nemotron_prefill_rows_mean": 15.0,
        "lane_occupancy": 100.0 * 120 / 128,
        "mimo_attn_bytes_share": 100.0 * _attn_bytes() / _least_bytes(),
    }[name]


COUNTER_READERS = {
    "moe_experts_touched_share": "moe_layer_steps",
    "moe_load_max_over_mean": "moe_picks_here",
    "kimi_prefill_padding_share": "prefill_chunks",
    "nemotron_prefill_rows_mean": "prefill_chunks",
    "lane_occupancy": "decode_steps",
    "mimo_attn_bytes_share": "decode_steps",
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

    window_s = 2.0

    def __init__(self, durations):
        self.durations = durations

    def program_durations(self, name):
        return self.durations.get(name, [])

    def program_time(self, names):
        return sum(sum(self.durations.get(n, [])) for n in names)


@pytest.mark.parametrize("name", ["mimo_attn_bytes_share",
                                  "mimo_decode_step_roofline"])
def test_new_readers_find_nothing_in_a_program_without_the_counter(name):
    """The parent counts no ring positions: a traced run of a program
    without ``decode_ring_positions`` leaves the metrics out and does not
    raise."""
    reader = spec.load_reader(BENCH_DIR, name)
    parent = {k: v for k, v in COUNTERS.items()
              if k != "decode_ring_positions"}
    trace = _Trace({"jit__mimo_decode_step_jit": [0.016]})
    assert reader.read(_run_data(parent, trace=trace)) is None


TRACE_READERS = ("mimo_decode_step_ms_p50", "mimo_prefill_time_share",
                 "mimo_decode_step_roofline")


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_readers_find_nothing_without_their_programs(name):
    reader = spec.load_reader(BENCH_DIR, name)
    assert reader.read(_run_data(dict(COUNTERS), trace=None)) is None
    others = _Trace({"jit__decode_step_jit": [0.05] * 10,
                     "jit__laguna_decode_step_jit": [0.01] * 10,
                     "jit__laguna_prefill_chunk_jit": [0.04],
                     "jit__zero_slot": [0.001]})
    assert reader.read(_run_data(dict(COUNTERS), trace=others)) is None


def test_trace_readers_on_a_hand_made_trace():
    trace = _Trace({"jit__mimo_decode_step_jit": [0.018, 0.016, 0.022],
                    "jit__mimo_prefill_chunk_jit": [0.05, 0.05],
                    "jit__laguna_decode_step_jit": [0.001]})
    run = _run_data(dict(COUNTERS), trace=trace)
    assert spec.load_reader(BENCH_DIR, "mimo_decode_step_ms_p50").read(
        run) == pytest.approx(18.0)
    assert spec.load_reader(BENCH_DIR, "mimo_prefill_time_share").read(
        run) == pytest.approx(100.0 * 0.1 / 2.0)
    # 120 lanes, 93 expert reads a step, 210,000 positions: memory binds
    least_s = _least_bytes() / 819e9
    got = spec.load_reader(BENCH_DIR, "mimo_decode_step_roofline").read(run)
    assert got == pytest.approx(100.0 * least_s / 0.018)
    assert 0 < got < 100


# -- the byte and FLOP functions against hand-worked numbers ----------------

def test_costs_against_hand_worked_numbers():
    """The issue's table of the cut, and its step."""
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    # a full layer's attention: q 4096 x 12288, k 4096 x 768, v 4096 x 512,
    # o 8192 x 4096; a window layer's: k 4096 x 1536, v 4096 x 1024, 64 sinks
    full = 4096 * 12288 + 4096 * 768 + 4096 * 512 + 8192 * 4096
    window = 4096 * 12288 + 4096 * 1536 + 4096 * 1024 + 8192 * 4096 + 64
    assert costs_mimo_v2.attention_params(cfg, False) == full == 89128960
    assert costs_mimo_v2.attention_params(cfg, True) == window == 94371904
    assert costs_mimo_v2.expert_params(cfg) == EXPERT == 25165824
    dense = 3 * 4096 * 16384
    assert costs_mimo_v2.dense_mlp_params(cfg) == dense == 201326592
    # the router at its published 256 columns, and its bias
    router = 4096 * 256 + 256
    assert costs_mimo_v2.router_params(cfg) == router == 1048832
    # layer 0: full + dense; 1-4 and 6: window + router; 5: full + router;
    # two norms a layer, the final norm, the 19,072 held rows of the head
    non_expert = ((full + dense) + 5 * (window + router) + (full + router)
                  + 14 * 4096 + 4096 + 4096 * 19072)
    assert costs_mimo_v2.non_expert_params(cfg) == non_expert == NON_EXPERT
    # the issue's table, without the norms: layer 0 is 290.5 M, a window
    # expert layer 498.1 M and layer 5 492.8 M with their 16 held experts
    assert full + dense == 290455552
    assert window + router + 16 * EXPERT == 498073920
    assert full + router + 16 * EXPERT == 492830976
    total = non_expert + 4096 * 19072 + 6 * 16 * EXPERT
    assert costs_mimo_v2.total_params(cfg) == total == 3429955392  # 6.86 GB
    assert 2 * costs_mimo_v2.kv_row_values(cfg, False) == 2560    # bytes
    assert 2 * costs_mimo_v2.kv_row_values(cfg, True) == 5120
    # the issue's step: 128 lanes that hold 217,000 positions, full rings,
    # every held expert of the six layers touched
    ctx, ring = 217000, 5 * 128 * 128
    got = costs_mimo_v2.decode_step_min_bytes(
        cfg, experts_touched=96, context_tokens=ctx, ring_positions=ring,
        weight_bytes=2)
    pages = 2 * ctx * 2560
    rings = ring * 5120
    assert costs_mimo_v2.page_bytes(cfg, context_tokens=ctx,
                                    kv_bytes=2) == pages == 1111040000
    assert costs_mimo_v2.ring_bytes(cfg, ring_positions=ring,
                                    kv_bytes=2) == rings == 419430400
    assert got == 2 * non_expert + 96 * EXPERT * 2 + pages + rings
    assert 8.2e9 < got < 8.3e9                    # "8.2 GB, 10 ms"
    assert 1.85e9 < 2 * non_expert < 1.9e9        # "1.9 GB of weights"
    assert 4.8e9 < 96 * EXPERT * 2 < 4.9e9        # "4.8 GB of experts"
    flops = costs_mimo_v2.decode_step_flops(
        cfg, lanes=128, picks=6 * 64, context_tokens=ctx, ring_positions=ring)
    assert flops == (2 * 128 * non_expert + 2 * 384 * EXPERT
                     + 2 * 64 * 320 * (2 * ctx + ring))
    # memory binds by far: the step's FLOPs take under a fifth of its bytes
    assert flops / 197e12 < 0.2 * got / 819e9
    assert costs_mimo_v2.step_means(dict(COUNTERS)) == (
        120, 210000, 72000, 93, 360)
    least, step_flops, attn = costs_mimo_v2.step_costs(cfg, dict(COUNTERS))
    assert (least, attn) == (_least_bytes(), _attn_bytes())
    assert step_flops == costs_mimo_v2.decode_step_flops(
        cfg, lanes=120, picks=360, context_tokens=210000,
        ring_positions=72000)
