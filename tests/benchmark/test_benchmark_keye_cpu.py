"""The Keye-VL cell end to end at tiny size on the CPU (traced and untraced),
with its own tiny root (``tiny_root.py`` writes tiny files for the first two
configurations only), the fp8 control and a wrong selection at that size,
the readers of the new per-layer metrics on counters made by hand, that the
appended readers give a number for this configuration, and the byte and
FLOP functions of ``costs_keye.py`` against hand-worked numbers. Every entry
of ``BENCHMARK.json`` is found by NAME, never by its place: the next
configuration is appended after this one.
"""

import json
import os
import shutil
import time
import types

import jax.numpy as jnp
import pytest

from benchmarks import run as run_mod
from benchmarks.harness import costs, costs_keye, runtime, serve_cell
from benchmarks.harness import spec
from benchmarks.refs import keye_ref
from benchmarks.refs import weights as weights_mod

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO, "benchmarks")
BENCH = spec.load_benchmark(REPO)
CELL = "keye_vl2_ep8_longdoc_closed64"
CONFIG = "keye_vl2_30b_serve_ep8"
TRAFFIC = "longdoc_closed64"
SEED = 2 ** 32 + 5                 # the driver's seeds pass 32 signed bits

# toy widths that keep the published shape: 8 query heads on 2 key-value
# heads of 16, an indexer of 4 heads of 8 on one key head with topk 24 (so
# that prompts of a few pages of 16 already prune), 4 of 16 experts held,
# top-4
TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
            num_hidden_layers=3, num_attention_heads=8,
            num_key_value_heads=2, head_dim=16, num_experts=4,
            num_local_experts=4, num_experts_per_tok=4,
            moe_intermediate_size=32, max_window_layers=3)
TINY_SA = dict(indexer_head_dim=8, indexer_num_heads=4, topk=24)
TINY_SECTIONS = [2, 2, 4]


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
    cfg["sa_config"].update(TINY_SA)
    cfg["rope_scaling"]["mrope_section"] = TINY_SECTIONS
    cfg["share"].update(num_experts_published=16, experts_first=4)
    # 4 lanes of 256 would be 1,024 tokens: a budget under full provision
    cfg["serving"].update(max_seq_len=256, max_slots=4, max_queue=64,
                          prompt_buckets=[200], kv_page_tokens=16,
                          kv_pool_tokens=768, prefill_chunk_tokens=64)
    cfg["check"]["limits"] = {"served_logit_gap": TINY_LIMIT}
    with open(os.path.join(bdir, "configs", CONFIG + ".json"), "w") as f:
        json.dump(cfg, f)
    t = _load(f"benchmarks/traffic/{TRAFFIC}.json")
    # prompts of two to eight times the tiny topk, as the cell's are of one
    # to seven times the published one
    t.update(clients=4, requests_per_client=200,
             prompt_tokens={"dist": "lognormal", "median": 72, "sigma": 0.6,
                            "min": 24, "max": 190},
             output_tokens={"dist": "lognormal", "median": 16, "sigma": 0.6,
                            "min": 4, "max": 40},
             max_total_tokens=240, warm_seconds=0.5, trace_seconds=0.3,
             check_requests=4)
    with open(os.path.join(bdir, "traffic", TRAFFIC + ".json"), "w") as f:
        json.dump(t, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(BENCH, f)
    return root


# read at THIS size on the CPU (bf16 program, 767 served tokens of the first
# 8 requests of each client; the gap is the reference's 32-token mean or a
# twentieth of the token's own). Over five seeds (4294967301, 42, 77, 3, 5)
# served tokens read 0.0062 to 0.0112 and the fp8 control 0.0309 to 0.0763.
# Both read higher than the other families' tiny cells do: with a topk of
# 24 one key selected otherwise is a twenty-fourth of a query's attention,
# bfloat16 rounding of the index scores swaps a key at the edge of many
# selections and fp8 rounding swaps several. The two do not overlap, and
# the limit stands a factor of 1.6 and more from either
TINY_LIMIT = 0.018


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny_keye_benchmark"))


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    real = costs.peaks_for
    monkeypatch.setattr(
        costs, "peaks_for",
        lambda kind, table_path=None: real(
            "TPU v5 lite" if kind == "cpu" else kind, table_path))


NEW_METRICS = ("keye_decode_step_ms_p50", "keye_prefill_time_share",
               "keye_decode_step_roofline", "keye_dsa_attended_share")
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
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "num_local_experts", "vocab_size"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    metrics = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in APPENDED:
        assert CELL in metrics[name]["workloads"]
    for name in NEW_METRICS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "serve_tokens_per_s"
    layers = {metrics[n]["layer"] for n in NEW_METRICS}
    assert len(layers) == 1 and "serving/families/keye.py" in layers.pop()
    assert metrics["keye_decode_step_roofline"]["unit"] == "%"
    # the read-back metrics' lists stay the five cells' that another test
    # file holds them to
    assert CELL not in metrics["serve_late_read_share"]["workloads"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(json.dumps(BENCH, indent=1)) < 64 * 1024


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's ``config`` under the same name and value
    but the four that count what is held, and the file states the published
    counts, the deployment and what is not served beside them."""
    published = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Keye-VL-2.0-30B-A3B":
                published = row
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    assert cfg["source"] == published["source_url"]
    assert set(published["config"]) <= set(cfg)
    differ = sorted(k for k, v in published["config"].items()
                    if cfg[k] != v)
    assert differ == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "num_local_experts",
        "vocab_size"]
    assert cfg["num_experts"] == cfg["num_local_experts"] == 16
    assert cfg["sa_config"] == published["config"]["sa_config"]
    assert cfg["rope_scaling"] == published["config"]["rope_scaling"]
    share = cfg["share"]
    assert share["num_hidden_layers_published"] == 48
    assert share["num_experts_published"] == 128
    assert share["vocab_size_published"] == 151936
    assert share["chips_sharing_a_layer"] == 8
    assert (share["experts_first"], share["vocab_first"]) == (0, 0)
    assert "vision tower" in share["not_served"]
    # the guide's floors: every layer is alike, six of them (four at
    # least), 16 >= 8 experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == 6 and cfg["mlp_only_layers"] == []
    assert cfg["vocab_size"] * 8 >= share["vocab_size_published"]
    assert cfg["vocab_size"] == 149 * 128
    for key in ("qk_norm", "indexer_input", "indexer_key_norm",
                "indexer_rope", "indexer_scale", "selection",
                "q_chunk_size_kv_chunk_size", "rope", "router", "weights",
                "cache", "decoding"):
        assert key in cfg["assumed"]
    assert "lower position first" in cfg["assumed"]["selection"]
    assert (cfg["kind"], cfg["adapter"], cfg["reference"]) == (
        "serve", "keye_serve", "keye_ref")


def test_the_traffic_file_is_the_issues_letter_for_letter():
    t = _load(f"benchmarks/traffic/{TRAFFIC}.json")
    assert (t["loop"], t["clients"], t["requests_per_client"]) == (
        "closed", 64, 32)
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 6144,
                                  "sigma": 0.6, "min": 2048, "max": 14336}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 320,
                                  "sigma": 0.7, "min": 64, "max": 1024}
    assert t["max_total_tokens"] == 15360
    assert (t["warm_seconds"], t["trace_seconds"],
            t["check_requests"]) == (10, 6, 4)
    seeds = {_load(f"benchmarks/traffic/{f}")["schedule_seed"]
             for f in os.listdir(os.path.join(BENCH_DIR, "traffic"))
             if f != TRAFFIC + ".json"
             and "schedule_seed" in _load(f"benchmarks/traffic/{f}")}
    assert t["schedule_seed"] not in seeds                # of its own
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    serving = cfg["serving"]
    assert serving["max_slots"] == t["clients"]           # one a lane
    assert serving["max_seq_len"] == 16384 >= t["max_total_tokens"]
    assert serving["prompt_buckets"] == [t["prompt_tokens"]["max"]]
    # every context is over topk from its first decoded token
    assert t["prompt_tokens"]["min"] >= cfg["sa_config"]["topk"]
    # the page budget is under full provision: 40 lanes' worth
    assert serving["kv_pool_tokens"] == 5120 * serving["kv_page_tokens"]
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
        # readers give a number for this configuration, and so does the new
        for name in ("lane_occupancy", "moe_experts_touched_share",
                     "moe_load_max_over_mean", "kimi_prefill_padding_share",
                     "nemotron_prefill_rows_mean", "keye_dsa_attended_share"):
            assert line["metrics"][name]["value"] > 0, name
        share = line["metrics"]["moe_experts_touched_share"]["value"]
        assert 100.0 / 4 <= share <= 100.0
        assert line["metrics"]["kimi_prefill_padding_share"]["value"] < 100.0
        assert 1.0 <= line["metrics"]["nemotron_prefill_rows_mean"][
            "value"] <= 4.0
        # the selection prunes at this traffic
        assert line["metrics"]["keye_dsa_attended_share"]["value"] < 60.0
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
    puts first (its index products rounded too, so its selection is another
    one) lies further below the reference's best than it allows."""
    cell = spec.load_cell(root, CELL)
    flights = _serve(cell, seed, 8)
    gap, control_gap, n_tokens = serve_cell.reference_gaps(
        cell, flights, seed, "fp8")
    limit = cell.limits["served_logit_gap"]
    assert n_tokens > 400
    assert gap <= limit, (gap, control_gap)
    assert control_gap > limit, (gap, control_gap)


def _first_positions(scores, qpos, topk):
    """The first ``topk`` positions and not the best."""
    s = jnp.arange(scores.shape[1])[None, :]
    return (s <= qpos[:, None]) & (s < topk)


def test_a_wrong_selection_is_not_correct(root, monkeypatch):
    """The same requests held against a reference that attends to the first
    ``topk`` positions instead of the best: what the program served lies
    further below THAT reference's best than the limit allows, which is
    what a program that selected so would read against the right one."""
    cell = spec.load_cell(root, CELL)
    flights = _serve(cell, 42, 8)
    monkeypatch.setattr(keye_ref, "select", _first_positions)
    real = keye_ref._logits
    monkeypatch.setattr(
        keye_ref, "_logits",
        lambda w, row, pos, D, pr, selection=None: real(
            w, row, pos, D, pr, _first_positions))
    gap, _, _ = serve_cell.reference_gaps(cell, flights, 42, "f32")
    assert gap > cell.limits["served_logit_gap"], gap


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

# a window of 100 decode steps over 6 layers of 16 held experts: 60 lanes a
# step whose contexts are 432,000 positions between them (7,200 a lane), so
# the six indexers score 2,592,000 keys a step and the attention reads 60 x
# 6 x 2,048 = 737,280; 15.5 of 16 experts touched a layer a step (9,300), 60
# picks a layer a step land here (36,000), the busiest expert 9 tokens a
# layer a step; 40 prefill calls of 16 rows of 128, of which 600 rows
# carried the 75,000 tokens of the prompts
COUNTERS = {
    "decode_steps": 100, "tokens_emitted": 6000, "moe_layer_steps": 600,
    "moe_picks_here": 36000, "moe_experts_touched": 9300,
    "moe_expert_load_max": 5400, "prefill_chunks": 40,
    "prefill_chunk_rows": 600, "prefill_tokens": 75000,
    "prefill_positions_run": 40 * 2048,
    "decode_context_tokens": 43200000, "dsa_keys_scored": 259200000,
    "dsa_keys_attended": 73728000, "pool_pages_in_use_steps": 350000,
    "decode_time_s": 2.1,
}


def _run_data(counters, trace=None):
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    return types.SimpleNamespace(
        counters=counters, trace=trace, device_kind="TPU v5 lite",
        host={"max_slots": 64, "mean_live_kv_tokens_per_lane": 7200.0},
        cell=types.SimpleNamespace(config=cfg))


NON_EXPERT = 167473408             # worked out in the costs test below
EXPERT = 3 * 2048 * 768


def _least_bytes():
    # 93 experts read a step; 2,592,000 keys scored at 128 B; 737,280
    # positions' keys and values at 2,048 B
    return (2 * NON_EXPERT + 93 * EXPERT * 2 + 2592000 * 128
            + 737280 * 2048)


def _expected(name):
    return {
        "moe_experts_touched_share": 100.0 * 9300 / (600 * 16),
        "moe_load_max_over_mean": 5400 * 16 / 36000,
        "kimi_prefill_padding_share": 100.0 * (1 - 75000 / 81920),
        "nemotron_prefill_rows_mean": 15.0,
        "lane_occupancy": 100.0 * 60 / 64,
        "keye_dsa_attended_share": 100.0 * 737280 / 2592000,
    }[name]


COUNTER_READERS = {
    "moe_experts_touched_share": "moe_layer_steps",
    "moe_load_max_over_mean": "moe_picks_here",
    "kimi_prefill_padding_share": "prefill_chunks",
    "nemotron_prefill_rows_mean": "prefill_chunks",
    "lane_occupancy": "decode_steps",
    "keye_dsa_attended_share": "dsa_keys_scored",
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


@pytest.mark.parametrize("name", ["keye_dsa_attended_share",
                                  "keye_decode_step_roofline"])
def test_new_readers_find_nothing_in_a_program_without_the_counters(name):
    """The parent counts no scored and no attended keys: a traced run of a
    program without ``dsa_keys_scored`` leaves the metrics out and does not
    raise."""
    reader = spec.load_reader(BENCH_DIR, name)
    parent = {k: v for k, v in COUNTERS.items() if not k.startswith("dsa_")}
    trace = _Trace({"jit__keye_decode_step_jit": [0.016]})
    assert reader.read(_run_data(parent, trace=trace)) is None


TRACE_READERS = ("keye_decode_step_ms_p50", "keye_prefill_time_share",
                 "keye_decode_step_roofline")


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
    trace = _Trace({"jit__keye_decode_step_jit": [0.018, 0.016, 0.022],
                    "jit__keye_prefill_chunk_jit": [0.05, 0.05],
                    "jit__mimo_decode_step_jit": [0.001]})
    run = _run_data(dict(COUNTERS), trace=trace)
    assert spec.load_reader(BENCH_DIR, "keye_decode_step_ms_p50").read(
        run) == pytest.approx(18.0)
    assert spec.load_reader(BENCH_DIR, "keye_prefill_time_share").read(
        run) == pytest.approx(100.0 * 0.1 / 2.0)
    # 60 lanes, 93 expert reads a step: memory binds
    least_s = _least_bytes() / 819e9
    got = spec.load_reader(BENCH_DIR, "keye_decode_step_roofline").read(run)
    assert got == pytest.approx(100.0 * least_s / 0.018)
    assert 0 < got < 100


# -- the byte and FLOP functions against hand-worked numbers ----------------

def test_costs_against_hand_worked_numbers():
    """The issue's table of the cut, and its step."""
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    # q 2048 x 4096, k and v 2048 x 512, o 4096 x 2048, two head norms of 128
    attn = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 128
    assert costs_keye.attention_params(cfg) == attn == 18874368 + 256
    # the issue's 2,260,992 and the key norm's scale and bias of 64
    index = 2048 * 1024 + 2048 * 64 + 2048 * 16 + 2 * 64
    assert costs_keye.indexer_params(cfg) == index == 2260992 + 128
    assert costs_keye.expert_params(cfg) == EXPERT == 4718592
    # the router at its published 128 columns, no bias
    assert costs_keye.router_params(cfg) == 2048 * 128 == 262144
    layer = attn + index + 262144 + 2 * 2048
    assert costs_keye.layer_non_expert_params(cfg) == layer
    # a layer held: the issue's 96.9 M with its 16 experts
    assert 96.85e6 < layer + 16 * EXPERT < 96.95e6
    non_expert = 6 * layer + 2048 + 2048 * 19072
    assert costs_keye.non_expert_params(cfg) == non_expert == NON_EXPERT
    total = non_expert + 2048 * 19072 + 6 * 16 * EXPERT
    assert costs_keye.total_params(cfg) == total
    assert 1.31e9 < 2 * total < 1.33e9            # "weights 1.32 GB"
    # a token caches 6 x (1,024 + 64) values: the issue's 13,056 B
    assert costs_keye.cache_bytes_per_token(cfg, 2) == 13056
    assert 8.55e9 < 655360 * 13056 < 8.57e9       # "the pool is 8.56 GB"
    assert 2 * costs_keye.index_row_values(cfg) == 128          # bytes
    assert 2 * costs_keye.kv_row_values(cfg) == 2048
    # the issue's step: 64 lanes at a mean context of 7,200, every held
    # expert of the six layers touched
    scored, attended = 64 * 6 * 7200, 64 * 6 * 2048
    got = costs_keye.decode_step_min_bytes(
        cfg, experts_touched=96, keys_scored=scored, keys_attended=attended,
        weight_bytes=2)
    ik = costs_keye.index_bytes(cfg, keys_scored=scored, kv_bytes=2)
    kv = costs_keye.selected_bytes(cfg, keys_attended=attended, kv_bytes=2)
    assert ik == 64 * 6 * 7200 * 128 == 353894400    # 0.92 MB a lane a layer
    assert kv == 64 * 6 * 2048 * 2048 == 1610612736  # 4.19 MB a lane a layer
    assert 1.95e9 < ik + kv < 1.97e9                 # "1.96 GB"
    assert got == 2 * non_expert + 96 * EXPERT * 2 + ik + kv
    # "60% are the indexer's and the selected columns'": 61%, since a step
    # does not read the embedding that the issue's 1.32 GB of weights holds
    assert 0.60 < (ik + kv) / got < 0.62
    dense = costs_keye.dense_bytes(cfg, keys_scored=scored, kv_bytes=2)
    assert 5.6e9 < dense < 5.7e9                     # "5.7 GB of keys"
    flops = costs_keye.decode_step_flops(
        cfg, lanes=64, picks=6 * 32, keys_scored=scored,
        keys_attended=attended)
    assert flops == (2 * 64 * non_expert + 2 * 192 * EXPERT
                     + 2 * 16 * 64 * scored + 4 * 32 * 128 * attended)
    # memory binds by far
    assert flops / 197e12 < 0.2 * got / 819e9
    assert costs_keye.step_means(dict(COUNTERS)) == (
        60, 2592000, 737280, 93, 360)
    least, step_flops, every = costs_keye.step_costs(cfg, dict(COUNTERS))
    assert least == _least_bytes()
    assert every == least - 737280 * 2048 + 2592000 * 2048
    assert step_flops == costs_keye.decode_step_flops(
        cfg, lanes=60, picks=360, keys_scored=2592000,
        keys_attended=737280)
    assert costs_keye.step_costs(cfg, {"decode_steps": 5}) is None
