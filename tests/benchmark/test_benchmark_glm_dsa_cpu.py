"""The GLM-5.2 cell end to end at tiny size on the CPU (traced and untraced),
with its own tiny root (``tiny_root.py`` writes tiny files for the first two
configurations only, so ``test_benchmark_cells_cpu.py``'s case of this cell
fails by design as its six siblings' do), the fp8 control and a wrong
selection at that size, the readers of the new per-layer metrics on counters
made by hand and on a run without them, that the appended readers give a
number for this configuration, and the byte and FLOP functions of
``costs_glm_dsa.py`` against hand-worked numbers. Every entry of
``BENCHMARK.json`` is found by NAME, never by its place: the next
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
from benchmarks.harness import costs, costs_glm_dsa, runtime, serve_cell
from benchmarks.harness import spec
from benchmarks.refs import glm_dsa_ref
from benchmarks.refs import weights as weights_mod

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO, "benchmarks")
BENCH = spec.load_benchmark(REPO)
CELL = "glm52_ep16_longdoc_closed64"
CONFIG = "glm_5p2_serve_ep16"
TRAFFIC = "longdoc_closed64"
SEED = 2 ** 32 + 5                 # the driver's seeds pass 32 signed bits

# toy widths that keep the published shape: 4 heads of (12 nope | 4 rope)
# and values of 16 on a latent of 24 + 4 behind a query latent of 32, an
# indexer of 4 heads of 8 (4 channels rotated) with topk 24 (so that prompts
# of a few pages of 16 already prune), 4 of 16 experts held, top-4; the
# per-layer lists stay the published ones, read from layer 2 for 6 layers
TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32, num_attention_heads=4,
            num_key_value_heads=4, head_dim=12, q_lora_rank=32,
            kv_lora_rank=24, qk_nope_head_dim=12, qk_rope_head_dim=4,
            qk_head_dim=16, v_head_dim=16, index_n_heads=4,
            index_head_dim=8, index_topk=24, n_routed_experts=4,
            num_experts=4, num_experts_per_tok=4)


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
# twentieth of the token's own). Over six seeds (4294967301, 42, 77, 3, 5,
# 11) served tokens read 0.0014 to 0.0028 and the fp8 control 0.0061 to
# 0.0203. Both read a third of what Keye-VL's tiny cell reads: two layers of
# six select here, so a key at the edge of a selection is swapped in a
# third of the places. The two do not overlap, and the limit stands a
# factor of 1.45 and more from either
TINY_LIMIT = 0.0042


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny_glm_benchmark"))


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    real = costs.peaks_for
    monkeypatch.setattr(
        costs, "peaks_for",
        lambda kind, table_path=None: real(
            "TPU v5 lite" if kind == "cpu" else kind, table_path))


NEW_METRICS = ("glm_decode_step_ms_p50", "glm_prefill_time_share",
               "glm_decode_step_roofline", "glm_index_reuse_share",
               "glm_dsa_attended_share")
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
    assert len(layers) == 1 and "serving/families/glm_dsa.py" in layers.pop()
    assert metrics["glm_decode_step_roofline"]["unit"] == "%"
    # the read-back metrics' lists stay the five cells' that another test
    # file holds them to
    assert CELL not in metrics["serve_late_read_share"]["workloads"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(json.dumps(BENCH, indent=1)) < 64 * 1024


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's ``config`` under the same name and value
    but the three that count what is held, and the file states the published
    counts, the deployment and what is not served beside them."""
    published = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "GLM-5.2":
                published = row
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    assert cfg["source"] == published["source_url"]
    assert set(published["config"]) <= set(cfg)
    differ = sorted(k for k, v in published["config"].items()
                    if cfg[k] != v)
    assert differ == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    # ``num_experts`` is the benchmark's alias, not a key of the source
    assert sorted(cfg["reduced"]) == sorted(differ + ["num_experts"])
    assert cfg["num_experts"] == cfg["n_routed_experts"] == 16
    assert cfg["indexer_types"] == published["config"]["indexer_types"]
    assert cfg["mlp_layer_types"] == published["config"]["mlp_layer_types"]
    share = cfg["share"]
    assert share["num_hidden_layers_published"] == 78
    assert share["n_routed_experts_published"] == 256
    assert share["vocab_size_published"] == 154880
    assert share["chips_sharing_a_layer"] == 16
    assert (share["experts_first"], share["vocab_first"],
            share["first_layer"]) == (0, 0, 2)
    assert "multi-token-prediction" in share["not_served"]
    # the cut: one dense layer, five expert layers (floor four), one whole
    # period of the indexer pattern, 16 >= 8 experts, an eighth of the
    # vocabulary in whole lane tiles
    held = range(2, 8)
    assert cfg["num_hidden_layers"] == len(held)
    assert [cfg["indexer_types"][l] for l in held] == [
        "full", "shared", "shared", "shared", "full", "shared"]
    assert [cfg["mlp_layer_types"][l] for l in held] == [
        "dense"] + ["sparse"] * 5
    assert cfg["vocab_size"] * 8 >= share["vocab_size_published"]
    assert cfg["vocab_size"] == 152 * 128
    for key in ("indexer", "indexer_rope", "shared", "selection", "head_dim",
                "softmax_scale", "rope", "router", "weights", "cache",
                "decoding", "max_position_embeddings"):
        assert key in cfg["assumed"]
    assert "lower position first" in cfg["assumed"]["selection"]
    assert (cfg["kind"], cfg["adapter"], cfg["reference"]) == (
        "serve", "glm_dsa_serve", "glm_dsa_ref")


def test_the_cell_runs_keye_vls_traffic_under_keye_vls_admission():
    """The traffic file is the one that was there, and the serving block is
    Keye-VL's, so that the two selecting configurations differ by the model
    alone."""
    t = _load(f"benchmarks/traffic/{TRAFFIC}.json")
    assert (t["loop"], t["clients"], t["requests_per_client"]) == (
        "closed", 64, 32)
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    keye = _load("benchmarks/configs/keye_vl2_30b_serve_ep8.json")
    assert cfg["serving"] == keye["serving"]
    # every context is over topk from its first decoded token
    assert t["prompt_tokens"]["min"] >= cfg["index_topk"]
    assert cfg["serving"]["max_seq_len"] >= t["max_total_tokens"]


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
                     "nemotron_prefill_rows_mean", "glm_dsa_attended_share",
                     "glm_index_reuse_share"):
            assert line["metrics"][name]["value"] > 0, name
        share = line["metrics"]["moe_experts_touched_share"]["value"]
        assert 100.0 / 4 <= share <= 100.0
        assert line["metrics"]["kimi_prefill_padding_share"]["value"] < 100.0
        assert 1.0 <= line["metrics"]["nemotron_prefill_rows_mean"][
            "value"] <= 4.0
        # the selection prunes at this traffic, and four layers of six
        # attend under a selection that is not their own
        assert line["metrics"]["glm_dsa_attended_share"]["value"] < 60.0
        assert line["metrics"]["glm_index_reuse_share"][
            "value"] == pytest.approx(100.0 * 4 / 6)
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
    one, in the layers that share it as well) lies further below the
    reference's best than it allows."""
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
    ``index_topk`` positions instead of the best: what the program served
    lies further below THAT reference's best than the limit allows, which is
    what a program that selected so would read against the right one."""
    cell = spec.load_cell(root, CELL)
    flights = _serve(cell, 42, 8)
    real = glm_dsa_ref._logits
    monkeypatch.setattr(
        glm_dsa_ref, "_logits",
        lambda w, row, pos, D, pr, selection=None: real(
            w, row, pos, D, pr, _first_positions))
    gap, _, _ = serve_cell.reference_gaps(cell, flights, 42, "f32")
    assert gap > cell.limits["served_logit_gap"], gap


# -- readers on counters made by hand ---------------------------------------

# a window of 100 decode steps over 6 layers, 5 of them of 16 held experts:
# 60 lanes a step whose contexts are 432,000 positions between them (7,200 a
# lane), so the TWO indexers score 864,000 keys a step and the attention of
# all SIX layers reads 60 x 6 x 2,048 = 737,280, 491,520 of them in the four
# layers that share; 15.5 of 16 experts touched a layer a step (7,750), 30
# picks a layer a step land here (15,000), the busiest expert 5 tokens a
# layer a step; 40 prefill calls of 16 rows of 128, of which 600 rows
# carried the 75,000 tokens of the prompts
COUNTERS = {
    "decode_steps": 100, "tokens_emitted": 6000, "moe_layer_steps": 500,
    "moe_picks_here": 15000, "moe_experts_touched": 7750,
    "moe_expert_load_max": 2500, "prefill_chunks": 40,
    "prefill_chunk_rows": 600, "prefill_tokens": 75000,
    "prefill_positions_run": 40 * 2048,
    "decode_context_tokens": 43200000, "dsa_keys_scored": 86400000,
    "dsa_keys_attended": 73728000, "dsa_layers_shared_attended": 49152000,
    "pool_pages_in_use_steps": 350000, "decode_time_s": 2.1,
}


def _run_data(counters, trace=None):
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    return types.SimpleNamespace(
        counters=counters, trace=trace, device_kind="TPU v5 lite",
        host={"max_slots": 64, "mean_live_kv_tokens_per_lane": 7200.0},
        cell=types.SimpleNamespace(config=cfg))


ATTENTION = 165022208              # worked out in the costs test below
INDEXER = 9371904
EXPERT = 3 * 6144 * 2048
NON_EXPERT = 1551596288


def _least_bytes():
    # 77.5 experts read a step; 864,000 keys scored at 256 B; 737,280
    # positions' latent rows at 1,152 B
    return (2 * NON_EXPERT + 77.5 * EXPERT * 2 + 864000 * 256
            + 737280 * 1152)


def _expected(name):
    return {
        "moe_experts_touched_share": 100.0 * 7750 / (500 * 16),
        "moe_load_max_over_mean": 2500 * 16 / 15000,
        "kimi_prefill_padding_share": 100.0 * (1 - 75000 / 81920),
        "nemotron_prefill_rows_mean": 15.0,
        "lane_occupancy": 100.0 * 60 / 64,
        "glm_index_reuse_share": 100.0 * 4 / 6,
        "glm_dsa_attended_share": 100.0 * 2048 / 7200,
    }[name]


COUNTER_READERS = {
    "moe_experts_touched_share": "moe_layer_steps",
    "moe_load_max_over_mean": "moe_picks_here",
    "kimi_prefill_padding_share": "prefill_chunks",
    "nemotron_prefill_rows_mean": "prefill_chunks",
    "lane_occupancy": "decode_steps",
    "glm_index_reuse_share": "dsa_keys_attended",
    "glm_dsa_attended_share": "dsa_keys_scored",
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


@pytest.mark.parametrize("name", ["glm_dsa_attended_share",
                                  "glm_index_reuse_share",
                                  "glm_decode_step_roofline"])
def test_new_readers_find_nothing_in_a_program_without_the_counters(name):
    """The parent counts no layer-positions attended under another layer's
    selection: a traced run of a program without
    ``dsa_layers_shared_attended`` (Keye-VL's counters alone, or none)
    leaves the metrics out and does not raise."""
    reader = spec.load_reader(BENCH_DIR, name)
    trace = _Trace({"jit__glm_decode_step_jit": [0.016]})
    keye_only = {k: v for k, v in COUNTERS.items()
                 if k != "dsa_layers_shared_attended"}
    assert reader.read(_run_data(keye_only, trace=trace)) is None
    none = {k: v for k, v in COUNTERS.items() if not k.startswith("dsa_")}
    assert reader.read(_run_data(none, trace=trace)) is None


TRACE_READERS = ("glm_decode_step_ms_p50", "glm_prefill_time_share",
                 "glm_decode_step_roofline")


@pytest.mark.parametrize("name", TRACE_READERS)
def test_trace_readers_find_nothing_without_their_programs(name):
    reader = spec.load_reader(BENCH_DIR, name)
    assert reader.read(_run_data(dict(COUNTERS), trace=None)) is None
    others = _Trace({"jit__decode_step_jit": [0.05] * 10,
                     "jit__keye_decode_step_jit": [0.01] * 10,
                     "jit__keye_prefill_chunk_jit": [0.04],
                     "jit__zero_slot": [0.001]})
    assert reader.read(_run_data(dict(COUNTERS), trace=others)) is None


def test_trace_readers_on_a_hand_made_trace():
    trace = _Trace({"jit__glm_decode_step_jit": [0.030, 0.028, 0.034],
                    "jit__glm_prefill_chunk_jit": [0.25, 0.25],
                    "jit__keye_decode_step_jit": [0.001]})
    run = _run_data(dict(COUNTERS), trace=trace)
    assert spec.load_reader(BENCH_DIR, "glm_decode_step_ms_p50").read(
        run) == pytest.approx(30.0)
    assert spec.load_reader(BENCH_DIR, "glm_prefill_time_share").read(
        run) == pytest.approx(100.0 * 0.5 / 2.0)
    # 60 lanes, 77.5 expert reads a step: memory binds
    least_s = _least_bytes() / 819e9
    got = spec.load_reader(BENCH_DIR, "glm_decode_step_roofline").read(run)
    assert got == pytest.approx(100.0 * least_s / 0.030)
    assert 0 < got < 100


# -- the byte and FLOP functions against hand-worked numbers ----------------

def test_costs_against_hand_worked_numbers():
    """The issue's table of the cut, and its step."""
    cfg = _load(f"benchmarks/configs/{CONFIG}.json")
    # q_a 6144 x 2048, q_b 2048 x 16384, kv_a 6144 x 576, kv_b 512 x 64 x
    # 448, o 16384 x 6144, two latent norms: the issue's 165.02 M
    attn = (6144 * 2048 + 2048 * 16384 + 6144 * 576 + 512 * 64 * 448
            + 16384 * 6144 + 2048 + 512)
    assert costs_glm_dsa.attention_params(cfg) == attn == ATTENTION
    # 2048 x 4096 + 6144 x 128 + 6144 x 32 + a LayerNorm of 128: 9.37 M
    index = 2048 * 4096 + 6144 * 128 + 6144 * 32 + 2 * 128
    assert costs_glm_dsa.indexer_params(cfg) == index == INDEXER
    assert costs_glm_dsa.expert_params(cfg) == EXPERT == 37748736
    assert costs_glm_dsa.dense_ffn_params(cfg) == 3 * 6144 * 12288
    # the router at its published 256 columns, with its bias
    assert costs_glm_dsa.router_params(cfg) == 6145 * 256
    assert (costs_glm_dsa.selecting_layers(cfg),
            costs_glm_dsa.expert_layers(cfg)) == (2, 5)
    # an expert layer outside its routed experts: the issue's 204.36 M
    outside = attn + EXPERT + 6145 * 256 + 2 * 6144
    assert 204.35e6 < outside < 204.37e6
    # a leading dense layer: the issue's 391.53 M
    assert 391.52e6 < attn + 3 * 6144 * 12288 + 2 * 6144 < 391.54e6
    non_expert = (6 * (attn + 2 * 6144) + 2 * index + 3 * 6144 * 12288
                  + 5 * (6145 * 256 + EXPERT) + 6144 + 6144 * 19456)
    assert costs_glm_dsa.non_expert_params(cfg) == non_expert == NON_EXPERT
    total = non_expert + 6144 * 19456 + 5 * 16 * EXPERT
    assert costs_glm_dsa.total_params(cfg) == total
    # "4.69 B parameters, 9.38 GB", and the reference's leaves say the same
    assert 4.69e9 < total < 4.70e9 and 9.38e9 < 2 * total < 9.39e9
    shapes = glm_dsa_ref.weight_shapes(cfg)
    assert sum(int(jnp.prod(jnp.array(s))) for s in shapes.values()) == total
    # a token caches 6 x 576 + 2 x 128 values: the issue's 7,424 B, and the
    # budget of 655,360 tokens 4.87 GB (5.37 GB as the pool lays a latent
    # row, 640 wide)
    assert costs_glm_dsa.cache_bytes_per_token(cfg, 2) == 7424
    assert 4.86e9 < 655360 * 7424 < 4.87e9
    assert 2 * costs_glm_dsa.index_row_values(cfg) == 256          # bytes
    assert 2 * costs_glm_dsa.latent_row_values(cfg) == 1152
    # the issue's step: 64 lanes at a mean context of 7,100, every held
    # expert of the five layers touched
    scored, attended = 64 * 2 * 7100, 64 * 6 * 2048
    got = costs_glm_dsa.decode_step_min_bytes(
        cfg, experts_touched=80, keys_scored=scored, keys_attended=attended,
        weight_bytes=2)
    ik = costs_glm_dsa.index_bytes(cfg, keys_scored=scored, kv_bytes=2)
    rows = costs_glm_dsa.selected_bytes(cfg, keys_attended=attended,
                                        kv_bytes=2)
    assert ik == 64 * 2 * 7100 * 256 and 0.23e9 < ik < 0.24e9   # "0.2 GB"
    assert rows == 64 * 6 * 2048 * 1152 and 0.90e9 < rows < 0.91e9
    assert got == 2 * non_expert + 80 * EXPERT * 2 + ik + rows
    # "about 9.1 GB of weights": 3.10 outside the experts + 6.04 of experts;
    # the selection's work is a tenth of the step's least bytes
    assert 9.1e9 < got - ik - rows < 9.2e9
    assert 0.10 < (ik + rows) / got < 0.12
    flops = costs_glm_dsa.decode_step_flops(
        cfg, lanes=64, picks=5 * 32, keys_scored=scored,
        keys_attended=attended)
    assert flops == (2 * 64 * non_expert + 2 * 160 * EXPERT
                     + 2 * 32 * 128 * scored + 2 * 64 * (576 + 512)
                     * attended)
    # memory binds by far
    assert flops / 197e12 < 0.2 * got / 819e9
    assert costs_glm_dsa.step_means(dict(COUNTERS)) == (
        60, 864000, 737280, 77.5, 150)
    least, step_flops = costs_glm_dsa.step_costs(cfg, dict(COUNTERS))
    assert least == _least_bytes()
    assert step_flops == costs_glm_dsa.decode_step_flops(
        cfg, lanes=60, picks=150, keys_scored=864000, keys_attended=737280)
    assert costs_glm_dsa.step_costs(cfg, {"decode_steps": 5}) is None
