"""The readers of the serving loop's account of its waits for the device
(``ServingMetrics``: dispatch, blocking reads by length, dry spells; over the
window: after minus before), on counters made by hand."""

import json
import os
import types

import pytest

from benchmarks.harness import read_account, spec

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO, "benchmarks")
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

SERVING_CELLS = [
    "gpt2_large_chat_steady", "gpt2_large_longprompt_closed",
    "kimi_linear_ep2_docgen_closed", "nemotron3_nano_ep2_chat_closed128",
    "laguna_xs2_pp8_mixedlen_closed64"]
EDGES = [str(250 * 2 ** i) for i in range(12)] + ["inf"]
WINDOW_S = 50.0


def _buckets(reads):
    """Counters of plain reads given as ``{edge: (count, seconds)}``."""
    out = {}
    for edge in EDGES:
        n, s = reads.get(edge, (0, 0.0))
        out["decode_reads_le_us_" + edge] = n
        out["decode_read_s_le_us_" + edge] = s
    return out


# a window of 50 s: 2,000 decode steps dispatched in 0.5 ms each; 1,900 plain
# reads of 18 ms, 90 that found their step done (a first token's read had
# waited it out) and 4 late ones of 100-200 ms; 6 behind a prefill call that
# ended no prompt (in the totals alone); 100 prefill reads of 40 ms; 100 dry spells of 3 ms behind
# them and none behind a decode read (a step is always in flight)
COUNTERS = dict(_buckets({"250": (90, 0.009), "32000": (1900, 34.2),
                          "128000": (1, 0.1), "256000": (3, 0.5)}), **{
    "decode_steps": 2000, "decode_dispatch_s": 1.0,
    "decode_reads": 2000, "decode_read_wait_s": 35.2,
    "prefill_reads": 100, "prefill_read_wait_s": 4.0,
    "dry_after_decode_s": 0.05, "dry_after_prefill_s": 0.3,
    "dry_spells_after_prefill": 100,
})

# name -> (expected on COUNTERS, what emptied makes it find nothing, unit)
READERS = {
    "serve_late_read_share": (100.0 * 0.6 / WINDOW_S, _buckets({}), "%"),
    "serve_late_read_from_ms": (64.0, _buckets({}), "ms"),
    "serve_decode_dispatch_ms": (0.5, {"decode_steps": 0}, "ms"),
    "serve_decode_read_ms": (1e3 * 34.209 / 1990, _buckets({}), "ms"),
    "serve_device_dry_share": (100.0 * 0.35 / WINDOW_S, None, "%"),
    "serve_dry_after_prefill_ms": (3.0, {"dry_spells_after_prefill": 0},
                                   "ms"),
    "serve_prefill_read_ms": (40.0, {"prefill_reads": 0}, "ms"),
}


def _run(counters, window_s=WINDOW_S):
    return types.SimpleNamespace(
        counters=counters, host={"max_slots": 8, "window_s": window_s},
        trace=None, cell=None, device_kind="cpu")


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_takes_its_number_from_the_counters(name):
    expected, _, _ = READERS[name]
    reader = spec.load_reader(BENCH_DIR, name)
    assert reader.read(_run(dict(COUNTERS))) == pytest.approx(expected)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_finds_nothing_where_its_denominator_is_zero(name):
    _, emptied, _ = READERS[name]
    reader = spec.load_reader(BENCH_DIR, name)
    if emptied is None:         # a share of the window: no window, no share
        assert reader.read(_run(dict(COUNTERS), window_s=0)) is None
    else:
        assert reader.read(_run(dict(COUNTERS, **emptied))) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_finds_nothing_in_a_program_without_the_counters(name):
    """The parent of the PR that brought these counters has none of them:
    its traced runs leave the metric out and do not raise."""
    parent = {"decode_steps": 2000, "tokens_emitted": 60000,
              "prefill_calls": 100, "decode_time_s": 37.0,
              "prefill_time_s": 4.2, "loop_busy_s": 49.9,
              "decode_host_s": 0.3, "admit_time_s": 4.6}
    reader = spec.load_reader(BENCH_DIR, name)
    assert reader.read(_run(parent)) is None
    assert reader.read(_run({})) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_entry_names_the_counters_source_and_its_cells(name):
    _, _, unit = READERS[name]
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "serve entry (serving/engine.py)"
    assert entry["moves"] == "serve_tokens_per_s"
    assert entry["better"] == "lower" and entry["unit"] == unit
    assert entry["workloads"] == SERVING_CELLS
    moved, = [m for m in BENCH["end_to_end"] if m["name"] == entry["moves"]]
    assert set(SERVING_CELLS) <= set(moved["workloads"])
    assert not spec.validate(BENCH)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_program_counts_what_the_readers_ask_for():
    """Every counter a reader names is a numeric key of the program's
    ``snapshot()``, so the harness's after-minus-before carries it."""
    from deepspeed_tpu.inference.serving.metrics import ServingMetrics

    snap = ServingMetrics().snapshot()
    for key in COUNTERS:
        assert isinstance(snap[key], (int, float)), key
        assert not isinstance(snap[key], bool)
    assert [k[len("decode_reads_le_us_"):] for k in snap
            if k.startswith("decode_reads_le_us_")] == EDGES


@pytest.mark.parametrize("median_ms,bucket,late_from_ms", [
    (18.0, "32000", 64.0),      # in (16, 32]: late from 64 ms
    (58.0, "64000", 128.0),     # in (32, 64]: late from 128 ms
])
def test_a_read_is_late_from_twice_its_medians_bucket(median_ms, bucket,
                                                      late_from_ms):
    """The issue's two worked examples: a read in the bucket whose lower
    edge is ``late_from_ms`` is late, one in the bucket below it is not."""
    at = EDGES.index(str(int(late_from_ms * 1e3)))   # upper edge = lower of next
    just_under, just_over = EDGES[at], EDGES[at + 1]
    reads = {bucket: (1000, median_ms)}              # 1,000 reads, in seconds
    reads[just_under] = (2, 2 * late_from_ms * 0.9e-3)
    reads[just_over] = (3, 3 * late_from_ms * 1.5e-3)
    on_time, late = read_account.split_late(
        read_account.buckets(_buckets(reads)))
    assert late == (3, pytest.approx(3 * late_from_ms * 1.5e-3))
    assert on_time[0] == 1002
    run = _run(_buckets(reads))
    share = spec.load_reader(BENCH_DIR, "serve_late_read_share").read(run)
    assert share == pytest.approx(100.0 * late[1] / WINDOW_S)
    mean = spec.load_reader(BENCH_DIR, "serve_decode_read_ms").read(run)
    assert mean == pytest.approx(1e3 * on_time[1] / 1002)


@pytest.mark.parametrize("median_ms,late_from_ms,late", [
    (16.1, 64.0, (3, 0.45)),        # the median in (16, 32]
    (15.9, 32.0, (43, 2.05)),       # in (8, 16]: (32, 64] is late as well
])
def test_the_late_rule_moves_with_the_medians_bucket_and_says_so(
        median_ms, late_from_ms, late):
    """Two runs whose median reads lie on either side of the 16 ms edge
    (Laguna's cell sits there) count late from 64 and from 32 ms: the same
    40 reads of 40 ms are on time in one and late in the other, and
    ``serve_late_read_from_ms`` tells the two runs apart."""
    edge = "32000" if median_ms > 16 else "16000"
    reads = {edge: (1000, median_ms), "64000": (40, 1.6),
             "256000": (3, 0.45)}
    rows = read_account.buckets(_buckets(reads))
    assert read_account.late_from_us(rows) == late_from_ms * 1e3
    assert read_account.split_late(rows)[1] == (late[0],
                                                pytest.approx(late[1]))
    run = _run(_buckets(reads))
    said = spec.load_reader(BENCH_DIR, "serve_late_read_from_ms").read(run)
    assert said == late_from_ms
    share = spec.load_reader(BENCH_DIR, "serve_late_read_share").read(run)
    assert share == pytest.approx(100.0 * late[1] / WINDOW_S)


def test_reads_that_found_their_step_done_do_not_move_the_median():
    """Nine reads in ten at 18 ms and one in ten near zero (behind a first
    token's read): the median stays in (16, 32]; and where the last bucket
    holds the median nothing is late."""
    rows = read_account.buckets(_buckets(
        {"250": (100, 0.01), "32000": (900, 16.2), "128000": (2, 0.2)}))
    _, late = read_account.split_late(rows)
    assert late == (2, pytest.approx(0.2))
    rows = read_account.buckets(_buckets({"inf": (10, 9.0)}))
    assert read_account.split_late(rows) == ((10, 9.0), (0, 0.0))
    said = spec.load_reader(BENCH_DIR, "serve_late_read_from_ms")
    assert said.read(_run(_buckets({"inf": (10, 9.0)}))) is None


def test_a_traced_run_of_a_tiny_chat_cell_reports_all_seven(
        tmp_path_factory, monkeypatch):
    """Through the harness itself, on the CPU at a tiny size: the window's
    after-minus-before carries every counter to its reader."""
    from benchmarks import run as run_mod
    from benchmarks.harness import costs
    import tiny_root

    real = costs.peaks_for
    monkeypatch.setattr(
        costs, "peaks_for", lambda kind, table_path=None: real(
            "TPU v5 lite" if kind == "cpu" else kind, table_path))
    root = tiny_root.make(tmp_path_factory.mktemp("tiny_read_account"))
    # a window of 3 s: on a worker short of CPU one second can hold a
    # prefill and not one whole decode step, and then three readers find
    # nothing to divide by
    line = run_mod.run_cell(root, "gpt2_large_chat_steady", 20260923, 3.0,
                            True, require_chip=False)
    assert line["correct"] is True
    got = {name: line["metrics"][name]["value"] for name in READERS
           if name in line["metrics"]}
    assert sorted(got) == sorted(READERS), line["metrics"]
    assert all(v >= 0 for v in got.values())
    # a synchronous step: every iteration leaves the device dry for a while
    assert got["serve_device_dry_share"] > 0
    assert got["serve_decode_read_ms"] > 0
