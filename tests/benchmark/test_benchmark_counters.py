"""The readers of the serving loop's own counters (``ServingMetrics``, over
the window: after minus before), on counters made by hand."""

import json
import os
import types

import pytest

from benchmarks.harness import spec

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO, "benchmarks")
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

CHAT, LONG = "gpt2_large_chat_steady", "gpt2_large_longprompt_closed"

# a window of 10 s: 100 decode steps of 56 ms, 12 prefills of 100 ms for 13
# prompts of 125 tokens in 8 rows x 128 and x 512, 0.7 ms of host work behind
# each step, 9 ms an admission beyond its prefill
COUNTERS = {
    "loop_busy_s": 7.0, "decode_time_s": 5.6, "prefill_time_s": 1.2,
    "decode_steps": 100, "decode_host_s": 0.07,
    "admit_time_s": 1.308, "prefill_calls": 12,
    "queue_wait_s": 13.0, "queue_waits": 13,
    "token_gaps": 600, "token_gap_s": 39.0,
    "stalled_gaps": 48, "stalled_gap_s": 6.72,
    "prefill_tokens": 1625, "prefill_positions_run": 8 * (9 * 128 + 3 * 512),
}

# name -> (expected on COUNTERS, the counter whose absence empties it,
#          cells that list it)
READERS = {
    "serve_loop_host_share": (100.0 * 0.2 / 7.0, "loop_busy_s",
                              [CHAT, LONG]),
    "serve_host_ms_per_decode_step": (0.7, "decode_steps", [CHAT, LONG]),
    "serve_admit_host_ms_per_prefill": (9.0, "prefill_calls", [CHAT]),
    "serve_queue_wait_mean_ms": (1000.0, "queue_waits", [CHAT]),
    "serve_stalled_gap_share": (8.0, "token_gaps", [CHAT]),
    "serve_stalled_gap_mean_ms": (140.0, "stalled_gaps", [CHAT]),
    "prefill_padding_share": (100.0 * (1 - 1625 / 21504),
                              "prefill_positions_run", [CHAT, LONG]),
}


def _run(counters):
    return types.SimpleNamespace(counters=counters, host={"max_slots": 8},
                                 trace=None, cell=None, device_kind="cpu")


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_takes_its_number_from_the_counters(name):
    expected, _, _ = READERS[name]
    reader = spec.load_reader(BENCH_DIR, name)
    assert reader.read(_run(dict(COUNTERS))) == pytest.approx(expected)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_finds_nothing_where_its_denominator_is_zero(name):
    _, denominator, _ = READERS[name]
    reader = spec.load_reader(BENCH_DIR, name)
    assert reader.read(_run(dict(COUNTERS, **{denominator: 0}))) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_finds_nothing_in_a_program_without_the_counters(name):
    """The parent of the PR that brought these counters has none of them:
    its traced runs leave the metric out and do not raise."""
    old = {"decode_steps": 100, "tokens_emitted": 600, "prefill_calls": 12,
           "prefill_tokens": 1625, "requests_completed": 11}
    reader = spec.load_reader(BENCH_DIR, name)
    assert reader.read(_run(old)) is None
    assert reader.read(_run({})) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_entry_names_the_counters_source_and_its_cells(name):
    _, _, cells = READERS[name]
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["source"] == "program_counter"
    assert entry["better"] == "lower"
    assert entry["workloads"] == cells
    moved, = [m for m in BENCH["end_to_end"] if m["name"] == entry["moves"]]
    assert set(cells) <= set(moved["workloads"])
    assert not spec.validate(BENCH)


def test_program_counts_what_the_readers_ask_for():
    """Every counter a reader names is a numeric key of the program's
    ``snapshot()``, so the harness's after-minus-before carries it."""
    from deepspeed_tpu.inference.serving.metrics import ServingMetrics

    snap = ServingMetrics().snapshot()
    for key in COUNTERS:
        assert isinstance(snap[key], (int, float)), key
        assert not isinstance(snap[key], bool)
