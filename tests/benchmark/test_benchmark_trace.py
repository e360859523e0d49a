"""The reduction from a trace to numbers, on a hand-made trace whose answer
is known and on a small trace recorded on the chip."""

import json
import os
import re

import pytest

from benchmarks.harness import tracing

HERE = os.path.dirname(os.path.abspath(__file__))


def _hand_made():
    """Device 0: two programs A [0,4] and B [6,9] with ops inside, idle 4-6
    between them and 1 s idle inside A; device 1: one op [0,5]."""
    return [
        (0, "modules", "jit_A", 0.0, 4.0),
        (0, "ops", "fusion.1_bf16_8_8_", 0.0, 1.0),
        (0, "ops", "all-gather.2_bf16_64_", 2.0, 1.0),
        (0, "ops", "fusion.1_bf16_8_8_", 3.0, 1.0),
        (0, "modules", "jit_B", 6.0, 3.0),
        (0, "ops", "copy.3_f32_4_", 6.0, 3.0),
        (1, "modules", "jit_A", 0.0, 5.0),
        (1, "ops", "fusion.1_bf16_8_8_", 0.0, 5.0),
        (1, "ops", "noop_f32__", 8.0, 1.0),
    ]


def test_busy_idle_and_gaps_on_a_hand_made_trace():
    t = tracing.TraceSummary(_hand_made())
    assert t.devices == [0, 1]
    assert t.window_s == pytest.approx(9.0)
    assert t.busy_s == pytest.approx((6.0 + 6.0) / 2)       # mean over chips
    assert t.idle_share == pytest.approx(1 - 6.0 / 9.0)
    assert t.program_durations("jit_A") == [4.0]
    assert t.program_time(("jit_A", "jit_B")) == pytest.approx((7.0 + 5.0) / 2)
    assert t.exposed_collective_s() == pytest.approx(0.5)   # 1 s on one of two
    assert t.op_time(re.compile("^copy")) == pytest.approx(1.5)
    top = t.top_ops(2)
    assert top[0] == ["copy.3_f32_4_", 3.0] and top[1][1] == 2.0
    gaps = dict(t.idle_gaps())
    assert gaps["jit_A-_jit_B"] == pytest.approx(2.0)
    assert gaps["within_jit_A"] == pytest.approx(1.0)
    assert sum(gaps.values()) == pytest.approx(3.0)         # all idle named


def test_an_empty_trace_reads_as_nothing():
    t = tracing.TraceSummary([])
    assert t.busy_s == 0.0 and t.window_s == 0.0 and t.idle_share is None
    assert t.top_ops() == [] and t.idle_gaps() == []


def test_labels_are_made_from_the_operation_and_its_result_shape():
    hlo = ("%copy.31 = bf16[36,65,20,128,64]{4,3,2,1,0:T(8,128)(2,1)} "
           "copy(bf16[36,65,20,128,64]{4,3,2,1,0} %p.1)")
    assert tracing.op_label(hlo) == "copy.31_bf16_36_65_20_128_64_"
    assert tracing.op_label("%all-gather-done.3 = f32[1024]{0} "
                            "all-gather-done(...)").startswith("all-gather")
    assert tracing.program_label("jit__decode_step_jit(1467)") == \
        "jit__decode_step_jit"
    assert tracing.COLLECTIVE.search("reduce-scatter.7_f32_128_")
    assert not tracing.COLLECTIVE.search("fusion.7_f32_128_")


def test_recorded_chip_trace_reduces_to_the_numbers_read_by_hand():
    """One decode step of ``gpt2_large_chat_steady`` on a v5e with the gap
    behind it, kept as plain rows. Read by hand from the two program events:
    the first step ran 55,944,311 ns and the next began 1,918,286 ns after
    it ended; every operation in between belongs to the first step, so that
    gap is the device's whole idle time in the slice."""
    with open(os.path.join(HERE, "data", "chat_trace_slice.json")) as f:
        rec = json.load(f)
    events = [(r[0], "modules" if r[1] == 0 else "ops", rec["names"][r[2]],
               r[3] * 1e-9, r[4] * 1e-9) for r in rec["events"]]
    t = tracing.TraceSummary(events)
    want = rec["expected"]
    assert want["first_step_ns"] == 55944311
    assert want["gap_between_steps_ns"] == 1918286
    steps = t.program_durations("jit__decode_step_jit")
    assert len(steps) == 2 and steps[0] == pytest.approx(55944311e-9)
    assert t.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert t.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    gaps = dict(t.idle_gaps())
    assert gaps["jit__decode_step_jit-_jit__decode_step_jit"] == \
        pytest.approx(1918286e-9, rel=1e-3)
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s,
                                               rel=1e-6)
    assert t.idle_share == pytest.approx(1918286 / 64798376, rel=2e-2)
    # the whole-pool copies lead, as in the ledger's breakdown of PR 22
    assert t.top_ops(1)[0][0] == want["top_op"]
    assert t.top_ops(1)[0][0].startswith("copy")
