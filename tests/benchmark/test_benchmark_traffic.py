"""The fixed schedule: arrival times and lengths come from the traffic file,
token ids from ``--seed``."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _mix(name):
    with open(os.path.join(REPO, "benchmarks", "traffic", name + ".json")) as f:
        return json.load(f)


def _flat(schedule):
    return schedule if not isinstance(schedule[0], list) else \
        [r for c in schedule for r in c]


@pytest.mark.parametrize("name", ["chat_steady", "longprompt_closed"])
def test_schedule_is_the_same_in_every_run_and_ids_follow_the_seed(name):
    mix = _mix(name)
    a = _flat(traffic.serve_schedule(mix, 50.0))
    b = _flat(traffic.serve_schedule(mix, 50.0))
    assert [(r.due_s, r.prompt_len, r.output_len, r.client) for r in a] == \
           [(r.due_s, r.prompt_len, r.output_len, r.client) for r in b]
    big = 2 ** 31 + 12345          # the driver's seeds pass 32 signed bits
    ids1 = traffic.prompt_ids(1, a[0], 50304)
    ids2 = traffic.prompt_ids(big, a[0], 50304)
    assert len(ids1) == len(ids2) == a[0].prompt_len
    assert not np.array_equal(ids1, ids2)
    assert np.array_equal(ids2, traffic.prompt_ids(big, b[0], 50304))
    assert ids2.min() >= 0 and ids2.max() < 50304


def test_another_schedule_seed_is_another_schedule():
    mix = _mix("chat_steady")
    other = dict(mix, schedule_seed=mix["schedule_seed"] + 1)
    a = traffic.serve_schedule(mix, 50.0)
    b = traffic.serve_schedule(other, 50.0)
    assert [r.due_s for r in a] != [r.due_s for r in b]


def test_chat_mix_is_what_its_file_says():
    mix = _mix("chat_steady")
    reqs = traffic.serve_schedule(mix, 4000.0)
    rate = len(reqs) / 4000.0
    assert rate == pytest.approx(mix["arrivals"]["rate_per_s"], rel=0.05)
    p = np.array([r.prompt_len for r in reqs])
    o = np.array([r.output_len for r in reqs])
    assert p.min() >= 16 and p.max() <= 512
    assert o.min() >= 16 and o.max() <= 256
    assert np.median(p) == pytest.approx(96, rel=0.1)
    assert np.median(o) == pytest.approx(52, rel=0.1)
    assert 0.25 < np.mean(p > 128) < 0.45       # about a third in bucket 512
    assert np.all(p + o <= mix["max_total_tokens"])


def test_closed_mix_fits_one_lane_each():
    mix = _mix("longprompt_closed")
    clients = traffic.serve_schedule(mix, 10.0)
    assert len(clients) == mix["clients"] == 8
    for reqs in clients:
        assert all(520 <= r.prompt_len <= 960 for r in reqs)
        assert all(24 <= r.output_len <= 40 for r in reqs)
        assert all(r.prompt_len + r.output_len <= 1024 for r in reqs)
    assert len({r.index for c in clients for r in c}) == \
        sum(len(c) for c in clients)


@pytest.mark.parametrize("call,spec", [
    (traffic.draw_arrivals, {"process": "gamma", "rate_per_s": 2.0, "cv": 3.0}),
    (traffic.draw_lengths, {"dist": "fixed", "value": 7}),
])
def test_a_process_or_distribution_no_mix_uses_is_refused(call, spec):
    """The generator knows what the committed mixes use and no more; the PR
    that adds a bursty mix brings its process along."""
    with pytest.raises(ValueError, match="unknown"):
        call(spec, 10, traffic._rng(3, 1))


def test_pretrain_batches_are_fresh_and_every_row_differs():
    mix = _mix("s128_mb64_1chip")
    gen = traffic.pretrain_batches(mix, 30528, 8, 2 ** 31 + 9)
    a, b = next(gen), next(gen)
    ids, types, attn, labels, nsp = a
    assert ids.shape == labels.shape == (8, 128) and nsp.shape == (8,)
    assert ids.dtype == np.int32 and ids.max() < 30528
    assert len({row.tobytes() for row in ids}) == 8
    assert not np.array_equal(a[0], b[0])
    assert np.all((labels == -1) | (labels == ids))
    assert np.all((labels >= 0).sum(axis=1) >= 1)
    again = next(traffic.pretrain_batches(mix, 30528, 8, 2 ** 31 + 9))
    assert np.array_equal(again[0], ids)
