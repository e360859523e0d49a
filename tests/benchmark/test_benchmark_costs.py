"""The yardstick's arithmetic: FLOPs, bytes and the table of peaks."""

import pytest

from benchmarks.harness import costs


def test_bert_large_flops_per_token_by_hand():
    got = costs.train_flops_per_token(hidden=1024, intermediate=4096,
                                      layers=24, seq_len=128, vocab=30528)
    blocks = 6 * 24 * (4 * 1024 * 1024 + 2 * 1024 * 4096)
    attention = 3 * 24 * 4 * 128 * 1024
    head = 6 * 1024 * 30528
    assert got == blocks + attention + head
    # bench.py's _perf_fields gives the same shape of number: ~2.04 GFLOP
    assert 1.9e9 < got < 2.2e9


def test_decode_step_bytes_are_the_weights_plus_the_live_kv():
    kw = dict(hidden=1280, intermediate=5120, layers=36, vocab=50304,
              weight_bytes=2, kv_bytes=2)
    empty = costs.decode_step_min_bytes(live_kv_tokens=0, **kw)
    params = 36 * (4 * 1280 * 1280 + 2 * 1280 * 5120 + 9 * 1280 + 5120) \
        + 2 * 1280 + 50304 * 1280
    assert empty == 2 * params
    assert 1.5e9 < empty < 1.6e9                 # GPT-2 large in bf16
    full = costs.decode_step_min_bytes(live_kv_tokens=1000, **kw)
    assert full - empty == 2 * 36 * 1000 * 1280 * 2
    flops = costs.decode_step_flops(hidden=1280, intermediate=5120,
                                    layers=36, vocab=50304, lanes=4,
                                    live_kv_tokens=1000)
    assert flops > 4 * 2 * 36 * 12 * 1280 * 1280


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_is_an_error():
    v5e = costs.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(costs.UnknownDeviceKind):
        costs.peaks_for("cpu")
    with pytest.raises(costs.UnknownDeviceKind):
        costs.peaks_for("TPU v9 imaginary")
