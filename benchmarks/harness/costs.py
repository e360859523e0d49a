"""Operations and bytes the algorithms need, computed from shapes, and the
table of hardware peaks they are held against. The yardstick: it lives with
the benchmark so that no PR that claims a gain can change it.

``train_flops_per_token`` is a copy of ``bench.py::_perf_fields``'
arithmetic (the original is listed in PERF.md for a later PR to delete).
"""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


class UnknownDeviceKind(KeyError):
    """The peaks table has no row for this ``device_kind``: an error, never
    a default."""


def peaks_for(device_kind, table_path=None):
    with open(table_path or os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise UnknownDeviceKind(
            f"no peaks recorded for device_kind {device_kind!r}; known: "
            f"{sorted(table)}")
    return dict(table[device_kind])


def transformer_matmul_params(hidden, intermediate, layers):
    """Weights that every token is multiplied by in the blocks: QKV, the
    attention output, and the two feed-forward matrices."""
    return layers * (4 * hidden * hidden + 2 * hidden * intermediate)


def train_flops_per_token(*, hidden, intermediate, layers, seq_len, vocab,
                          head_tokens_share=1.0):
    """Forward + backward model FLOPs per trained token; recomputation is
    not counted. 6 FLOPs per matmul weight (2 forward, 4 backward), the
    attention scores and the weighted sum (2 * 2 * S * hidden forward per
    layer, three times that with the backward), and the vocabulary
    projection on the share of tokens that reach it."""
    blocks = 6 * transformer_matmul_params(hidden, intermediate, layers)
    attention = 3 * layers * 4 * seq_len * hidden
    head = 6 * hidden * vocab * head_tokens_share
    return float(blocks + attention + head)


def decode_step_min_bytes(*, hidden, intermediate, layers, vocab,
                          live_kv_tokens, weight_bytes, kv_bytes):
    """Least HBM traffic of one decode step: every weight read once (blocks,
    biases and layer norms, the tied embedding for the logits; the one row of
    the position table is ignored) plus the live keys and values of the
    active lanes read once (the one new row written per lane is thousands of
    times smaller and ignored)."""
    block = transformer_matmul_params(hidden, intermediate, layers)
    small = layers * (9 * hidden + intermediate) + 2 * hidden
    weights = (block + small + vocab * hidden) * weight_bytes
    kv = 2 * layers * live_kv_tokens * hidden * kv_bytes
    return float(weights + kv)


def decode_step_flops(*, hidden, intermediate, layers, vocab, lanes,
                      live_kv_tokens):
    """FLOPs of one decode step over ``lanes`` active lanes."""
    per_lane = 2 * (transformer_matmul_params(hidden, intermediate, layers)
                    + vocab * hidden)
    return float(lanes * per_lane + 4 * layers * live_kv_tokens * hidden)
