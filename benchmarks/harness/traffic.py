"""The one general traffic generator. A traffic mix is a data file of
parameters (``traffic/<name>.json``); this module turns it into a schedule.

Serving: arrival times and lengths come from the file's ``schedule_seed`` and
are the same in every run; ``--seed`` draws only the token ids. So every run
offers the same requests, parent and change are compared on identical
traffic, and what a set of runs spreads by is the system and not the draw.

Training: token batches come from ``--seed``; every step gets a fresh batch
of the same shape.
"""

import math

import numpy as np


def _rng(seed, stream=0):
    """A generator for any whole-number seed (the driver's are above 2**31);
    ``stream`` separates independent draws of one seed."""
    return np.random.Generator(np.random.PCG64([int(seed), int(stream)]))


def draw_lengths(spec, n, rng):
    """``n`` whole-number lengths from a distribution spec:
    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"dist": "uniform", "min", "max"}`` (inclusive)."""
    dist = spec["dist"]
    if dist == "uniform":
        return rng.integers(int(spec["min"]), int(spec["max"]) + 1, n)
    if dist == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def draw_arrivals(spec, horizon_s, rng):
    """Arrival times in [0, horizon_s) of an open loop:
    ``{"process": "poisson", "rate_per_s"}``."""
    rate = float(spec["rate_per_s"])
    n = int(rate * horizon_s * 1.5) + 64
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    times = np.cumsum(rng.exponential(1.0 / rate, n))
    if times[-1] < horizon_s:
        raise ValueError("arrival draw too short for the horizon")
    return times[times < horizon_s]


class Request:
    __slots__ = ("index", "client", "due_s", "prompt_len", "output_len")

    def __init__(self, index, client, due_s, prompt_len, output_len):
        self.index = index
        self.client = client          # closed loop: which client sends it
        self.due_s = due_s            # open loop: seconds after schedule start
        self.prompt_len = int(prompt_len)
        self.output_len = int(output_len)


def serve_schedule(traffic, horizon_s):
    """The fixed schedule of a serving mix: a list of ``Request`` for an open
    loop (sorted by due time), or one list per client for a closed loop (each
    client sends its next request when the previous one completes).
    Depends on the file alone, never on ``--seed``."""
    seed = traffic["schedule_seed"]
    limit = int(traffic["max_total_tokens"])

    def lengths(n, stream):
        p = draw_lengths(traffic["prompt_tokens"], n, _rng(seed, stream))
        o = draw_lengths(traffic["output_tokens"], n, _rng(seed, stream + 1))
        return p, np.minimum(o, limit - p)

    if traffic["loop"] == "open":
        due = draw_arrivals(traffic["arrivals"], horizon_s, _rng(seed, 1))
        p, o = lengths(len(due), 2)
        return [Request(i, None, float(due[i]), p[i], o[i])
                for i in range(len(due))]
    if traffic["loop"] == "closed":
        per_client = int(traffic["requests_per_client"])
        out, index = [], 0
        for c in range(int(traffic["clients"])):
            p, o = lengths(per_client, 10 + 2 * c)
            out.append([Request(index + j, c, None, p[j], o[j])
                        for j in range(per_client)])
            index += per_client
        return out
    raise ValueError(f"unknown loop kind {traffic['loop']!r}")


def prompt_ids(seed, request, vocab_size):
    """Token ids of one request's prompt, from ``--seed`` and the request's
    place in the schedule (uniform over the vocabulary)."""
    rng = _rng(seed, 1000 + request.index)
    return rng.integers(0, vocab_size, request.prompt_len).astype(np.int32)


def pretrain_batches(traffic, vocab_size, global_batch, seed):
    """Endless masked-LM + next-sentence batches with something to learn:
    token ids follow a Zipf law, and the label of a masked position is the
    token under the mask. Every row differs; every step gets a fresh batch.
    Yields (input_ids, token_type_ids, attention_mask, mlm_labels,
    nsp_labels) as int32 numpy arrays."""
    seq = int(traffic["seq_len"])
    tok = traffic["tokens"]
    if tok["dist"] != "zipf":
        raise ValueError(f"unknown token distribution {tok['dist']!r}")
    p = 1.0 / (np.arange(vocab_size) + float(tok["offset"]))
    cdf = np.cumsum(p / p.sum())
    rng = _rng(seed, 7)
    rate = float(traffic["mlm_mask_rate"])
    while True:
        u = rng.random((global_batch, seq))
        ids = np.minimum(np.searchsorted(cdf, u), vocab_size - 1)
        masked = rng.random((global_batch, seq)) < rate
        masked[:, 0] |= ~masked.any(axis=1)     # every row has a label
        yield (ids.astype(np.int32),
               np.zeros((global_batch, seq), np.int32),
               np.ones((global_batch, seq), np.int32),
               np.where(masked, ids, -1).astype(np.int32),
               rng.integers(0, 2, global_batch).astype(np.int32))
