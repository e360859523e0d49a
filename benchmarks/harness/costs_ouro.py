"""Operations and bytes one decode step of the Ouro configuration needs,
computed from the configuration's shapes. The yardstick of
``ouro_decode_step_roofline`` and ``ouro_loop_cache_bytes_share``: it lives
with the benchmark so that no PR that claims a gain can change it.
Everything is a function of the configuration file's keys. The stack of
``num_hidden_layers`` layers is run ``total_ut_steps`` times a token with
one set of weights: a step owes ``total_ut_steps`` sweeps of the layers'
weights whatever implements it (pass ``t + 1`` of layer 0 needs pass ``t``
of the last layer, and the layers do not stay on the chip between sweeps),
and a token caches a row of keys and a row of values a (pass, layer)."""


def layer_params(cfg):
    """One layer: the four attention projections, the three matrices of
    the SwiGLU and four norms."""
    d = cfg["hidden_size"]
    n, g, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    return (2 * d * n * hd + 2 * d * g * hd
            + 3 * d * cfg["intermediate_size"] + 4 * d)


def stack_params(cfg):
    """The weights one pass of the stack reads."""
    return cfg["num_hidden_layers"] * layer_params(cfg)


def head_params(cfg):
    """The output head and the final norm. (Of the embedding a step reads
    one row a lane: ignored.)"""
    d = cfg["hidden_size"]
    return d * cfg["vocab_size"] + d


def total_params(cfg):
    """All of it: the layers once, the embedding, the untied head, the
    final norm and the exit gate with its bias."""
    d = cfg["hidden_size"]
    return stack_params(cfg) + head_params(cfg) + cfg["vocab_size"] * d + d + 1


def cache_rows(cfg):
    """Rows a token caches of keys, and as many of values."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def cache_bytes_per_token(cfg, kv_bytes):
    """Keys and values a token caches over all its rows."""
    return (cache_rows(cfg) * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * kv_bytes)


def cache_bytes(cfg, *, context_tokens, kv_bytes):
    """Keys and values a step reads whose active lanes hold
    ``context_tokens`` positions between them: every row of every one."""
    return float(context_tokens * cache_bytes_per_token(cfg, kv_bytes))


def decode_step_min_bytes(cfg, *, context_tokens, weight_bytes):
    """Least HBM traffic of one decode step: ``total_ut_steps`` sweeps of
    the layers' weights, the head once, and the cached keys and values of
    the positions the active lanes hold, every (pass, layer) row of them."""
    return (cfg["total_ut_steps"] * stack_params(cfg) * weight_bytes
            + head_params(cfg) * weight_bytes
            + cache_bytes(cfg, context_tokens=context_tokens,
                          kv_bytes=weight_bytes))


def decode_step_flops(cfg, *, lanes, context_tokens):
    """FLOPs of one decode step: 2 a weight a lane a pass for the layers
    and 2 a weight a lane for the head, and attention (2 a query head a
    cached position a channel, for the scores and again for the context, in
    every (pass, layer))."""
    attend = (4 * cfg["num_attention_heads"] * cfg["head_dim"]
              * context_tokens * cache_rows(cfg))
    return float(2 * lanes * (cfg["total_ut_steps"] * stack_params(cfg)
                              + head_params(cfg)) + attend)


def step_means(counters):
    """What an average decode step of a window held, from the program's
    counters over it (``ServingMetrics``): ``(lanes, context tokens)``;
    None where the program does not count them."""
    steps = counters.get("decode_steps", 0)
    if not steps or "decode_context_tokens" not in counters or not (
            counters.get("loop_passes", 0)):
        return None
    return (counters.get("tokens_emitted", 0) / steps,
            counters["decode_context_tokens"] / steps)


def step_costs(cfg, counters):
    """``(least bytes, FLOPs, bytes of keys and values)`` of an average
    decode step of a window, or None where ``step_means`` finds nothing."""
    means = step_means(counters)
    if means is None:
        return None
    lanes, context = means
    width = {"bfloat16": 2, "float32": 4}[cfg["serving"]["param_dtype"]]
    return (decode_step_min_bytes(cfg, context_tokens=context,
                                  weight_bytes=width),
            decode_step_flops(cfg, lanes=lanes, context_tokens=context),
            cache_bytes(cfg, context_tokens=context, kv_bytes=width))
