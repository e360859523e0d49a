"""Operations and bytes one decode step of the MiMo-V2 configuration needs,
computed from the configuration's shapes. The yardstick of
``mimo_decode_step_roofline`` and ``mimo_attn_bytes_share``: it lives with
the benchmark so that no PR that claims a gain can change it. Everything is
a function of the configuration file's keys (the first ``num_hidden_layers``
entries of ``hybrid_layer_pattern`` and ``moe_layer_freq``; the file's
``n_routed_experts`` counts the experts held, ``share`` gives the published
count, which is the router's width)."""


def _layers(cfg):
    """``(window?, sparse?)`` of each layer that is run."""
    L = cfg["num_hidden_layers"]
    return list(zip((p == 1 for p in cfg["hybrid_layer_pattern"][:L]),
                    (f == 1 for f in cfg["moe_layer_freq"][:L])))


def _shape(cfg, window):
    """``(query heads, key-value heads, key head, value head)`` of a layer
    kind."""
    pre = "swa_" if window else ""
    return (cfg[pre + "num_attention_heads"],
            cfg[pre + "num_key_value_heads"], cfg[pre + "head_dim"],
            cfg[pre + "v_head_dim"])


def attention_params(cfg, window):
    """``q_proj`` and ``k_proj`` at the key head's size, ``v_proj`` and
    ``o_proj`` at the value head's, and a window layer's sink a query
    head."""
    d = cfg["hidden_size"]
    n, g, hd, vd = _shape(cfg, window)
    sink = n if window and cfg["add_swa_attention_sink_bias"] else 0
    return d * n * hd + d * g * hd + d * g * vd + n * vd * d + sink


def expert_params(cfg):
    """One routed expert: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg):
    """The router at its published width, and its correction bias."""
    experts = cfg.get("share", {}).get("n_routed_experts_published",
                                       cfg["n_routed_experts"])
    return cfg["hidden_size"] * experts + experts


def non_expert_params(cfg):
    """Every weight a decode step reads whatever the routing: attention
    with its sinks, the two norms a layer, the dense MLP, routers, the final
    norm and the rows of the output head that are held. (Of the embedding a
    step reads one row a lane: ignored.)"""
    d = cfg["hidden_size"]
    total = d + d * cfg["vocab_size"]                # final norm, head
    for window, sparse in _layers(cfg):
        total += 2 * d + attention_params(cfg, window)
        total += router_params(cfg) if sparse else dense_mlp_params(cfg)
    return total


def total_params(cfg):
    """All of it, the embedding and every held expert too."""
    sparse = sum(1 for _w, s in _layers(cfg) if s)
    return (non_expert_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"]
            + sparse * cfg["n_routed_experts"] * expert_params(cfg))


def kv_row_values(cfg, window):
    """Values a token caches in one layer of a kind: keys and values of the
    kind's key-value heads, each at its own head size."""
    _n, g, hd, vd = _shape(cfg, window)
    return g * (hd + vd)


def page_bytes(cfg, *, context_tokens, kv_bytes):
    """Keys and values the full-attention layers read in a step whose
    active lanes hold ``context_tokens`` positions between them."""
    n_full = sum(1 for w, _s in _layers(cfg) if not w)
    return float(n_full * context_tokens * kv_row_values(cfg, False)
                 * kv_bytes)


def ring_bytes(cfg, *, ring_positions, kv_bytes):
    """Keys and values the window layers read in a step: ``ring_positions``
    is what the rings of the active lanes hold behind their masks, summed
    over the window layers (``decode_ring_positions``)."""
    return float(ring_positions * kv_row_values(cfg, True) * kv_bytes)


def decode_step_min_bytes(cfg, *, experts_touched, context_tokens,
                          ring_positions, weight_bytes):
    """Least HBM traffic of one decode step: the non-expert weights and the
    head once; the experts the step TOUCHED (``experts_touched``: summed
    over the step's expert layers), each read once at three matrices; the
    full layers' keys and values of the positions the active lanes hold;
    the window layers' rings as far as they are behind the mask."""
    return (non_expert_params(cfg) * weight_bytes
            + experts_touched * expert_params(cfg) * weight_bytes
            + page_bytes(cfg, context_tokens=context_tokens,
                         kv_bytes=weight_bytes)
            + ring_bytes(cfg, ring_positions=ring_positions,
                         kv_bytes=weight_bytes))


def decode_step_flops(cfg, *, lanes, picks, context_tokens, ring_positions):
    """FLOPs of one decode step: 2 a weight a lane for what every lane is
    multiplied by, 2 a weight a pick for the routed experts, and attention
    (2 a query head a cached position a channel, over the key head for the
    scores and over the value head for the context)."""
    window_layers = sum(1 for w, _s in _layers(cfg) if w)
    attend = 0.0
    for window, _sparse in _layers(cfg):
        n, _g, hd, vd = _shape(cfg, window)
        held = (ring_positions / window_layers if window else context_tokens)
        attend += 2 * n * (hd + vd) * held
    return float(2 * lanes * non_expert_params(cfg)
                 + 2 * picks * expert_params(cfg) + attend)


def step_means(counters):
    """What an average decode step of a window held, from the program's
    counters over it (``ServingMetrics``): ``(lanes, context tokens, ring
    positions, experts touched, picks)``; None where the program does not
    count them."""
    steps = counters.get("decode_steps", 0)
    if not steps or any(name not in counters for name in (
            "decode_context_tokens", "decode_ring_positions",
            "moe_experts_touched")):
        return None
    return (counters.get("tokens_emitted", 0) / steps,
            counters["decode_context_tokens"] / steps,
            counters["decode_ring_positions"] / steps,
            counters["moe_experts_touched"] / steps,
            counters.get("moe_picks_here", 0) / steps)


def step_costs(cfg, counters):
    """``(least bytes, FLOPs, bytes of pages and rings)`` of an average
    decode step of a window, or None where ``step_means`` finds nothing."""
    means = step_means(counters)
    if means is None:
        return None
    lanes, context, ring, touched, picks = means
    width = {"bfloat16": 2, "float32": 4}[cfg["serving"]["param_dtype"]]
    return (decode_step_min_bytes(cfg, experts_touched=touched,
                                  context_tokens=context,
                                  ring_positions=ring, weight_bytes=width),
            decode_step_flops(cfg, lanes=lanes, picks=picks,
                              context_tokens=context, ring_positions=ring),
            page_bytes(cfg, context_tokens=context, kv_bytes=width)
            + ring_bytes(cfg, ring_positions=ring, kv_bytes=width))
