"""Operations and bytes one decode step of the Laguna configuration needs,
computed from the configuration's shapes. The yardstick of
``laguna_decode_step_roofline`` and ``laguna_full_kv_bytes_share``: it lives
with the benchmark so that no PR that claims a gain can change it.
Everything is a function of the configuration file's keys (the first
``num_hidden_layers`` entries of ``layer_types``, ``mlp_layer_types`` and
``num_attention_heads_per_layer``; every expert of a layer is held)."""


def _layers(cfg):
    """``(window?, sparse?, query heads)`` of each layer that is run."""
    L = cfg["num_hidden_layers"]
    return list(zip(
        (t == "sliding_attention" for t in cfg["layer_types"][:L]),
        (t == "sparse" for t in cfg["mlp_layer_types"][:L]),
        cfg["num_attention_heads_per_layer"][:L]))


def attention_params(cfg, heads):
    """``q_proj`` and ``o_proj`` at the layer's own head count, ``k_proj``
    and ``v_proj`` at the key-value heads', and the gate's one column a
    head."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return (2 * d * heads * hd + 2 * d * cfg["num_key_value_heads"] * hd
            + d * heads)


def expert_params(cfg):
    """One routed expert: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"]


def dense_mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def non_expert_params(cfg):
    """Every weight a decode step reads whatever the routing: attention with
    its gate, the two norms a layer, the dense MLP, routers, shared experts,
    the final norm and the output head. (Of the embedding a step reads one
    row a lane: ignored.)"""
    d = cfg["hidden_size"]
    total = d + d * cfg["vocab_size"]                # final norm, head
    for _window, sparse, heads in _layers(cfg):
        total += 2 * d + attention_params(cfg, heads)
        if sparse:
            total += d * cfg["num_experts"] + shared_expert_params(cfg)
        else:
            total += dense_mlp_params(cfg)
    return total


def total_params(cfg):
    """All of it, the embedding and every expert too."""
    sparse = sum(1 for _w, s, _h in _layers(cfg) if s)
    return (non_expert_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"]
            + sparse * cfg["num_experts"] * expert_params(cfg))


def kv_row_values(cfg):
    """Values a token caches in one layer: keys and values of the key-value
    heads."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def full_kv_bytes(cfg, *, context_tokens, kv_bytes):
    """Keys and values the full-attention layers read in a step whose
    active lanes hold ``context_tokens`` positions between them."""
    n_full = sum(1 for w, _s, _h in _layers(cfg) if not w)
    return float(n_full * context_tokens * kv_row_values(cfg) * kv_bytes)


def ring_bytes(cfg, *, lanes, kv_bytes):
    """The window layers' rings of ``lanes`` active lanes, read once (a
    ring is read whole whatever it holds; the one new row a lane is
    ignored)."""
    n_window = sum(1 for w, _s, _h in _layers(cfg) if w)
    return float(n_window * lanes * cfg["sliding_window"]
                 * kv_row_values(cfg) * kv_bytes)


def decode_step_min_bytes(cfg, *, lanes, experts_touched, context_tokens,
                          weight_bytes):
    """Least HBM traffic of one decode step: the non-expert weights and the
    head once; the experts the step TOUCHED (``experts_touched``: summed
    over the step's expert layers), each read once at three matrices; the
    full layers' keys and values of the positions the active lanes hold;
    the window layers' rings of the active lanes."""
    return (non_expert_params(cfg) * weight_bytes
            + experts_touched * expert_params(cfg) * weight_bytes
            + full_kv_bytes(cfg, context_tokens=context_tokens,
                            kv_bytes=weight_bytes)
            + ring_bytes(cfg, lanes=lanes, kv_bytes=weight_bytes))


def decode_step_flops(cfg, *, lanes, picks, context_tokens):
    """FLOPs of one decode step: 2 a weight a lane for what every lane is
    multiplied by, 2 a weight a pick for the routed experts, and attention
    (scores and values, 2 each a query head a cached position a channel:
    the context in a full layer, the ring in a window layer)."""
    hd, W = cfg["head_dim"], cfg["sliding_window"]
    attend = sum(4 * heads * hd * (lanes * W if window else context_tokens)
                 for window, _s, heads in _layers(cfg))
    return float(2 * lanes * non_expert_params(cfg)
                 + 2 * picks * expert_params(cfg) + attend)


def step_means(counters):
    """What an average decode step of a window held, from the program's
    counters over it (``ServingMetrics``): ``(lanes, context tokens, experts
    touched, picks)``; None where the program does not count them."""
    steps = counters.get("decode_steps", 0)
    if not steps or "decode_context_tokens" not in counters or (
            "moe_experts_touched" not in counters):
        return None
    return (counters.get("tokens_emitted", 0) / steps,
            counters["decode_context_tokens"] / steps,
            counters["moe_experts_touched"] / steps,
            counters.get("moe_picks_here", 0) / steps)
