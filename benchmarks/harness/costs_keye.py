"""Operations and bytes one decode step of the Keye-VL configuration needs,
computed from the configuration's shapes. The yardstick of
``keye_decode_step_roofline``: it lives with the benchmark so that no PR
that claims a gain can change it. Everything is a function of the
configuration file's keys (every layer is alike; the file's ``num_experts``
counts the experts held, ``share`` gives the published count, which is the
router's width).

What a step owes its cache is what the mechanism says it reads and no more:
every cached key of the indexer (one head of ``indexer_head_dim`` a
position), and the keys and values of the ``min(context, topk)`` positions
it selected. A step that reads every key and value of its lanes' contexts
reads more than this and so reads LOW on the roofline, not high."""


def _sa(cfg):
    return cfg["sa_config"]


def attention_params(cfg):
    """``q_proj``, ``k_proj``, ``v_proj``, ``o_proj`` and the two head
    norms' scales."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    n, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * n * hd + 2 * d * g * hd + n * hd * d + 2 * hd


def indexer_params(cfg):
    """The indexer's query, key and head-weight projections, and its key
    norm's scale and bias."""
    d, sa = cfg["hidden_size"], _sa(cfg)
    hi = sa["indexer_head_dim"]
    return (d * sa["indexer_num_heads"] * hi
            + d * hi * sa["indexer_num_kv_heads"]
            + d * sa["indexer_num_heads"] + 2 * hi)


def expert_params(cfg):
    """One routed expert: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg):
    """The router at its published width; no bias."""
    experts = cfg.get("share", {}).get("num_experts_published",
                                       cfg["num_experts"])
    return cfg["hidden_size"] * experts


def layer_non_expert_params(cfg):
    """What of a layer a decode step reads whatever the routing."""
    return (2 * cfg["hidden_size"] + attention_params(cfg)
            + indexer_params(cfg) + router_params(cfg))


def non_expert_params(cfg):
    """Every weight a decode step reads whatever the routing: attention,
    indexer, router and the two norms of every layer, the final norm and the
    rows of the output head that are held. (Of the embedding a step reads
    one row a lane: ignored.)"""
    d = cfg["hidden_size"]
    return (d + d * cfg["vocab_size"]
            + cfg["num_hidden_layers"] * layer_non_expert_params(cfg))


def total_params(cfg):
    """All of it, the embedding and every held expert too."""
    return (non_expert_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"]
            + cfg["num_hidden_layers"] * cfg["num_experts"]
            * expert_params(cfg))


def index_row_values(cfg):
    """Values a position caches a layer for the indexer."""
    sa = _sa(cfg)
    return sa["indexer_num_kv_heads"] * sa["indexer_head_dim"]


def kv_row_values(cfg):
    """Values a position caches a layer for keys and values together."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def cache_bytes_per_token(cfg, kv_bytes):
    return cfg["num_hidden_layers"] * (
        kv_row_values(cfg) + index_row_values(cfg)) * kv_bytes


def index_bytes(cfg, *, keys_scored, kv_bytes):
    """Indexer keys a step reads: ``keys_scored`` sums, over the step's
    layers and active lanes, the keys the indexer scored."""
    return float(keys_scored * index_row_values(cfg) * kv_bytes)


def selected_bytes(cfg, *, keys_attended, kv_bytes):
    """Keys and values of the selected positions: ``keys_attended`` sums,
    over layers and lanes, ``min(context, topk)``."""
    return float(keys_attended * kv_row_values(cfg) * kv_bytes)


def dense_bytes(cfg, *, keys_scored, kv_bytes):
    """What the same lanes' keys and values are in all: what a step that
    attends to every position reads."""
    return float(keys_scored * kv_row_values(cfg) * kv_bytes)


def decode_step_min_bytes(cfg, *, experts_touched, keys_scored,
                          keys_attended, weight_bytes):
    """Least HBM traffic of one decode step: the non-expert weights and the
    head once; the experts the step TOUCHED (summed over its layers), each
    read once at three matrices; the indexer's keys of every position the
    active lanes hold; the keys and values of the positions selected."""
    return (non_expert_params(cfg) * weight_bytes
            + experts_touched * expert_params(cfg) * weight_bytes
            + index_bytes(cfg, keys_scored=keys_scored, kv_bytes=weight_bytes)
            + selected_bytes(cfg, keys_attended=keys_attended,
                             kv_bytes=weight_bytes))


def decode_step_flops(cfg, *, lanes, picks, keys_scored, keys_attended):
    """FLOPs of one decode step: 2 a weight a lane for what every lane is
    multiplied by, 2 a weight a pick for the routed experts, the index
    scores (2 an indexer head a scored key a channel) and attention (2 a
    query head a selected key a channel, for the scores and again for the
    context)."""
    sa = _sa(cfg)
    index = 2 * sa["indexer_num_heads"] * sa["indexer_head_dim"] * keys_scored
    attend = (4 * cfg["num_attention_heads"] * cfg["head_dim"]
              * keys_attended)
    return float(2 * lanes * non_expert_params(cfg)
                 + 2 * picks * expert_params(cfg) + index + attend)


def step_means(counters):
    """What an average decode step of a window held, from the program's
    counters over it (``ServingMetrics``): ``(lanes, keys scored, keys
    attended, experts touched, picks)``; None where the program does not
    count them (a checkout without the indexer's counters)."""
    steps = counters.get("decode_steps", 0)
    if not steps or any(name not in counters for name in (
            "dsa_keys_scored", "dsa_keys_attended", "moe_experts_touched")):
        return None
    return (counters.get("tokens_emitted", 0) / steps,
            counters["dsa_keys_scored"] / steps,
            counters["dsa_keys_attended"] / steps,
            counters["moe_experts_touched"] / steps,
            counters.get("moe_picks_here", 0) / steps)


def step_costs(cfg, counters):
    """``(least bytes, FLOPs, dense bytes)`` of an average decode step of a
    window, or None where ``step_means`` finds nothing; the third is what
    the step's least bytes would be if it attended to every position."""
    means = step_means(counters)
    if means is None:
        return None
    lanes, scored, attended, touched, picks = means
    width = {"bfloat16": 2, "float32": 4}[cfg["serving"]["param_dtype"]]
    least = decode_step_min_bytes(cfg, experts_touched=touched,
                                  keys_scored=scored, keys_attended=attended,
                                  weight_bytes=width)
    every = (least - selected_bytes(cfg, keys_attended=attended,
                                    kv_bytes=width)
             + dense_bytes(cfg, keys_scored=scored, kv_bytes=width))
    return (least,
            decode_step_flops(cfg, lanes=lanes, picks=picks,
                              keys_scored=scored, keys_attended=attended),
            every)
