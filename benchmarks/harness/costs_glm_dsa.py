"""Operations and bytes one decode step of the GLM-5.2 configuration needs,
computed from the configuration's shapes. The yardstick of
``glm_decode_step_roofline``: it lives with the benchmark so that no PR that
claims a gain can change it. Everything is a function of the configuration
file's keys (the file's ``num_hidden_layers``, ``n_routed_experts`` and
``vocab_size`` count what is held; ``share`` gives the published counts,
where the share starts and so which entries of ``indexer_types`` and
``mlp_layer_types`` are this chip's).

What a step owes its cache is what the mechanism says it reads and no more:
in the layers that run the indexer (``full``), every cached key of the
indexer (one head of ``index_head_dim`` a position); in every layer, the
latent rows of the ``min(context, index_topk)`` positions selected, at the
576 values a token caches (the pool keeps a row 640 wide so that it lies
along the chip's lanes: the 64 of padding are the implementation's, not the
mechanism's, and are not owed). A step that read every latent row of its
lanes' contexts, or ran an indexer in a ``shared`` layer, reads more than
this and so reads LOW on the roofline, not high."""


def layers_held(cfg):
    """The published numbers of the layers this chip holds."""
    first = cfg.get("share", {}).get("first_layer", 0)
    return range(first, first + cfg["num_hidden_layers"])


def selecting_layers(cfg):
    return sum(cfg["indexer_types"][l] == "full" for l in layers_held(cfg))


def expert_layers(cfg):
    return sum(cfg["mlp_layer_types"][l] == "sparse"
               for l in layers_held(cfg))


def attention_params(cfg):
    """``q_a``, ``q_b``, ``kv_a``, ``kv_b``, ``o`` and the two latent
    norms' scales."""
    d, n = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * qr + qr * n * cfg["qk_head_dim"]
            + d * (kr + cfg["qk_rope_head_dim"])
            + kr * n * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + n * cfg["v_head_dim"] * d + qr + kr)


def indexer_params(cfg):
    """A ``full`` layer's indexer: queries from the query latent, one key
    head and the head weights from the layer's input, and the key's
    LayerNorm (scale and bias)."""
    hi = cfg["index_head_dim"]
    return (cfg["q_lora_rank"] * cfg["index_n_heads"] * hi
            + cfg["hidden_size"] * hi
            + cfg["hidden_size"] * cfg["index_n_heads"] + 2 * hi)


def expert_params(cfg):
    """One routed expert (or the shared one): three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_ffn_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg):
    """The router at its published width, with its correction bias."""
    experts = cfg.get("share", {}).get("n_routed_experts_published",
                                       cfg["n_routed_experts"])
    return (cfg["hidden_size"] + 1) * experts


def non_expert_params(cfg):
    """Every weight a decode step reads whatever the routing: attention and
    the two norms of every layer, the indexer of a ``full`` layer, the dense
    FFN of a ``dense`` layer, the router and the shared expert of an expert
    layer, the final norm and the rows of the output head that are held.
    (Of the embedding a step reads one row a lane: ignored.)"""
    d = cfg["hidden_size"]
    sparse = expert_layers(cfg)
    return (d + d * cfg["vocab_size"]
            + cfg["num_hidden_layers"] * (2 * d + attention_params(cfg))
            + selecting_layers(cfg) * indexer_params(cfg)
            + (cfg["num_hidden_layers"] - sparse) * dense_ffn_params(cfg)
            + sparse * (router_params(cfg)
                        + cfg["n_shared_experts"] * expert_params(cfg)))


def total_params(cfg):
    """All of it, the embedding and every held expert too."""
    return (non_expert_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"]
            + expert_layers(cfg) * cfg["n_routed_experts"]
            * expert_params(cfg))


def index_row_values(cfg):
    """Values a position caches in a ``full`` layer for the indexer."""
    return cfg["index_head_dim"]


def latent_row_values(cfg):
    """Values a position caches a layer for attention: the latent and the
    rotary key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def cache_bytes_per_token(cfg, kv_bytes):
    return (cfg["num_hidden_layers"] * latent_row_values(cfg)
            + selecting_layers(cfg) * index_row_values(cfg)) * kv_bytes


def index_bytes(cfg, *, keys_scored, kv_bytes):
    """Indexer keys a step reads: ``keys_scored`` sums, over the step's
    ``full`` layers and active lanes, the keys the indexer scored."""
    return float(keys_scored * index_row_values(cfg) * kv_bytes)


def selected_bytes(cfg, *, keys_attended, kv_bytes):
    """Latent rows of the selected positions: ``keys_attended`` sums, over
    ALL the step's layers and lanes, ``min(context, index_topk)``."""
    return float(keys_attended * latent_row_values(cfg) * kv_bytes)


def decode_step_min_bytes(cfg, *, experts_touched, keys_scored,
                          keys_attended, weight_bytes):
    """Least HBM traffic of one decode step: the non-expert weights and the
    head once; the experts the step TOUCHED (summed over its layers), each
    read once at three matrices; the indexer's keys of every position the
    active lanes hold, in the ``full`` layers; the latent rows of the
    positions selected, in every layer."""
    return (non_expert_params(cfg) * weight_bytes
            + experts_touched * expert_params(cfg) * weight_bytes
            + index_bytes(cfg, keys_scored=keys_scored, kv_bytes=weight_bytes)
            + selected_bytes(cfg, keys_attended=keys_attended,
                             kv_bytes=weight_bytes))


def decode_step_flops(cfg, *, lanes, picks, keys_scored, keys_attended):
    """FLOPs of one decode step: 2 a weight a lane for what every lane is
    multiplied by, 2 a weight a pick for the routed experts, the index
    scores (2 an indexer head a scored key a channel) and absorbed latent
    attention (a query head against a selected row: 2 a cached value for the
    score, 2 a latent value for the context)."""
    index = (2 * cfg["index_n_heads"] * cfg["index_head_dim"] * keys_scored)
    attend = (2 * cfg["num_attention_heads"]
              * (latent_row_values(cfg) + cfg["kv_lora_rank"])
              * keys_attended)
    return float(2 * lanes * non_expert_params(cfg)
                 + 2 * picks * expert_params(cfg) + index + attend)


def step_means(counters):
    """What an average decode step of a window held, from the program's
    counters over it (``ServingMetrics``): ``(lanes, keys scored, keys
    attended, experts touched, picks)``; None where the program does not
    count them (a checkout without the counters)."""
    steps = counters.get("decode_steps", 0)
    if not steps or any(name not in counters for name in (
            "dsa_keys_scored", "dsa_keys_attended",
            "dsa_layers_shared_attended", "moe_experts_touched")):
        return None
    return (counters.get("tokens_emitted", 0) / steps,
            counters["dsa_keys_scored"] / steps,
            counters["dsa_keys_attended"] / steps,
            counters["moe_experts_touched"] / steps,
            counters.get("moe_picks_here", 0) / steps)


def step_costs(cfg, counters):
    """``(least bytes, FLOPs)`` of an average decode step of a window, or
    None where ``step_means`` finds nothing."""
    means = step_means(counters)
    if means is None:
        return None
    lanes, scored, attended, touched, picks = means
    width = {"bfloat16": 2, "float32": 4}[cfg["serving"]["param_dtype"]]
    return (decode_step_min_bytes(cfg, experts_touched=touched,
                                  keys_scored=scored, keys_attended=attended,
                                  weight_bytes=width),
            decode_step_flops(cfg, lanes=lanes, picks=picks,
                              keys_scored=scored, keys_attended=attended))
