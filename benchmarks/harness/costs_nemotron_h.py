"""Operations and bytes one decode step of the Nemotron-H configuration
needs, computed from the configuration's shapes. The yardstick of
``nemotron_decode_step_roofline``: it lives with the benchmark so that no PR
that claims a gain can change it. Everything is a function of the
configuration file's keys (the share this chip holds: ``n_routed_experts``
experts of each expert block, ``vocab_size`` rows of the vocabulary, the
first ``num_hidden_layers`` letters of ``hybrid_override_pattern``)."""


def _blocks(cfg):
    kinds = {"M": "mamba", "E": "moe", "*": "attn"}
    return [kinds[c] for c in
            cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]]


def _mamba_dims(cfg):
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return H, P, N, H * P, H * P + 2 * G * N


def mamba_mixer_params(cfg):
    """``in_proj`` to [z, xBC, dt], the convolution with its bias, ``A_log``,
    ``D`` and ``dt_bias`` a head, the gated norm's scale, ``out_proj``."""
    d, K = cfg["hidden_size"], cfg["conv_kernel"]
    H, _, _, di, cd = _mamba_dims(cfg)
    return d * (di + cd + H) + K * cd + cd + 3 * H + di + di * d


def attention_mixer_params(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * nq * hd + 2 * d * nkv * hd + nq * hd * d


def expert_params(cfg):
    """One routed expert: two matrices."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_expert_params(cfg):
    return (2 * cfg["hidden_size"]
            * cfg["moe_shared_expert_intermediate_size"])


def non_expert_params(cfg):
    """Every weight a decode step reads whatever the routing: mixers, norms,
    routers with their bias, shared experts, the final norm and the output
    head. (Of the embedding a step reads one row a lane: ignored.)"""
    d = cfg["hidden_size"]
    routed = cfg.get("share", {}).get("n_routed_experts_published",
                                      cfg["n_routed_experts"])
    total = d + d * cfg["vocab_size"]                # final norm, head
    for kind in _blocks(cfg):
        total += d                                   # the block's norm
        if kind == "mamba":
            total += mamba_mixer_params(cfg)
        elif kind == "attn":
            total += attention_mixer_params(cfg)
        else:
            total += d * routed + routed
            total += cfg["n_shared_experts"] * shared_expert_params(cfg)
    return total


def ssm_state_bytes_per_lane(cfg, conv_bytes):
    """One lane's recurrent state over all Mamba-2 blocks: the float32 state
    and the convolution tails."""
    H, P, N, _, cd = _mamba_dims(cfg)
    n = _blocks(cfg).count("mamba")
    return n * (H * P * N * 4 + (cfg["conv_kernel"] - 1) * cd * conv_bytes)


def kv_row_values(cfg):
    """Values a token caches in one attention block: keys and values of the
    key-value heads."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def decode_step_min_bytes(cfg, *, lanes, experts_touched, live_kv_rows,
                          weight_bytes):
    """Least HBM traffic of one decode step: the non-expert weights and the
    head once; the held experts the step TOUCHED (``experts_touched``: summed
    over the step's expert blocks), each read once at two matrices; the SSM
    state and convolution tails of the active lanes read and written; the
    live key and value rows of the active lanes read once in every attention
    block (the one new row a lane is ignored)."""
    n_attn = _blocks(cfg).count("attn")
    return float(
        non_expert_params(cfg) * weight_bytes
        + experts_touched * expert_params(cfg) * weight_bytes
        + 2 * lanes * ssm_state_bytes_per_lane(cfg, weight_bytes)
        + n_attn * live_kv_rows * kv_row_values(cfg) * weight_bytes)


def decode_step_flops(cfg, *, lanes, picks_here, live_kv_rows):
    """FLOPs of one decode step: 2 a weight a lane for what every lane is
    multiplied by, 2 a weight a pick for the routed experts held here, the
    Mamba-2 recurrence (decay, a rank-one update and one state-vector
    product: 5 a state element) and attention (scores and values, 2 each a
    query head a cached row a channel)."""
    H, P, N, _, _ = _mamba_dims(cfg)
    kinds = _blocks(cfg)
    return float(
        2 * lanes * non_expert_params(cfg)
        + 2 * picks_here * expert_params(cfg)
        + 5 * lanes * kinds.count("mamba") * H * P * N
        + 4 * kinds.count("attn") * live_kv_rows
        * cfg["num_attention_heads"] * cfg["head_dim"])
