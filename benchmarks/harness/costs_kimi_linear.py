"""Operations and bytes one decode step of the Kimi-Linear configuration
needs, computed from the configuration's shapes. The yardstick of
``kimi_decode_step_roofline``: it lives with the benchmark so that no PR that
claims a gain can change it. Everything is a function of the configuration
file's keys (the share this chip holds: ``num_experts`` experts of each
expert layer, ``vocab_size`` rows of the vocabulary, ``num_hidden_layers``
layers)."""


def _layers(cfg):
    lin = cfg["linear_attn_config"]
    return ["kda" if i in lin["kda_layers"] else "mla"
            for i in range(1, cfg["num_hidden_layers"] + 1)]


def kda_mixer_params(cfg):
    """q, k, v projections and their convolutions, the two low-rank gates
    (decay with ``dt_bias``, output with its bias), ``A_log``, beta, the
    head norm and the output projection."""
    d = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    H, D, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    W = H * D
    return (3 * d * W + 3 * K * W            # projections, convolutions
            + d * D + D * W + W + H          # decay gate, dt_bias, A_log
            + d * H                          # beta
            + d * D + D * W + W              # output gate and its bias
            + D + W * d)                     # head norm, output projection


def mla_mixer_params(cfg):
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, nope, rope, vh = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                            cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    return (d * nh * (nope + rope) + d * (rank + rope) + rank
            + rank * nh * (nope + vh) + nh * vh * d)


def expert_params(cfg):
    """One routed (or the shared) expert: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def non_expert_params(cfg):
    """Every weight a decode step reads whatever the routing: mixers, norms,
    the dense FFN of the leading layers, routers with their bias, shared
    experts, the final norm and the output head. (Of the embedding a step
    reads one row a lane: ignored.)"""
    d = cfg["hidden_size"]
    routed = cfg.get("share", {}).get("num_experts_published",
                                      cfg["num_experts"])
    total = d + d * cfg["vocab_size"]                # final norm, head
    for i, kind in enumerate(_layers(cfg), start=1):
        total += 2 * d
        total += kda_mixer_params(cfg) if kind == "kda" else \
            mla_mixer_params(cfg)
        if i <= cfg["first_k_dense_replace"]:
            total += 3 * d * cfg["intermediate_size"]
        else:
            total += d * routed + routed
            total += cfg["num_shared_experts"] * expert_params(cfg)
    return total


def kda_state_bytes_per_lane(cfg, conv_bytes):
    """One lane's recurrent state over all KDA layers: the float32 state and
    the convolution tails."""
    lin = cfg["linear_attn_config"]
    H, D, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    n = _layers(cfg).count("kda")
    return n * (H * D * D * 4 + (K - 1) * 3 * H * D * conv_bytes)


def decode_step_min_bytes(cfg, *, lanes, experts_touched, live_latent_rows,
                          weight_bytes):
    """Least HBM traffic of one decode step: the non-expert weights and the
    head once; the held experts the step TOUCHED (``experts_touched``: summed
    over the step's expert layers), each read once; the KDA state and
    convolution tails of the active lanes read and written; the live latent
    rows of the active lanes read once in every MLA layer (the one new row a
    lane is ignored)."""
    n_mla = _layers(cfg).count("mla")
    latent = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return float(
        non_expert_params(cfg) * weight_bytes
        + experts_touched * expert_params(cfg) * weight_bytes
        + 2 * lanes * kda_state_bytes_per_lane(cfg, weight_bytes)
        + n_mla * live_latent_rows * latent * weight_bytes)


def decode_step_flops(cfg, *, lanes, picks_here, live_latent_rows):
    """FLOPs of one decode step: 2 a weight a lane for what every lane is
    multiplied by, 2 a weight a pick for the routed experts held here, the
    KDA recurrence (decay, two state-vector products and a rank-one update:
    about 7 a state element) and absorbed latent attention (scores over rank
    + rope, values over rank, 2 each a head a row)."""
    lin = cfg["linear_attn_config"]
    H, D = lin["num_heads"], lin["head_dim"]
    kinds = _layers(cfg)
    nh = cfg["num_attention_heads"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return float(
        2 * lanes * non_expert_params(cfg)
        + 2 * picks_here * expert_params(cfg)
        + 7 * lanes * kinds.count("kda") * H * D * D
        + 2 * kinds.count("mla") * live_latent_rows * nh * (2 * rank + rope))
