"""Plain reference of the MiMo-V2 decoder (XiaomiMiMo/MiMo-V2.5,
``model_type: mimo_v2``): the full forward pass in straightforward
``jax.numpy`` float32 at ``highest`` matmul precision, no cache, no paging,
no chunking, no kernels, one request at a time. It imports nothing of the
program.

The equations, layers numbered from 0; ``x`` is the normed input of a
sub-layer (RMSNorm, eps ``layernorm_epsilon``); no bias anywhere; untied
head:

    h += Attn_l(RMSNorm(h));  h += FFN_l(RMSNorm(h));  logits = W_lm RMSNorm(h)

- ``Attn_l``, full where ``hybrid_layer_pattern[l]`` is 0 and window where it
  is 1. ``n`` query heads on ``g`` key-value heads (64 on 4 in a full layer,
  64 on 8 in a window layer: ``num_key_value_heads`` and
  ``swa_num_key_value_heads``), key-value head ``i`` serving query heads ``i
  n / g .. (i + 1) n / g - 1``. ``q = x W_q`` as ``[n, 192]``, ``k = x W_k``
  as ``[g, 192]``, ``v = attention_value_scale (x W_v)`` as ``[g, 128]``: a
  value head is narrower than a key head. ``q, k = rope_l(q, k, position)``;
  scores ``q k^T / sqrt(192)``; the output is ``W_o a`` with ``a [n, 128]``
  and ``W_o [n 128, d]``.
  - full: ``a = softmax(scores + causal mask) v``.
  - window: the mask is causal and ``position_q - position_k <
    sliding_window`` (128 keys, the query's own among them), and the softmax
    has a sink: one learned scalar ``s_j`` a query head is appended to the
    head's scores as one more column, the softmax is taken over keys and
    sink together, and the sink's column is dropped: it takes part of the
    mass and adds no value.
- ``rope_l``, rotate-half convention (the rotated dimensions split in two
  halves; ``[x1 cos - x2 sin, x2 cos + x1 sin]``), on the first ``r =
  int(192 x partial_rotary_factor) = int(64.128) = 64`` dimensions of each
  head, the other 128 passed through; ``inv_freq_i = theta^(-2i / r)`` with
  ``theta = rope_theta`` in a full layer and ``swa_rope_theta`` in a window
  layer; no scaling (``rope_scaling`` default).
- ``FFN_l`` where ``moe_layer_freq[l]`` is 0: ``W_d (SiLU(x W_g') * (x
  W_u))`` at ``intermediate_size``. Elsewhere: ``s = sigmoid(x W_r)`` in
  float32 over all published ``n_routed_experts``; the
  ``num_experts_per_tok`` largest of ``s + b`` (``e_score_correction_bias``:
  it chooses and does not weigh); ``w = s[idx] / sum(s[idx])``; ``sum_e w_e
  SwiGLU_e(x)`` over the picks that fall on the experts held (``share``:
  ``experts_first`` and the file's ``n_routed_experts``); no shared expert,
  no scaling factor. What the other chips' experts would add is left out,
  here as in the program, and the partial sum goes on to the next layer.

Departures from the published description: the share just named (a
sixteenth of the experts, an eighth of the vocabulary, 7 of 48 layers); the
vision and audio towers and the multi-token-prediction layers are not here
(the ``config.json`` has no key for them); and what that file does not pin,
which the configuration's file lists under ``assumed``: the value scale's
place, the sink's form, the window's edge, the rotation's convention, no
norm on ``q`` and ``k``, the bias's presence.

Every held expert is applied, one at a time, to every token with the weight
the router gave (zero where the expert was not picked), upcast one at a
time, so that the weights stay in the type they are served in. Attention
runs in blocks of queries; a window layer's block reads only the keys its
window can reach.

What ``served_token_gaps`` reports at a token is what the Kimi-Linear
reference reports (``kimi_linear_ref.reported``, imported: the larger of the
mean of the gap over the token and the 31 before it in its request, and a
twentieth of its own gap), for the reason given there: the 8th and the 9th
largest of 256 router scores are often closer than bfloat16 rounding moves
them, a swapped expert moves single tokens and a lower precision every
token.

The harness calls ``served_token_gaps(weights, ids, positions, tokens,
valid, n_heads=, precision=)`` with no configuration: ``weight_shapes(cfg)``,
which it always calls first, binds the configuration's sizes for the calls
that follow (``bind``).
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.refs import lowp
# what the references have letter for letter in common: float32 casts,
# RMSNorm, a SwiGLU at a stated precision, a sub-tree of the flat weights,
# and what is reported of a served token's gap
from benchmarks.refs.kimi_linear_ref import (
    _f32,
    _rms,
    _sub,
    _swiglu,
    reported,
)

QUERY_BLOCK = 256       # queries of the softmax at a time

_DIMS = None


def dims_of(cfg):
    """The sizes the equations need, from the configuration's keys (the
    file's ``n_routed_experts`` counts the experts held; ``share`` gives
    the published count and where the share starts)."""
    L = cfg["num_hidden_layers"]
    share = cfg.get("share", {})
    return {
        "layers": L, "hidden": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "window_layer": tuple(p == 1
                              for p in cfg["hybrid_layer_pattern"][:L]),
        "sparse": tuple(f == 1 for f in cfg["moe_layer_freq"][:L]),
        "heads": cfg["num_attention_heads"],
        # (query heads, key-value heads, key head, value head) by kind
        "full": (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"], cfg["v_head_dim"]),
        "swa": (cfg["swa_num_attention_heads"],
                cfg["swa_num_key_value_heads"], cfg["swa_head_dim"],
                cfg["swa_v_head_dim"]),
        "window": cfg["sliding_window"], "eps": cfg["layernorm_epsilon"],
        "rotary_factor": cfg["partial_rotary_factor"],
        "theta_full": cfg["rope_theta"], "theta_swa": cfg["swa_rope_theta"],
        "value_scale": cfg["attention_value_scale"],
        "sink_full": bool(cfg["add_full_attention_sink_bias"]),
        "sink_swa": bool(cfg["add_swa_attention_sink_bias"]),
        "dense_width": cfg["intermediate_size"],
        "expert_width": cfg["moe_intermediate_size"],
        "experts": share.get("n_routed_experts_published",
                             cfg["n_routed_experts"]),
        "held": (share.get("experts_first", 0), cfg["n_routed_experts"]),
        "top_k": cfg["num_experts_per_tok"],
        "renormalize": bool(cfg["norm_topk_prob"]),
        "scaling": cfg.get("routed_scaling_factor") or 1.0,
    }


def bind(cfg):
    global _DIMS
    _DIMS = dims_of(cfg)
    return _DIMS


def weight_shapes(cfg):
    """Leaves by layer (``layers/<l>/...``, l from 0), so that no leaf is
    larger than one layer's held experts of one matrix. Norm scales end in
    ``/scale`` (made as 1 + normal); sinks and the correction bias are
    drawn like the other weights."""
    D = bind(cfg)
    d, V = D["hidden"], D["vocab"]
    out = {"embed_tokens/embedding": (V, d), "lm_head/kernel": (d, V),
           "norm/scale": (d,)}
    for l in range(D["layers"]):
        p = f"layers/{l}/"
        kind = "swa" if D["window_layer"][l] else "full"
        n, g, hd, vd = D[kind]
        out[p + "input_layernorm/scale"] = (d,)
        out[p + "post_attention_layernorm/scale"] = (d,)
        out[p + "self_attn/q_proj/kernel"] = (d, n * hd)
        out[p + "self_attn/k_proj/kernel"] = (d, g * hd)
        out[p + "self_attn/v_proj/kernel"] = (d, g * vd)
        out[p + "self_attn/o_proj/kernel"] = (n * vd, d)
        if D["sink_" + kind]:
            out[p + "self_attn/attention_sink_bias"] = (n,)
        f = D["expert_width"] if D["sparse"][l] else D["dense_width"]
        matrices = (("gate_proj", (d, f)), ("up_proj", (d, f)),
                    ("down_proj", (f, d)))
        if not D["sparse"][l]:
            for name, shape in matrices:
                out[p + f"mlp/{name}/kernel"] = shape
            continue
        out[p + "mlp/gate/kernel"] = (d, D["experts"])
        out[p + "mlp/gate/e_score_correction_bias"] = (D["experts"],)
        for name, shape in matrices:
            out[p + f"mlp/experts/{name}"] = (D["held"][1],) + shape
    return out


def inv_freq(theta, head, factor):
    """``(inv_freq [r / 2], r)``: plain frequencies over the first ``r =
    int(head x factor)`` dimensions."""
    r = int(head * factor)
    return theta ** (-2.0 * np.arange(r // 2, dtype=np.float64) / r), r


def rope(x, positions, theta, factor):
    """``x [T, n, head]`` rotated to ``positions [T]``."""
    inv, r = inv_freq(theta, x.shape[-1], factor)
    ang = _f32(positions)[:, None, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., r:]], axis=-1)


def attention(w, x, D, l, pr):
    """``x [T, d]`` -> ``[T, d]``: layer ``l``'s attention at positions ``0
    .. T - 1``, softmax in blocks of queries."""
    T = x.shape[0]
    kind = "swa" if D["window_layer"][l] else "full"
    n, g, hd, vd = D[kind]
    window = D["window"] if kind == "swa" else None
    theta, factor = D["theta_" + kind], D["rotary_factor"]
    pos = jnp.arange(T)
    q = rope(lowp.matmul(x, _f32(w["q_proj/kernel"]), pr).reshape(T, n, hd),
             pos, theta, factor)
    k = rope(lowp.matmul(x, _f32(w["k_proj/kernel"]), pr).reshape(T, g, hd),
             pos, theta, factor)
    v = D["value_scale"] * lowp.matmul(
        x, _f32(w["v_proj/kernel"]), pr).reshape(T, g, vd)
    k = jnp.repeat(k, n // g, axis=1)        # query head j reads j // (n/g)
    v = jnp.repeat(v, n // g, axis=1)
    sink = _f32(w["attention_sink_bias"]) if D["sink_" + kind] else None
    qb = min(QUERY_BLOCK, T)
    nb = -(-T // qb)
    qpad = jnp.pad(q, ((0, nb * qb - T), (0, 0), (0, 0)))
    # the keys a block of queries can reach: all of them, or, in a window
    # layer, the block's own and the window - 1 before its first
    front = 0 if window is None else window - 1
    reach = qb + front
    kpad = jnp.pad(k, ((front, nb * qb - T), (0, 0), (0, 0)))
    vpad = jnp.pad(v, ((front, nb * qb - T), (0, 0), (0, 0)))

    def block(j):
        qs = jax.lax.dynamic_slice_in_dim(qpad, j * qb, qb, axis=0)
        qpos = j * qb + jnp.arange(qb)
        if window is None:
            ks, vs, kpos = k, v, jnp.arange(T)
        else:
            ks = jax.lax.dynamic_slice_in_dim(kpad, j * qb, reach, axis=0)
            vs = jax.lax.dynamic_slice_in_dim(vpad, j * qb, reach, axis=0)
            kpos = j * qb - front + jnp.arange(reach)
        s = lowp.einsum("qhd,shd->hqs", qs, ks, pr) / jnp.sqrt(
            jnp.float32(hd))
        ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
        if window is not None:
            ok = ok & (qpos[:, None] - kpos[None, :] < window)
        s = jnp.where(ok[None], s, -1e30)
        if sink is not None:
            # one more column a head, dropped after the softmax
            column = jnp.broadcast_to(sink[:, None, None], s.shape[:2] + (1,))
            s = jnp.concatenate([s, column], axis=-1)
        p = jax.nn.softmax(s, axis=-1)[..., :ks.shape[0]]
        return lowp.einsum("hqs,shd->qhd", p, vs, pr)

    ctx = jax.lax.map(block, jnp.arange(nb)).reshape(nb * qb, n, vd)[:T]
    return lowp.matmul(ctx.reshape(T, n * vd), _f32(w["o_proj/kernel"]), pr)


def route(w, x, D):
    """Picks ``[T, k]`` among all published experts and their weights."""
    s = jax.nn.sigmoid(jnp.matmul(x, _f32(w["gate/kernel"]),
                                  precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + _f32(w["gate/e_score_correction_bias"]),
                           D["top_k"])
    wt = jnp.take_along_axis(s, idx, axis=-1)
    if D["renormalize"]:
        wt = wt / jnp.sum(wt, -1, keepdims=True)
    return idx, wt * D["scaling"]


def expert_ffn(w, x, D, pr, held=None):
    """What the experts ``held = (first, count)`` give of the expert layer
    for ``x [T, d]`` (default: the configuration's share): each weighted as
    routed. ``w["experts/..."]`` holds exactly those ``count`` experts."""
    idx, wt = route(w, x, D)
    first, E = held or D["held"]
    assert w["experts/up_proj"].shape[0] == E, (w["experts/up_proj"].shape, E)
    # weight of every held expert for every token (0 where not picked)
    dense = jnp.sum(jnp.where((idx - first)[..., None] == jnp.arange(E),
                              wt[..., None], 0.0), axis=1)       # [T, E]

    def one(y, e):
        matrices = [jax.lax.dynamic_index_in_dim(w[f"experts/{n}"], e, 0,
                                                 False)
                    for n in ("gate_proj", "up_proj", "down_proj")]
        weight = jax.lax.dynamic_slice_in_dim(dense, e, 1, axis=1)
        return y + weight * _swiglu(x, *matrices, pr), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(E))
    return y


def hidden_states(weights, ids, D, pr="f32"):
    """Final-norm inputs ``[T, d]`` after reading ``ids [T]`` causally."""
    h = _f32(weights["embed_tokens/embedding"])[ids]
    for l in range(D["layers"]):
        w = _sub(weights, f"layers/{l}/")
        x = _rms(h, w["input_layernorm/scale"], D["eps"])
        h = h + attention(_sub(w, "self_attn/"), x, D, l, pr)
        x = _rms(h, w["post_attention_layernorm/scale"], D["eps"])
        m = _sub(w, "mlp/")
        if D["sparse"][l]:
            h = h + expert_ffn(m, x, D, pr)
        else:
            h = h + _swiglu(x, m["gate_proj/kernel"], m["up_proj/kernel"],
                            m["down_proj/kernel"], pr)
    return h


def _bound(dims, n_heads):
    D = dims or _DIMS
    if D is None:
        raise RuntimeError("call weight_shapes(cfg) or bind(cfg) first")
    if n_heads is not None and n_heads != D["heads"]:
        raise ValueError(f"n_heads={n_heads}, configuration has {D['heads']}")
    return D


def _logits(weights, row, pos, D, precision):
    """Logits ``[n, V]`` at ``pos [n]`` after reading ``row [T]``."""
    h = hidden_states(weights, row, D, precision)[pos]
    h = _rms(h, weights["norm/scale"], D["eps"])
    return lowp.matmul(h, _f32(weights["lm_head/kernel"]), precision)


def logits_at(weights, ids, positions, n_heads=None, precision="f32",
              dims=None):
    """Logits ``[B, n, V]`` after reading ``ids [B, T]`` causally, at
    ``positions [B, n]`` (the logits at position p predict token p + 1);
    one request at a time. ``weights`` is the flat ``{name: array}`` of
    ``weight_shapes``; ``dims`` defaults to the bound configuration."""
    D = _bound(dims, n_heads)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda a: _logits(weights, a[0], a[1], D, precision),
            (ids, positions))


def served_token_gaps(weights, ids, positions, tokens, valid, n_heads,
                      precision="f32"):
    """For each compared position: how far the logits of ``tokens`` lie
    below the reference's best, as ``reported`` weighs it (``gap``), and the
    same for the tokens that a ``precision`` forward pass puts first
    (``control_gap``; zero by construction at ``f32``). ``positions`` of a
    request are consecutive; ``valid`` masks the padding. A request at a
    time, and of its logits (3,072 positions x 19,072 rows are 0.23 GB in
    the cell) only the three numbers a position needs are kept."""
    D = _bound(None, n_heads)

    def one(args):
        row, pos, toks = args
        ref = _logits(weights, row, pos, D, "f32")
        best = jnp.max(ref, axis=-1)
        served = jnp.take_along_axis(ref, toks[:, None], axis=-1)[:, 0]
        if precision == "f32":
            return best - served, jnp.zeros_like(best)
        first = jnp.argmax(_logits(weights, row, pos, D, precision), axis=-1)
        chosen = jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0]
        return best - served, best - chosen

    with jax.default_matmul_precision("highest"):
        gap, control = jax.lax.map(one, (ids, positions, tokens))
    gap = reported(jnp.where(valid, gap, 0.0))
    if precision == "f32":
        return gap, jnp.zeros_like(gap)
    return gap, reported(jnp.where(valid, control, 0.0))
