"""Plain reference of the Keye-VL-2.0 decoder (Kwai-Keye/Keye-VL-2.0-30B-A3B,
``model_type: KeyeVL2``): the full forward pass in straightforward
``jax.numpy`` float32 at ``highest`` matmul precision, no cache, no paging,
no chunking, no kernels, one request at a time. It imports nothing of the
program.

The equations, layers numbered from 0; every layer is alike
(``decoder_sparse_step`` 1, ``mlp_only_layers`` []); ``x`` is the normed
input of a sub-layer (RMSNorm, eps ``rms_norm_eps``); no bias in any
projection (``attention_bias`` false); untied head:

    h += Attn(RMSNorm(h));  h += Experts(RMSNorm(h));  logits = W_lm RMSNorm(h)

- ``Attn`` at query position ``t``, key positions ``s <= t``. ``n`` = 32
  query heads on ``g`` = 4 key-value heads of 128, key-value head ``i``
  serving query heads ``i n / g .. (i + 1) n / g - 1``. ``q = RMSNorm_head(x
  W_q)`` as ``[n, 128]``, ``k = RMSNorm_head(x W_k)`` as ``[g, 128]`` (one
  learned scale of 128 for the queries and one for the keys, the norm over
  a head), ``v = x W_v`` as ``[g, 128]``; ``q`` and ``k`` rotated by
  ``mrope`` below.
  - **The indexer**: ``qI = x W_qI`` as ``[16, 64]``, ``kI = LayerNorm(x
    W_kI)`` as ``[64]`` (one key head, a learned scale and bias), both
    rotated over the whole indexer head; ``w = x W_w / sqrt(16 x 64)`` as
    ``[16]``. ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``, float32.
  - **The selection**: ``S_t`` = the ``min(topk, t + 1)`` positions ``s <=
    t`` with the largest ``I[t, s]``, the lower position first among equal
    scores; one set a query position, shared by all its heads.
  - ``a = softmax over s in S_t of (q_t . k_s / sqrt(128))``, the output is
    ``W_o (sum_s a_s v_s)``.
- ``mrope``, rotate-half convention over the whole head (``[x1 cos - x2
  sin, x2 cos + x1 sin]`` on the head's two halves), ``inv_freq_i =
  rope_theta^(-2i / head)``. A position is a triple (temporal, height,
  width) and frequency ``i`` takes the component whose section it falls in:
  of the 64 frequencies of a head of 128 the first ``mrope_section[0]`` = 16
  the temporal one, the next 24 the height, the last 24 the width. The
  indexer's head of 64 has 32 frequencies and takes the sections in the
  same proportion (8, 12, 12). A text token's three components are all its
  index in the sequence, which is all that is served: ``mrope`` then IS
  plain RoPE (``tests/unit/test_keye.py`` holds the two to each other).
- ``Experts``: ``p = softmax(x W_r)`` in float32 over all published
  ``num_experts`` (128); the ``num_experts_per_tok`` (8) largest are
  picked and weigh ``p / sum of the picked p`` (``norm_topk_prob``); ``sum_e
  w_e SwiGLU_e(x)`` at width ``moe_intermediate_size`` over the picks that
  fall on the experts held (``share``: ``experts_first`` and the file's
  ``num_experts``); no shared expert, no bias, no scaling. What the other
  chips' experts would add is left out, here as in the program, and the
  partial sum goes on to the next layer.

Departures from the published description: the share just named (an eighth
of the experts and of the vocabulary, 6 of 48 layers); the vision tower is
not here (the ``config.json`` has no key for it) and every position is a
text token's; and what that file does not pin, which the configuration's
file lists under ``assumed``: the norm on query and key heads, what the
indexer reads, its LayerNorm, rotation and scale, the order among equal
scores. ``q_chunk_size`` and ``kv_chunk_size`` are tile sizes of the
published kernel and nothing here depends on them.

A block of ``QUERY_BLOCK`` query positions at a time scores every key,
selects and attends, so that neither ``[T, T]`` array exists whole. Every
held expert is applied, one at a time, to every token with the weight the
router gave (zero where it was not picked). The ``fp8`` control rounds the
operands of every product, the indexer's among them; its selection is then
another set, which is part of what the control shows.

What ``served_token_gaps`` reports at a token is what the Kimi-Linear
reference reports (``kimi_linear_ref.reported``, imported), for the reason
given there: a router's 8th and 9th scores, and here the 2,048th and
2,049th index scores too, are often closer than bfloat16 rounding moves
them; a swapped expert or key moves single tokens a little and a lower
precision every token.

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

QUERY_BLOCK = 128       # query positions that score, select and attend at once

_DIMS = None


def dims_of(cfg):
    """The sizes the equations need, from the configuration's keys (the
    file's ``num_experts`` counts the experts held; ``share`` gives the
    published count and where the share starts)."""
    share = cfg.get("share", {})
    sa = cfg["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the indexer has one key head")
    sections = tuple((cfg.get("rope_scaling") or {}).get("mrope_section")
                     or (cfg["head_dim"] // 2,))
    if sum(sections) != cfg["head_dim"] // 2:
        raise ValueError(f"mrope_section {sections} does not cover the "
                         f"{cfg['head_dim'] // 2} frequencies of a head")
    return {
        "layers": cfg["num_hidden_layers"], "hidden": cfg["hidden_size"],
        "vocab": cfg["vocab_size"], "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head": cfg["head_dim"],
        "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"],
        "sections": sections,
        "index_heads": sa["indexer_num_heads"],
        "index_head": sa["indexer_head_dim"], "topk": sa["topk"],
        "expert_width": cfg["moe_intermediate_size"],
        "experts": share.get("num_experts_published", cfg["num_experts"]),
        "held": (share.get("experts_first", 0), cfg["num_experts"]),
        "top_k": cfg["num_experts_per_tok"],
        "renormalize": bool(cfg["norm_topk_prob"]),
    }


def bind(cfg):
    global _DIMS
    _DIMS = dims_of(cfg)
    return _DIMS


def weight_shapes(cfg):
    """Leaves by layer (``layers/<l>/...``, l from 0). Norm scales end in
    ``/scale`` (made as 1 + normal); the LayerNorm's bias is drawn like the
    other weights."""
    D = bind(cfg)
    d, V = D["hidden"], D["vocab"]
    n, g, hd = D["heads"], D["kv_heads"], D["head"]
    ni, hi = D["index_heads"], D["index_head"]
    f = D["expert_width"]
    out = {"embed_tokens/embedding": (V, d), "lm_head/kernel": (d, V),
           "norm/scale": (d,)}
    for l in range(D["layers"]):
        p = f"layers/{l}/"
        out[p + "input_layernorm/scale"] = (d,)
        out[p + "post_attention_layernorm/scale"] = (d,)
        a = p + "self_attn/"
        out[a + "q_proj/kernel"] = (d, n * hd)
        out[a + "k_proj/kernel"] = (d, g * hd)
        out[a + "v_proj/kernel"] = (d, g * hd)
        out[a + "o_proj/kernel"] = (n * hd, d)
        out[a + "q_norm/scale"] = (hd,)
        out[a + "k_norm/scale"] = (hd,)
        out[a + "indexer/wq/kernel"] = (d, ni * hi)
        out[a + "indexer/wk/kernel"] = (d, hi)
        out[a + "indexer/k_norm/scale"] = (hi,)
        out[a + "indexer/k_norm/bias"] = (hi,)
        out[a + "indexer/weights_proj/kernel"] = (d, ni)
        out[p + "mlp/gate/kernel"] = (d, D["experts"])
        for name, shape in (("gate_proj", (d, f)), ("up_proj", (d, f)),
                            ("down_proj", (f, d))):
            out[p + f"mlp/experts/{name}"] = (D["held"][1],) + shape
    return out


# -- positions ----------------------------------------------------------------

def text_positions(T):
    """``[3, T]``: a text token's temporal, height and width components are
    all its index."""
    return jnp.broadcast_to(jnp.arange(T), (3, T))


def sections_for(sections, head):
    """The sections of a head of ``head`` dimensions: as published for the
    head they sum to, in the same proportion for a narrower one."""
    total = 2 * sum(sections)
    if head == total:
        return tuple(sections)
    if total % head or any(s * head % total for s in sections):
        raise ValueError(f"sections {sections} do not divide a head of "
                         f"{head}")
    return tuple(s * head // total for s in sections)


def mrope(x, positions, theta, sections):
    """``x [T, n, head]`` rotated to ``positions [3, T]``: frequency ``i``
    turns by the component of the position whose section holds ``i``."""
    head = x.shape[-1]
    sections = sections_for(sections, head)
    inv = theta ** (-2.0 * np.arange(head // 2, dtype=np.float64) / head)
    part = np.repeat(np.arange(len(sections)), sections)        # [head / 2]
    pos = _f32(positions)[part]                                 # [head/2, T]
    ang = pos.T[:, None, :] * jnp.asarray(inv, jnp.float32)     # [T, 1, h/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :head // 2], x[..., head // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def rope(x, positions, theta):
    """Plain rotary positions over the whole head: what ``mrope`` is where
    the three components are equal. ``x [T, n, head]``, ``positions [T]``."""
    head = x.shape[-1]
    inv = theta ** (-2.0 * np.arange(head // 2, dtype=np.float64) / head)
    ang = _f32(positions)[:, None, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :head // 2], x[..., head // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


# -- one layer ----------------------------------------------------------------

def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(scale) + _f32(bias)


def indexer_inputs(w, x, D, positions, pr):
    """``(qI [T, 16, 64], kI [T, 64], w [T, 16])`` of the layer whose
    ``self_attn/indexer/`` leaves are ``w``."""
    T = x.shape[0]
    ni, hi = D["index_heads"], D["index_head"]
    qI = lowp.matmul(x, _f32(w["wq/kernel"]), pr).reshape(T, ni, hi)
    kI = _layer_norm(lowp.matmul(x, _f32(w["wk/kernel"]), pr),
                     w["k_norm/scale"], w["k_norm/bias"], D["eps"])
    qI = mrope(qI, positions, D["theta"], D["sections"])
    kI = mrope(kI[:, None, :], positions, D["theta"], D["sections"])[:, 0]
    wI = lowp.matmul(x, _f32(w["weights_proj/kernel"]), pr) * (
        (ni * hi) ** -0.5)
    return qI, kI, wI


def index_scores(qI, kI, wI, pr):
    """``I [Tq, Ts]`` of query rows ``qI [Tq, 16, 64]``, ``wI [Tq, 16]``
    against keys ``kI [Ts, 64]``."""
    dots = lowp.einsum("qjd,sd->qjs", qI, kI, pr)
    return jnp.sum(wI[:, :, None] * jax.nn.relu(dots), axis=1)


def select(scores, qpos, topk):
    """``[Tq, Ts]`` bool: the ``min(topk, qpos + 1)`` keys ``s <= qpos`` of
    each row with the largest score, the lower position first among equal
    ones (``lax.top_k`` keeps the lower index)."""
    Ts = scores.shape[1]
    causal = jnp.arange(Ts)[None, :] <= qpos[:, None]
    k = min(topk, Ts)
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), k)
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(True)
    return picked & causal


def attention(w, x, D, pr, selection=select):
    """``x [T, d]`` -> ``[T, d]`` at positions ``0 .. T - 1``: a block of
    queries scores every key, selects (``selection(scores, qpos, topk)``)
    and attends to what it selected."""
    T = x.shape[0]
    n, g, hd = D["heads"], D["kv_heads"], D["head"]
    positions = text_positions(T)
    q = _rms(lowp.matmul(x, _f32(w["q_proj/kernel"]), pr).reshape(T, n, hd),
             w["q_norm/scale"], D["eps"])
    k = _rms(lowp.matmul(x, _f32(w["k_proj/kernel"]), pr).reshape(T, g, hd),
             w["k_norm/scale"], D["eps"])
    v = lowp.matmul(x, _f32(w["v_proj/kernel"]), pr).reshape(T, g, hd)
    q = mrope(q, positions, D["theta"], D["sections"])
    k = mrope(k, positions, D["theta"], D["sections"])
    qI, kI, wI = indexer_inputs(_sub(w, "indexer/"), x, D, positions, pr)
    qb = min(QUERY_BLOCK, T)
    nb = -(-T // qb)
    pad = nb * qb - T

    def padded(a):
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))

    qp, qIp, wIp = padded(q.reshape(T, g, n // g, hd)), padded(qI), padded(wI)

    def block(j):
        def rows(a):
            return jax.lax.dynamic_slice_in_dim(a, j * qb, qb, axis=0)

        qpos = j * qb + jnp.arange(qb)
        chosen = selection(index_scores(rows(qIp), kI, rows(wIp), pr), qpos,
                           D["topk"])
        s = lowp.einsum("qgjd,sgd->gjqs", rows(qp), k, pr) / jnp.sqrt(
            jnp.float32(hd))
        a = jax.nn.softmax(jnp.where(chosen[None, None], s, -1e30), axis=-1)
        return lowp.einsum("gjqs,sgd->qgjd", a, v, pr)

    ctx = jax.lax.map(block, jnp.arange(nb)).reshape(nb * qb, n * hd)[:T]
    return lowp.matmul(ctx, _f32(w["o_proj/kernel"]), pr)


def route(w, x, D):
    """Picks ``[T, k]`` among all published experts and their weights: a
    softmax over all of them, the picked ones renormalised."""
    p = jax.nn.softmax(jnp.matmul(x, _f32(w["gate/kernel"]),
                                  precision=jax.lax.Precision.HIGHEST),
                       axis=-1)
    wt, idx = jax.lax.top_k(p, D["top_k"])
    if D["renormalize"]:
        wt = wt / jnp.sum(wt, -1, keepdims=True)
    return idx, wt


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


def hidden_states(weights, ids, D, pr="f32", selection=select):
    """Final-norm inputs ``[T, d]`` after reading ``ids [T]`` causally."""
    h = _f32(weights["embed_tokens/embedding"])[ids]
    for l in range(D["layers"]):
        w = _sub(weights, f"layers/{l}/")
        x = _rms(h, w["input_layernorm/scale"], D["eps"])
        h = h + attention(_sub(w, "self_attn/"), x, D, pr, selection)
        x = _rms(h, w["post_attention_layernorm/scale"], D["eps"])
        h = h + expert_ffn(_sub(w, "mlp/"), x, D, pr)
    return h


def _bound(dims, n_heads):
    D = dims or _DIMS
    if D is None:
        raise RuntimeError("call weight_shapes(cfg) or bind(cfg) first")
    if n_heads is not None and n_heads != D["heads"]:
        raise ValueError(f"n_heads={n_heads}, configuration has {D['heads']}")
    return D


def _logits(weights, row, pos, D, precision, selection=select):
    """Logits ``[n, V]`` at ``pos [n]`` after reading ``row [T]``."""
    h = hidden_states(weights, row, D, precision, selection)[pos]
    h = _rms(h, weights["norm/scale"], D["eps"])
    return lowp.matmul(h, _f32(weights["lm_head/kernel"]), precision)


def logits_at(weights, ids, positions, n_heads=None, precision="f32",
              dims=None, selection=select):
    """Logits ``[B, n, V]`` after reading ``ids [B, T]`` causally, at
    ``positions [B, n]`` (the logits at position p predict token p + 1);
    one request at a time. ``weights`` is the flat ``{name: array}`` of
    ``weight_shapes``; ``dims`` defaults to the bound configuration;
    ``selection`` is for the tests that put a wrong one in its place."""
    D = _bound(dims, n_heads)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda a: _logits(weights, a[0], a[1], D, precision, selection),
            (ids, positions))


def served_token_gaps(weights, ids, positions, tokens, valid, n_heads,
                      precision="f32"):
    """For each compared position: how far the logits of ``tokens`` lie
    below the reference's best, as ``reported`` weighs it (``gap``), and the
    same for the tokens that a ``precision`` forward pass puts first
    (``control_gap``; zero by construction at ``f32``). ``positions`` of a
    request are consecutive; ``valid`` masks the padding. A request at a
    time, and of its logits only the three numbers a position needs are
    kept."""
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
