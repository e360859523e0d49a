"""Plain reference of the GLM-5.2 decoder (zai-org/GLM-5.2, ``model_type:
glm_moe_dsa``): the full forward pass in straightforward ``jax.numpy``
float32 at ``highest`` matmul precision, no cache, no paging, no chunking, no
absorption, no kernels, one request at a time. It imports nothing of the
program.

The equations, layers by their published numbers; ``x`` is the normed input
of a sub-layer (RMSNorm, eps ``rms_norm_eps``); no bias in any projection;
untied head:

    h += Attn_l(RMSNorm(h));  h += FFN_l(RMSNorm(h));  logits = W_lm RMSNorm(h)

- ``Attn`` (MLA), query position ``t``, key positions ``s <= t``, 64 heads:
  ``cq = RMSNorm(x W_qa)`` [2048]; ``q = cq W_qb`` as ``[64, 192 | 64]``
  (nope | rope), the rope part rotated; ``[c | kr] = x W_kva`` [512 | 64],
  ``c = RMSNorm(c)``, ``kr`` rotated, one rotary key for all heads; ``k_nope
  = c W_kvb^K`` as ``[64, 192]``, ``v = c W_kvb^V`` as ``[64, 256]``
  (expanded: every position's keys and values exist); the score of head
  ``h`` is ``(q_nope_h . k_nope_h(s) + q_rope_h . kr(s)) / sqrt(256)``;
  softmax over the selected positions ``S_t`` only; ``W_o (sum_s p v(s))``.
- Rotation: plain frequencies ``theta^(-2i / 64)`` (``rope_theta`` 8e6),
  **interleaved pairs**: channels ``(2i, 2i + 1)`` turn together by
  ``position x frequency_i`` and stay where they are (``rope_interleave``).
- **The indexer**, in a layer whose ``indexer_types`` entry is ``full``:
  ``qI = cq W_qI`` as ``[32, 128]`` (from the query latent), ``kI =
  LayerNorm(x W_kI)`` as ``[128]`` (one key head, learned scale and bias),
  ``w = x W_w / sqrt(32)`` as ``[32]``; the first 64 channels of every
  ``qI`` head and of ``kI`` rotated as above (interleaved pairs), the other
  64 pass through. ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) /
  sqrt(128)``, float32. ``S_t`` = the ``min(index_topk, t + 1)`` positions
  ``s <= t`` with the largest ``I[t, s]``, the lower position first among
  equals; one set a query position for all 64 heads.
- **A ``shared`` layer** has no indexer weights: it attends under the
  ``S_t`` of the nearest ``full`` layer below it.
- ``FFN``: a SwiGLU of width ``intermediate_size`` where ``mlp_layer_types``
  says ``dense``; elsewhere ``s = sigmoid(x W_r)`` in float32 over all
  published experts, the 8 largest of ``s + e_score_correction_bias`` picked,
  weights ``routed_scaling_factor x s[idx] / sum(s[idx])``, ``sum_e g_e
  SwiGLU_e(x)`` over the picks that fall on the experts held, plus one shared
  SwiGLU expert on every token (``kimi_linear_ref.expert_ffn``, imported:
  the same layer).

Departures from the published model, all shared with the program under test
and stated in the configuration's file: the share (``num_hidden_layers``
layers from ``share.first_layer`` of the published per-layer lists, the
file's ``n_routed_experts`` experts from ``share.experts_first`` of the
``share.n_routed_experts_published`` the router scores, ``vocab_size`` rows
of the vocabulary; what the other chips' experts would add is left out and
the partial sum goes on); the multi-token-prediction layer is not here; and
what the config's keys do not pin, under ``assumed`` there. DeepSeek-V3.2's
Hadamard rotation of ``qI`` and ``kI`` is orthogonal and changes no score:
it is not made; nor is its fp8 cast of the index operands.

Memory, at the cell's size (16,384 positions of width 6,144 beside 9.4 GB
of bfloat16 weights): a layer's weights are cast up as the layer is reached;
queries are made, scored, selected and attended ``QUERY_BLOCK`` positions at
a time, so neither ``[T, T]`` score array exists whole; the expanded keys
(0.8 GB) and values (1.07 GB) of ONE layer do exist whole, because
expanding them anew for every block of queries would cost a hundred times
the attention itself. The selection goes from a ``full`` layer to the
``shared`` layers above it as a ``[T, T]`` array of booleans. The ``fp8``
control rounds the operands of every product, the indexer's among them; its
selection is then another set, which is part of what the control shows.

What ``served_token_gaps`` reports at a token is what the Kimi-Linear
reference reports (``kimi_linear_ref.reported``, imported), for the reason
given there.

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
# the sigmoid-routed expert layer with its shared expert, and what is
# reported of a served token's gap
from benchmarks.refs.kimi_linear_ref import (
    _f32,
    _rms,
    _sub,
    _swiglu,
    expert_ffn,
    reported,
)

QUERY_BLOCK = 128       # query positions that score, select and attend at once

_DIMS = None


def dims_of(cfg):
    """The sizes the equations need, from the configuration's keys (the
    file's ``num_hidden_layers``, ``n_routed_experts`` and ``vocab_size``
    count what is held; ``share`` gives the published counts and where the
    share starts)."""
    share = cfg.get("share", {})
    first = share.get("first_layer", 0)
    layers = tuple(range(first, first + cfg["num_hidden_layers"]))
    kinds, mlps = cfg["indexer_types"], cfg["mlp_layer_types"]
    if kinds[first] != "full":
        raise ValueError(f"layer {first} is {kinds[first]!r}: the first "
                         f"layer held must select")
    if cfg["qk_head_dim"] != cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is qk_nope_head_dim + qk_rope_head_dim")
    return {
        "layers": layers, "hidden": cfg["hidden_size"],
        "vocab": cfg["vocab_size"], "heads": cfg["num_attention_heads"],
        "selects": {l: kinds[l] == "full" for l in layers},
        "moe": {l: mlps[l] == "sparse" for l in layers},
        "q_rank": cfg["q_lora_rank"], "rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v_head": cfg["v_head_dim"], "eps": cfg["rms_norm_eps"],
        "theta": cfg["rope_parameters"]["rope_theta"],
        "index_heads": cfg["index_n_heads"],
        "index_head": cfg["index_head_dim"], "topk": cfg["index_topk"],
        "dense_width": cfg["intermediate_size"],
        # the names ``kimi_linear_ref.expert_ffn`` reads
        "expert_width": cfg["moe_intermediate_size"],
        "experts_held": cfg["n_routed_experts"],
        "experts_first": share.get("experts_first", 0),
        "experts_routed": share.get("n_routed_experts_published",
                                    cfg["n_routed_experts"]),
        "top_k": cfg["num_experts_per_tok"],
        "scaling": cfg["routed_scaling_factor"],
        "renormalize": bool(cfg["norm_topk_prob"]),
        "shared": cfg["n_shared_experts"],
    }


def bind(cfg):
    global _DIMS
    _DIMS = dims_of(cfg)
    return _DIMS


def weight_shapes(cfg):
    """Leaves by layer (``layers/<l>/...``, ``l`` the published number). Norm
    scales end in ``/scale`` (made as 1 + normal); the LayerNorm's bias and
    the router's correction bias are drawn like the other weights. A
    ``shared`` layer has no ``indexer/`` leaves."""
    D = bind(cfg)
    d, V, nh = D["hidden"], D["vocab"], D["heads"]
    ni, hi = D["index_heads"], D["index_head"]
    out = {"embed_tokens/embedding": (V, d), "lm_head/kernel": (d, V),
           "norm/scale": (d,)}
    for l in D["layers"]:
        p = f"layers/{l}/"
        out[p + "input_layernorm/scale"] = (d,)
        out[p + "post_attention_layernorm/scale"] = (d,)
        a = p + "self_attn/"
        out[a + "q_a_proj/kernel"] = (d, D["q_rank"])
        out[a + "q_a_layernorm/scale"] = (D["q_rank"],)
        out[a + "q_b_proj/kernel"] = (D["q_rank"],
                                      nh * (D["nope"] + D["rope"]))
        out[a + "kv_a_proj_with_mqa/kernel"] = (d, D["rank"] + D["rope"])
        out[a + "kv_a_layernorm/scale"] = (D["rank"],)
        out[a + "kv_b_proj/kernel"] = (D["rank"],
                                       nh * (D["nope"] + D["v_head"]))
        out[a + "o_proj/kernel"] = (nh * D["v_head"], d)
        if D["selects"][l]:
            out[a + "indexer/wq_b/kernel"] = (D["q_rank"], ni * hi)
            out[a + "indexer/wk/kernel"] = (d, hi)
            out[a + "indexer/k_norm/scale"] = (hi,)
            out[a + "indexer/k_norm/bias"] = (hi,)
            out[a + "indexer/weights_proj/kernel"] = (d, ni)
        m = p + "mlp/"
        if not D["moe"][l]:
            f = D["dense_width"]
            out[m + "gate_proj/kernel"] = (d, f)
            out[m + "up_proj/kernel"] = (d, f)
            out[m + "down_proj/kernel"] = (f, d)
            continue
        f, E = D["expert_width"], D["experts_held"]
        out[m + "gate/kernel"] = (d, D["experts_routed"])
        out[m + "gate/e_score_correction_bias"] = (D["experts_routed"],)
        out[m + "experts/gate_proj"] = (E, d, f)
        out[m + "experts/up_proj"] = (E, d, f)
        out[m + "experts/down_proj"] = (E, f, d)
        if D["shared"]:
            out[m + "shared_experts/gate_proj/kernel"] = (d, f)
            out[m + "shared_experts/up_proj/kernel"] = (d, f)
            out[m + "shared_experts/down_proj/kernel"] = (f, d)
    return out


# -- rotation ----------------------------------------------------------------

def rope_interleaved(x, positions, theta, r):
    """The first ``r`` channels of each head of ``x [T, n, head]`` rotated to
    ``positions [T]``: channels ``2i`` and ``2i + 1`` are a pair that turns
    by ``position x theta^(-2i / r)``; every channel stays in its place."""
    inv = theta ** (-2.0 * np.arange(r // 2, dtype=np.float64) / r)
    ang = _f32(positions)[:, None, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0:r:2], x[..., 1:r:2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                       axis=-1).reshape(x.shape[:-1] + (r,))
    return jnp.concatenate([turned, x[..., r:]], axis=-1)


# -- one layer ---------------------------------------------------------------

def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(scale) + _f32(bias)


def index_keys(w, x, D, pr):
    """``(kI [T, 128], wI [T, 32])`` of the layer whose
    ``self_attn/indexer/`` leaves are ``w``: what of the indexer is made from
    the layer's input."""
    T = x.shape[0]
    kI = _layer_norm(lowp.matmul(x, _f32(w["wk/kernel"]), pr),
                     w["k_norm/scale"], w["k_norm/bias"], D["eps"])
    kI = rope_interleaved(kI[:, None, :], jnp.arange(T), D["theta"],
                          D["rope"])[:, 0]
    wI = lowp.matmul(x, _f32(w["weights_proj/kernel"]), pr) * (
        D["index_heads"] ** -0.5)
    return kI, wI


def index_scores(w, cq, qpos, kI, wI, D, pr):
    """``I [Tq, Ts]`` of the queries whose latents are ``cq [Tq, q_rank]`` at
    positions ``qpos`` with head weights ``wI [Tq, 32]`` against keys ``kI
    [Ts, 128]``."""
    qI = lowp.matmul(cq, _f32(w["wq_b/kernel"]), pr).reshape(
        cq.shape[0], D["index_heads"], D["index_head"])
    qI = rope_interleaved(qI, qpos, D["theta"], D["rope"])
    dots = lowp.einsum("qjd,sd->qjs", qI, kI, pr)
    return jnp.sum(wI[:, :, None] * jax.nn.relu(dots), axis=1) * (
        D["index_head"] ** -0.5)


def select(scores, qpos, topk):
    """``[Tq, Ts]`` bool: the ``min(topk, qpos + 1)`` keys ``s <= qpos`` of
    each row with the largest score, the lower position first among equal
    ones (``lax.top_k`` keeps the lower index, and orders -0 below +0, which
    are one number here)."""
    Ts = scores.shape[1]
    causal = jnp.arange(Ts)[None, :] <= qpos[:, None]
    scores = jnp.where(scores == 0, 0.0, scores)    # -0 and +0 are equals
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, Ts))
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(True)
    return picked & causal


def attention(w, x, D, pr, chosen=None, selection=select):
    """``x [T, d]`` -> ``(y [T, d], chosen [T', T] bool)`` at positions ``0 ..
    T - 1`` (``T'`` is ``T`` rounded up to whole blocks of queries). A layer
    with ``indexer/`` leaves scores every key a block of queries at a time
    and selects (``selection(scores, qpos, topk)``); one without attends
    under the ``chosen`` it is given. Keys and values are expanded from the
    latent for every position."""
    T = x.shape[0]
    nh, dn, dr, dv, rank = (D["heads"], D["nope"], D["rope"], D["v_head"],
                            D["rank"])
    pos = jnp.arange(T)
    cq = _rms(lowp.matmul(x, _f32(w["q_a_proj/kernel"]), pr),
              w["q_a_layernorm/scale"], D["eps"])
    ckr = lowp.matmul(x, _f32(w["kv_a_proj_with_mqa/kernel"]), pr)
    c = _rms(ckr[:, :rank], w["kv_a_layernorm/scale"], D["eps"])
    kr = rope_interleaved(ckr[:, None, rank:], pos, D["theta"], dr)[:, 0]
    wkv = _f32(w["kv_b_proj/kernel"]).reshape(rank, nh, dn + dv)
    k_nope = lowp.einsum("sc,chd->shd", c, wkv[..., :dn], pr)
    v = lowp.einsum("sc,chd->shd", c, wkv[..., dn:], pr)
    qb = min(QUERY_BLOCK, T)
    nb = -(-T // qb)
    cq_pad = jnp.pad(cq, ((0, nb * qb - T), (0, 0)))

    def rows(a, j):
        return jax.lax.dynamic_slice_in_dim(a, j * qb, qb, axis=0)

    index = _sub(w, "indexer/")
    if index:
        kI, wI = index_keys(index, x, D, pr)
        wI_pad = jnp.pad(wI, ((0, nb * qb - T), (0, 0)))

        def choose(j):
            qpos = j * qb + jnp.arange(qb)
            return selection(index_scores(index, rows(cq_pad, j), qpos, kI,
                                          rows(wI_pad, j), D, pr), qpos,
                             D["topk"])

        chosen = jax.lax.map(choose, jnp.arange(nb)).reshape(nb * qb, T)
    assert chosen is not None, "a shared layer with no selection below it"

    def block(j):
        qpos = j * qb + jnp.arange(qb)
        q = lowp.matmul(rows(cq_pad, j), _f32(w["q_b_proj/kernel"]),
                        pr).reshape(qb, nh, dn + dr)
        q_rope = rope_interleaved(q[..., dn:], qpos, D["theta"], dr)
        s = (lowp.einsum("qhd,shd->hqs", q[..., :dn], k_nope, pr)
             + lowp.einsum("qhd,sd->hqs", q_rope, kr, pr)) / jnp.sqrt(
                 jnp.float32(dn + dr))
        a = jax.nn.softmax(jnp.where(rows(chosen, j)[None], s, -1e30),
                           axis=-1)
        return lowp.einsum("hqs,shd->qhd", a, v, pr)

    ctx = jax.lax.map(block, jnp.arange(nb)).reshape(nb * qb, nh * dv)[:T]
    return lowp.matmul(ctx, _f32(w["o_proj/kernel"]), pr), chosen


def hidden_states(weights, ids, D, pr="f32", selection=select):
    """Final-norm inputs ``[T, d]`` after reading ``ids [T]`` causally."""
    h = _f32(weights["embed_tokens/embedding"])[ids]
    chosen = None
    for l in D["layers"]:
        w = _sub(weights, f"layers/{l}/")
        x = _rms(h, w["input_layernorm/scale"], D["eps"])
        y, chosen = attention(_sub(w, "self_attn/"), x, D, pr, chosen,
                              selection)
        h = h + y
        x = _rms(h, w["post_attention_layernorm/scale"], D["eps"])
        m = _sub(w, "mlp/")
        if D["moe"][l]:
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
