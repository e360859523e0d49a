"""Plain reference of the Laguna decoder (poolside/Laguna-XS.2, ``model_type:
laguna``): the full forward pass in straightforward ``jax.numpy`` float32 at
``highest`` matmul precision, no cache, no chunking, no kernels, one request
at a time. It imports nothing of the program.

The equations, layers numbered from 0; ``x`` is the normed input of a
sub-layer (RMSNorm, eps 1e-6); no bias anywhere; untied head:

    h += Attn_l(RMSNorm(h));  h += FFN_l(RMSNorm(h));  logits = W_lm RMSNorm(h)

- ``Attn_l``: ``n_l = num_attention_heads_per_layer[l]`` query heads (48
  where ``layer_types[l]`` is ``full_attention``, 64 where
  ``sliding_attention``) on 8 key-value heads of 128, key-value head ``g``
  serving query heads ``g n_l / 8 .. (g + 1) n_l / 8 - 1``. ``q = x W_q``
  as ``[n_l, 128]``; ``k = x W_k``, ``v = x W_v`` as ``[8, 128]``; ``q, k =
  rope_l(q, k, position)``; ``a = softmax(q k^T / sqrt(128) + mask_l) v``;
  ``g = sigmoid(x W_g)`` as ``[n_l]``, one scalar a head; the output is
  ``W_o (a * g[:, None])``. ``mask_l`` is causal, and in a window layer also
  ``position_q - position_k < sliding_window`` (512 keys, the query's own
  among them).
- ``rope_l``, rotate-half convention (the rotated dimensions split in two
  halves; ``[x1 cos - x2 sin, x2 cos + x1 sin]``), on the first ``r = 128 x
  partial_rotary_factor`` dimensions of each head, the rest passed through.
  Window layers: ``r = 128``, ``inv_freq_i = 10000^(-2i / r)``, no scaling.
  Full layers: ``r = 64``, base 500,000, YaRN as the family's published code
  computes it: ``extrap_i = base^(-2i / r)``; ``interp_i = extrap_i /
  factor``; ``d(n) = r ln(original_max / (2 pi n)) / (2 ln base)``; ``low =
  floor(d(beta_fast))``, ``high = ceil(d(beta_slow))``, clamped to ``[0, r -
  1]``; ``ramp_i = clip((i - low) / (high - low), 0, 1)`` for ``i < r /
  2``; ``inv_freq_i = interp_i ramp_i + extrap_i (1 - ramp_i)``; ``cos`` and
  ``sin`` both times ``attention_factor``.
- ``FFN_l`` where ``mlp_layer_types[l]`` is ``dense``: ``W_d (SiLU(x W_g') *
  (x W_u))`` at ``intermediate_size``. Where ``sparse``: ``s = sigmoid(x
  W_r)`` in float32 over all ``num_experts``; the ``num_experts_per_tok``
  largest; ``w = s[idx] / sum(s[idx]) * moe_routed_scaling_factor``; ``sum_e
  w_e SwiGLU_e(x) + SwiGLU_shared(x)``, weights on the outputs.

What the published ``config.json`` does not pin, and the configuration's
file lists under ``assumed``: the form of ``gating: true`` (one sigmoid
scalar a head from the layer's input), the router's score (sigmoid with
renormalisation, no correction bias), no norm on ``q`` and ``k``, and the
window's off-by-one as above.

Every expert is applied, a block at a time, to every token with the weight
the router gave (zero where the expert was not picked), upcast a block at a
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

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.refs import lowp
# what the two references have letter for letter in common: float32 casts,
# RMSNorm, a SwiGLU at a stated precision, a sub-tree of the flat weights,
# and what is reported of a served token's gap
from benchmarks.refs.kimi_linear_ref import (
    _f32,
    _rms,
    _sub,
    _swiglu,
    reported,
)

EXPERT_BLOCK = 8        # experts upcast and applied at a time
QUERY_BLOCK = 256       # queries of the causal softmax at a time

_DIMS = None


def dims_of(cfg):
    """The sizes the equations need, from the configuration's keys."""
    L = cfg["num_hidden_layers"]
    rope = cfg["rope_parameters"]
    return {
        "layers": L, "hidden": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "window_layer": tuple(t == "sliding_attention"
                              for t in cfg["layer_types"][:L]),
        "sparse": tuple(t == "sparse" for t in cfg["mlp_layer_types"][:L]),
        "heads_of": tuple(cfg["num_attention_heads_per_layer"][:L]),
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head": cfg["head_dim"],
        "window": cfg["sliding_window"], "eps": cfg["rms_norm_eps"],
        "rope_full": tuple(sorted(rope["full_attention"].items())),
        "rope_window": tuple(sorted(rope["sliding_attention"].items())),
        "dense_width": cfg["intermediate_size"],
        "expert_width": cfg["moe_intermediate_size"],
        "shared_width": cfg["shared_expert_intermediate_size"],
        "experts": cfg["num_experts"], "top_k": cfg["num_experts_per_tok"],
        "scaling": cfg["moe_routed_scaling_factor"],
    }


def bind(cfg):
    global _DIMS
    _DIMS = dims_of(cfg)
    return _DIMS


def weight_shapes(cfg):
    """Leaves by layer (``layers/<l>/...``, l from 0), so that no leaf is
    larger than one layer's experts of one matrix. Norm scales end in
    ``/scale`` (made as 1 + normal)."""
    D = bind(cfg)
    d, V, hd = D["hidden"], D["vocab"], D["head"]
    out = {"embed_tokens/embedding": (V, d), "lm_head/kernel": (d, V),
           "norm/scale": (d,)}
    for l in range(D["layers"]):
        p = f"layers/{l}/"
        n, kv = D["heads_of"][l], D["kv_heads"]
        out[p + "input_layernorm/scale"] = (d,)
        out[p + "post_attention_layernorm/scale"] = (d,)
        out[p + "self_attn/q_proj/kernel"] = (d, n * hd)
        out[p + "self_attn/k_proj/kernel"] = (d, kv * hd)
        out[p + "self_attn/v_proj/kernel"] = (d, kv * hd)
        out[p + "self_attn/g_proj/kernel"] = (d, n)
        out[p + "self_attn/o_proj/kernel"] = (n * hd, d)
        if not D["sparse"][l]:
            f = D["dense_width"]
            for name, shape in (("gate_proj", (d, f)), ("up_proj", (d, f)),
                                ("down_proj", (f, d))):
                out[p + f"mlp/{name}/kernel"] = shape
            continue
        f, E, fs = D["expert_width"], D["experts"], D["shared_width"]
        out[p + "mlp/gate/kernel"] = (d, E)
        for name, shape in (("gate_proj", (d, f)), ("up_proj", (d, f)),
                            ("down_proj", (f, d))):
            out[p + f"mlp/experts/{name}"] = (E,) + shape
        for name, shape in (("gate_proj", (d, fs)), ("up_proj", (d, fs)),
                            ("down_proj", (fs, d))):
            out[p + f"mlp/shared_experts/{name}/kernel"] = shape
    return out


def inv_freq(rope, head):
    """``(inv_freq [r / 2], r, attention_factor)`` of one entry of
    ``rope_parameters`` (a dict), as the module's docstring writes it."""
    r = int(head * rope["partial_rotary_factor"])
    base = rope["rope_theta"]
    i = np.arange(r // 2, dtype=np.float64)
    extrap = base ** (-2.0 * i / r)
    if rope["rope_type"] == "default":
        return extrap, r, 1.0
    assert rope["rope_type"] == "yarn", rope["rope_type"]
    interp = extrap / rope["factor"]

    def d(n):
        return (r * math.log(rope["original_max_position_embeddings"]
                             / (2 * math.pi * n)) / (2 * math.log(base)))

    low = max(math.floor(d(rope["beta_fast"])), 0)
    high = min(math.ceil(d(rope["beta_slow"])), r - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (interp * ramp + extrap * (1.0 - ramp), r,
            rope.get("attention_factor", 1.0))


def rope(x, positions, spec, head):
    """``x [T, n, head]`` rotated to ``positions [T]``."""
    inv, r, factor = inv_freq(spec, head)
    ang = _f32(positions)[:, None, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., r:]], axis=-1)


def attention(w, x, D, l, pr):
    """``x [T, d]`` -> ``[T, d]``: layer ``l``'s grouped-query attention at
    positions ``0 .. T - 1``, softmax in blocks of queries."""
    T = x.shape[0]
    n, nkv, hd = D["heads_of"][l], D["kv_heads"], D["head"]
    window = D["window"] if D["window_layer"][l] else None
    spec = dict(D["rope_window"] if window else D["rope_full"])
    pos = jnp.arange(T)
    q = rope(lowp.matmul(x, _f32(w["q_proj/kernel"]), pr).reshape(T, n, hd),
             pos, spec, hd)
    k = rope(lowp.matmul(x, _f32(w["k_proj/kernel"]), pr).reshape(T, nkv, hd),
             pos, spec, hd)
    v = lowp.matmul(x, _f32(w["v_proj/kernel"]), pr).reshape(T, nkv, hd)
    k = jnp.repeat(k, n // nkv, axis=1)      # query head j reads j // (n/KV)
    v = jnp.repeat(v, n // nkv, axis=1)
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
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
        return lowp.einsum("hqs,shd->qhd", p, vs, pr)

    ctx = jax.lax.map(block, jnp.arange(nb)).reshape(nb * qb, n, hd)[:T]
    g = jax.nn.sigmoid(lowp.matmul(x, _f32(w["g_proj/kernel"]), pr))
    return lowp.matmul((ctx * g[:, :, None]).reshape(T, n * hd),
                       _f32(w["o_proj/kernel"]), pr)


def route(w, x, D):
    """Picks ``[T, k]`` among all experts and their weights."""
    s = jax.nn.sigmoid(jnp.matmul(x, _f32(w["gate/kernel"]),
                                  precision=jax.lax.Precision.HIGHEST))
    wt, idx = jax.lax.top_k(s, D["top_k"])
    return idx, wt / jnp.sum(wt, -1, keepdims=True) * D["scaling"]


def expert_ffn(w, x, D, pr, held=None):
    """The expert layer for ``x [T, d]``: every expert weighted as routed,
    plus the shared expert. ``held = (first, count)`` keeps a share's
    experts only (the tests' sum over shares; the cell holds them all)."""
    idx, wt = route(w, x, D)
    first, E = held or (0, D["experts"])
    # weight of every held expert for every token (0 where not picked)
    dense = jnp.sum(jnp.where((idx - first)[..., None] == jnp.arange(E),
                              wt[..., None], 0.0), axis=1)       # [T, E]
    eb = min(EXPERT_BLOCK, E)
    assert E % eb == 0, (E, eb)

    def block(y, j):
        sl = [jax.lax.dynamic_slice_in_dim(w[f"experts/{n}"], first + j * eb,
                                           eb, 0)
              for n in ("gate_proj", "up_proj", "down_proj")]
        wts = jax.lax.dynamic_slice_in_dim(dense, j * eb, eb, axis=1)
        for e in range(eb):
            y = y + wts[:, e:e + 1] * _swiglu(x, sl[0][e], sl[1][e], sl[2][e],
                                              pr)
        return y, None

    y, _ = jax.lax.scan(block, jnp.zeros_like(x), jnp.arange(E // eb))
    return y + _swiglu(x, w["shared_experts/gate_proj/kernel"],
                       w["shared_experts/up_proj/kernel"],
                       w["shared_experts/down_proj/kernel"], pr)


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
    time, and of its logits (2,048 positions x 100,352 rows are 0.8 GB in
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
