"""Plain reference of the Ouro decoder (ByteDance/Ouro-2.6B, ``model_type:
ouro``; "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741): the full forward pass in straightforward ``jax.numpy``
float32 at ``highest`` matmul precision, no cache, no paging, no chunking,
no kernels, one request at a time. It imports nothing of the program.

The equations; ``h`` the residual stream of a token, ``t = 0 ..
total_ut_steps - 1`` the pass, ``l = 0 .. num_hidden_layers - 1`` the layer;
RMSNorm with eps ``rms_norm_eps``; no bias in any projection; untied head:

    h = embed[id]
    for t:
        for l:
            x = RMSNorm_l1(h);  q, k, v = x W_q, x W_k, x W_v   [n heads of hd]
            q, k = rope(q, k, position)
            a = softmax_{s <= position}(q . k_s / sqrt(hd));  ctx = sum a v_s
            h = h + RMSNorm_l2(ctx W_o)
            y = RMSNorm_l3(h)
            h = h + RMSNorm_l4(W_down (SiLU(W_gate y) * W_up y))
        h = RMSNorm_f(h)                       # the input of pass t + 1
    logits = h W_head                          # h the last pass's normed h

- One set of weights serves every pass: ``W^l``, the four norms of layer
  ``l`` (``input_layernorm``, ``input_layernorm_2``,
  ``post_attention_layernorm``, ``post_attention_layernorm_2``: a norm ahead
  of each sub-layer and one on its output ahead of the residual add) and
  ``RMSNorm_f`` (``norm``).
- Multi-head attention: ``num_key_value_heads`` equals
  ``num_attention_heads`` (a key-value head a query head; where they differ
  key-value head ``i`` serves query heads ``i n / g .. (i + 1) n / g - 1``).
  Pass ``t`` of layer ``l`` attends to the keys and values that pass ``t`` of
  layer ``l`` made at the earlier positions, which a forward pass without a
  cache does by construction: a program that reads another pass's cache row
  disagrees with it.
- ``rope``, rotate-half convention over the whole head, ``inv_freq_i =
  rope_theta^(-2i / hd)``, no scaling (``rope_scaling`` null).
- The exit gate: ``g_t = h_t . w_g + b_g`` on pass ``t``'s normed ``h_t``
  (``early_exit_gate``), ``lambda_t = sigmoid(g_t)``, ``p_t = lambda_t
  prod_{s<t} (1 - lambda_s)`` and the last pass takes what is left
  (``exit_distribution``); a token leaves at the first pass whose cumulated
  ``p`` reaches ``early_exit_threshold`` (``exit_pass``). At the published
  threshold of 1 that is the last pass, exactly, and the gate changes no
  served number: ``served_token_gaps`` and ``logits_at`` run every pass and
  read the gate nowhere; ``pass_states`` gives the ``h_t`` for the gate's
  own tests.

Departures from the published description: none in sizes (nothing is
reduced). What ``config.json`` does not pin and the configuration's file
lists under ``assumed``: the four-norm layer and the final norm between
passes, no bias and no norm on ``q`` or ``k``, the gate's form, one cache row
a (pass, layer) (the paper's last-pass or averaged cache reuse in decoding
is an approximation that changes logits and is not this configuration).

The weights arrive in the type they are served in, 5.3 GB of bfloat16 by
layer (``layers/<l>/...``, the public layout). All of them in float32 would
be 10.7 GB more, so a layer's weights are made float32 where they are used:
the layers are stacked once (bfloat16, 4.9 GB) and scanned, and the casts
are of the scan's own slice, which depends on the loop's index, so the
compiler cannot hoist them out of the loop.

What ``served_token_gaps`` reports at a token is what the Kimi-Linear
reference reports (``kimi_linear_ref.reported``, imported: the larger of the
mean of the gap over the token and the 31 before it in its request, and a
twentieth of its own gap).

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
# RMSNorm, a SwiGLU at a stated precision and what is reported of a served
# token's gap
from benchmarks.refs.kimi_linear_ref import _f32, _rms, _swiglu, reported

_DIMS = None

NORMS = ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
         "post_attention_layernorm_2")
_LAYER_LEAVES = tuple(f"{n}/scale" for n in NORMS) + tuple(
    f"self_attn/{n}_proj/kernel" for n in "qkvo") + tuple(
    f"mlp/{n}_proj/kernel" for n in ("gate", "up", "down"))


def dims_of(cfg):
    """The sizes the equations need, from the configuration's keys."""
    return {
        "layers": cfg["num_hidden_layers"], "passes": cfg["total_ut_steps"],
        "hidden": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head": cfg["head_dim"],
        "width": cfg["intermediate_size"], "eps": cfg["rms_norm_eps"],
        "theta": cfg["rope_theta"],
        "threshold": cfg["early_exit_threshold"],
    }


def bind(cfg):
    global _DIMS
    _DIMS = dims_of(cfg)
    return _DIMS


def weight_shapes(cfg):
    """Leaves by layer in the public layout (``layers/<l>/...``, l from 0).
    Norm scales end in ``/scale`` (made as 1 + normal); the gate's bias is
    drawn like the other weights."""
    D = bind(cfg)
    d, V, f = D["hidden"], D["vocab"], D["width"]
    n, g, hd = D["heads"], D["kv_heads"], D["head"]
    out = {"embed_tokens/embedding": (V, d), "lm_head/kernel": (d, V),
           "norm/scale": (d,), "early_exit_gate/kernel": (d, 1),
           "early_exit_gate/bias": (1,)}
    for l in range(D["layers"]):
        p = f"layers/{l}/"
        for name in NORMS:
            out[p + name + "/scale"] = (d,)
        out[p + "self_attn/q_proj/kernel"] = (d, n * hd)
        out[p + "self_attn/k_proj/kernel"] = (d, g * hd)
        out[p + "self_attn/v_proj/kernel"] = (d, g * hd)
        out[p + "self_attn/o_proj/kernel"] = (n * hd, d)
        out[p + "mlp/gate_proj/kernel"] = (d, f)
        out[p + "mlp/up_proj/kernel"] = (d, f)
        out[p + "mlp/down_proj/kernel"] = (f, d)
    return out


def rope(x, positions, theta):
    """``x [T, n, hd]`` rotated over the whole head to ``positions [T]``."""
    hd = x.shape[-1]
    inv = theta ** (-2.0 * np.arange(hd // 2, dtype=np.float64) / hd)
    ang = _f32(positions)[:, None, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(w, x, D, pr):
    """``x [T, d]`` -> ``[T, d]``: causal attention at positions ``0 .. T -
    1``; ``w`` one layer's ``self_attn`` leaves."""
    T = x.shape[0]
    n, g, hd = D["heads"], D["kv_heads"], D["head"]
    pos = jnp.arange(T)
    q = rope(lowp.matmul(x, _f32(w["self_attn/q_proj/kernel"]), pr).reshape(
        T, n, hd), pos, D["theta"])
    k = rope(lowp.matmul(x, _f32(w["self_attn/k_proj/kernel"]), pr).reshape(
        T, g, hd), pos, D["theta"])
    v = lowp.matmul(x, _f32(w["self_attn/v_proj/kernel"]), pr).reshape(
        T, g, hd)
    k = jnp.repeat(k, n // g, axis=1)        # query head j reads j // (n/g)
    v = jnp.repeat(v, n // g, axis=1)
    s = lowp.einsum("qhd,shd->hqs", q, k, pr) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where((pos[None, :] <= pos[:, None])[None], s, -1e30)
    ctx = lowp.einsum("hqs,shd->qhd", jax.nn.softmax(s, axis=-1), v, pr)
    return lowp.matmul(ctx.reshape(T, n * hd),
                       _f32(w["self_attn/o_proj/kernel"]), pr)


def layer(w, h, D, pr="f32"):
    """One layer over ``h [T, d]``; ``w`` its leaves (``NORMS``'s scales,
    ``self_attn/...``, ``mlp/...``) in whatever type they are held."""
    eps = D["eps"]
    x = _rms(h, w["input_layernorm/scale"], eps)
    h = h + _rms(attention(w, x, D, pr), w["input_layernorm_2/scale"], eps)
    y = _rms(h, w["post_attention_layernorm/scale"], eps)
    return h + _rms(
        _swiglu(y, w["mlp/gate_proj/kernel"], w["mlp/up_proj/kernel"],
                w["mlp/down_proj/kernel"], pr),
        w["post_attention_layernorm_2/scale"], eps)


def stacked_layers(weights, D):
    """The layers' leaves stacked on a leading axis, in their own type."""
    return {name: jnp.stack([weights[f"layers/{l}/{name}"]
                             for l in range(D["layers"])])
            for name in _LAYER_LEAVES}


def run_stack(stack, h, D, pr="f32"):
    """The ``num_hidden_layers`` layers once over ``h [T, d]``, no final
    norm: a scan, so that a layer's float32 weights exist while it runs
    (the barrier keeps the compiler from casting the whole stack ahead of
    the loop and slicing the cast)."""
    def step(h, w):
        return layer(jax.lax.optimization_barrier(w), h, D, pr), None

    h, _ = jax.lax.scan(step, h, stack)
    return h


def pass_states(weights, ids, D, pr="f32", stack=None):
    """``h_t [passes, T, d]``: every pass's normed output after reading
    ``ids [T]`` causally. ``stack`` is ``stacked_layers(weights, D)`` where
    the caller has made it already (once for all its requests)."""
    if stack is None:
        stack = stacked_layers(weights, D)
    h = _f32(weights["embed_tokens/embedding"][ids])

    def one_pass(h, _):
        h = _rms(run_stack(stack, h, D, pr), weights["norm/scale"], D["eps"])
        return h, h

    _, hs = jax.lax.scan(one_pass, h, None, length=D["passes"])
    return hs


def exit_distribution(gates):
    """``p [..., passes]`` from the gate's logits ``g [..., passes]``:
    ``lambda_t = sigmoid(g_t)``, ``p_t = lambda_t prod_{s<t} (1 -
    lambda_s)`` for every pass but the last, which takes what is left: the
    ``p`` sum to one."""
    lam = jax.nn.sigmoid(_f32(gates))
    stay = jnp.cumprod(1.0 - lam, axis=-1)
    before = jnp.concatenate([jnp.ones_like(stay[..., :1]), stay[..., :-1]],
                             axis=-1)
    return jnp.concatenate([(lam * before)[..., :-1], before[..., -1:]],
                           axis=-1)


def exit_pass(p, threshold):
    """The first pass at which the cumulated ``p [..., passes]`` reaches
    ``threshold``, and the last where none does. Before the last pass the
    cumulated ``p`` is under one (a sigmoid is under one), so a threshold of
    one is the last pass, exactly: a float32 sigmoid that rounds to one ends
    no walk early, and a sum that rounds below one falls back to the last
    pass."""
    early = (jnp.cumsum(p, axis=-1)[..., :-1] >= threshold) & (threshold < 1)
    return jnp.where(early.any(-1), jnp.argmax(early, -1), p.shape[-1] - 1)


def gate_logits(weights, hs):
    """``g [T, passes]`` of ``hs [passes, T, d]``."""
    g = jnp.matmul(hs, _f32(weights["early_exit_gate/kernel"]),
                   precision=jax.lax.Precision.HIGHEST)[..., 0]
    return (g + _f32(weights["early_exit_gate/bias"])[0]).T


def _bound(dims, n_heads):
    D = dims or _DIMS
    if D is None:
        raise RuntimeError("call weight_shapes(cfg) or bind(cfg) first")
    if n_heads is not None and n_heads != D["heads"]:
        raise ValueError(f"n_heads={n_heads}, configuration has {D['heads']}")
    return D


def _logits(weights, stack, row, pos, D, precision):
    """Logits ``[n, V]`` at ``pos [n]`` after reading ``row [T]``: the head
    over the LAST pass's normed ``h``."""
    h = pass_states(weights, row, D, precision, stack)[-1][pos]
    return lowp.matmul(h, _f32(weights["lm_head/kernel"]), precision)


def logits_at(weights, ids, positions, n_heads=None, precision="f32",
              dims=None):
    """Logits ``[B, n, V]`` after reading ``ids [B, T]`` causally, at
    ``positions [B, n]`` (the logits at position p predict token p + 1);
    one request at a time. ``weights`` is the flat ``{name: array}`` of
    ``weight_shapes``; ``dims`` defaults to the bound configuration."""
    D = _bound(dims, n_heads)
    stack = stacked_layers(weights, D)
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda a: _logits(weights, stack, a[0], a[1], D, precision),
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
    stack = stacked_layers(weights, D)

    def one(args):
        row, pos, toks = args
        ref = _logits(weights, stack, row, pos, D, "f32")
        best = jnp.max(ref, axis=-1)
        served = jnp.take_along_axis(ref, toks[:, None], axis=-1)[:, 0]
        if precision == "f32":
            return best - served, jnp.zeros_like(best)
        first = jnp.argmax(_logits(weights, stack, row, pos, D, precision),
                           axis=-1)
        chosen = jnp.take_along_axis(ref, first[:, None], axis=-1)[:, 0]
        return best - served, best - chosen

    with jax.default_matmul_precision("highest"):
        gap, control = jax.lax.map(one, (ids, positions, tokens))
    gap = reported(jnp.where(valid, gap, 0.0))
    if precision == "f32":
        return gap, jnp.zeros_like(gap)
    return gap, reported(jnp.where(valid, control, 0.0))
