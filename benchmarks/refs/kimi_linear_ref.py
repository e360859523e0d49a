"""Plain reference of the Kimi-Linear decoder (moonshotai/Kimi-Linear-48B-A3B,
``model_type: kimi_linear``): the full forward pass in straightforward
``jax.numpy`` float32 at ``highest`` matmul precision, no cache, no batching
tricks, no kernels, one request at a time. It imports nothing of the program.

The equations, layers numbered from 1 (``kda_layers`` / ``full_attn_layers``
say which mixer; layers up to ``first_k_dense_replace`` have the dense
SwiGLU, the others the expert FFN):

    h += Mixer(RMSNorm(h));  h += FFN(RMSNorm(h));  final RMSNorm;  lm_head

- KDA: ``q = l2norm(SiLU(conv(x Wq))) * d^-0.5``, ``k = l2norm(SiLU(conv(x
  Wk)))``, ``v = SiLU(conv(x Wv))``; conv is a depthwise causal convolution
  over time, ``y_t = sum_j w[j] u_{t-(K-1)+j}``; per-channel log-decay
  ``g_t = -exp(A_log[head]) * softplus((x Wfa) Wfb + dt_bias)``, ``beta_t =
  sigmoid(x Wb)``; state per head, token by token in a ``lax.scan``:
  ``S' = exp(g_t)[:, None] * S``; ``u = beta_t (v_t - S'^T k_t)``;
  ``S = S' + k_t u^T``; ``o_t = S^T q_t``; output ``Wo (RMSNorm_head(o_t) *
  sigmoid((x Wga) Wgb + b))``.
- MLA without rotation: ``q = x Wq`` per head nope + rope wide; ``[c, kr] =
  x Wkva``; ``c = RMSNorm(c)``; ``[k_nope, v] = c Wkvb``; ``k = [k_nope, kr
  for every head]``; causal softmax of ``q k^T / sqrt(nope + rope)`` in
  blocks of queries; ``Wo (P v)``. Nothing is rotated anywhere.
- Expert FFN: ``s = sigmoid(x Wr)``; the ``k`` largest of ``s + b``;
  ``w = s[idx] / sum(s[idx]) * scaling``; ``sum_e w_e SwiGLU_e(x) +
  SwiGLU_shared(x)``.

Departures from the published model, all shared with the program under test
and stated in the configuration's file: this chip's share of a deployment
(``num_experts`` experts held from ``experts_first`` on, of the
``num_experts_published`` the router scores; the part of the sum the other
experts would add is left out and the partial sum goes on to the next
layer; ``vocab_size`` rows of the vocabulary), and the sizes the public
config does not give (``assumed`` there). The held experts are applied a
block at a time to every token with the weight the router gave (zero where
the expert was not picked), upcast a block at a time, so that the weights
stay in the type they are served in.

What ``served_token_gaps`` reports at a token is the larger of two numbers:
the mean of its gap and the 31 gaps before it in its request
(``GAP_WINDOW``), and a twentieth of its own gap (``TOKEN_SHARE``); not the
single gap that the GPT-2 reference reports. The reason is the router: the
8th and the 9th largest of 256 scores are often closer than bfloat16
rounding moves them, a swapped expert moves a logit vector by up to a third
of its norm (read on the chip in THIS reference with its operands rounded
to bfloat16, PERF.md), and so a sound bfloat16 run's largest single gap
(0.6-0.8 over some 2,000 tokens) is not told from the fp8 control's (1.2)
by more than their spread. A swap touches single tokens; a lower precision
touches every token: over 32 tokens a sound run reads 0.03-0.06 and the fp8
control 0.16-0.25. The mean alone would let one wrong token through if it
lay less than 32 limits below the best; the twentieth holds every single
token to 20 limits (2.0 at the limit of 0.1: twice a sound run's worst,
0.6-1.1 over 21 runs on the chip, and a token drawn at random lies 4.3
below).

The harness calls ``served_token_gaps(weights, ids, positions, tokens,
valid, n_heads=, precision=)`` with no configuration: ``weight_shapes(cfg)``,
which it always calls first, binds the configuration's sizes for the calls
that follow (``bind``).
"""

import jax
import jax.numpy as jnp

from benchmarks.refs import lowp

EXPERT_BLOCK = 8        # held experts upcast and applied at a time
QUERY_BLOCK = 512       # queries of the causal softmax at a time
GAP_WINDOW = 32         # served tokens a reported gap is the mean over
TOKEN_SHARE = 20        # ... or this part of the token's own gap, if larger

_DIMS = None


def dims_of(cfg):
    """The sizes the equations need, from the configuration's keys."""
    lin = cfg["linear_attn_config"]
    L = cfg["num_hidden_layers"]
    share = cfg.get("share", {})
    return {
        "layers": L, "hidden": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "kinds": tuple("kda" if i in lin["kda_layers"] else "mla"
                       for i in range(1, L + 1)),
        "dense_layers": cfg["first_k_dense_replace"],
        "dense_width": cfg["intermediate_size"],
        "expert_width": cfg["moe_intermediate_size"],
        "experts_held": cfg["num_experts"],
        "experts_first": share.get("experts_first", 0),
        "experts_routed": share.get("num_experts_published",
                                    cfg["num_experts"]),
        "top_k": cfg["num_experts_per_token"],
        "scaling": cfg["routed_scaling_factor"],
        "renormalize": cfg["moe_renormalize"],
        "shared": cfg["num_shared_experts"],
        "eps": cfg["rms_norm_eps"],
        "heads": cfg["num_attention_heads"], "rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v_head": cfg["v_head_dim"],
        "kda_heads": lin["num_heads"], "kda_head": lin["head_dim"],
        "conv": lin["short_conv_kernel_size"],
    }


def bind(cfg):
    global _DIMS
    _DIMS = dims_of(cfg)
    return _DIMS


def weight_shapes(cfg):
    """Leaves by layer (``layers/<i>/...``, i from 1), so that no leaf is
    larger than one layer's experts of one matrix. Norm scales end in
    ``/scale`` (made as 1 + normal)."""
    D = bind(cfg)
    d, V = D["hidden"], D["vocab"]
    W = D["kda_heads"] * D["kda_head"]
    nh = D["heads"]
    out = {"embed_tokens/embedding": (V, d), "lm_head/kernel": (d, V),
           "norm/scale": (d,)}
    for i in range(1, D["layers"] + 1):
        p = f"layers/{i}/"
        out[p + "input_layernorm/scale"] = (d,)
        out[p + "post_attention_layernorm/scale"] = (d,)
        a = p + "self_attn/"
        if D["kinds"][i - 1] == "kda":
            for n in "qkv":
                out[a + f"{n}_proj/kernel"] = (d, W)
                out[a + f"{n}_conv/kernel"] = (D["conv"], W)
            out[a + "f_a_proj/kernel"] = (d, D["kda_head"])
            out[a + "f_b_proj/kernel"] = (D["kda_head"], W)
            out[a + "dt_bias"] = (W,)
            out[a + "A_log"] = (D["kda_heads"],)
            out[a + "b_proj/kernel"] = (d, D["kda_heads"])
            out[a + "g_a_proj/kernel"] = (d, D["kda_head"])
            out[a + "g_b_proj/kernel"] = (D["kda_head"], W)
            out[a + "g_b_proj/bias"] = (W,)
            out[a + "o_norm/scale"] = (D["kda_head"],)
            out[a + "o_proj/kernel"] = (W, d)
        else:
            out[a + "q_proj/kernel"] = (d, nh * (D["nope"] + D["rope"]))
            out[a + "kv_a_proj_with_mqa/kernel"] = (d, D["rank"] + D["rope"])
            out[a + "kv_a_layernorm/scale"] = (D["rank"],)
            out[a + "kv_b_proj/kernel"] = (D["rank"],
                                           nh * (D["nope"] + D["v_head"]))
            out[a + "o_proj/kernel"] = (nh * D["v_head"], d)
        m = p + "mlp/"
        if i <= D["dense_layers"]:
            f = D["dense_width"]
            out[m + "gate_proj/kernel"] = (d, f)
            out[m + "up_proj/kernel"] = (d, f)
            out[m + "down_proj/kernel"] = (f, d)
        else:
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


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * _f32(scale)


def _swiglu(x, gate, up, down, pr):
    a = jax.nn.silu(lowp.matmul(x, _f32(gate), pr)) * lowp.matmul(
        x, _f32(up), pr)
    return lowp.matmul(a, _f32(down), pr)


def _causal_conv(u, w):
    """``y_t = sum_j w[j] u_{t-(K-1)+j}`` with zeros before the start."""
    K, T = w.shape[0], u.shape[0]
    ext = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
    return sum(w[j] * ext[j:j + T] for j in range(K))


def kda_mixer(w, x, D, pr):
    """``x [T, d]`` -> ``[T, d]``; the recurrence as written."""
    T = x.shape[0]
    H, dh = D["kda_heads"], D["kda_head"]

    def branch(n):
        u = lowp.matmul(x, _f32(w[f"{n}_proj/kernel"]), pr)
        return jax.nn.silu(_causal_conv(u, _f32(w[f"{n}_conv/kernel"]))
                           ).reshape(T, H, dh)

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q = l2(branch("q")) * dh ** -0.5
    k = l2(branch("k"))
    v = branch("v")
    f = lowp.matmul(lowp.matmul(x, _f32(w["f_a_proj/kernel"]), pr),
                    _f32(w["f_b_proj/kernel"]), pr) + _f32(w["dt_bias"])
    g = -jnp.exp(_f32(w["A_log"]))[:, None] * jax.nn.softplus(
        f.reshape(T, H, dh))
    beta = jax.nn.sigmoid(lowp.matmul(x, _f32(w["b_proj/kernel"]), pr))

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, :, None] * S
        u = b_t[:, None] * (v_t - jnp.sum(S * k_t[:, :, None], axis=1))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.sum(S * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(step, jnp.zeros((H, dh, dh), jnp.float32),
                        (q, k, v, g, beta))
    gate = jax.nn.sigmoid(
        lowp.matmul(lowp.matmul(x, _f32(w["g_a_proj/kernel"]), pr),
                    _f32(w["g_b_proj/kernel"]), pr)
        + _f32(w["g_b_proj/bias"])).reshape(T, H, dh)
    o = _rms(o, w["o_norm/scale"], D["eps"]) * gate
    return lowp.matmul(o.reshape(T, H * dh), _f32(w["o_proj/kernel"]), pr)


def mla_mixer(w, x, D, pr):
    """``x [T, d]`` -> ``[T, d]``; expanded keys and values, causal softmax
    in blocks of queries."""
    T = x.shape[0]
    nh, dn, dr, dv, rank = (D["heads"], D["nope"], D["rope"], D["v_head"],
                            D["rank"])
    q = lowp.matmul(x, _f32(w["q_proj/kernel"]), pr).reshape(T, nh, dn + dr)
    ckr = lowp.matmul(x, _f32(w["kv_a_proj_with_mqa/kernel"]), pr)
    c = _rms(ckr[:, :rank], w["kv_a_layernorm/scale"], D["eps"])
    kv = lowp.matmul(c, _f32(w["kv_b_proj/kernel"]), pr).reshape(
        T, nh, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(ckr[:, None, rank:], (T, nh, dr))], -1)
    v = kv[..., dn:]
    qb = min(QUERY_BLOCK, T)
    nb = -(-T // qb)
    qpad = jnp.pad(q, ((0, nb * qb - T), (0, 0), (0, 0)))

    def block(j):
        qs = jax.lax.dynamic_slice_in_dim(qpad, j * qb, qb, axis=0)
        s = lowp.einsum("qhd,shd->hqs", qs, k, pr) / jnp.sqrt(
            jnp.float32(dn + dr))
        ok = jnp.arange(T)[None, :] <= (j * qb + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
        return lowp.einsum("hqs,shd->qhd", p, v, pr)

    ctx = jax.lax.map(block, jnp.arange(nb)).reshape(nb * qb, nh * dv)[:T]
    return lowp.matmul(ctx, _f32(w["o_proj/kernel"]), pr)


def route(w, x, D):
    """Picks ``[T, k]`` among ALL routed experts and their weights."""
    s = jax.nn.sigmoid(jnp.matmul(x, _f32(w["gate/kernel"]),
                                  precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + _f32(w["gate/e_score_correction_bias"]),
                           D["top_k"])
    wt = jnp.take_along_axis(s, idx, -1)
    if D["renormalize"]:
        wt = wt / jnp.sum(wt, -1, keepdims=True)
    return idx, wt * D["scaling"]


def expert_ffn(w, x, D, pr):
    """This share's part of the expert layer for ``x [T, d]``: the held
    experts (``experts_first`` on) weighted as routed, plus the shared
    expert."""
    idx, wt = route(w, x, D)
    E = D["experts_held"]
    # weight of every held expert for every token (0 where not picked)
    local = idx - D["experts_first"]
    dense = jnp.sum(jnp.where(local[..., None] == jnp.arange(E), wt[..., None],
                              0.0), axis=1)                      # [T, E]
    eb = min(EXPERT_BLOCK, E)
    assert E % eb == 0, (E, eb)

    def block(y, j):
        sl = [jax.lax.dynamic_slice_in_dim(w[f"experts/{n}"], j * eb, eb, 0)
              for n in ("gate_proj", "up_proj", "down_proj")]
        wts = jax.lax.dynamic_slice_in_dim(dense, j * eb, eb, axis=1)
        for e in range(eb):
            y = y + wts[:, e:e + 1] * _swiglu(x, sl[0][e], sl[1][e], sl[2][e],
                                              pr)
        return y, None

    y, _ = jax.lax.scan(block, jnp.zeros_like(x), jnp.arange(E // eb))
    if D["shared"]:
        y = y + _swiglu(x, w["shared_experts/gate_proj/kernel"],
                        w["shared_experts/up_proj/kernel"],
                        w["shared_experts/down_proj/kernel"], pr)
    return y


def _sub(w, prefix):
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def hidden_states(weights, ids, D, pr="f32"):
    """Final-norm inputs ``[T, d]`` after reading ``ids [T]`` causally."""
    h = _f32(weights["embed_tokens/embedding"])[ids]
    for i in range(1, D["layers"] + 1):
        w = _sub(weights, f"layers/{i}/")
        x = _rms(h, w["input_layernorm/scale"], D["eps"])
        mixer = kda_mixer if D["kinds"][i - 1] == "kda" else mla_mixer
        h = h + mixer(_sub(w, "self_attn/"), x, D, pr)
        x = _rms(h, w["post_attention_layernorm/scale"], D["eps"])
        m = _sub(w, "mlp/")
        if i <= D["dense_layers"]:
            h = h + _swiglu(x, m["gate_proj/kernel"], m["up_proj/kernel"],
                            m["down_proj/kernel"], pr)
        else:
            h = h + expert_ffn(m, x, D, pr)
    return h


def logits_at(weights, ids, positions, n_heads=None, precision="f32",
              dims=None):
    """Logits ``[B, n, V]`` after reading ``ids [B, T]`` causally, at
    ``positions [B, n]`` (the logits at position p predict token p + 1);
    one request at a time. ``weights`` is the flat ``{name: array}`` of
    ``weight_shapes``; ``dims`` defaults to the bound configuration."""
    D = dims or _DIMS
    if D is None:
        raise RuntimeError("call weight_shapes(cfg) or bind(cfg) first")
    if n_heads is not None and n_heads != D["heads"]:
        raise ValueError(f"n_heads={n_heads}, configuration has {D['heads']}")

    def one(args):
        row, pos = args
        h = hidden_states(weights, row, D, precision)[pos]
        h = _rms(h, weights["norm/scale"], D["eps"])
        return lowp.matmul(h, _f32(weights["lm_head/kernel"]), precision)

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, (ids, positions))


def windowed(gap):
    """``gap [B, n]`` -> at each position, the sum of its gap and the
    ``GAP_WINDOW - 1`` before it over ``GAP_WINDOW`` (a request's first
    positions have fewer before them, and read lower for it)."""
    total = jnp.cumsum(gap, axis=1)
    before = jnp.pad(total, ((0, 0), (GAP_WINDOW, 0)))[:, :gap.shape[1]]
    return (total - before) / GAP_WINDOW


def reported(gap):
    """What is compared with the limit at each position of ``gap [B, n]``:
    the windowed mean, or ``1 / TOKEN_SHARE`` of the position's own gap
    where that is larger (the module's docstring says why)."""
    return jnp.maximum(windowed(gap), gap / TOKEN_SHARE)


def served_token_gaps(weights, ids, positions, tokens, valid, n_heads,
                      precision="f32"):
    """For each compared position: how far the logits of ``tokens`` lie
    below the reference's best, as ``reported`` weighs it (``gap``: the mean
    over the position and the ``GAP_WINDOW - 1`` before it, or a
    ``TOKEN_SHARE``-th of its own),
    and the same for the tokens that a ``precision`` forward pass puts first
    (``control_gap``; zero by construction at ``f32``). ``positions`` of a
    request are consecutive; ``valid`` masks the padding."""
    ref = logits_at(weights, ids, positions, n_heads, "f32")
    best = jnp.max(ref, axis=-1)
    served = jnp.take_along_axis(ref, tokens[:, :, None], axis=-1)[..., 0]
    gap = reported(jnp.where(valid, best - served, 0.0))
    if precision == "f32":
        return gap, jnp.zeros_like(gap)
    low = logits_at(weights, ids, positions, n_heads, precision)
    first = jnp.argmax(low, axis=-1)
    chosen = jnp.take_along_axis(ref, first[:, :, None], axis=-1)[..., 0]
    return gap, reported(jnp.where(valid, best - chosen, 0.0))
