"""Plain reference of the Nemotron-H decoder (nvidia/NVIDIA-Nemotron-3-Nano-
30B-A3B-BF16, ``model_type: nemotron_h``): the full forward pass in
straightforward ``jax.numpy`` float32 at ``highest`` matmul precision, no
cache, no chunking, no kernels, one request at a time. It imports nothing of
the program.

The equations, blocks numbered from 1, one mixer a block by the letter of
``hybrid_override_pattern`` (``M``, ``E``, ``*``); no projection has a bias
but the convolution:

    h += Mixer_i(RMSNorm(h));  final RMSNorm;  lm_head

- Mamba-2 (``M``): ``[z, xBC, dt] = x W_in`` (widths ``d_inner``, ``d_inner
  + 2 G N``, ``H``; ``d_inner = H P``, not ``expand`` x hidden);
  ``xBC = SiLU(conv(xBC) + b)``, depthwise and causal over time, ``y_t =
  sum_j w[j] u_{t-(K-1)+j}``; split into ``x [H, P]``, ``B [G, N]``, ``C [G,
  N]``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)`` a head; head
  ``h`` reads group ``h // (H / G)``. State a head, token by token in a
  ``lax.scan``: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``; ``y_t = S_t
  C_t + D x_t``. Then gate first, ``y * SiLU(z)``, RMS-normalised in ``G``
  groups of channels, times a scale; ``W_out``.
- Attention (``*``): ``q = x W_q`` [Q, hd], ``k, v = x W_k, x W_v`` [KV,
  hd]; nothing is rotated or otherwise given a position; causal softmax of
  ``q k^T / sqrt(hd)`` in blocks of queries, query head ``j`` reading
  key-value head ``j // (Q / KV)``; ``W_o (P v)``.
- Experts (``E``): ``s = sigmoid(x W_r)``; the ``k`` largest of ``s + b``;
  ``w = s[idx] / sum(s[idx]) * scaling``; ``sum_e w_e down_e(relu(up_e
  x)^2) + down_s(relu(up_s x)^2)``, the shared expert of a width of its own.

Departures from the published model, all shared with the program under test
and stated in the configuration's file: this chip's share of a deployment
(``n_routed_experts`` experts held from ``experts_first`` on, of the
``n_routed_experts_published`` the router scores; the part of the sum the
other experts would add is left out and the partial sum goes on to the next
block; ``vocab_size`` rows of the vocabulary; the first ``num_hidden_layers``
letters of the pattern), and what the public config does not say
(``assumed`` there). The held experts are applied a block at a time to every
token with the weight the router gave (zero where the expert was not
picked), upcast a block at a time, so that the weights stay in the type they
are served in.

What ``served_token_gaps`` reports at a token is what the Kimi-Linear
reference reports (``kimi_linear_ref.reported``, imported: the larger of the
mean of the gap over the token and the 31 before it in its request, and a
twentieth of its own gap), for the reason given there: the 6th and the 7th
largest of 128 router scores are often closer than bfloat16 rounding moves
them, a swapped expert moves single tokens and a lower precision every
token.

The routers' bias is made by the benchmark, not drawn (``balanced``): with
random weights a squared-ReLU expert's output has a mean that no token
moves (``relu(a)^2`` is never negative), so every token's hidden state
shares a direction and a random router prefers the same experts for all of
them: on the chip the busiest held expert took 4.5-5.3 times the mean load,
87-91% of the held experts were touched a step depending on the seed, and
six seeds' rates spread by 1.7% where 0.5% admits a cell (PERF.md, PR 31).
A trained router is balanced, by this very bias (``e_score_correction_bias``
is what aux-loss-free balancing updates). So ``balanced`` reads ``CAL_SEQS``
sequences of ``CAL_TOKENS`` random tokens through THIS reference, block by
block, and at each expert block sets the bias so that the sample's picks
fall evenly on all routed experts, before it goes on. The adapter gives the
program the weights with that bias; ``logits_at`` puts the same values in
place of the drawn ones when it is handed the weights they were made from
(``_BALANCED`` keeps the last ones made with a fingerprint of their weights;
the graph asks for them when it runs and compares the fingerprint: other
weights are used as they come, so the unit tests, which never balance, are
not touched).

The harness calls ``served_token_gaps(weights, ids, positions, tokens,
valid, n_heads=, precision=)`` with no configuration: ``weight_shapes(cfg)``,
which it always calls first, binds the configuration's sizes for the calls
that follow (``bind``).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from benchmarks.refs import lowp
from benchmarks.refs.kimi_linear_ref import reported

EXPERT_BLOCK = 8        # held experts upcast and applied at a time
QUERY_BLOCK = 512       # queries of the causal softmax at a time
CAL_SEQS, CAL_TOKENS = 4, 1024      # the sample the routers are balanced on
CAL_KEY = 20260931
CAL_STEPS, CAL_RATE = 300, 0.05     # bias updates, and the first one's size

_DIMS = None
_BALANCED = None        # (fingerprint [8], {leaf: bias [E]}) last made


def dims_of(cfg):
    """The sizes the equations need, from the configuration's keys."""
    L = cfg["num_hidden_layers"]
    share = cfg.get("share", {})
    kinds = {"M": "mamba", "E": "moe", "*": "attn"}
    return {
        "layers": L, "hidden": cfg["hidden_size"], "vocab": cfg["vocab_size"],
        "kinds": tuple(kinds[c] for c in cfg["hybrid_override_pattern"][:L]),
        "eps": cfg["layer_norm_epsilon"],
        "m_heads": cfg["mamba_num_heads"], "m_head": cfg["mamba_head_dim"],
        "state": cfg["ssm_state_size"], "groups": cfg["n_groups"],
        "conv": cfg["conv_kernel"],
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head": cfg["head_dim"],
        "expert_width": cfg["moe_intermediate_size"],
        "shared_width": cfg["moe_shared_expert_intermediate_size"],
        "shared": cfg["n_shared_experts"],
        "experts_held": cfg["n_routed_experts"],
        "experts_first": share.get("experts_first", 0),
        "experts_routed": share.get("n_routed_experts_published",
                                    cfg["n_routed_experts"]),
        "top_k": cfg["num_experts_per_tok"],
        "scaling": cfg["routed_scaling_factor"],
        "renormalize": cfg["norm_topk_prob"],
    }


def bind(cfg):
    global _DIMS
    _DIMS = dims_of(cfg)
    return _DIMS


def weight_shapes(cfg):
    """Leaves by block (``layers/<i>/...``, i from 1), so that no leaf is
    larger than one block's experts of one matrix. Norm scales end in
    ``/scale`` (made as 1 + normal)."""
    D = bind(cfg)
    d, V = D["hidden"], D["vocab"]
    di = D["m_heads"] * D["m_head"]
    cd = di + 2 * D["groups"] * D["state"]
    out = {"embed_tokens/embedding": (V, d), "lm_head/kernel": (d, V),
           "norm_f/scale": (d,)}
    for i in range(1, D["layers"] + 1):
        out[f"layers/{i}/norm/scale"] = (d,)
        m = f"layers/{i}/mixer/"
        kind = D["kinds"][i - 1]
        if kind == "mamba":
            out[m + "in_proj/kernel"] = (d, di + cd + D["m_heads"])
            out[m + "conv1d/kernel"] = (D["conv"], cd)
            out[m + "conv1d/bias"] = (cd,)
            for n in ("A_log", "D", "dt_bias"):
                out[m + n] = (D["m_heads"],)
            out[m + "norm/scale"] = (di,)
            out[m + "out_proj/kernel"] = (di, d)
        elif kind == "attn":
            out[m + "q_proj/kernel"] = (d, D["heads"] * D["head"])
            out[m + "k_proj/kernel"] = (d, D["kv_heads"] * D["head"])
            out[m + "v_proj/kernel"] = (d, D["kv_heads"] * D["head"])
            out[m + "o_proj/kernel"] = (D["heads"] * D["head"], d)
        else:
            f, E = D["expert_width"], D["experts_held"]
            out[m + "gate/kernel"] = (d, D["experts_routed"])
            out[m + "gate/e_score_correction_bias"] = (D["experts_routed"],)
            out[m + "experts/up_proj"] = (E, d, f)
            out[m + "experts/down_proj"] = (E, f, d)
            if D["shared"]:
                out[m + "shared_experts/up_proj/kernel"] = (
                    d, D["shared_width"])
                out[m + "shared_experts/down_proj/kernel"] = (
                    D["shared_width"], d)
    return out


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * _f32(scale)


def _relu2(x, up, down, pr):
    a = jax.nn.relu(lowp.matmul(x, _f32(up), pr))
    return lowp.matmul(a * a, _f32(down), pr)


def _causal_conv(u, w, b):
    """``y_t = sum_j w[j] u_{t-(K-1)+j} + b`` with zeros before the start."""
    K, T = w.shape[0], u.shape[0]
    ext = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
    return sum(w[j] * ext[j:j + T] for j in range(K)) + b


def mamba_mixer(w, x, D, pr):
    """``x [T, d]`` -> ``[T, d]``; the recurrence as written."""
    T = x.shape[0]
    H, P, N, G = D["m_heads"], D["m_head"], D["state"], D["groups"]
    di = H * P
    zxbcdt = lowp.matmul(x, _f32(w["in_proj/kernel"]), pr)
    z, xBC, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + di + 2 * G * N],
                  zxbcdt[:, di + di + 2 * G * N:])
    xBC = jax.nn.silu(_causal_conv(xBC, _f32(w["conv1d/kernel"]),
                                   _f32(w["conv1d/bias"])))
    xs = xBC[:, :di].reshape(T, H, P)
    # head h reads group h // (H / G)
    B = jnp.repeat(xBC[:, di:di + G * N].reshape(T, G, N), H // G, axis=1)
    C = jnp.repeat(xBC[:, di + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + _f32(w["dt_bias"]))            # [T, H]
    A = -jnp.exp(_f32(w["A_log"]))

    def step(S, row):
        x_t, B_t, C_t, dt_t = row
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.sum(S * C_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (xs, B, C, dt))
    y = y + _f32(w["D"])[:, None] * xs
    y = y.reshape(T, di) * jax.nn.silu(z)
    y = y.reshape(T, G, di // G)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + D["eps"])
    y = y.reshape(T, di) * _f32(w["norm/scale"])
    return lowp.matmul(y, _f32(w["out_proj/kernel"]), pr)


def attention_mixer(w, x, D, pr):
    """``x [T, d]`` -> ``[T, d]``; grouped queries, no positions, causal
    softmax in blocks of queries."""
    T = x.shape[0]
    nq, nkv, hd = D["heads"], D["kv_heads"], D["head"]
    q = lowp.matmul(x, _f32(w["q_proj/kernel"]), pr).reshape(T, nq, hd)
    k = lowp.matmul(x, _f32(w["k_proj/kernel"]), pr).reshape(T, nkv, hd)
    v = lowp.matmul(x, _f32(w["v_proj/kernel"]), pr).reshape(T, nkv, hd)
    k = jnp.repeat(k, nq // nkv, axis=1)     # query head j reads j // (Q/KV)
    v = jnp.repeat(v, nq // nkv, axis=1)
    qb = min(QUERY_BLOCK, T)
    nb = -(-T // qb)
    qpad = jnp.pad(q, ((0, nb * qb - T), (0, 0), (0, 0)))

    def block(j):
        qs = jax.lax.dynamic_slice_in_dim(qpad, j * qb, qb, axis=0)
        s = lowp.einsum("qhd,shd->hqs", qs, k, pr) / jnp.sqrt(jnp.float32(hd))
        ok = jnp.arange(T)[None, :] <= (j * qb + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1)
        return lowp.einsum("hqs,shd->qhd", p, v, pr)

    ctx = jax.lax.map(block, jnp.arange(nb)).reshape(nb * qb, nq * hd)[:T]
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


def expert_mixer(w, x, D, pr):
    """This share's part of the expert block for ``x [T, d]``: the held
    experts (``experts_first`` on) weighted as routed, plus the shared
    expert."""
    idx, wt = route(w, x, D)
    E = D["experts_held"]
    local = idx - D["experts_first"]
    # weight of every held expert for every token (0 where not picked)
    dense = jnp.sum(jnp.where(local[..., None] == jnp.arange(E), wt[..., None],
                              0.0), axis=1)                      # [T, E]
    eb = min(EXPERT_BLOCK, E)
    assert E % eb == 0, (E, eb)

    def block(y, j):
        up, down = (jax.lax.dynamic_slice_in_dim(w[f"experts/{n}"], j * eb,
                                                 eb, 0)
                    for n in ("up_proj", "down_proj"))
        wts = jax.lax.dynamic_slice_in_dim(dense, j * eb, eb, axis=1)
        for e in range(eb):
            y = y + wts[:, e:e + 1] * _relu2(x, up[e], down[e], pr)
        return y, None

    y, _ = jax.lax.scan(block, jnp.zeros_like(x), jnp.arange(E // eb))
    if D["shared"]:
        y = y + _relu2(x, w["shared_experts/up_proj/kernel"],
                       w["shared_experts/down_proj/kernel"], pr)
    return y


MIXERS = {"mamba": mamba_mixer, "attn": attention_mixer,
          "moe": expert_mixer}


def _sub(w, prefix):
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def balance_bias(scores, k):
    """The bias ``[E]`` under which the ``k`` largest of ``scores + bias``
    fall evenly on the experts, for ``scores [T, E]``: ``CAL_STEPS`` updates
    of ``bias += rate * (1 - load / mean load)``, the rate shrinking by 1% a
    step (the published balancing updates by the sign of the same error)."""
    T, E = scores.shape

    def step(i, b):
        _, idx = jax.lax.top_k(scores + b, k)
        load = jnp.zeros(E).at[idx.reshape(-1)].add(E / (T * k))
        return b + CAL_RATE * 0.99 ** i * jnp.clip(1.0 - load, -1.0, 1.0)

    return jax.lax.fori_loop(0, CAL_STEPS, step, jnp.zeros(E))


def _fingerprint(weights):
    return _f32(weights["norm_f/scale"])[:8]


def balanced_biases(weights, D):
    """``{leaf: bias [E]}`` for every expert block, each set on the hidden
    states that the blocks before it give with THEIR balanced bias, and
    rounded to the type the weights are served in. ``D`` is ``dims_of``'s
    sizes as sorted items (a static argument of the jitted call)."""
    D = dict(D)
    ids = jax.random.randint(jax.random.PRNGKey(CAL_KEY),
                             (CAL_SEQS, CAL_TOKENS), 0, D["vocab"])
    h = _f32(weights["embed_tokens/embedding"])[ids]
    out = {}
    with jax.default_matmul_precision("highest"):
        for i in range(1, D["layers"] + 1):
            x = _rms(h, weights[f"layers/{i}/norm/scale"], D["eps"])
            w = _sub(weights, f"layers/{i}/mixer/")
            kind = D["kinds"][i - 1]
            if kind == "moe":
                flat = x.reshape(-1, x.shape[-1])
                s = jax.nn.sigmoid(jnp.matmul(flat, _f32(w["gate/kernel"])))
                leaf = "gate/e_score_correction_bias"
                b = _f32(balance_bias(s, D["top_k"]).astype(w[leaf].dtype))
                out[f"layers/{i}/mixer/{leaf}"] = b
                h = h + expert_mixer(dict(w, **{leaf: b}), flat, D,
                                     "f32").reshape(h.shape)
            else:
                h = h + jax.vmap(lambda row, w=w, kind=kind: MIXERS[kind](
                    w, row, D, "f32"))(x)
    return out


def balanced(weights, cfg):
    """``weights`` with every router's bias balanced (see the module's
    docstring); remembers the biases for ``logits_at``."""
    global _BALANCED
    biases = jax.jit(balanced_biases, static_argnums=1)(
        weights, tuple(sorted(dims_of(cfg).items())))
    _BALANCED = (jax.device_get(_fingerprint(weights)),
                 jax.device_get(biases))
    return dict(weights, **{n: jnp.asarray(b, weights[n].dtype)
                            for n, b in biases.items()})


def _as_balanced(weights):
    """The weights with the biases ``balanced`` last made, where these are
    the weights it made them from. What was last made is asked for when the
    program RUNS (a host callback), not when it is traced: the harness jits
    ``served_token_gaps`` once a process and calls it for every seed."""
    names = sorted(n for n in weights
                   if n.endswith("gate/e_score_correction_bias"))
    if not names:
        return weights
    shapes = [weights[n].shape for n in names]

    def last_made():
        tag, biases = _BALANCED or (None, {})
        if sorted(biases) != names or [biases[n].shape
                                       for n in names] != shapes:
            return (np.full(8, np.nan, np.float32),
                    [np.zeros(sh, np.float32) for sh in shapes])
        return (np.asarray(tag, np.float32),
                [np.asarray(biases[n], np.float32) for n in names])

    tag, biases = io_callback(
        last_made, (jax.ShapeDtypeStruct((8,), jnp.float32),
                    [jax.ShapeDtypeStruct(sh, jnp.float32) for sh in shapes]))
    same = jnp.all(_fingerprint(weights) == tag)
    return dict(weights, **{n: jnp.where(same, b, _f32(weights[n]))
                            for n, b in zip(names, biases)})


def hidden_states(weights, ids, D, pr="f32"):
    """Final-norm inputs ``[T, d]`` after reading ``ids [T]`` causally."""
    h = _f32(weights["embed_tokens/embedding"])[ids]
    for i in range(1, D["layers"] + 1):
        x = _rms(h, weights[f"layers/{i}/norm/scale"], D["eps"])
        h = h + MIXERS[D["kinds"][i - 1]](
            _sub(weights, f"layers/{i}/mixer/"), x, D, pr)
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
    weights = _as_balanced(weights)

    def one(args):
        row, pos = args
        h = hidden_states(weights, row, D, precision)[pos]
        h = _rms(h, weights["norm_f/scale"], D["eps"])
        return lowp.matmul(h, _f32(weights["lm_head/kernel"]), precision)

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, (ids, positions))


def served_token_gaps(weights, ids, positions, tokens, valid, n_heads,
                      precision="f32"):
    """For each compared position: how far the logits of ``tokens`` lie
    below the reference's best, as ``reported`` weighs it (``gap``), and the
    same for the tokens that a ``precision`` forward pass puts first
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
