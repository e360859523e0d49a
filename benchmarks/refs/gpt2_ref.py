"""Plain reference of the GPT-2 decoder (OpenAI GPT-2; pre-layer-norm
blocks, learned positions, exact GELU, tied output table): the full forward
pass in straightforward ``jax.numpy`` float32, no cache, no batching tricks,
no kernels. It imports nothing of the program.

Departures from the published model, all shared with the program under test:
the vocabulary is padded to 50304 rows and layer-norm epsilon is 1e-6.
"""

import jax
import jax.numpy as jnp

from benchmarks.refs import lowp

LAYER = "params/transformer/layers/DeepSpeedTransformerLayer_0/"
TOP = "params/transformer/"
LN_EPS = 1e-6


def weight_shapes(cfg):
    H, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    P, F = cfg["max_position_embeddings"], 4 * cfg["hidden_size"]
    return {
        TOP + "wte/embedding": (V, H), TOP + "wpe/embedding": (P, H),
        TOP + "ln_f/scale": (H,), TOP + "ln_f/bias": (H,),
        LAYER + "ln_attn/scale": (L, H), LAYER + "ln_attn/bias": (L, H),
        LAYER + "qkv/kernel": (L, H, 3 * H), LAYER + "qkv/bias": (L, 3 * H),
        LAYER + "attn_out/kernel": (L, H, H), LAYER + "attn_out/bias": (L, H),
        LAYER + "ln_ffn/scale": (L, H), LAYER + "ln_ffn/bias": (L, H),
        LAYER + "ff1/kernel": (L, H, F), LAYER + "ff1/bias": (L, F),
        LAYER + "ff2/kernel": (L, F, H), LAYER + "ff2/bias": (L, H),
    }


def _ln(x, scale, bias):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * scale + bias


def _block(h, w, n_heads, precision):
    B, T, H = h.shape
    hd = H // n_heads
    a = _ln(h, w["ln_attn/scale"], w["ln_attn/bias"])
    qkv = lowp.matmul(a, w["qkv/kernel"], precision) + w["qkv/bias"]
    q, k, v = (t.reshape(B, T, n_heads, hd) for t in jnp.split(qkv, 3, -1))
    scores = lowp.einsum("bqnd,bknd->bnqk", q, k, precision)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = lowp.einsum("bnqk,bknd->bqnd", probs, v, precision)
    h = h + lowp.matmul(ctx.reshape(B, T, H), w["attn_out/kernel"],
                        precision) + w["attn_out/bias"]
    f = _ln(h, w["ln_ffn/scale"], w["ln_ffn/bias"])
    f = jax.nn.gelu(lowp.matmul(f, w["ff1/kernel"], precision)
                    + w["ff1/bias"], approximate=False)
    return h + lowp.matmul(f, w["ff2/kernel"], precision) + w["ff2/bias"]


def logits_at(weights, ids, positions, n_heads, precision="f32"):
    """Logits ``[B, n, V]`` after reading ``ids [B, T]`` causally, at
    ``positions [B, n]`` (the logits at position p predict token p + 1).
    ``weights`` is the flat ``{name: array}`` of ``weight_shapes``."""
    w = {k: jnp.asarray(v, jnp.float32) for k, v in weights.items()}
    T = ids.shape[1]
    h = w[TOP + "wte/embedding"][ids] + w[TOP + "wpe/embedding"][:T][None]
    layer = {k[len(LAYER):]: v for k, v in w.items() if k.startswith(LAYER)}

    def body(h, lw):
        return _block(h, lw, n_heads, precision), None

    h, _ = jax.lax.scan(body, h, layer)
    h = jnp.take_along_axis(h, positions[:, :, None], axis=1)
    h = _ln(h, w[TOP + "ln_f/scale"], w[TOP + "ln_f/bias"])
    return lowp.matmul(h, w[TOP + "wte/embedding"].T, precision)


def served_token_gaps(weights, ids, positions, tokens, valid, n_heads,
                      precision="f32"):
    """For each compared position: how far the logit of ``tokens`` lies
    below the reference's best (``gap``), and the same for the token that a
    ``precision`` forward pass puts first (``control_gap``; zero by
    construction at ``f32``). ``valid`` masks the padding."""
    ref = logits_at(weights, ids, positions, n_heads, "f32")
    best = jnp.max(ref, axis=-1)
    served = jnp.take_along_axis(ref, tokens[:, :, None], axis=-1)[..., 0]
    gap = jnp.where(valid, best - served, 0.0)
    if precision == "f32":
        return gap, jnp.zeros_like(gap)
    low = logits_at(weights, ids, positions, n_heads, precision)
    first = jnp.argmax(low, axis=-1)
    chosen = jnp.take_along_axis(ref, first[:, :, None], axis=-1)[..., 0]
    return gap, jnp.where(valid, best - chosen, 0.0)
