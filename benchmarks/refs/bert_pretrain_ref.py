"""Plain reference of BERT pretraining as this benchmark runs it: the
forward pass, the masked-LM + next-sentence loss, its gradients and Adam, in
straightforward ``jax.numpy`` float32 with no kernels, no recomputation and
no sharding. It imports nothing of the program.

The architecture is the pre-layer-norm BERT of the reference system's
"fastest BERT training" benchmark (each block normalises its input; the
residual stream is not normalised after the last block), exact GELU,
layer-norm epsilon 1e-6, vocabulary padded to 30528, the masked-LM decoder
tied to the word embeddings. Dropout is off (see the configuration file).
"""

import jax
import jax.numpy as jnp

from benchmarks.refs import lowp

LAYER = "params/bert/encoder/layers/DeepSpeedTransformerLayer_0/"
EMB = "params/bert/embeddings/"
LN_EPS = 1e-6
# the leaves whose gradient every labelled token feeds and nothing else: their
# norm is steady from seed to seed (the next-sentence path's, fed by one
# position per row, swings eightfold)
STEADY_LEAVES = ("params/mlm_transform/kernel", "params/mlm_transform/bias",
                 "params/mlm_ln/scale", "params/mlm_ln/bias",
                 "params/mlm_bias")


def weight_shapes(cfg):
    H, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    F, P = cfg["intermediate_size"], cfg["max_position_embeddings"]
    return {
        EMB + "word_embeddings/embedding": (V, H),
        EMB + "position_embeddings/embedding": (P, H),
        EMB + "token_type_embeddings/embedding": (cfg["type_vocab_size"], H),
        EMB + "LayerNorm/scale": (H,), EMB + "LayerNorm/bias": (H,),
        LAYER + "ln_attn/scale": (L, H), LAYER + "ln_attn/bias": (L, H),
        LAYER + "qkv/kernel": (L, H, 3 * H), LAYER + "qkv/bias": (L, 3 * H),
        LAYER + "attn_out/kernel": (L, H, H), LAYER + "attn_out/bias": (L, H),
        LAYER + "ln_ffn/scale": (L, H), LAYER + "ln_ffn/bias": (L, H),
        LAYER + "ff1/kernel": (L, H, F), LAYER + "ff1/bias": (L, F),
        LAYER + "ff2/kernel": (L, F, H), LAYER + "ff2/bias": (L, H),
        "params/bert/pooler/kernel": (H, H), "params/bert/pooler/bias": (H,),
        "params/mlm_transform/kernel": (H, H),
        "params/mlm_transform/bias": (H,),
        "params/mlm_ln/scale": (H,), "params/mlm_ln/bias": (H,),
        "params/mlm_bias": (V,),
        "params/nsp_head/kernel": (H, 2), "params/nsp_head/bias": (2,),
    }


def _ln(x, scale, bias):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * scale + bias


def _block(h, key_bias, w, n_heads, precision):
    B, T, H = h.shape
    hd = H // n_heads
    a = _ln(h, w["ln_attn/scale"], w["ln_attn/bias"])
    qkv = lowp.matmul(a, w["qkv/kernel"], precision) + w["qkv/bias"]
    q, k, v = (t.reshape(B, T, n_heads, hd) for t in jnp.split(qkv, 3, -1))
    scores = lowp.einsum("bqnd,bknd->bnqk", q, k, precision)
    scores = scores / jnp.sqrt(jnp.float32(hd)) + key_bias
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = lowp.einsum("bnqk,bknd->bqnd", probs, v, precision)
    h = h + lowp.matmul(ctx.reshape(B, T, H), w["attn_out/kernel"],
                        precision) + w["attn_out/bias"]
    f = _ln(h, w["ln_ffn/scale"], w["ln_ffn/bias"])
    f = jax.nn.gelu(lowp.matmul(f, w["ff1/kernel"], precision)
                    + w["ff1/bias"], approximate=False)
    return h + lowp.matmul(f, w["ff2/kernel"], precision) + w["ff2/bias"]


def _nll_sum(logits, labels):
    """Sum of the negative log-likelihoods of ``labels`` (-1 = ignored)."""
    valid = labels >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None],
                                 axis=-1)[..., 0]
    return -jnp.sum(jnp.where(valid, picked, 0.0))


def loss_sums(w, batch, n_heads, precision="f32"):
    """(sum of masked-LM NLLs, sum of next-sentence NLLs) over the rows of
    ``batch`` = (ids, token types, attention mask, mlm labels, nsp labels)."""
    ids, types, attn, mlm_labels, nsp_labels = batch
    T = ids.shape[1]
    h = (w[EMB + "word_embeddings/embedding"][ids]
         + w[EMB + "position_embeddings/embedding"][:T][None]
         + w[EMB + "token_type_embeddings/embedding"][types])
    h = _ln(h, w[EMB + "LayerNorm/scale"], w[EMB + "LayerNorm/bias"])
    key_bias = (1.0 - attn[:, None, None, :].astype(jnp.float32)) * -10000.0
    layer = {k[len(LAYER):]: v for k, v in w.items() if k.startswith(LAYER)}

    def body(h, lw):
        return _block(h, key_bias, lw, n_heads, precision), None

    h, _ = jax.lax.scan(body, h, layer)
    pooled = jnp.tanh(lowp.matmul(h[:, 0], w["params/bert/pooler/kernel"],
                                  precision) + w["params/bert/pooler/bias"])
    t = jax.nn.gelu(lowp.matmul(h, w["params/mlm_transform/kernel"],
                                precision) + w["params/mlm_transform/bias"],
                    approximate=False)
    t = _ln(t, w["params/mlm_ln/scale"], w["params/mlm_ln/bias"])
    mlm_logits = lowp.matmul(t, w[EMB + "word_embeddings/embedding"].T,
                             precision) + w["params/mlm_bias"]
    nsp_logits = lowp.matmul(pooled, w["params/nsp_head/kernel"],
                             precision) + w["params/nsp_head/bias"]
    return _nll_sum(mlm_logits, mlm_labels), _nll_sum(nsp_logits, nsp_labels)


def _block_loss(w, block, n_labelled, n_rows, n_heads, precision):
    mlm, nsp = loss_sums(w, block, n_heads, precision)
    return mlm / n_labelled + nsp / n_rows


_block_step = jax.jit(jax.value_and_grad(_block_loss),
                      static_argnames=("n_rows", "n_heads", "precision"))


def loss_and_grads(w, batch, n_heads, precision="f32", rows_per_block=16):
    """Mean masked-LM NLL (over the labelled positions of the WHOLE batch)
    plus mean next-sentence NLL, and its gradients, accumulated over blocks
    of rows so that float32 activations of the whole batch never sit in
    memory at once."""
    n_rows = int(batch[0].shape[0])
    n_labelled = jnp.maximum(jnp.sum(batch[3] >= 0), 1).astype(jnp.float32)
    loss, grads = 0.0, None
    for lo in range(0, n_rows, rows_per_block):
        block = tuple(x[lo:lo + rows_per_block] for x in batch)
        l, g = _block_step(w, block, n_labelled, n_rows=n_rows,
                           n_heads=n_heads, precision=precision)
        loss = loss + l
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    return loss, grads


@jax.jit
def adam_step(w, m, v, grads, step, lr, b1, b2, eps):
    """Adam with bias correction and no weight decay, in float32."""
    bc1 = 1.0 - b1 ** step
    bc2 = 1.0 - b2 ** step

    def upd(p, m_, v_, g):
        m_ = b1 * m_ + (1.0 - b1) * g
        v_ = b2 * v_ + (1.0 - b2) * jnp.square(g)
        return p - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps), m_, v_

    out = {k: upd(w[k], m[k], v[k], grads[k]) for k in w}
    return ({k: o[0] for k, o in out.items()},
            {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})


def change_skip(cfg):
    """By leaf name, ``(period, lo, hi)``: the elements whose place in the
    flattened leaf, modulo ``period``, falls in [lo, hi) are left out when
    the parameters' change is compared. One entry, the key third of
    ``qkv/bias``: a bias added to every key moves each row of scores by a
    constant, which the softmax removes, so its gradient is exactly zero.
    Rounding noise in the program's gradient there is all there is, Adam
    divides it by its own size, and the program takes full steps where the
    reference takes none; that says nothing of the step."""
    H = cfg["hidden_size"]
    return {LAYER + "qkv/bias": (3 * H, H, 2 * H)}


def _without(x, skip):
    """``x`` with the elements ``skip`` (see ``change_skip``) names set to
    zero; the period is the leaf's last axis."""
    if skip is None:
        return x
    period, lo, hi = skip
    assert x.shape[-1] == period
    return x.at[..., lo:hi].set(0.0)


def leaf_norms(tree):
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


SKETCH_BUCKETS = 1024


def sketch(x):
    """Signed strided bucket sums of a leaf: element i, times a fixed
    pseudo-random sign, is added to bucket i mod SKETCH_BUCKETS (the tail
    that does not fill a row is left out). Two gradients that differ by
    rounding noise of relative size e give sketches that differ by about e
    as well, whatever the leaf's size, which a norm cannot show: zero-mean
    noise moves a norm by e squared. The signs keep the signal from
    cancelling where a leaf has structure (the rows of a weight fed by a
    layer norm sum to nearly nothing, and unsigned sums then read noise)."""
    flat = jnp.ravel(x).astype(jnp.float32)
    k = min(SKETCH_BUCKETS, flat.shape[0])
    rows = flat.shape[0] // k
    n = rows * k
    mixed = jax.lax.iota(jnp.uint32, n) * jnp.uint32(2654435761)
    sign = 1.0 - 2.0 * ((mixed >> 15) & 1).astype(jnp.float32)
    return jnp.sum((flat[:n] * sign).reshape(rows, k), axis=0)


def follow(weights, batches, n_heads, optimizer, skip, precision="f32",
           rows_per_block=16):
    """Follow the first ``len(batches)`` training steps from ``weights``.
    Returns each step's loss, the norm and the sketch of each leaf's FIRST
    gradient, and the norm of each leaf's change after the last step over
    all elements but those ``skip`` (see ``change_skip``) names."""
    w0 = {k: jnp.asarray(v, jnp.float32) for k, v in weights.items()}
    w = w0
    m = {k: jnp.zeros_like(x) for k, x in w.items()}
    v = {k: jnp.zeros_like(x) for k, x in w.items()}
    losses, first_grad = [], None
    for i, batch in enumerate(batches):
        batch = tuple(jnp.asarray(x) for x in batch)
        loss, grads = loss_and_grads(w, batch, n_heads, precision,
                                     rows_per_block)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = leaf_norms(grads)
            first_sketch = {k: jax.device_get(sketch(g))
                            for k, g in grads.items()}
        w, m, v = adam_step(w, m, v, grads, jnp.float32(i + 1),
                            jnp.float32(optimizer["lr"]),
                            jnp.float32(optimizer["betas"][0]),
                            jnp.float32(optimizer["betas"][1]),
                            jnp.float32(optimizer["eps"]))
    change = leaf_norms({k: _without(w[k] - w0[k], skip.get(k)) for k in w})
    return {"losses": losses, "first_grad_norms": first_grad,
            "first_grad_sketch": first_sketch, "change_norms": change}
