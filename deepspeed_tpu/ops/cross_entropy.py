"""Chunked vocabulary cross-entropy: never materializes [B,S,V] logits.

The reference computes LM losses through full logits + CrossEntropyLoss
(vocab-sized activations); at BERT/GPT-2 vocab sizes the fp32 logits tensor
is the single largest transient of the training step (~1GB for GPT-2 at
micro-batch 8 x seq 1024 x 50304). TPU-first replacement: scan over row
chunks, compute each chunk's logits -> logsumexp -> gold-logit gather ->
masked NLL, and wrap the chunk in ``jax.checkpoint`` so the backward
recomputes chunk logits instead of saving them. Peak memory drops from
O(B*S*V) to O(chunk*V) with identical math (logsumexp - gold in fp32).
"""

import jax
import jax.numpy as jnp


def chunked_cross_entropy(hidden, kernel, bias, labels, ignore_index=-1,
                          rows_per_chunk=512):
    """Mean NLL of ``softmax(hidden @ kernel + bias)`` against ``labels``.

    - ``hidden``: [..., H] (any leading batch/seq dims)
    - ``kernel``: [H, V]; ``bias``: [V] or None
    - ``labels``: [...] int, ``ignore_index`` entries contribute 0
    Matches ``cross_entropy(full_logits, labels)`` exactly: per-row NLL is
    logsumexp(logits) - logits[gold], both in fp32.
    """
    H = hidden.shape[-1]
    h = hidden.reshape(-1, H)
    y = labels.reshape(-1)
    n = h.shape[0]

    rows = max(1, min(rows_per_chunk, n))
    pad = (-n) % rows
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, H), h.dtype)])
        y = jnp.concatenate([y, jnp.full((pad,), ignore_index, y.dtype)])
    n_chunks = h.shape[0] // rows
    # Chunk c holds flat rows c, c + n_chunks, ...: the axis the scan walks
    # is then never the one a batch sharding splits (every chunk takes an
    # equal slice of each device's rows), so no device gathers the others'.
    h = jnp.moveaxis(h.reshape(rows, n_chunks, H), 1, 0)
    y = y.reshape(rows, n_chunks).T

    @jax.checkpoint
    def chunk_nll(hc, yc):
        logits = (hc @ kernel).astype(jnp.float32)
        if bias is not None:
            logits = logits + bias.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        valid = yc != ignore_index
        gold = jnp.take_along_axis(
            logits, jnp.where(valid, yc, 0)[:, None], axis=-1
        )[:, 0]
        nll = jnp.where(valid, lse - gold, 0.0)
        return nll.sum(), valid.sum()

    def body(carry, xs):
        tot, cnt = carry
        hc, yc = xs
        s, c = chunk_nll(hc, yc)
        return (tot + s, cnt + c), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.int32(0)), (h, y)
    )
    return total / jnp.maximum(count, 1)
