"""Chunked vocabulary CE must equal dense log_softmax CE — value and grads —
including padding tails, ignore_index masking, and bias/no-bias."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.bert import cross_entropy
from deepspeed_tpu.ops.cross_entropy import chunked_cross_entropy


def _dense_ce(h, w, b, labels, ignore_index=-1):
    """Oracle: the exact models-side dense CE the chunked op replaces."""
    logits = h @ w
    if b is not None:
        logits = logits + b.astype(logits.dtype)
    return cross_entropy(logits, labels, ignore_index=ignore_index)


@pytest.mark.parametrize("rows_per_chunk", [7, 64, 512])
@pytest.mark.parametrize("with_bias", [True, False])
def test_chunked_ce_matches_dense(rows_per_chunk, with_bias):
    rng = np.random.RandomState(0)
    B, S, H, V = 2, 9, 16, 131  # awkward sizes: padding tail exercised
    h = jnp.asarray(rng.randn(B, S, H).astype(np.float32))
    w = jnp.asarray(rng.randn(H, V).astype(np.float32) * 0.1)
    b = jnp.asarray(rng.randn(V).astype(np.float32) * 0.1) if with_bias else None
    labels = jnp.asarray(
        np.where(rng.rand(B, S) < 0.3, -1, rng.randint(0, V, (B, S))).astype(np.int32)
    )

    got = chunked_cross_entropy(h, w, b, labels, rows_per_chunk=rows_per_chunk)
    want = _dense_ce(h, w, b, labels)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)

    if b is None:
        g_c = jax.grad(lambda h_, w_: chunked_cross_entropy(
            h_, w_, None, labels, rows_per_chunk=rows_per_chunk), argnums=(0, 1))(h, w)
        g_d = jax.grad(lambda h_, w_: _dense_ce(h_, w_, None, labels), argnums=(0, 1))(h, w)
    else:
        g_c = jax.grad(lambda h_, w_, b_: chunked_cross_entropy(
            h_, w_, b_, labels, rows_per_chunk=rows_per_chunk), argnums=(0, 1, 2))(h, w, b)
        g_d = jax.grad(lambda h_, w_, b_: _dense_ce(h_, w_, b_, labels), argnums=(0, 1, 2))(h, w, b)
    for a, d in zip(g_c, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(d), rtol=1e-5, atol=1e-6)


def test_chunked_ce_all_ignored():
    h = jnp.ones((1, 4, 8))
    w = jnp.ones((8, 32))
    labels = jnp.full((1, 4), -1, jnp.int32)
    assert float(chunked_cross_entropy(h, w, None, labels)) == 0.0


def test_chunked_ce_no_logits_in_backward_residuals():
    """The memory contract: no [N, V]-shaped residual survives to backward
    (chunk logits recompute under jax.checkpoint). Assert via the jaxpr of
    the grad: no intermediate output with the FULL (unpadded N x V) shape is
    produced outside the chunk body's remat."""
    rng = np.random.RandomState(1)
    B, S, H, V = 4, 128, 32, 1024
    h = jnp.asarray(rng.randn(B, S, H).astype(np.float32))
    w = jnp.asarray(rng.randn(H, V).astype(np.float32) * 0.1)
    labels = jnp.asarray(rng.randint(0, V, (B, S)).astype(np.int32))

    fn = jax.jit(jax.grad(lambda h_: chunked_cross_entropy(
        h_, w, None, labels, rows_per_chunk=64)))
    hlo = fn.lower(h).compile().as_text()
    assert f"f32[{B * S},{V}]" not in hlo, "full logits materialized"


# name -> (B, S, H, V, rows_per_chunk, whether the compiled text is read)
_SHARDED_CASES = {
    # the dp4 cell's ratio: 256 x 128 rows in 64 chunks of 512
    "dp4_cell": (256, 128, 16, 128, 512, True),
    # GPT-2's call: h[:, :-1] against labels[:, 1:], n a multiple of rows
    "gpt2_call": (8, 65, 16, 128, 64, True),
    # n = 4 * 9 is no multiple of 7: the padding path, numbers only
    "padded_tail": (4, 9, 16, 131, 7, False),
}


@pytest.mark.parametrize("case", sorted(_SHARDED_CASES))
def test_chunked_ce_gathers_nothing_over_a_sharded_batch(case):
    """Chunk c holds flat rows c, c + n_chunks, ...: the scan walks an axis
    the batch sharding does not split, so no device gathers the others' rows
    (the contiguous order all-gathered the whole [n_chunks, rows, H] array,
    forward and backward). ``hidden`` and ``labels`` are split over the batch
    on 4 of the tests' 8 devices, the kernel whole on each."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    B, S, H, V, rows, read_text = _SHARDED_CASES[case]
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    by_batch, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    rng = np.random.RandomState(2)
    h = jax.device_put(rng.randn(B, S, H).astype(np.float32), by_batch)
    labels = jax.device_put(
        np.where(rng.rand(B, S) < 0.3, -1, rng.randint(0, V, (B, S))).astype(np.int32),
        by_batch)
    w = jax.device_put(rng.randn(H, V).astype(np.float32) * 0.1, whole)
    if case == "gpt2_call":     # the call site's own slicing, inside the jit
        view = lambda h_, y_: (h_[:, :-1], y_[:, 1:])
    else:
        view = lambda h_, y_: (h_, y_)

    def chunked(h_, w_):
        hv, yv = view(h_, labels)
        return chunked_cross_entropy(hv, w_, None, yv, rows_per_chunk=rows)

    def dense(h_, w_):
        hv, yv = view(h_, labels)
        return _dense_ce(hv, w_, None, yv)

    fn = jax.jit(jax.value_and_grad(chunked, argnums=(0, 1)))
    if read_text:
        assert "all-gather" not in fn.lower(h, w).compile().as_text()
    got, g_c = fn(h, w)
    want, g_d = jax.value_and_grad(dense, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for a, d in zip(g_c, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(d), rtol=1e-5, atol=1e-6)
