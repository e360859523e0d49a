"""Fused/block-sparse attention kernel numerics.

Mirrors the reference's kernel-vs-dense-reference strategy
(tests/unit/test_sparse_attention.py, test_cuda_forward.py): the Pallas kernel
(interpret mode on CPU) must match the dense jnp reference, under dense,
sparse-layout, masked, and causal configurations.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.attention import (
    _attention_pallas,
    _attention_reference,
    _dense_lut,
    _expand_layout_mask,
    flash_attention,
    layout_to_lut,
)


def rand_qkv(B=2, H=2, S=256, D=64, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)) * 0.3
    return mk(), mk(), mk()


def test_dense_kernel_matches_reference():
    q, k, v = rand_qkv()
    B, H, S, D = q.shape
    bias = jnp.zeros((B, S), jnp.float32)
    lut, counts = _dense_lut(H, S // 128, S // 128)
    out_k, _ = _attention_pallas(q, k, v, bias, lut, counts, block_q=128, block_k=128,
                              causal=False, interpret=True)
    out_r = _attention_reference(q, k, v, bias, None, causal=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-5, rtol=2e-5)


def test_masked_kernel_matches_reference():
    q, k, v = rand_qkv(seed=1)
    B, H, S, D = q.shape
    rng = np.random.RandomState(2)
    pad = rng.rand(B, S) < 0.2
    bias = jnp.asarray(np.where(pad, -10000.0, 0.0).astype(np.float32))
    lut, counts = _dense_lut(H, S // 128, S // 128)
    out_k, _ = _attention_pallas(q, k, v, bias, lut, counts, block_q=128, block_k=128,
                              causal=False, interpret=True)
    out_r = _attention_reference(q, k, v, bias, None, causal=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-5, rtol=2e-5)


def test_causal_kernel_matches_reference():
    q, k, v = rand_qkv(seed=3)
    B, H, S, D = q.shape
    bias = jnp.zeros((B, S), jnp.float32)
    lut, counts = _dense_lut(H, S // 128, S // 128)
    out_k, _ = _attention_pallas(q, k, v, bias, lut, counts, block_q=128, block_k=128,
                              causal=True, interpret=True)
    out_r = _attention_reference(q, k, v, bias, None, causal=True)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-5, rtol=2e-5)


def test_sparse_layout_kernel_matches_masked_reference():
    q, k, v = rand_qkv(seed=4)
    B, H, S, D = q.shape
    nb = S // 128
    rng = np.random.RandomState(5)
    layout = (rng.rand(H, nb, nb) < 0.5).astype(np.int64)
    layout[:, :, 0] = 1  # keep every row alive
    bias = jnp.zeros((B, S), jnp.float32)
    lut, counts = layout_to_lut(layout)
    out_k, _ = _attention_pallas(q, k, v, bias, lut, counts, block_q=128, block_k=128,
                              causal=False, interpret=True)
    out_r = _attention_reference(q, k, v, bias, _expand_layout_mask(layout, S, 128),
                                 causal=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-5, rtol=2e-5)


def test_empty_rows_give_zero():
    q, k, v = rand_qkv(seed=6)
    B, H, S, D = q.shape
    nb = S // 128
    layout = np.ones((H, nb, nb), np.int64)
    layout[0, 1, :] = 0  # head 0, q-block 1 attends to nothing
    bias = jnp.zeros((B, S), jnp.float32)
    lut, counts = layout_to_lut(layout)
    out_k, _ = _attention_pallas(q, k, v, bias, lut, counts, block_q=128, block_k=128,
                              causal=False, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_k[:, 0, 128:256, :]), 0.0)


def test_flash_attention_grads():
    """Public entry must be differentiable (rematerialized backward)."""
    q, k, v = rand_qkv(B=1, H=2, S=128, D=32, seed=7)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for t in g:
        assert np.isfinite(np.asarray(t)).all()

    # matches autodiff through the reference math
    def loss_ref(q, k, v):
        bias = jnp.zeros((q.shape[0], q.shape[2]), jnp.float32)
        return jnp.sum(_attention_reference(q, k, v, bias, None, causal=False) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


def _bwd_check(layout=None, causal=False, bias=None, seed=10):
    """Flash backward kernels (interpret mode) vs dense-masked VJP."""
    from deepspeed_tpu.ops.transformer.attention import (
        _attention_pallas_bwd,
        _luts_for,
    )

    q, k, v = rand_qkv(B=2, H=2, S=256, D=32, seed=seed)
    B, H, S, D = q.shape
    if bias is None:
        bias = jnp.zeros((B, S), jnp.float32)
    lut, counts, qlut, qcounts = _luts_for(layout, H, S, 128)
    out, lse = _attention_pallas(q, k, v, bias, lut, counts, block_q=128,
                                 block_k=128, causal=causal, interpret=True)
    g = jnp.asarray(np.random.RandomState(seed + 1).randn(*out.shape).astype(np.float32))
    dq, dk, dv, dbias = _attention_pallas_bwd(
        q, k, v, bias, out, lse, g, lut, counts, qlut, qcounts,
        block_q=128, block_k=128, causal=causal, interpret=True,
    )

    mask = _expand_layout_mask(layout, S, 128)

    def f(q, k, v, bias):
        return _attention_reference(q, k, v, bias, mask, causal=causal)

    _, vjp = jax.vjp(f, q, k, v, bias)
    rq, rk, rv, rb = vjp(g)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(np.asarray(dbias), np.asarray(rb), atol=3e-3, rtol=3e-3)


def test_flash_bwd_dense():
    _bwd_check()


def test_flash_bwd_causal():
    _bwd_check(causal=True, seed=11)


def test_flash_bwd_masked():
    rng = np.random.RandomState(12)
    bias = jnp.asarray(np.where(rng.rand(2, 256) < 0.2, -10000.0, 0.0).astype(np.float32))
    _bwd_check(bias=bias, seed=12)


def test_flash_bwd_sparse_layout():
    rng = np.random.RandomState(13)
    layout = (rng.rand(2, 2, 2) < 0.6).astype(np.int64)
    layout[:, :, 0] = 1
    _bwd_check(layout=layout, seed=13)


@functools.lru_cache(maxsize=None)
def _kernels_fwd_bwd(shape, causal, rows, dtype=jnp.float32):
    """(out, lse, dq, dk, dv, dbias) of the three kernels at ``rows`` (batch,
    head) rows a grid step, interpret mode, dense layout, with a key bias;
    plus the dense reference's (out, dq, dk, dv, dbias)."""
    from deepspeed_tpu.ops.transformer.attention import (
        _attention_pallas_bwd,
        _luts_for,
    )

    B, H, S, D = shape
    q, k, v = (t.astype(dtype) for t in rand_qkv(B, H, S, D, seed=20))
    rng = np.random.RandomState(21)
    bias = jnp.asarray(np.where(rng.rand(B, S) < 0.2, -10000.0, 0.0).astype(np.float32))
    g = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)).astype(dtype)
    lut, counts, qlut, qcounts = _luts_for(None, H, S, 128)
    kw = dict(block_q=128, block_k=128, causal=causal, interpret=True, rows=rows)
    out, lse = _attention_pallas(q, k, v, bias, lut, counts, **kw)
    grads = _attention_pallas_bwd(q, k, v, bias, out, lse, g, lut, counts,
                                  qlut, qcounts, **kw)
    ref_out, vjp = jax.vjp(
        lambda q, k, v, b: _attention_reference(q, k, v, b, None, causal=causal),
        q, k, v, bias)
    return (out, lse) + tuple(grads), (ref_out,) + tuple(vjp(g))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 4, 128, 64), (1, 8, 384, 32)])
@pytest.mark.parametrize("rows", [1, 2, 8])
def test_grouped_kernels_equal_one_row_a_step(rows, shape, causal):
    """G (batch, head) rows a grid step change the schedule, not one bit of
    a row's result: forward output, lse, dq, dk, dv and dbias of the grouped
    kernels equal the one-row kernels' bit for bit, and the dense reference
    within the tolerances the one-row kernels are held to."""
    got, ref = _kernels_fwd_bwd(shape, causal, rows)
    if rows > 1:
        one, _ = _kernels_fwd_bwd(shape, causal, 1)
        for name, a, b in zip(("out", "lse", "dq", "dk", "dv", "dbias"), got, one):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    out, _, dq, dk, dv, dbias = got
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref[0]), atol=2e-5, rtol=2e-5)
    for a, b in zip((dq, dk, dv), ref[1:4]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(np.asarray(dbias), np.asarray(ref[4]), atol=3e-3, rtol=3e-3)


def test_grouped_kernels_equal_one_row_a_step_in_bf16():
    """The cells' dtype: bf16 operands, fp32 scores and accumulators."""
    got, _ = _kernels_fwd_bwd((2, 4, 128, 64), False, 8, jnp.bfloat16)
    one, _ = _kernels_fwd_bwd((2, 4, 128, 64), False, 1, jnp.bfloat16)
    for a, b in zip(got, one):
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))


def test_rows_per_step_rule():
    """The rule alone: a power of two that divides the rows of the call,
    1 for a block-sparse layout, falling as the sequence grows (a row then
    brings enough work of its own, and its blocks fill the VMEM budget)."""
    from deepspeed_tpu.ops.transformer.attention import rows_per_step

    bf16 = jnp.bfloat16
    by_seq = [rows_per_step(1024, S, 64, bf16, True)
              for S in (128, 512, 2048, 8192)]
    assert by_seq[0] > 1 and by_seq[-1] == 1
    assert by_seq == sorted(by_seq, reverse=True)
    for bh in (1, 2, 12, 15, 20, 256, 1024, 1280):
        for S in (128, 384, 1024):
            for dtype in (bf16, jnp.float32):
                rows = rows_per_step(bh, S, 64, dtype, True)
                assert rows & (rows - 1) == 0 and bh % rows == 0, (bh, S, rows)
                assert rows_per_step(bh, S, 64, dtype, False) == 1
    assert rows_per_step(15, 128, 64, bf16, True) == 1          # B 3, H 5
    assert rows_per_step(12, 128, 64, bf16, True) == 4
    # wider rows and wider elements take more VMEM each: never more rows
    assert rows_per_step(1024, 128, 64, jnp.float32, True) <= by_seq[0]
    assert rows_per_step(1024, 128, 256, bf16, True) <= by_seq[0]


@pytest.mark.parametrize("case,B,H,want", [
    ("dense", 2, 4, 8),          # every row of the call in one grid step
    ("odd_rows", 3, 5, 1),       # the preferred 8 does not divide 15
    ("block_sparse", 2, 4, 1),   # a sparse LUT differs by head
])
def test_flash_attention_picks_rows_per_step(monkeypatch, case, B, H, want):
    """The public entry on the TPU branch (kernels in interpret mode) takes
    its rows a grid step from the rule, says so in the registry, and still
    equals the dense (masked) reference, forward and backward."""
    from deepspeed_tpu.ops.transformer import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    # the kernels are under test: no call is small enough to hold its scores
    monkeypatch.setattr(A, "_SCORE_BUDGET", 0)
    monkeypatch.setattr(
        A, "_attention_pallas", functools.partial(A._attention_pallas, interpret=True))
    monkeypatch.setattr(
        A, "_attention_pallas_bwd",
        functools.partial(A._attention_pallas_bwd, interpret=True))
    q, k, v = rand_qkv(B=B, H=H, S=256, D=32, seed=30)
    layout = None
    if case == "block_sparse":
        layout = np.ones((H, 2, 2), np.int64)
        layout[::2, 0, 1] = 0                      # heads differ
    mask = jnp.asarray(np.where(
        np.random.RandomState(31).rand(B, 256) < 0.2, -10000.0, 0.0).astype(np.float32))

    def loss(q, k, v, **kw):
        return jnp.sum(A.flash_attention(q, k, v, mask=mask, layout=layout, **kw) ** 2)

    val, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert A.traced_rows_per_step() == want
    val_ref, g_ref = jax.value_and_grad(
        functools.partial(loss, force_reference=True), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(val), float(val_ref), rtol=1e-5)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4, rtol=3e-4)


def test_grad_binds_flash_backward_kernels(monkeypatch):
    """jax.grad through flash_attention must hit the Pallas backward kernels on
    the TPU path (VERDICT r3 item 9): patch the backend check to the TPU branch
    (kernels in interpret mode so this runs on CPU) and assert the bwd kernel
    entry point is actually invoked, with grads matching the dense reference."""
    import functools

    from deepspeed_tpu.ops.transformer import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(A, "_SCORE_BUDGET", 0)     # the kernels are under test
    monkeypatch.setattr(
        A, "_attention_pallas", functools.partial(A._attention_pallas, interpret=True)
    )
    calls = {"bwd": 0}
    real_bwd = A._attention_pallas_bwd

    def spy_bwd(*args, **kwargs):
        calls["bwd"] += 1
        kwargs["interpret"] = True
        return real_bwd(*args, **kwargs)

    monkeypatch.setattr(A, "_attention_pallas_bwd", spy_bwd)

    q, k, v = rand_qkv(B=1, H=2, S=256, D=64, seed=9)

    def loss(q, k, v):
        return jnp.sum(A.flash_attention(q, k, v) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert calls["bwd"] == 1, "flash backward kernels were not invoked"

    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(
            A.flash_attention(q, k, v, force_reference=True) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


def test_flash_kernels_shard_map_over_the_context_mesh(monkeypatch):
    """Under a mesh in context the TPU path shard_maps its kernels (GSPMD
    cannot partition a Mosaic call): batch over ``data``, heads over
    ``model``. Kernels in interpret mode on a 1x2x2 CPU mesh; outputs and
    grads must equal the unsharded reference, and come back sharded."""
    import functools

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.ops.transformer import attention as A

    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        A, "_attention_pallas", functools.partial(A._attention_pallas, interpret=True))
    monkeypatch.setattr(
        A, "_attention_pallas_bwd",
        functools.partial(A._attention_pallas_bwd, interpret=True))

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(1, 2, 2),
                ("pipe", "data", "model"))
    q, k, v = rand_qkv(B=4, H=2, S=100, D=16, seed=3)      # S pads to 128
    placed = [jax.device_put(t, NamedSharding(mesh, P("data", "model")))
              for t in (q, k, v)]

    def loss(q, k, v, **kw):
        return jnp.sum(A.flash_attention(q, k, v, causal=True, **kw) ** 2)

    def meshed(q, k, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return loss(q, k, v)

    val, g = jax.jit(jax.value_and_grad(meshed, argnums=(0, 1, 2)))(*placed)
    val_ref, g_ref = jax.value_and_grad(
        functools.partial(loss, force_reference=True), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(val), float(val_ref), rtol=1e-5)
    for a, b in zip(g, g_ref):
        assert a.sharding.spec == P("data", "model")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


def test_dropout_reference_path_statistics_and_determinism():
    """dropout_rate>0 on the (CPU) reference path: deterministic per rng,
    different across rngs, keep-rate ~ (1-p), unbiased in expectation."""
    q, k, v = rand_qkv(B=1, H=2, S=256, D=64, seed=11)
    rng = jax.random.PRNGKey(3)
    o1 = flash_attention(q, k, v, dropout_rate=0.3, dropout_rng=rng)
    o2 = flash_attention(q, k, v, dropout_rate=0.3, dropout_rng=rng)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    o3 = flash_attention(q, k, v, dropout_rate=0.3, dropout_rng=jax.random.PRNGKey(4))
    assert np.abs(np.asarray(o1) - np.asarray(o3)).max() > 0

    # E[dropout(probs)] == probs: average many seeds approaches the no-drop out
    outs = [
        np.asarray(flash_attention(q, k, v, dropout_rate=0.3,
                                   dropout_rng=jax.random.PRNGKey(100 + i)))
        for i in range(24)
    ]
    base = np.asarray(flash_attention(q, k, v))
    err = np.abs(np.mean(outs, axis=0) - base).max()
    assert err < 0.25, err


def test_dropout_grads_match_explicit_mask_reference():
    """jax.grad through the dropout path equals the grad of an explicit
    jnp reimplementation drawing the SAME mask (the bwd recompute must
    reproduce the forward's mask exactly)."""
    q, k, v = rand_qkv(B=1, H=2, S=128, D=64, seed=12)
    rng = jax.random.PRNGKey(9)
    rate = 0.25

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, dropout_rate=rate, dropout_rng=rng) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    # same seed derivation as flash_attention's reference path
    seed = jax.random.randint(rng, (1,), 0, 2**31 - 1, dtype=jnp.int32)
    key = jax.random.PRNGKey(jnp.asarray(seed).reshape(())[()].astype(jnp.uint32))

    def loss_ref(q, k, v):
        D = q.shape[-1]
        s = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32), k.astype(jnp.float32))
        s = s / np.sqrt(D)
        probs = jax.nn.softmax(s, axis=-1)
        keep = jax.random.bernoulli(key, 1.0 - rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - rate), 0.0)
        out = jnp.einsum("bhst,bhtd->bhsd", probs, v.astype(jnp.float32))
        return jnp.sum(out ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


def test_transformer_layer_training_uses_fused_path_with_dropout(monkeypatch):
    """With attn dropout > 0 in TRAINING, _attention_core routes to
    flash_attention (in-kernel dropout) instead of the jnp fallback."""
    from deepspeed_tpu.ops.transformer import attention as A
    from deepspeed_tpu.ops.transformer import transformer as T

    calls = {"n": 0}
    real = A.flash_attention

    def spy(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(A, "flash_attention", spy)

    q, k, v = rand_qkv(B=1, H=2, S=128, D=64, seed=5)
    out = T._attention_core(q, k, v, None, 0.1, False, jax.random.PRNGKey(0))
    assert calls["n"] == 1
    assert out.shape == q.shape


@pytest.mark.parametrize("dtype,fwd_tol,bwd_tol", [
    (jnp.bfloat16, 2e-2, 5e-2),
    (jnp.float16, 1e-2, 3e-2),
])
def test_half_precision_kernel_matches_reference(dtype, fwd_tol, bwd_tol):
    """Half-precision inputs (bf16 = the TPU-native story; fp16 = the fp16
    engine mode) keep matmul operands in the input dtype (native MXU path)
    with fp32 softmax/accumulation — numerics must track the fp32 reference
    within the dtype's tolerance, fwd and bwd."""
    q, k, v = rand_qkv(B=1, H=2, S=256, D=64, seed=21)
    qh, kh, vh = (t.astype(dtype) for t in (q, k, v))
    B, H, S, D = q.shape
    bias = jnp.zeros((B, S), jnp.float32)
    lut, counts = _dense_lut(H, S // 128, S // 128)
    out_k, lse = _attention_pallas(qh, kh, vh, bias, lut, counts, block_q=128,
                                   block_k=128, causal=False, interpret=True)
    out_r = _attention_reference(q, k, v, bias, None, causal=False)
    np.testing.assert_allclose(np.asarray(out_k, np.float32), np.asarray(out_r),
                               atol=fwd_tol, rtol=fwd_tol)

    from deepspeed_tpu.ops.transformer.attention import _attention_pallas_bwd, _luts_for
    lut, counts, qlut, qcounts = _luts_for(None, H, S, 128)
    g = jnp.ones_like(qh)
    dq, dk, dv, db = _attention_pallas_bwd(
        qh, kh, vh, bias, out_k, lse, g, lut, counts, qlut, qcounts,
        block_q=128, block_k=128, causal=False, interpret=True)
    g_ref = jax.grad(lambda q, k, v: jnp.sum(
        _attention_reference(q, k, v, bias, None, causal=False)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip((dq, dk, dv), g_ref):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=bwd_tol, rtol=bwd_tol)


def test_bias_shape_contract():
    """Pin flash_attention's mask contract (VERDICT r4 weak #8): key biases
    [B,S] and [B,1,1,S] are accepted (and equivalent); full per-query masks
    [B,1,S,S] / [B,H,S,S] are loudly rejected with a pointer to the dense
    reference path, never silently sliced."""
    from deepspeed_tpu.ops.transformer.attention import (
        attention_reference,
        flash_attention,
    )

    B, H, S, D = 1, 2, 128, 32
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D).astype(np.float32) * 0.1)
               for _ in range(3))
    key_bias = jnp.asarray(
        np.where(rng.rand(B, S) < 0.2, -10000.0, 0.0).astype(np.float32))

    out_2d = flash_attention(q, k, v, mask=key_bias)
    out_4d = flash_attention(q, k, v, mask=key_bias[:, None, None, :])
    np.testing.assert_allclose(np.asarray(out_2d), np.asarray(out_4d))
    ref = attention_reference(q, k, v, mask=key_bias)
    np.testing.assert_allclose(np.asarray(out_2d), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)

    full = jnp.zeros((B, 1, S, S), jnp.float32)
    with pytest.raises(ValueError, match="key-bias"):
        flash_attention(q, k, v, mask=full)
    with pytest.raises(ValueError, match="key-bias"):
        flash_attention(q, k, v, mask=jnp.zeros((B, H, S, S), jnp.float32))
    # the documented escape hatch accepts what the kernel rejects
    out_ref_full = attention_reference(q, k, v, mask=full)
    np.testing.assert_allclose(np.asarray(out_ref_full),
                               np.asarray(attention_reference(q, k, v)),
                               atol=1e-6)


def test_dropout_seed_fold_is_two_words_and_injective():
    """Mosaic's tpu.prng_set_seed_32 accepts at most TWO seed words — more
    fails to compile ONLY on real hardware (interpret mode cannot lower
    prng_seed on CPU at all), so pin the fold in pure Python: exactly two
    words out, and distinct (bh, qi, kj) never collide (a collision would
    silently correlate dropout masks between attention blocks)."""
    from deepspeed_tpu.ops.transformer.attention import _fold_dropout_seed

    words = _fold_dropout_seed(jnp.int32(123), jnp.int32(1), jnp.int32(2),
                               jnp.int32(3))
    assert len(words) == 2

    # realistic block-index ranges: bh = batch*heads (large), qi/kj = S/block;
    # one vectorized fold call over the whole grid, then a uniqueness check
    bh = np.asarray(list(range(64)) + [255, 1024, 4095, 65535], np.int32)
    qi = np.arange(8, dtype=np.int32)
    kj = np.arange(8, dtype=np.int32)
    bh_g, qi_g, kj_g = (g.ravel() for g in np.meshgrid(bh, qi, kj))
    a, b = _fold_dropout_seed(np.int32(123), bh_g, qi_g, kj_g)
    pairs = np.stack([np.asarray(a), np.asarray(b)], axis=1)
    assert len(np.unique(pairs, axis=0)) == len(pairs), "seed fold collision"


def test_grouped_dropout_masks_keep_their_rows_identity(monkeypatch):
    """A row's dropout mask is seeded by its number in the call (its grid
    step times G plus its place in the group), whatever G: the forward and
    both backwards of any grouping regenerate what one row a step draws.
    The TPU PRNG is replaced by a recorder (it only compiles on a chip)."""
    from deepspeed_tpu.ops.transformer import attention as A

    seeded = []
    monkeypatch.setattr(A.pltpu, "prng_seed", lambda *words: seeded.append(
        tuple(int(w) for w in words)))
    monkeypatch.setattr(A.pltpu, "prng_random_bits", lambda shape: jnp.full(
        shape, len(seeded) * 2**29, jnp.uint32))
    seed, qi, kj = 1234, 1, 2

    def row_seed(bh):
        return tuple(int(w) for w in A._fold_dropout_seed(jnp.int32(seed), bh, qi, kj))

    one = [A._dropout_keep([jnp.int32(seed)], bh, 1, qi, kj, 8, 128, 0.25)
           for bh in range(8, 12)]
    assert seeded == [row_seed(bh) for bh in range(8, 12)]
    seeded.clear()
    group = A._dropout_keep([jnp.int32(seed)], 8, 4, qi, kj, 8, 128, 0.25)
    assert seeded == [row_seed(bh) for bh in range(8, 12)]
    assert group.shape == (4, 8, 128)
    # bits 1..4 x 2**29 against the threshold 2**30: the first row is dropped
    assert [float(x) > 0 for x in group[:, 0, 0]] == [False, True, True, True]
    for g in range(4):
        np.testing.assert_array_equal(np.asarray(one[g][0]), np.asarray(group[g]))



# ---------------------------------------------------------------------------
# the materialised path, and the rule that chooses it
# ---------------------------------------------------------------------------

def _dense_case(case, dtype):
    """(q, k, v, bias, causal) of one call of the materialised path."""
    S = {"short_of_a_block": 100}.get(case, 128)
    B = 3
    q, k, v = (t.astype(dtype) for t in rand_qkv(B=B, H=2, S=S, D=32, seed=40))
    rng = np.random.RandomState(41)
    bias = np.zeros((B, S), np.float32)
    if case in ("key_bias", "short_of_a_block", "causal"):
        bias = np.where(rng.rand(B, S) < 0.2, -10000.0, 0.0).astype(np.float32)
    if case == "masked_row":
        bias[1] = -1e30           # every key of the second sequence is out
        bias[2, ::3] = -1e30
    return q, k, v, jnp.asarray(bias), case == "causal"


@pytest.mark.parametrize("dtype,fwd_tol,bwd_tol", [
    (jnp.float32, 2e-5, 2e-4),
    (jnp.bfloat16, 2e-2, 5e-2),      # the kernels' own, against the same oracle
])
@pytest.mark.parametrize("case", ["plain", "key_bias", "masked_row",
                                  "short_of_a_block", "causal"])
def test_materialised_path_matches_reference(case, dtype, fwd_tol, bwd_tol):
    """``_attention_dense`` against the float32 oracle: output, dq, dk, dv
    and dbias, with a key bias, with a sequence whose every key is masked
    (its rows give 0 and take no gradient), at a length that is no block
    multiple (nothing is padded) and under the causal mask."""
    from deepspeed_tpu.ops.transformer.attention import _attention_dense

    q, k, v, bias, causal = _dense_case(case, dtype)

    def loss(fn, q, k, v, bias):
        out = fn(q, k, v, bias)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    dense = lambda *a: _attention_dense(*a, causal=causal)
    oracle = lambda *a: _attention_reference(*a, None, causal=causal)
    (_, out), got = jax.value_and_grad(
        functools.partial(loss, dense), argnums=(0, 1, 2, 3), has_aux=True)(
            q, k, v, bias)
    f32 = [t.astype(jnp.float32) for t in (q, k, v)]
    (_, want_out), want = jax.value_and_grad(
        functools.partial(loss, oracle), argnums=(0, 1, 2, 3), has_aux=True)(
            *f32, bias)
    assert out.dtype == dtype and all(g.dtype == dtype for g in got[:3])
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want_out), atol=fwd_tol, rtol=fwd_tol)
    for name, a, b in zip(("dq", "dk", "dv", "dbias"), got, want):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b), atol=bwd_tol,
            rtol=bwd_tol, err_msg=name)
    if case == "masked_row":
        assert not np.asarray(out[1], np.float32).any()
        assert not any(np.asarray(g[1], np.float32).any() for g in got[:3])


@pytest.mark.parametrize("causal", [False, True])
def test_materialised_path_holds_the_kernels_precision(causal):
    """In bf16 the materialised path keeps float32 wherever the kernels do
    (scores, statistics, the softmax's backward) and rounds the matmuls'
    operands and results as they do: output and all four gradients lie
    within 1% of the kernels' (interpret mode) by norm, at scores so large
    that a softmax over scores rounded to bf16 is several times further."""
    from deepspeed_tpu.ops.transformer.attention import (
        _attention_dense, _attention_pallas_bwd, _luts_for)
    from deepspeed_tpu.ops.transformer.transformer import _attention_core

    B, H, S = 2, 2, 128
    q, k, v = (jnp.asarray(t * 10.0, jnp.bfloat16)
               for t in rand_qkv(B=B, H=H, S=S, D=64, seed=50))
    bias = jnp.asarray(np.where(
        np.random.RandomState(51).rand(B, S) < 0.2, -10000.0, 0.0), jnp.float32)
    lut, counts, qlut, qcounts = _luts_for(None, H, S, 128)
    out_k, lse = _attention_pallas(q, k, v, bias, lut, counts, block_q=128,
                                   block_k=128, causal=causal, interpret=True)
    g = (out_k * 2).astype(jnp.bfloat16)
    want = (out_k,) + _attention_pallas_bwd(
        q, k, v, bias, out_k, lse, g, lut, counts, qlut, qcounts,
        block_q=128, block_k=128, causal=causal, interpret=True)

    def gaps(attend):
        out, vjp = jax.vjp(attend, q, k, v, bias)
        f32 = lambda t: np.asarray(t, np.float32)
        return [float(np.linalg.norm(f32(a) - f32(b)) / np.linalg.norm(f32(b)))
                for a, b in zip((out,) + vjp(g), want)]

    dense = gaps(lambda *a: _attention_dense(*a, causal=causal))
    assert max(dense) < 0.01, dense
    # the instrument: scores rounded to the input dtype ahead of the softmax
    # (``_attention_core``'s tail without the kernel) are not that close
    rounded = gaps(lambda q, k, v, bias: _attention_core(
        q, k, v, bias[:, None, None, :].astype(q.dtype), 0.0, True, None,
        use_pallas=False, causal=causal))
    assert rounded[1] > 0.02 and rounded[2] > 0.02, rounded


_MIB = 2 ** 20


@pytest.mark.parametrize("call,want", [
    # (B, H, S_q, S_k) on one device, then what the call says of itself
    (dict(B=64, H=16, S_q=128, S_k=128), True),         # both BERT cells: 64 MiB
    (dict(B=65, H=16, S_q=128, S_k=128), False),        # 65 MiB: over the edge
    (dict(B=1, H=1, S_q=4096, S_k=4096), True),         # the edge again, one row
    (dict(B=1, H=1, S_q=4096, S_k=4097), False),
    (dict(B=16, H=16, S_q=256, S_k=256), True),         # 64 MiB at two key blocks
    (dict(B=4, H=16, S_q=512, S_k=512), True),          # and at four
    (dict(B=16, H=16, S_q=512, S_k=512), False),        # 256 MiB
    (dict(B=1, H=16, S_q=8192, S_k=8192), False),       # 4 GiB: the kernels' case
    (dict(B=2, H=20, S_q=1024, S_k=1024), False),       # GPT-2 large, 160 MiB
    (dict(B=2, H=4, S_q=100, S_k=100), True),           # no block multiple
    (dict(B=64, H=16, S_q=128, S_k=128, dense=False), False),   # any LUT
    (dict(B=1, H=1, S_q=128, S_k=128, dense=False), False),
    (dict(B=64, H=16, S_q=128, S_k=128, dropout_rate=0.1), False),
    (dict(B=1, H=1, S_q=128, S_k=128, dropout_rate=0.01), False),
])
def test_materialises_scores_rule(call, want):
    """The rule alone, a pure function of what a call shows: a dense call
    without dropout whose float32 scores on one device fit ``_SCORE_BUDGET``
    (64 MiB, the edge on both sides) takes the materialised path; a
    block-sparse layout and a call with dropout never do."""
    from deepspeed_tpu.ops.transformer import attention as A

    assert A._SCORE_BUDGET == 64 * _MIB
    assert A.materialises_scores(**dict(dict(dense=True), **call)) is want


@pytest.fixture()
def tpu_branch(monkeypatch):
    """The public entry as a TPU sees it (kernels in interpret mode), over a
    metrics registry of its own."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.ops.transformer import attention as A
    from deepspeed_tpu.telemetry.registry import MetricsRegistry

    monkeypatch.setattr(telemetry, "_registry", MetricsRegistry())
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        A, "_attention_pallas", functools.partial(A._attention_pallas, interpret=True))
    monkeypatch.setattr(
        A, "_attention_pallas_bwd",
        functools.partial(A._attention_pallas_bwd, interpret=True))
    return A


def test_the_three_tallies_name_what_was_traced(tpu_branch, monkeypatch):
    """Each trace of the public entry counts under the implementation it
    lowered to, ``traced_implementation`` names it since a snapshot, and the
    kernels' rows-a-step gauge stays 0 until a kernel is traced."""
    A = tpu_branch
    q, k, v = rand_qkv(B=2, H=2, S=128, D=32, seed=60)
    mask = jnp.zeros((2, 128), jnp.float32)
    assert A.traced_implementation() == "none"
    start = A.trace_counts()
    assert start == (0, 0, 0)

    out = A.flash_attention(q, k, v, mask=mask)
    assert A.trace_counts() == (0, 1, 0)
    assert A.traced_implementation() == A.traced_implementation(since=start) == "dense"
    assert A.traced_rows_per_step() == 0

    after_dense = A.trace_counts()
    ref = A.flash_attention(q, k, v, mask=mask, force_reference=True)
    assert A.trace_counts() == (0, 1, 1)
    assert A.traced_implementation(since=after_dense) == "reference"
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    after_reference = A.trace_counts()
    monkeypatch.setattr(A, "_SCORE_BUDGET", 2 * 2 * 128 * 128 * 4 - 1)
    kern = A.flash_attention(q, k, v, mask=mask)      # one byte over: kernels
    assert A.trace_counts() == (1, 1, 1)
    assert A.traced_implementation(since=after_reference) == "pallas"
    assert A.traced_implementation(since=start) == "mixed"
    assert A.traced_rows_per_step() == 4
    np.testing.assert_allclose(np.asarray(out), np.asarray(kern), atol=2e-5)


def _sparsity_layouts():
    from deepspeed_tpu.ops import sparse_attention as sa

    return {cls.__name__: cls for cls in (
        sa.DenseSparsityConfig, sa.FixedSparsityConfig,
        sa.VariableSparsityConfig, sa.BigBirdSparsityConfig,
        sa.BSLongformerSparsityConfig)}


@pytest.mark.parametrize("config", sorted(_sparsity_layouts()))
def test_a_sparsity_layout_never_takes_the_materialised_path(tpu_branch, config):
    """Every sparsity configuration's layout, the all-ones one included,
    runs the LUT kernels at a size whose scores would fit many times over."""
    A = tpu_branch
    layout = _sparsity_layouts()[config](num_heads=2, block=16).make_layout(128)
    q, k, v = rand_qkv(B=1, H=2, S=128, D=32, seed=61)
    before = A.trace_counts()
    out = A.flash_attention(q, k, v, layout=layout, block=16)
    assert A.traced_implementation(since=before) == "pallas"
    assert A.traced_rows_per_step() == 1
    ref = A.flash_attention(q, k, v, layout=layout, block=16, force_reference=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("case,kw,want", [
    ("key_bias", {}, "dense"),
    ("causal", {"causal": True}, "dense"),          # won with and without
    ("dropout", {"dropout_rate": 0.1, "dropout_rng": jax.random.PRNGKey(3)},
     "pallas"),                                     # lost: the kernels' masks
])
def test_the_public_entry_follows_the_rule(tpu_branch, case, kw, want):
    """What the rule decided on the chip, seen from the entry: a causal call
    takes the materialised path like a plain one, a call with dropout the
    kernels; the materialised results are the float32 oracle's."""
    A = tpu_branch
    q, k, v = rand_qkv(B=2, H=2, S=128, D=32, seed=70)
    mask = jnp.asarray(np.where(
        np.random.RandomState(71).rand(2, 128) < 0.2, -10000.0, 0.0), jnp.float32)
    before = A.trace_counts()
    jax.make_jaxpr(lambda q, k, v: A.flash_attention(q, k, v, mask=mask, **kw))(
        q, k, v)
    assert A.traced_implementation(since=before) == want
    if case != "dropout":       # the chip's generator has no interpret mode
        out = A.flash_attention(q, k, v, mask=mask, **kw)
        ref = A.flash_attention(q, k, v, mask=mask, force_reference=True, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_the_rule_counts_the_rows_one_device_holds(tpu_branch, monkeypatch):
    """Under a mesh the rule sees the call as one device will: a batch whose
    scores are over the budget whole and within it split four ways over
    ``data`` takes the materialised path, with no shard_map around it."""
    from jax.sharding import Mesh

    A = tpu_branch
    q, k, v = rand_qkv(B=8, H=2, S=128, D=32, seed=72)
    monkeypatch.setattr(A, "_SCORE_BUDGET", 2 * 2 * 128 * 128 * 4)    # 2 of 8
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(1, 4, 1),
                ("pipe", "data", "model"))

    def attend(q, k, v):
        return A.flash_attention(q, k, v)

    before = A.trace_counts()
    whole = jax.make_jaxpr(attend)(q, k, v)
    assert A.traced_implementation(since=before) == "pallas"
    before = A.trace_counts()
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        split = jax.make_jaxpr(attend)(q, k, v)
    assert A.traced_implementation(since=before) == "dense"
    assert "shard_map" not in str(split) and "pallas_call" in str(whole)
