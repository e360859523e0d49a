"""Fused (optionally block-sparse) attention kernel for TPU.

Capability parity with TWO reference native-kernel subsystems at once:

- the fused attention-softmax chain of the transformer op
  (``csrc/transformer/softmax_kernels.cu``: scaled masked softmax fwd/bwd up to
  8K sequence), and
- the Triton block-sparse attention suite
  (``deepspeed/ops/sparse_attention/trsrc/{matmul.tr,softmax_*.tr}`` +
  ``csrc/sparse_attention/utils.cpp``'s layout->LUT preprocessing).

TPU-first design: ONE Pallas kernel computes QK^T -> masked online-softmax ->
PV per (batch*head, query-block-row) grid cell, streaming key/value blocks
named by a per-row lookup table (LUT). A dense layout makes it flash
attention; a sparse layout (Fixed/BigBird/Longformer, see
``sparsity_config.py``) skips absent blocks entirely, which is exactly the
load-balanced-LUT design of the reference's Triton kernels re-tiled for the
MXU (128-lane blocks instead of 16/32). Memory stays O(S*D + nnz_blocks) —
scores never materialize.

The backward pass on the TPU path runs dedicated flash backward Pallas
kernels (``_attn_bwd_dq_kernel`` / ``_attn_bwd_dkv_kernel``): dq streams the
row LUT, dk/dv/dbias stream the transposed (column) LUT, recomputing p from
the saved log-sum-exp residual so memory stays O(S*D). On non-TPU backends
the dense jnp reference path runs fwd and bwd (same numerics, dense-masked).
On a TPU nothing switches implementation behind the caller's back: a dense
sequence that is not a block multiple is padded up to one (pad keys masked,
pad queries sliced off) and still runs the kernels; only an explicit
``force_reference=True`` selects the jnp path there. Which path was traced is
counted in the shared metrics registry (``Kernels/flash_attention/*_traces``).

Several devices: GSPMD cannot partition a Mosaic kernel, so under a mesh the
kernels ``shard_map`` themselves over the mesh in context at trace time (the
engine traces the model under its own): batch over ``data``, heads over
``model`` — see ``_shard_over_context_mesh``.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AxisType, PartitionSpec

from deepspeed_tpu import telemetry
from deepspeed_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

DEFAULT_BLOCK = 128

# Trace-time tallies of the implementation each flash_attention call lowered
# to: what bench.py and chip_smoke.py read to report the attention that RAN.
PALLAS_TRACES = "Kernels/flash_attention/pallas_traces"
REFERENCE_TRACES = "Kernels/flash_attention/reference_traces"


def _count_trace(tag):
    telemetry.get_registry().counter(
        tag, help="flash_attention traces lowered to this implementation").inc()


def trace_counts():
    """(Pallas, reference) flash_attention traces in this process so far."""
    counters = telemetry.get_registry()
    return (counters.counter(PALLAS_TRACES).value,
            counters.counter(REFERENCE_TRACES).value)


def traced_implementation(since=(0, 0)):
    """Which implementation flash_attention lowered to since the
    ``trace_counts()`` snapshot ``since``: "pallas" (the TPU kernels),
    "reference" (the dense jnp path), "mixed" when both were, "none" when it
    was never traced — so a report never attributes one implementation's
    numbers to another."""
    pallas, reference = (now - then
                         for now, then in zip(trace_counts(), since))
    if pallas and reference:
        return "mixed"
    return "pallas" if pallas else ("reference" if reference else "none")


# ---------------------------------------------------------------------------
# layout -> LUT  (reference csrc/sparse_attention/utils.cpp in numpy)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _dense_lut(num_heads, num_q_blocks, num_k_blocks):
    lut = np.tile(np.arange(num_k_blocks, dtype=np.int32), (num_heads, num_q_blocks, 1))
    counts = np.full((num_heads, num_q_blocks), num_k_blocks, np.int32)
    return lut, counts


def layout_to_lut(layout):
    """[H, Qb, Kb] 0/1 layout -> (lut [H, Qb, maxnnz] int32, counts [H, Qb]).

    Rows are padded to the max row population; the kernel loops ``counts``
    blocks so padding is never touched. Delegates to the native OpenMP
    segmenter (csrc/host_ops.cpp, parity with the reference's
    csrc/sparse_attention/utils.cpp) when the library is built.
    """
    from deepspeed_tpu.ops.host_ops import layout_to_lut_host

    return layout_to_lut_host(np.asarray(layout))


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

# Odd, so multiplying by it permutes i32 space: the stride between the
# dropout seeds of consecutive (batch, head) rows.
_SEED_ROW_STRIDE = -1640531527


def _fold_dropout_seed(seed, bh, qi, kj):
    """Fold the 4-word dropout-PRNG identity into the TWO seed words Mosaic's
    tpu.prng_set_seed_32 accepts (real-TPU compile rejects more). Injective
    for fixed ``seed``: an odd multiplier permutes i32 space (distinguishes
    bh), and block indices are always < 2**16 (distinguishes (qi, kj)) —
    distinct blocks must never share a dropout mask. Works on concrete ints
    and traced i32 alike (unit-tested for injectivity; the kernel path is
    only compilable on real TPU hardware)."""
    return (
        seed + bh * jnp.int32(_SEED_ROW_STRIDE),
        qi * jnp.int32(65536) + kj,
    )


def _dropout_keep(seed_ref, bh, qi, kj, block_q, block_k, rate):
    """[BQ, BK] keep/(1-rate) scale mask from the TPU PRNG, deterministically
    re-derivable from (seed, bh, qi, kj) — the forward and BOTH backward
    kernels regenerate the identical mask instead of storing O(S^2) bits
    (the flash-dropout trick; reference stores the mask from its fused
    dropout kernels, csrc/transformer/dropout_kernels.cu)."""
    pltpu.prng_seed(*_fold_dropout_seed(seed_ref[0], bh, qi, kj))
    bits = pltpu.prng_random_bits((block_q, block_k)).astype(jnp.uint32)
    threshold = jnp.uint32(min(int(rate * 2**32), 2**32 - 1))
    return jnp.where(bits >= threshold, 1.0 / (1.0 - rate), 0.0)


def _attn_kernel(seed_ref, counts_ref, lut_ref, q_ref, k_ref, v_ref, bias_ref,
                 o_ref, lse_ref,
                 *, num_heads, block_q, block_k, maxn, scale, causal, dropout_rate):
    """One (batch*head, q-block-row) cell: stream LUT-named k/v blocks with
    online softmax. carry = (m, l, acc) runs in registers/VMEM values.

    Dropout (rate > 0) applies to the softmax PROBS: the normalizer l
    accumulates the UNDROPPED p while acc accumulates (mask * p / keep) @ v,
    so out = dropout(softmax(s)) @ v exactly."""
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    h = jax.lax.rem(bh, num_heads)

    # MXU dtype discipline: matmul OPERANDS stay in the input dtype (bf16
    # inputs hit the native bf16 MXU path — fp32 matmuls are several times
    # slower on TPU) while every accumulation/softmax runs in fp32 via
    # preferred_element_type. Scale applies to the fp32 scores, not to q.
    q = q_ref[0]                                      # [BQ, D], input dtype
    in_dtype = q.dtype
    D = q.shape[-1]
    count = counts_ref[h, qi]

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(n, carry):
        m, l, acc = carry
        kj = lut_ref[h, qi, n]
        k_blk = k_ref[0, pl.ds(kj * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kj * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                      # [BQ, BK] fp32
        s = s + bias_ref[0, 0, pl.ds(kj * block_k, block_k)].astype(jnp.float32)[None, :]
        if causal:
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        p_acc = p
        if dropout_rate > 0.0:
            p_acc = p * _dropout_keep(seed_ref, bh, qi, kj, block_q, block_k, dropout_rate)
        acc_new = acc * corr + jax.lax.dot_general(
            p_acc.astype(in_dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, count, body, (m0, l0, acc0))

    out = jnp.where(l > 0.0, acc / jnp.where(l > 0.0, l, 1.0), 0.0)
    o_ref[0] = out.astype(o_ref.dtype)
    # log-sum-exp residual for the flash backward; +inf-like for empty rows so
    # exp(s - lse) == 0 there. Stored [1,1,BQ]: Mosaic requires the last two
    # block dims be (8,128)-aligned or equal to the array dims, which a 2D
    # (1, BQ) block on a (BH, S) array violates whenever BH > 1.
    lse = jnp.where(l[:, 0] > 0.0, m[:, 0] + jnp.log(jnp.where(l[:, 0] > 0, l[:, 0], 1.0)), 1e30)
    lse_ref[0, 0] = lse


def _attention_pallas(q, k, v, bias, lut, counts, *, block_q, block_k, causal,
                      interpret=False, dropout_rate=0.0, seed=None):
    """q,k,v: [B, H, S, D]; bias additive [B, S] (key bias, e.g. padding).
    ``seed``: [1] int32 array feeding the in-kernel dropout PRNG."""
    _count_trace(PALLAS_TRACES)
    B, H, S, D = q.shape
    BH = B * H
    qr = q.reshape(BH, S, D)
    kr = k.reshape(BH, S, D)
    vr = v.reshape(BH, S, D)
    maxn = lut.shape[-1]
    scale = 1.0 / float(np.sqrt(D))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(BH, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, *_: (bh, qi, 0)),
            pl.BlockSpec((1, S, D), lambda bh, qi, *_: (bh, 0, 0)),
            pl.BlockSpec((1, S, D), lambda bh, qi, *_: (bh, 0, 0)),
            pl.BlockSpec((1, 1, S), lambda bh, qi, *_: (bh, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, D), lambda bh, qi, *_: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, *_: (bh, 0, qi)),
        ),
    )
    kernel = functools.partial(
        _attn_kernel, num_heads=H, block_q=block_q, block_k=block_k,
        maxn=maxn, scale=scale, causal=causal, dropout_rate=dropout_rate,
    )
    bias_r = jnp.broadcast_to(bias[:, None, :], (B, H, S)).reshape(BH, 1, S)
    seed_arr = jnp.zeros((1,), jnp.int32) if seed is None else jnp.asarray(seed, jnp.int32).reshape(1)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, S), jnp.float32),
        ),
        interpret=interpret,
        name="flash_attention_fwd",
    )(seed_arr, jnp.asarray(counts), jnp.asarray(lut), qr, kr, vr, bias_r)
    return out.reshape(B, H, S, D), lse.reshape(BH, S)


def _attn_bwd_dq_kernel(seed_ref, counts_ref, lut_ref, q_ref, k_ref, v_ref, bias_ref,
                        do_ref, lse_ref, delta_ref, dq_ref,
                        *, num_heads, block_q, block_k, scale, causal, dropout_rate):
    """dq for one (bh, q-block-row): dq = scale * sum_j ds_j @ k_j with
    ds = p * (mask * dO @ v^T - delta) and p = exp(s - lse). The dropout mask
    regenerates from (seed, bh, qi, kj) — identical to the forward's."""
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    h = jax.lax.rem(bh, num_heads)

    q = q_ref[0]                      # input dtype; scale applied to scores
    do = do_ref[0]
    in_dtype = q.dtype
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    D = q.shape[-1]
    count = counts_ref[h, qi]
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(n, dq):
        kj = lut_ref[h, qi, n]
        k_blk = k_ref[0, pl.ds(kj * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kj * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + bias_ref[0, 0, pl.ds(kj * block_k, block_k)].astype(jnp.float32)[None, :]
        if causal:
            k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            dp = dp * _dropout_keep(seed_ref, bh, qi, kj, block_q, block_k, dropout_rate)
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(ds.astype(in_dtype), k_blk, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, count, body, jnp.zeros((block_q, D), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(seed_ref, qcounts_ref, qlut_ref, q_ref, k_ref, v_ref, bias_ref,
                         do_ref, lse_ref, delta_ref, dk_ref, dv_ref, db_ref,
                         *, num_heads, block_q, block_k, scale, causal, dropout_rate):
    """dk/dv/dbias for one (bh, k-block-column), looping the transposed LUT's
    q blocks: dv = sum (mask*p)^T dO; dk = sum ds^T (scale*q); dbias =
    sum_rows ds. The dropout mask regenerates with the same (seed, bh, qi,
    kj) ordering as the forward, regardless of this kernel's transposed
    iteration order."""
    bh = pl.program_id(0)
    kj = pl.program_id(1)
    h = jax.lax.rem(bh, num_heads)

    k_blk = k_ref[0]                  # input dtype; scale folded at write-out
    v_blk = v_ref[0]
    in_dtype = k_blk.dtype
    bias_j = bias_ref[0, 0].astype(jnp.float32)
    D = k_blk.shape[-1]
    count = qcounts_ref[h, kj]
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def body(n, carry):
        dk, dv, db = carry
        qi = qlut_ref[h, kj, n]
        q_i = q_ref[0, pl.ds(qi * block_q, block_q), :]
        do_i = do_ref[0, pl.ds(qi * block_q, block_q), :]
        lse_i = lse_ref[0, 0, pl.ds(qi * block_q, block_q)]
        delta_i = delta_ref[0, 0, pl.ds(qi * block_q, block_q)]
        s = jax.lax.dot_general(q_i, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + bias_j[None, :]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        p = jnp.exp(s - lse_i[:, None])
        dp = jax.lax.dot_general(do_i, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        p_drop = p
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref, bh, qi, kj, block_q, block_k, dropout_rate)
            p_drop = p * keep
            dp = dp * keep
        dv = dv + jax.lax.dot_general(p_drop.astype(in_dtype), do_i, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        ds = p * (dp - delta_i[:, None])
        dk = dk + jax.lax.dot_general(ds.astype(in_dtype), q_i, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        db = db + jnp.sum(ds, axis=0)
        return dk, dv, db

    zero = jnp.zeros((block_k, D), jnp.float32)
    dk, dv, db = jax.lax.fori_loop(0, count, body, (zero, zero, jnp.zeros((block_k,), jnp.float32)))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    db_ref[0, 0] = db


def _attention_pallas_bwd(q, k, v, bias, out, lse, g, lut, counts, qlut, qcounts,
                          *, block_q, block_k, causal, interpret=False,
                          dropout_rate=0.0, seed=None):
    """Flash backward: returns (dq, dk, dv, dbias[B,S])."""
    B, H, S, D = q.shape
    BH = B * H
    rs = lambda t: t.reshape(BH, S, D)
    qr, kr, vr, dor, outr = rs(q), rs(k), rs(v), rs(g), rs(out)
    scale = 1.0 / float(np.sqrt(D))
    bias_r = jnp.broadcast_to(bias[:, None, :], (B, H, S)).reshape(BH, 1, S)
    # [BH,1,S] so the (1,1,block) / (1,1,S) blockspecs below are Mosaic-legal
    # (a 2D (1,block) block on a (BH,S) array is rejected when BH > 1).
    delta = jnp.sum(dor.astype(jnp.float32) * outr.astype(jnp.float32), axis=-1)
    delta_r = delta.reshape(BH, 1, S)
    lse_r = lse.reshape(BH, 1, S)

    seed_arr = jnp.zeros((1,), jnp.int32) if seed is None else jnp.asarray(seed, jnp.int32).reshape(1)

    # dq: grid over q block rows
    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(BH, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, *_: (bh, qi, 0)),
            pl.BlockSpec((1, S, D), lambda bh, qi, *_: (bh, 0, 0)),
            pl.BlockSpec((1, S, D), lambda bh, qi, *_: (bh, 0, 0)),
            pl.BlockSpec((1, 1, S), lambda bh, qi, *_: (bh, 0, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, qi, *_: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, *_: (bh, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, *_: (bh, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi, *_: (bh, qi, 0)),
    )
    dq = pl.pallas_call(
        functools.partial(_attn_bwd_dq_kernel, num_heads=H, block_q=block_q,
                          block_k=block_k, scale=scale, causal=causal,
                          dropout_rate=dropout_rate),
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(seed_arr, jnp.asarray(counts), jnp.asarray(lut), qr, kr, vr, bias_r, dor, lse_r, delta_r)

    # dk/dv/dbias: grid over k block columns with the TRANSPOSED LUT
    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(BH, S // block_k),
        in_specs=[
            pl.BlockSpec((1, S, D), lambda bh, kj, *_: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, kj, *_: (bh, kj, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, kj, *_: (bh, kj, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bh, kj, *_: (bh, 0, kj)),
            pl.BlockSpec((1, S, D), lambda bh, kj, *_: (bh, 0, 0)),
            pl.BlockSpec((1, 1, S), lambda bh, kj, *_: (bh, 0, 0)),
            pl.BlockSpec((1, 1, S), lambda bh, kj, *_: (bh, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, D), lambda bh, kj, *_: (bh, kj, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, kj, *_: (bh, kj, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bh, kj, *_: (bh, 0, kj)),
        ),
    )
    dk, dv, db = pl.pallas_call(
        functools.partial(_attn_bwd_dkv_kernel, num_heads=H, block_q=block_q,
                          block_k=block_k, scale=scale, causal=causal,
                          dropout_rate=dropout_rate),
        grid_spec=dkv_spec,
        out_shape=(
            jax.ShapeDtypeStruct((BH, S, D), k.dtype),
            jax.ShapeDtypeStruct((BH, S, D), v.dtype),
            jax.ShapeDtypeStruct((BH, 1, S), jnp.float32),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(seed_arr, jnp.asarray(qcounts), jnp.asarray(qlut), qr, kr, vr, bias_r, dor, lse_r, delta_r)

    unrs = lambda t: t.reshape(B, H, S, D)
    dbias = db.reshape(B, H, S).sum(axis=1).astype(bias.dtype)
    return unrs(dq), unrs(dk), unrs(dv), dbias


# ---------------------------------------------------------------------------
# jnp reference path (non-TPU backends + the recompute backward)
# ---------------------------------------------------------------------------

def _attention_reference(q, k, v, bias, layout_mask, *, causal,
                         dropout_rate=0.0, seed=None):
    _count_trace(REFERENCE_TRACES)
    B, H, S, D = q.shape
    scale = 1.0 / np.sqrt(D)
    s = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    s = s + bias[:, None, None, :].astype(jnp.float32)
    if causal:
        cm = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(cm[None, None], s, -1e30)
    if layout_mask is not None:
        s = jnp.where(layout_mask[None], s, -1e30)
    # Rows with no admissible key (all -inf) produce 0, matching the kernel.
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    alive = m > -1e29
    probs = jnp.where(alive, p / jnp.where(l > 0, l, 1.0), 0.0)
    if dropout_rate > 0.0 and seed is not None:
        # Seed-deterministic prob dropout (same semantics as the Pallas
        # kernels' in-kernel PRNG; the bit streams differ between backends,
        # which is fine — dropout is stochastic regularization).
        key = jax.random.PRNGKey(jnp.asarray(seed).reshape(())[()].astype(jnp.uint32))
        keep = jax.random.bernoulli(key, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_reference(q, k, v, mask=None, causal=False):
    """Dense attention accepting an arbitrary ADDITIVE mask broadcastable to
    [B,H,S,S] (the reference transformer's mask shape) — the documented
    fallback for masks ``flash_attention`` cannot express in-kernel."""
    B, H, S, D = q.shape
    scale = 1.0 / np.sqrt(D)
    s = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if mask is not None:
        m = jnp.asarray(mask, jnp.float32)
        if m.ndim == 2:
            m = m[:, None, None, :]
        s = s + m
    if causal:
        cm = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(cm[None, None], s, -1e30)
    probs = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", probs, v.astype(jnp.float32)).astype(q.dtype)


def _expand_layout_mask(layout, S, block):
    if layout is None:
        return None
    layout = jnp.asarray(layout, bool)
    return jnp.repeat(jnp.repeat(layout, block, axis=1), block, axis=2)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def _luts_for(layout, H, S, block):
    """(row LUT, counts, transposed LUT, transposed counts)."""
    nb = S // block
    if layout is None:
        lut, counts = _dense_lut(H, nb, nb)
        return lut, counts, lut, counts
    lut, counts = layout_to_lut(layout)
    qlut, qcounts = layout_to_lut(np.asarray(layout).transpose(0, 2, 1))
    return lut, counts, qlut, qcounts


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _attention(q, k, v, bias, seed, layout_key, block, causal, force_ref, dropout_rate):
    layout = _LAYOUTS.get(layout_key) if layout_key is not None else None
    if force_ref or not _on_tpu():
        return _attention_reference(
            q, k, v, bias, _expand_layout_mask(layout, q.shape[2], block),
            causal=causal, dropout_rate=dropout_rate, seed=seed,
        )
    B, H, S, D = q.shape
    lut, counts, _, _ = _luts_for(layout, H, S, block)
    out, _ = _attention_pallas(
        q, k, v, bias, lut, counts, block_q=block, block_k=block, causal=causal,
        dropout_rate=dropout_rate, seed=seed,
    )
    return out


def _on_tpu():
    return jax.default_backend() == "tpu"


def _attention_fwd(q, k, v, bias, seed, layout_key, block, causal, force_ref, dropout_rate):
    layout = _LAYOUTS.get(layout_key) if layout_key is not None else None
    if force_ref or not _on_tpu():
        out = _attention_reference(
            q, k, v, bias, _expand_layout_mask(layout, q.shape[2], block),
            causal=causal, dropout_rate=dropout_rate, seed=seed,
        )
        return out, (q, k, v, bias, seed, None, None)
    B, H, S, D = q.shape
    lut, counts, _, _ = _luts_for(layout, H, S, block)
    out, lse = _attention_pallas(
        q, k, v, bias, lut, counts, block_q=block, block_k=block, causal=causal,
        dropout_rate=dropout_rate, seed=seed,
    )
    return out, (q, k, v, bias, seed, out, lse)


def _attention_bwd(layout_key, block, causal, force_ref, dropout_rate, res, g):
    """Flash backward kernels on the Pallas path (O(S*D) memory, dropout mask
    regenerated in-kernel from the saved seed); dense rematerialized VJP on
    the reference path (same seed reproduces the same mask)."""
    q, k, v, bias, seed, out, lse = res
    layout = _LAYOUTS.get(layout_key) if layout_key is not None else None
    seed_ct = (
        None if seed is None
        else np.zeros(np.shape(seed), jax.dtypes.float0)
    )

    if lse is not None:
        B, H, S, D = q.shape
        lut, counts, qlut, qcounts = _luts_for(layout, H, S, block)
        dq, dk, dv, dbias = _attention_pallas_bwd(
            q, k, v, bias, out, lse, g, lut, counts, qlut, qcounts,
            block_q=block, block_k=block, causal=causal,
            dropout_rate=dropout_rate, seed=seed,
        )
        return dq, dk, dv, dbias, seed_ct

    def f(q, k, v, bias):
        return _attention_reference(
            q, k, v, bias, _expand_layout_mask(layout, q.shape[2], block),
            causal=causal, dropout_rate=dropout_rate, seed=seed,
        )

    _, vjp = jax.vjp(f, q, k, v, bias)
    return vjp(g) + (seed_ct,)


_attention.defvjp(_attention_fwd, _attention_bwd)

# Layouts must be hashable for custom_vjp nondiff args: register by key.
_LAYOUTS = {}


def _register_layout(layout):
    if layout is None:
        return None
    arr = np.asarray(layout)
    key = hash(arr.tobytes()) ^ hash(arr.shape)
    _LAYOUTS[key] = arr
    return key


def _shard_over_context_mesh(attend, q_shape, shard_heads, has_seed):
    """Make ``attend(q, k, v, bias[, seed])`` run per device under a mesh.

    XLA's partitioner refuses a Mosaic custom call ("Mosaic kernels cannot be
    automatically partitioned"), and attention is embarrassingly parallel
    over batch and heads, so the kernels are shard_mapped explicitly: batch
    over the ``data`` axis, heads over the ``model`` axis. The mesh is the
    one in context at trace time. Mosaic wants EVERY mesh axis manual, so
    the map takes all the axes that are still automatic (any other one just
    sees replicated operands); axes that are already manual (the caller
    sits in its own shard_map: ring/Ulysses attention, the 1-bit Adam step)
    are the caller's. With one device under automatic axes ``attend`` is
    returned as it is. ``shard_heads=False`` keeps the heads together (a
    block-sparse LUT is indexed by GLOBAL head)."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = [name for name, kind in zip(mesh.axis_names, mesh.axis_types)
            if kind == AxisType.Auto]
    if all(mesh.shape[name] == 1 for name in auto):
        return attend
    b_ax = DATA_AXIS if DATA_AXIS in auto else None
    h_ax = MODEL_AXIS if MODEL_AXIS in auto and shard_heads else None
    axes = tuple(ax for ax in (b_ax, h_ax) if ax is not None)
    local_bh = q_shape[0] * q_shape[1]
    for ax in axes:
        local_bh //= mesh.shape[ax]

    def local(q, k, v, bias, *seed):
        if has_seed:
            # every shard numbers its (batch, head) rows from 0: shift the
            # seed by the shard's first global row so no two shards draw
            # the same dropout masks
            shard = 0
            for ax in axes:
                shard = shard * mesh.shape[ax] + jax.lax.axis_index(ax)
            seed = (seed[0] + (shard * local_bh).astype(jnp.int32)
                    * jnp.int32(_SEED_ROW_STRIDE),)
        return attend(q, k, v, bias, *seed)

    qkv = PartitionSpec(b_ax, h_ax, None, None)
    in_specs = (qkv, qkv, qkv, PartitionSpec(b_ax, None))
    if has_seed:
        in_specs += (PartitionSpec(),)
    return jax.shard_map(local, in_specs=in_specs, out_specs=qkv,
                         axis_names=frozenset(auto), check_vma=False)


def flash_attention(q, k, v, mask=None, layout=None, block=DEFAULT_BLOCK,
                    causal=False, force_reference=False,
                    dropout_rate=0.0, dropout_rng=None):
    """Fused attention. q,k,v: [B,H,S,D]; ``mask``: additive [B,1,1,S] (or
    [B,S]) key bias; ``layout``: optional [H, S/block, S/block] 0/1 block
    sparsity; ``causal`` adds the autoregressive mask in-kernel.

    ``dropout_rate`` > 0 (with a ``dropout_rng`` PRNG key) applies dropout to
    the softmax probs IN-KERNEL: the mask is regenerated from a seed in the
    backward kernels instead of being stored, so memory stays O(S*D) — the
    fused-softmax-dropout capability of the reference's transformer kernels
    (csrc/transformer/{softmax,dropout}_kernels.cu). The TPU kernel and the
    reference path draw from different PRNGs (same distribution)."""
    B, H, S, D = q.shape
    if not (0.0 <= dropout_rate < 1.0):
        raise ValueError(
            f"dropout_rate must be in [0, 1), got {dropout_rate} "
            "(a fraction, not a percentage)"
        )
    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError("dropout_rate > 0 requires dropout_rng")
        seed = jax.random.randint(dropout_rng, (1,), 0, 2**31 - 1, dtype=jnp.int32)
    else:
        seed = None
        dropout_rate = 0.0
    if mask is None:
        bias = jnp.zeros((B, S), q.dtype)
    elif mask.ndim == 4:
        # Only a broadcastable key bias [B,1,1,S] collapses losslessly; a full
        # [B,1,S,S]/[B,H,S,S] additive mask (the reference's shape) must NOT be
        # silently sliced to its first query row.
        if mask.shape[-2] != 1 or mask.shape[1] != 1:
            raise ValueError(
                f"flash_attention only supports key-bias masks [B,1,1,S] or [B,S]; "
                f"got {mask.shape}. For causal masking pass causal=True; for an "
                f"arbitrary S x S additive mask use the dense reference path "
                f"(ops.transformer.attention.attention_reference)."
            )
        bias = mask[:, 0, 0, :]
    else:
        bias = mask
    key = _register_layout(layout)
    if force_reference or not _on_tpu():
        return _attention(q, k, v, bias, seed, key, block, causal, True,
                          float(dropout_rate))

    def attend(q, k, v, bias, seed=None):
        return _attention(q, k, v, bias, seed, key, block, causal, False,
                          float(dropout_rate))

    pad = -S % block
    if pad:
        # The kernels tile S in whole blocks. Pad rather than change
        # implementation: pad keys get a -1e30 bias (exact-zero probability,
        # like every other masked key) and pad queries are sliced off; the
        # pad/slice pair sits outside the custom VJP, so autodiff handles it.
        if layout is not None:
            raise ValueError(
                f"block-sparse flash_attention needs S % block == 0 "
                f"(the layout is in whole blocks), got S={S}, block={block}")
        widen = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)))
        q, k, v = widen(q), widen(k), widen(v)
        bias = jnp.pad(bias, ((0, 0), (0, pad)), constant_values=-1e30)
    attend = _shard_over_context_mesh(attend, q.shape, layout is None,
                                      seed is not None)
    out = attend(q, k, v, bias, *(() if seed is None else (seed,)))
    return out[:, :, :S] if pad else out
