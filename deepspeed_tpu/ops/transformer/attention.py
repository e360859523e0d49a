"""Fused (optionally block-sparse) attention kernel for TPU.

Capability parity with TWO reference native-kernel subsystems at once:

- the fused attention-softmax chain of the transformer op
  (``csrc/transformer/softmax_kernels.cu``: scaled masked softmax fwd/bwd up to
  8K sequence), and
- the Triton block-sparse attention suite
  (``deepspeed/ops/sparse_attention/trsrc/{matmul.tr,softmax_*.tr}`` +
  ``csrc/sparse_attention/utils.cpp``'s layout->LUT preprocessing).

TPU-first design: ONE Pallas kernel computes QK^T -> masked online-softmax ->
PV per (group of G batch*head rows, query-block-row) grid cell, streaming
key/value blocks named by a per-row lookup table (LUT). G follows the shape
(``rows_per_step``: as many rows as a fixed VMEM budget holds, so 16 at
BERT's seq 128 and 1 at 8k; 1 for a block-sparse layout): at a short
sequence a row is a few dozen MXU cycles behind a grid step's fixed cost,
and G rows in one step share that cost and overlap their softmax chains. A
row's arithmetic does not depend on G. A dense layout makes it flash
attention; a sparse layout (Fixed/BigBird/Longformer, see
``sparsity_config.py``) skips absent blocks entirely, which is exactly the
load-balanced-LUT design of the reference's Triton kernels re-tiled for the
MXU (128-lane blocks instead of 16/32). Memory stays O(S*D + nnz_blocks) —
scores never materialize.

The backward pass of the kernels runs dedicated flash backward Pallas
kernels (``_attn_bwd_dq_kernel`` / ``_attn_bwd_dkv_kernel``): dq streams the
row LUT, dk/dv/dbias stream the transposed (column) LUT, recomputing p from
the saved log-sum-exp residual so memory stays O(S*D). On non-TPU backends
the dense jnp reference path runs fwd and bwd (same numerics, dense-masked).

On a TPU the public entry chooses between TWO algorithms, by a rule on what
it can see in the call and nothing else (``materialises_scores``: the layout,
the call's rows and lengths on one device, the dropout rate; no option, no
environment variable):

- the streaming kernels above, for every block-sparse layout, for every call
  with dropout (the kernels draw their masks from the chip's generator) and
  for every dense call whose scores are too large to hold, and
- the **materialised path** (``_attention_dense``) where the float32 scores
  of the device's call fit ``_SCORE_BUDGET`` (64 MiB: BERT-large at seq 128
  and micro-batch 64): QK^T, scale, bias, mask, softmax and PV as plain
  ``jnp`` operations (the kernels' mathematics at the kernels' precision),
  a chain XLA keeps in on-chip memory, autodiff differentiates and the
  remat policy sees, 2.5-3 times faster than three kernel launches
  (PERF.md section 6, PR 32). It needs no ``shard_map``: GSPMD partitions
  einsums over a sharded batch.

A dense sequence that is not a block multiple and goes to the kernels is
padded up to one (pad keys masked, pad queries sliced off); only an explicit
``force_reference=True`` selects the float32 jnp reference on a TPU. Which
implementation was traced is counted in the shared metrics registry
(``Kernels/flash_attention/{pallas,dense,reference}_traces``;
``traced_implementation()`` names it).

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
DENSE_TRACES = "Kernels/flash_attention/dense_traces"
REFERENCE_TRACES = "Kernels/flash_attention/reference_traces"
# Trace-time gauge: the (batch, head) rows a grid step of the last traced
# kernels handles (``rows_per_step``).
ROWS_PER_STEP = "Kernels/flash_attention/rows_per_step"


def _count_trace(tag):
    telemetry.get_registry().counter(
        tag, help="flash_attention traces lowered to this implementation").inc()


def trace_counts():
    """(Pallas kernels, materialised path, float32 reference) traces of
    flash_attention in this process so far."""
    counters = telemetry.get_registry()
    return tuple(counters.counter(tag).value
                 for tag in (PALLAS_TRACES, DENSE_TRACES, REFERENCE_TRACES))


def traced_implementation(since=(0, 0, 0)):
    """Which implementation flash_attention lowered to since the
    ``trace_counts()`` snapshot ``since``: "pallas" (the TPU kernels),
    "dense" (the materialised path the rule chose), "reference" (the
    float32 jnp path), "mixed" when more than one was, "none" when it was
    never traced — so a report never attributes one implementation's
    numbers to another."""
    traced = [name for name, now, then in zip(
        ("pallas", "dense", "reference"), trace_counts(), since) if now > then]
    return "mixed" if len(traced) > 1 else (traced[0] if traced else "none")


def traced_rows_per_step():
    """(batch, head) rows a grid step of the flash kernels traced last took
    (``rows_per_step``); 0 while the kernels were never traced."""
    return int(telemetry.get_registry().gauge(ROWS_PER_STEP).value)


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


def _dropout_keep(seed_ref, bh0, rows, qi, kj, block_q, block_k, rate):
    """[G, BQ, BK] keep/(1-rate) scale masks from the TPU PRNG, one per row
    of the group, each deterministically re-derivable from (seed, bh, qi,
    kj) with ``bh = bh0 + g`` the row's number in the call — the forward and
    BOTH backward kernels regenerate the identical mask instead of storing
    O(S^2) bits (the flash-dropout trick; reference stores the mask from its
    fused dropout kernels, csrc/transformer/dropout_kernels.cu). A row draws
    what it draws alone in a group of one: the group size is no part of the
    identity."""
    threshold = jnp.uint32(min(int(rate * 2**32), 2**32 - 1))
    bits = []
    for g in range(rows):
        pltpu.prng_seed(*_fold_dropout_seed(seed_ref[0], bh0 + g, qi, kj))
        bits.append(pltpu.prng_random_bits((block_q, block_k)).astype(jnp.uint32))
    return jnp.where(jnp.stack(bits) >= threshold, 1.0 / (1.0 - rate), 0.0)


def _rows_dot(a, b, a_axis, b_axis):
    """Per-row contraction of ``[G, ., .]`` operands over ``a_axis`` of ``a``
    and ``b_axis`` of ``b``, fp32 result: the G (batch, head) rows of a grid
    step ride as the leading batch dimension of ONE dot, so their MXU passes
    and the softmax chains between them are scheduled side by side."""
    return jax.lax.dot_general(
        a, b, (((a_axis,), (b_axis,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _stat_columns(x):
    """[G, 1, N] row statistics (lse, delta: stored along lanes) as the
    [G, N, 1] columns the [G, N, K] scores broadcast. Written as a reshape,
    which Mosaic lowers row by row and fuses with the broadcast that follows:
    the form to use inside a loop (measured on a v5e, PERF.md PR 30)."""
    return x[:, 0, :][:, :, None]


def _attn_kernel(seed_ref, counts_ref, lut_ref, q_ref, k_ref, v_ref, bias_ref,
                 o_ref, lse_ref,
                 *, num_heads, block_q, block_k, maxn, scale, causal, dropout_rate):
    """One (group of G batch*head rows, q-block-row) cell: stream LUT-named
    k/v blocks with online softmax, all G rows in every operation. carry =
    (m, l, acc) runs in registers/VMEM values. G is the blocks' leading
    extent (``rows_per_step``); the rows of a group share the LUT row of
    the group's first head, which is why only dense layouts group.

    Dropout (rate > 0) applies to the softmax PROBS: the normalizer l
    accumulates the UNDROPPED p while acc accumulates (mask * p / keep) @ v,
    so out = dropout(softmax(s)) @ v exactly."""
    rows = q_ref.shape[0]
    bh0 = pl.program_id(0) * rows
    qi = pl.program_id(1)
    h = jax.lax.rem(bh0, num_heads)

    # MXU dtype discipline: matmul OPERANDS stay in the input dtype (bf16
    # inputs hit the native bf16 MXU path — fp32 matmuls are several times
    # slower on TPU) while every accumulation/softmax runs in fp32 via
    # preferred_element_type. Scale applies to the fp32 scores, not to q.
    q = q_ref[...]                                    # [G, BQ, D], input dtype
    in_dtype = q.dtype
    D = q.shape[-1]
    count = counts_ref[h, qi]

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(n, carry):
        m, l, acc = carry
        kj = lut_ref[h, qi, n]
        cols = pl.ds(kj * block_k, block_k)
        k_blk = k_ref[:, cols, :]
        v_blk = v_ref[:, cols, :]
        s = _rows_dot(q, k_blk, 2, 2) * scale         # [G, BQ, BK] fp32
        s = s + bias_ref[:, :, cols].astype(jnp.float32)
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
            p_acc = p * _dropout_keep(seed_ref, bh0, rows, qi, kj,
                                      block_q, block_k, dropout_rate)
        acc_new = acc * corr + _rows_dot(p_acc.astype(in_dtype), v_blk, 2, 1)
        return m_new, l_new, acc_new

    m0 = jnp.full((rows, block_q, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((rows, block_q, 1), jnp.float32)
    acc0 = jnp.zeros((rows, block_q, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, count, body, (m0, l0, acc0))

    out = jnp.where(l > 0.0, acc / jnp.where(l > 0.0, l, 1.0), 0.0)
    o_ref[...] = out.astype(o_ref.dtype)
    # log-sum-exp residual for the flash backward; +inf-like for empty rows so
    # exp(s - lse) == 0 there. Stored [G,1,BQ]: Mosaic requires the last two
    # block dims be (8,128)-aligned or equal to the array dims, which a 2D
    # (G, BQ) block on a (BH, S) array violates whenever G is not 8-aligned.
    lse = jnp.where(l > 0.0, m + jnp.log(jnp.where(l > 0.0, l, 1.0)), 1e30)
    if rows == 1:
        lse_ref[0, 0] = lse[0, :, 0]
    else:
        # one transpose for the whole group beats G relayouts (PERF.md PR 30)
        lse_ref[...] = jnp.swapaxes(lse, 1, 2)


# VMEM one grid step's blocks and temporaries may take, by ``rows_per_step``'s
# own count, of the 16 MiB a Mosaic kernel is given by default. The count is
# an upper estimate: at every sequence length the groups this budget admits
# compile for a v5e (head sizes 32-256, bf16 and fp32, with dropout), and the
# next power of two, 17 MiB or more by the same count, is refused.
_VMEM_BUDGET = 12 * 2**20


def rows_per_step(bh, S, D, dtype, dense, block=DEFAULT_BLOCK):
    """How many (batch, head) rows one grid step of the three kernels takes.

    A row's own work at a short sequence is a few dozen MXU cycles behind a
    fixed cost per grid step and one dependent chain (QK^T, max, exp, sum,
    PV), so rows are grouped until the step's VMEM is spent: the largest
    power of two that divides ``bh`` (the rows of THIS call: per device
    under ``shard_map``) and whose blocks fit ``_VMEM_BUDGET``, sized by the
    hungriest kernel (dk/dv: q and dO whole, k, v, dk, dv one block each,
    all double-buffered, with [., block, D] tiles padded to 128 lanes; four
    fp32 [block, block] temporaries and two fp32 accumulators). It falls as
    ``S`` grows, when a row brings enough work of its own. A block-sparse
    layout keeps 1: the rows of a group share one LUT row, and a sparse
    LUT differs by head."""
    if not dense:
        return 1
    lanes = -(-D // 128) * 128
    tile = block * lanes * jnp.dtype(dtype).itemsize
    per_row = (2 * (2 * (S // block) + 4) * tile
               + 4 * block * block * 4 + 2 * block * lanes * 4)
    rows = 1
    while bh % (2 * rows) == 0 and 2 * rows * per_row <= _VMEM_BUDGET:
        rows *= 2
    return rows


# Float32 scores a call may hold on one device and still take the materialised
# path: the most for which the chip's compiler keeps the whole chain (scores,
# probabilities and their gradients) in on-chip memory. Compiled for a v5e,
# forward+backward, the chain's HBM temporaries are 2 MB at 64 MiB of scores
# ([64,16,128,128], [16,16,256,256] and [4,16,512,512] alike), 40 MB at 80 MiB
# and 1.4 times the scores from 96 MiB up; timed on the chip it is 2.5-3.1
# times faster than the kernels at the three 64 MiB shapes and 1.5 times
# SLOWER at 256 MiB and 1 GiB, where the scores travel to HBM and back
# (``tests/perf/attention_ab.py``; PERF.md section 6, PR 32).
_SCORE_BUDGET = 64 * 2**20


def materialises_scores(B, H, S_q, S_k, dense, dropout_rate=0.0):
    """Whether a call of ``B`` x ``H`` (batch, head) rows ON ONE DEVICE,
    ``S_q`` queries against ``S_k`` keys, runs the materialised path
    (``_attention_dense``) in place of the streaming kernels: a dense
    layout (a LUT is the kernels' whole point), no dropout (a mask drawn
    with ``jax.random`` costs four times the attention itself: 2.50 ms
    against the kernels' 1.27 at BERT's shape) and float32 scores within
    ``_SCORE_BUDGET``. The causal flag is not asked: the materialised path
    won by the same factor with it and without."""
    if not dense or dropout_rate > 0.0:
        return False
    return B * H * S_q * S_k * 4 <= _SCORE_BUDGET


def _group_specs(rows, S, D, block):
    """BlockSpecs of the three kernels' operands for ``rows`` (batch, head)
    rows a grid step, grid ``(BH // rows, S // block)``: (a [BH, S, D]
    array's block of the step's sequence block, the same array's whole
    sequence, a [BH, 1, S] row statistic's block, its whole sequence)."""
    return (
        pl.BlockSpec((rows, block, D), lambda g, i, *_: (g, i, 0)),
        pl.BlockSpec((rows, S, D), lambda g, i, *_: (g, 0, 0)),
        pl.BlockSpec((rows, 1, block), lambda g, i, *_: (g, 0, i)),
        pl.BlockSpec((rows, 1, S), lambda g, i, *_: (g, 0, 0)),
    )


def _attention_pallas(q, k, v, bias, lut, counts, *, block_q, block_k, causal,
                      interpret=False, dropout_rate=0.0, seed=None, rows=1):
    """q,k,v: [B, H, S, D]; bias additive [B, S] (key bias, e.g. padding).
    ``seed``: [1] int32 array feeding the in-kernel dropout PRNG. ``rows``:
    (batch, head) rows a grid step, from ``rows_per_step``."""
    _count_trace(PALLAS_TRACES)
    telemetry.get_registry().gauge(
        ROWS_PER_STEP, help="(batch, head) rows a grid step of the last "
        "traced flash_attention kernels handles").set(rows)
    B, H, S, D = q.shape
    BH = B * H
    qr = q.reshape(BH, S, D)
    kr = k.reshape(BH, S, D)
    vr = v.reshape(BH, S, D)
    maxn = lut.shape[-1]
    scale = 1.0 / float(np.sqrt(D))

    blk, seq, stat_blk, stat_seq = _group_specs(rows, S, D, block_q)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(BH // rows, S // block_q),
        in_specs=[blk, seq, seq, stat_seq],
        out_specs=(blk, stat_blk),
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
    """dq for one (group of rows, q-block-row): dq = scale * sum_j ds_j @ k_j
    with ds = p * (mask * dO @ v^T - delta) and p = exp(s - lse). The dropout
    mask regenerates from (seed, bh, qi, kj) — identical to the forward's."""
    rows = q_ref.shape[0]
    bh0 = pl.program_id(0) * rows
    qi = pl.program_id(1)
    h = jax.lax.rem(bh0, num_heads)

    q = q_ref[...]                    # input dtype; scale applied to scores
    do = do_ref[...]
    in_dtype = q.dtype
    lse = lse_ref[...]                # [G, 1, BQ], along lanes as stored
    delta = delta_ref[...]
    columns = _stat_columns
    if rows > 1:
        # A group's statistics turn into columns ONCE, by a transpose ahead
        # of the loop: G relayouts a key block would cost more than they do
        # for one row, where they stay in the loop (PERF.md PR 30).
        lse, delta = jnp.swapaxes(lse, 1, 2), jnp.swapaxes(delta, 1, 2)
        columns = lambda x: x
    D = q.shape[-1]
    count = counts_ref[h, qi]
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(n, dq):
        kj = lut_ref[h, qi, n]
        cols = pl.ds(kj * block_k, block_k)
        k_blk = k_ref[:, cols, :]
        v_blk = v_ref[:, cols, :]
        s = _rows_dot(q, k_blk, 2, 2) * scale
        s = s + bias_ref[:, :, cols].astype(jnp.float32)
        if causal:
            k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        p = jnp.exp(s - columns(lse))
        dp = _rows_dot(do, v_blk, 2, 2)
        if dropout_rate > 0.0:
            dp = dp * _dropout_keep(seed_ref, bh0, rows, qi, kj,
                                    block_q, block_k, dropout_rate)
        ds = p * (dp - columns(delta))
        return dq + _rows_dot(ds.astype(in_dtype), k_blk, 2, 1)

    dq = jax.lax.fori_loop(0, count, body, jnp.zeros((rows, block_q, D), jnp.float32))
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(seed_ref, qcounts_ref, qlut_ref, q_ref, k_ref, v_ref, bias_ref,
                         do_ref, lse_ref, delta_ref, dk_ref, dv_ref, db_ref,
                         *, num_heads, block_q, block_k, scale, causal, dropout_rate):
    """dk/dv/dbias for one (group of rows, k-block-column), looping the
    transposed LUT's q blocks: dv = sum (mask*p)^T dO; dk = sum ds^T
    (scale*q); dbias = sum_rows ds. The dropout mask regenerates with the
    same (seed, bh, qi, kj) ordering as the forward, regardless of this
    kernel's transposed iteration order."""
    rows = k_ref.shape[0]
    bh0 = pl.program_id(0) * rows
    kj = pl.program_id(1)
    h = jax.lax.rem(bh0, num_heads)

    k_blk = k_ref[...]                # input dtype; scale folded at write-out
    v_blk = v_ref[...]
    in_dtype = k_blk.dtype
    bias_j = bias_ref[...].astype(jnp.float32)        # [G, 1, BK]
    D = k_blk.shape[-1]
    count = qcounts_ref[h, kj]
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def body(n, carry):
        dk, dv, db = carry
        qi = qlut_ref[h, kj, n]
        qrows = pl.ds(qi * block_q, block_q)
        q_i = q_ref[:, qrows, :]
        do_i = do_ref[:, qrows, :]
        lse_i = _stat_columns(lse_ref[:, :, qrows])
        delta_i = _stat_columns(delta_ref[:, :, qrows])
        s = _rows_dot(q_i, k_blk, 2, 2) * scale
        s = s + bias_j
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        p = jnp.exp(s - lse_i)
        dp = _rows_dot(do_i, v_blk, 2, 2)
        p_drop = p
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref, bh0, rows, qi, kj,
                                 block_q, block_k, dropout_rate)
            p_drop = p * keep
            dp = dp * keep
        dv = dv + _rows_dot(p_drop.astype(in_dtype), do_i, 1, 1)
        ds = p * (dp - delta_i)
        dk = dk + _rows_dot(ds.astype(in_dtype), q_i, 1, 1)
        db = db + jnp.sum(ds, axis=1, keepdims=True)
        return dk, dv, db

    zero = jnp.zeros((rows, block_k, D), jnp.float32)
    dk, dv, db = jax.lax.fori_loop(
        0, count, body, (zero, zero, jnp.zeros((rows, 1, block_k), jnp.float32)))
    dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)
    db_ref[...] = db


def _attention_pallas_bwd(q, k, v, bias, out, lse, g, lut, counts, qlut, qcounts,
                          *, block_q, block_k, causal, interpret=False,
                          dropout_rate=0.0, seed=None, rows=1):
    """Flash backward: returns (dq, dk, dv, dbias[B,S])."""
    B, H, S, D = q.shape
    BH = B * H
    rs = lambda t: t.reshape(BH, S, D)
    qr, kr, vr, dor, outr = rs(q), rs(k), rs(v), rs(g), rs(out)
    scale = 1.0 / float(np.sqrt(D))
    bias_r = jnp.broadcast_to(bias[:, None, :], (B, H, S)).reshape(BH, 1, S)
    # [BH,1,S] so the (G,1,block) / (G,1,S) blockspecs below are Mosaic-legal
    # (a 2D (G,block) block on a (BH,S) array is rejected unless G is
    # 8-aligned).
    delta = jnp.sum(dor.astype(jnp.float32) * outr.astype(jnp.float32), axis=-1)
    delta_r = delta.reshape(BH, 1, S)
    lse_r = lse.reshape(BH, 1, S)

    seed_arr = jnp.zeros((1,), jnp.int32) if seed is None else jnp.asarray(seed, jnp.int32).reshape(1)

    # dq: grid over q block rows
    blk, seq, stat_blk, stat_seq = _group_specs(rows, S, D, block_q)
    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(BH // rows, S // block_q),
        in_specs=[blk, seq, seq, stat_seq, blk, stat_blk, stat_blk],
        out_specs=blk,
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
    blk, seq, stat_blk, stat_seq = _group_specs(rows, S, D, block_k)
    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(BH // rows, S // block_k),
        in_specs=[seq, blk, blk, stat_blk, seq, stat_seq, stat_seq],
        out_specs=(blk, blk, stat_blk),
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


def _attention_dense(q, k, v, bias, *, causal):
    """The materialised path: the kernels' mathematics at the kernels'
    precision as plain operations, for calls ``materialises_scores`` takes.
    Matmul operands stay in the input dtype and accumulate in float32;
    scale, key bias and causal mask apply to the float32 scores; the softmax
    statistics are float32; the probabilities are rounded to the input
    dtype only as the PV operand; a row with no admissible key gives 0.
    No custom VJP: autodiff differentiates it (``dbias`` included) and a
    remat policy sees its einsums, which carry batch dimensions."""
    _count_trace(DENSE_TRACES)
    S = q.shape[2]
    scale = 1.0 / float(np.sqrt(q.shape[-1]))
    s = jnp.einsum("bhsd,bhtd->bhst", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = s + bias[:, None, None, :].astype(jnp.float32)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m)
    probs = jnp.where(m > -1e29, p / jnp.sum(p, axis=-1, keepdims=True), 0.0)
    out = jnp.einsum("bhst,bhtd->bhsd", probs.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


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


def _rows_for(layout, q, block):
    """``rows_per_step`` of a [B, H, S, D] call as the kernels see it."""
    B, H, S, D = q.shape
    return rows_per_step(B * H, S, D, q.dtype, layout is None, block)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _attention(q, k, v, bias, seed, layout_key, block, causal, force_ref, dropout_rate):
    layout = _LAYOUTS.get(layout_key) if layout_key is not None else None
    if force_ref or not _on_tpu():
        return _attention_reference(
            q, k, v, bias, _expand_layout_mask(layout, q.shape[2], block),
            causal=causal, dropout_rate=dropout_rate, seed=seed,
        )
    lut, counts, _, _ = _luts_for(layout, q.shape[1], q.shape[2], block)
    out, _ = _attention_pallas(
        q, k, v, bias, lut, counts, block_q=block, block_k=block, causal=causal,
        dropout_rate=dropout_rate, seed=seed, rows=_rows_for(layout, q, block),
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
    lut, counts, _, _ = _luts_for(layout, q.shape[1], q.shape[2], block)
    out, lse = _attention_pallas(
        q, k, v, bias, lut, counts, block_q=block, block_k=block, causal=causal,
        dropout_rate=dropout_rate, seed=seed, rows=_rows_for(layout, q, block),
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
        lut, counts, qlut, qcounts = _luts_for(layout, q.shape[1], q.shape[2], block)
        dq, dk, dv, dbias = _attention_pallas_bwd(
            q, k, v, bias, out, lse, g, lut, counts, qlut, qcounts,
            block_q=block, block_k=block, causal=causal,
            dropout_rate=dropout_rate, seed=seed, rows=_rows_for(layout, q, block),
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


def _context_axes(shard_heads):
    """(mesh in context at trace time, its axes that are still automatic,
    the axis a call's batch is split over, the axis its heads are): batch
    over ``data``, heads over ``model`` unless ``shard_heads`` is off, each
    only while automatic. Axes that are already manual (the caller sits in
    its own shard_map) are the caller's, and its shapes are per device."""
    mesh = jax.sharding.get_abstract_mesh()
    auto = [name for name, kind in zip(mesh.axis_names, mesh.axis_types)
            if kind == AxisType.Auto]
    b_ax = DATA_AXIS if DATA_AXIS in auto else None
    h_ax = MODEL_AXIS if MODEL_AXIS in auto and shard_heads else None
    return mesh, auto, b_ax, h_ax


def _rows_on_one_device(B, H, shard_heads):
    """(batch, heads) of a ``[B, H, ...]`` call that one device of the mesh
    in context holds."""
    mesh, _, b_ax, h_ax = _context_axes(shard_heads)
    return (B // (mesh.shape[b_ax] if b_ax else 1),
            H // (mesh.shape[h_ax] if h_ax else 1))


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
    mesh, auto, b_ax, h_ax = _context_axes(shard_heads)
    if all(mesh.shape[name] == 1 for name in auto):
        return attend
    axes = tuple(ax for ax in (b_ax, h_ax) if ax is not None)
    local_b, local_h = _rows_on_one_device(q_shape[0], q_shape[1], shard_heads)
    local_bh = local_b * local_h

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

    if materialises_scores(*_rows_on_one_device(B, H, layout is None), S, S,
                           layout is None, dropout_rate):
        return _attention_dense(q, k, v, bias, causal=causal)

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
