"""Continuous-batching serving engine over the KV-cache decode path.

The decode loop is ONE jitted program for the life of the server: a
masked batched step over the pool's ``MaxSlots`` lanes, each lane
running the SAME per-token ``_step`` the one-shot ``generate()`` path
uses (vmapped with a per-lane position counter). ``MaxSlots`` is static,
the lane-active mask and positions are traced operands — so requests
joining, retiring, or swapping slots NEVER recompile.

Prefill is a SINGLE-PASS batched causal forward (``_forward_chunk`` —
the same core ``generate()``/``beam_search()`` prefill with): the
scheduler groups queued requests that share a prompt bucket and
prefills them as one ``[MaxSlots, Sb]`` call straight into their pool
slots, so a prompt of length S costs one whole-sequence forward instead
of S sequential batch-1 matmuls. The batch dimension is padded to the
static ``MaxSlots`` and per-lane starts/true-lengths are traced, so the
compile count stays bounded by the bucket ladder — never by how many
requests happen to arrive together. Long prompts can additionally be
split into fixed-size chunks (``serving.prefill_chunk_tokens``)
interleaved with decode steps, and previously-served prompt prefixes
can be seeded from the prefix KV cache (``serving.prefix_cache_mb``,
prefix_cache.py) instead of recomputed.

Correctness oracle (tests/unit/test_serving.py): continuous-batched
greedy output is BITWISE equal to per-request ``generate()`` output for
any arrival order. Why it holds:

- prefill pads the prompt up to its bucket but *selects* the logits at
  the true last prompt position; a valid query position only ever
  attends true prompt tokens (causal mask), so the selected logits
  match the unpadded forward;
- pad/stale cache beyond a lane's position is either overwritten before
  it is reachable (decode writes position p before attending to it) or
  hidden by the causal mask, whose -1e30 scores underflow to exactly 0
  probability — extra masked cache length is numerically invisible;
- lanes are vmapped, hence computed independently: a neighbor admitting,
  retiring, or holding garbage cannot perturb another lane's values
  (the batch-independence property test_generation.py already pins);
- a prefix-cache hit seeds bits a previous identical computation
  produced, so seeding and recomputing are the same bits.

Greedy only: serving argmax-decodes (temperature-0), the mode with a
bitwise oracle. Sampling needs per-request RNG streams and is future
work.

Speculative decoding (``serving.speculative_k > 0``): each step drafts
``k`` tokens per lane with a free n-gram drafter over the lane's own
history (no second model), verifies all k+1 positions in ONE batched
causal forward (the same ``_forward_chunk`` core prefill uses), and
emits the longest draft prefix the greedy oracle confirms — plus the
oracle's own next token, so every step yields between 1 and k+1 tokens
per lane. Emitted tokens always COME FROM the oracle, so draft quality
affects only throughput, never output: the emitted sequence is
output-identical to ``speculative_k=0`` (and the k=0 path itself stays
bitwise — it runs the exact same program as before). Rejected drafts
need no KV rollback: their stale cache rows sit inside the next step's
k+1-wide write window and are overwritten before any mask can expose
them, so "rollback" is just advancing the position counter by
accepted+1. ``k`` and ``MaxSlots`` are static; acceptance counts,
drafts, and noise are traced — variable acceptance never recompiles and
steady state still runs under ``transfer_free()``.

KV quantization (``serving.kv_cache_dtype``): "fp32" stores the model's
compute dtype (bitwise-transparent default); "bf16" and "int8" store
the pool narrower and dequantize at use inside the decode/verify reads
(int8 carries per-(slot, head) symmetric scales, fixed at install — see
kv_pool.py). Quantized modes trade a threshold-based parity oracle
(token-match rate, allclose attention outputs) for 2-4x more KV slots
per byte.

Paged KV pool (this file + kv_pool.py): KV lives in fixed-size pages
under one shared token budget; lanes hold page TABLES, not contiguous
stripes. The jitted programs gather a lane's pages back into the exact
contiguous layout (bitwise — gather/scatter move bits, never values)
and scatter back only freshly-written rows, so short chat requests and
16k-token documents share the pool without ``MaxSlots × S_max`` blowup.
Page tables ride the same churn-only upload as the lane masks.

Attention backends (``serving.attention_impl``): per-prompt-bucket
selection of dense | flash | sparse_xla, threaded through prefill,
decode, and the speculative verify. Dense remains the bitwise parity
oracle. Flash is math-equal dense (online softmax) and shares the
dense decode program — its lanes are "full-gather class". sparse_xla
lanes decode through a windowed program that touches only
O(page_tokens) KV per token (window + anchor pages) — the long-context
speedup — and hold the bitwise oracle against sparse ``generate()``.
Requests are grouped at admission by (bucket, backend); the lane
classes run as (at most) one jitted call per armed class per step
sharing the token/position/pool operands, still with ONE host read per
step.

Kernel-tier backends (``pallas_decode`` / ``pallas_sparse``): the same
dispatch seam routed through ``deepspeed_tpu/kernels`` — hand-fused
Pallas attention resolved ONCE at engine construction through the
op_builder-style ``KernelRegistry`` (``serving.attention_kernel`` can
force "pallas"/"xla"; None takes the probe result: on a TPU a failed
probe raises ``KernelProbeError``, off-TPU it degrades to the
composed-XLA twin with an edge-triggered ``jax/kernel_fallback``
instant). ``pallas_decode`` lanes decode through
``_decode_step_kernel_jit``: the fused paged kernel consumes the pool's
STORAGE-dtype pages directly through the lane page tables (int8 scales
fused into the matmul — no dequantized gather copy), so the paged
``pool[tables]`` reassembly disappears into the kernel's DMA schedule.
``pallas_sparse`` lanes run the windowed program with the band math
swapped for the fused band kernel. The resolved (impl, interpret) pair
is threaded into every jitted program as STATIC arguments — selection
is part of the jit cache key, and each backend holds the same
continuous-vs-``generate()`` oracle as its XLA twin (bitwise for
fp32/bf16-compute parity classes, threshold for int8).
"""

import queue as _queue_mod
import threading
import time
from contextlib import nullcontext
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.generation import (
    ATTENTION_IMPLS,
    DEFAULT_PAGE_TOKENS,
    SPARSE_BAND,
    _attend_window_one,
    _cache_dtype,
    _chunk_layer_with,
    _forward_chunk,
    _layer_tree,
    _ln,
    _ngram_draft,
    _round_up,
    _speculative_verify,
    _step,
    _window_base,
    _window_finish,
    _window_qkv,
    _window_slice_one,
    resolve_page_tokens,
)
from deepspeed_tpu.profiling.sentinels import CompileSentinel, transfer_free
from deepspeed_tpu import kernels, telemetry
from deepspeed_tpu.parallel.mesh import mp_world_size
from deepspeed_tpu.parallel.sharding_registry import (
    create_serving_mesh,
    serving_registry,
    serving_sharding,
)
from deepspeed_tpu.inference.quantization import (
    dequantize_kv,
    dequantize_kv_np,
    embed_rows,
    logits_table,
    quantize_kv_np,
    requantize_kv,
    vocab_size,
)
from deepspeed_tpu.inference.serving.config import ServingConfig
from deepspeed_tpu.inference.serving.family import family_for
from deepspeed_tpu.inference.serving.fault_injection import ServingFaultInjector
from deepspeed_tpu.inference.serving.kv_pool import (
    KV_CACHE_DTYPES,
    KVCachePool,
    PoolExhaustedError,
)
from deepspeed_tpu.inference.serving.metrics import ServingMetrics
from deepspeed_tpu.inference.serving.prefix_cache import (
    MemoryPressureGuard,
    PrefixKVCache,
    read_host_rss_mb,
)
from deepspeed_tpu.inference.serving.degrade import DegradeLadder
from deepspeed_tpu.inference.serving.scheduler import (
    ContinuousBatchingScheduler,
    EngineDrainingError,
    QueueFullError,
    RequestTimeoutError,
    bucket_for,
    default_buckets,
)


def _parse_attention_impl(spec, buckets):
    """Validate ``serving.attention_impl``: None / a backend name (every
    bucket) / a ``{bucket: impl}`` dict with an optional ``"default"``
    key. Returns ``(default_impl, {bucket: impl})``."""
    if spec is None:
        return "dense", {}
    if isinstance(spec, str):
        if spec not in ATTENTION_IMPLS:
            raise ValueError(
                f"serving.attention_impl must be one of {ATTENTION_IMPLS}, "
                f"got {spec!r}")
        return spec, {}
    if not isinstance(spec, dict):
        raise ValueError(
            f"serving.attention_impl must be one of {ATTENTION_IMPLS} or a "
            f"{{bucket: impl}} dict, got {spec!r}")
    default = "dense"
    table = {}
    for key, impl in spec.items():
        if impl not in ATTENTION_IMPLS:
            raise ValueError(
                f"serving.attention_impl[{key!r}] must be one of "
                f"{ATTENTION_IMPLS}, got {impl!r}")
        if key == "default":
            default = impl
            continue
        if isinstance(key, bool) or not isinstance(key, int):
            raise ValueError(
                f"serving.attention_impl keys must be prompt-bucket ints "
                f"or 'default', got {key!r}")
        if key not in tuple(buckets):
            raise ValueError(
                f"serving.attention_impl bucket {key} is not in the prompt "
                f"bucket ladder {tuple(buckets)}")
        table[int(key)] = impl
    return default, table


# -- paged-pool index plumbing ------------------------------------------
# The pool stores KV as fixed-size pages ([L, n_pages, nh, pt, hd]) with
# per-lane page tables ([MaxSlots, mp], physical page 0 reserved as the
# null/garbage sink — see kv_pool.py). The decode programs below never
# see a contiguous [S_max] lane; they gather the pages a lane actually
# owns and scatter back only the rows they wrote.

def _gather_lanes(pool_side, page_tables):
    """Reassemble every lane's contiguous [nh, S_max, hd] KV stripe from
    its pages: pool [L, P, nh, pt, hd] + tables [B, mp] ->
    [L, B, nh, mp*pt, hd]. Unmapped logical pages read the null page;
    those positions are either beyond the lane's position counter
    (masked to exact-zero probability by the causal mask) or belong to
    inactive lanes (outputs discarded) — the same invisible-garbage
    argument the contiguous layout relied on."""
    L, _, nh, pt, hd = pool_side.shape
    B, mp = page_tables.shape
    g = pool_side[:, page_tables]                    # [L, B, mp, nh, pt, hd]
    return jnp.moveaxis(g, 2, 3).reshape(L, B, nh, mp * pt, hd)


def _row_pages(page_tables, tok, active, page_tokens):
    """Physical destination page for per-lane token indices ``tok``
    ([B] or [B, n]): the lane's mapped page, or the null page 0 for
    inactive lanes and out-of-range indices — bad writes are DROPPED
    into the sink, never clipped onto a live row."""
    B, mp = page_tables.shape
    tok2 = tok if tok.ndim == 2 else tok[:, None]
    logical = jnp.clip(tok2 // page_tokens, 0, mp - 1)
    phys = jnp.take_along_axis(page_tables, logical, axis=1)
    ok = active[:, None] & (tok2 >= 0) & (tok2 < mp * page_tokens)
    phys = jnp.where(ok, phys, 0)
    return phys if tok.ndim == 2 else phys[:, 0]


def _lane_rows(lanes, tok):
    """Extract each lane's row(s) at token indices ``tok`` from gathered
    [L, B, nh, S, hd] stripes -> [L, B, nh, hd] (or [L, B, n, nh, hd]
    for ``tok`` [B, n]): the freshly-written KV the pool needs back.
    Reads clip (the scatter drops the same indices, so a clipped read
    is never stored anywhere that matters)."""
    S = lanes.shape[3]
    tok2 = tok if tok.ndim == 2 else tok[:, None]
    idx = jnp.clip(tok2, 0, S - 1)
    out = jnp.take_along_axis(
        lanes, idx[None, :, None, :, None], axis=3)  # [L, B, nh, n, hd]
    out = jnp.moveaxis(out, 3, 2)                    # [L, B, n, nh, hd]
    return out[:, :, 0] if tok.ndim == 1 else out


def _scatter_rows(pool_side, page_tables, rows, tok, active, page_tokens):
    """Write per-lane rows back into their pages. ``rows`` is
    [L, B, nh, hd] (``tok`` [B]) or [L, B, n, nh, hd] (``tok`` [B, n]);
    writes from inactive lanes or beyond a lane's mapped pages land on
    the null page. Advanced indices at non-adjacent axes put the batch
    dims FIRST, hence the moveaxis."""
    dp = _row_pages(page_tables, tok, active, page_tokens)
    off = tok % page_tokens
    vals = jnp.moveaxis(rows, 0, 1 if tok.ndim == 1 else 2)
    return pool_side.at[:, dp, :, off].set(vals.astype(pool_side.dtype))


@partial(jax.jit, static_argnames=("n_heads",), donate_argnums=(1, 2))
def _prefill_batch_jit(params, init_k, init_v, padded_ids, starts, true_lens,
                       *, n_heads):
    """Single-pass batched prefill: ``padded_ids`` [B, Sb] (each lane's
    to-be-computed tokens, right-padded to the bucket) forwarded in ONE
    causal call into ``init_k``/``init_v`` ([L, B, nh, S_max, hd] —
    zeros, or prefix-cache KV for lanes resuming at ``starts[i] > 0``).
    Returns (k, v, first greedy token per lane).

    ``starts`` and ``true_lens`` are traced [B] vectors, so ONE compiled
    program per (B, Sb, S_max) serves every group composition: plain
    prompts, prefix-cache hits at any offset, and (at B=1, Sb=chunk)
    every chunk of a chunked prefill. The logits are *selected* at each
    lane's true last prompt position, which makes both pad tokens and
    dummy lanes invisible to the emitted token."""
    B, Sb = padded_ids.shape
    tr = params["params"]["transformer"]
    h, (k, v) = _forward_chunk(params, n_heads, (init_k, init_v),
                               padded_ids, starts)
    idx = jnp.clip(true_lens - 1 - starts, 0, Sb - 1)
    h_sel = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]
    h_sel = _ln(h_sel, tr["ln_f"])
    logits = h_sel @ logits_table(tr["wte"], h_sel.dtype).T
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return k, v, first


def _prefill_tail(params, h, starts, true_lens):
    """Shared logits tail of every prefill program: select each lane's
    true last prompt position, final LN, greedy first token."""
    Sb = h.shape[1]
    tr = params["params"]["transformer"]
    idx = jnp.clip(true_lens - 1 - starts, 0, Sb - 1)
    h_sel = jnp.take_along_axis(h, idx[:, None, None], axis=1)[:, 0]
    h_sel = _ln(h_sel, tr["ln_f"])
    logits = h_sel @ logits_table(tr["wte"], h_sel.dtype).T
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("n_heads", "page_tokens"),
         donate_argnums=(1, 2))
def _prefill_batch_flash_jit(params, init_k, init_v, padded_ids, starts,
                             true_lens, *, n_heads, page_tokens):
    """``_prefill_batch_jit`` with the flash (online-softmax) backend:
    same contract, never materializes the [Sb, S_max] score matrix.
    Math-equal to dense (allclose, not bitwise); the cache length is a
    page multiple by construction (``resolve_page_tokens``)."""
    h, (k, v) = _forward_chunk(params, n_heads, (init_k, init_v),
                               padded_ids, starts, attn_impl="flash",
                               page_tokens=page_tokens)
    return k, v, _prefill_tail(params, h, starts, true_lens)


@partial(jax.jit, static_argnames=("n_heads", "page_tokens"),
         donate_argnums=(1, 2))
def _prefill_batch_window_jit(params, init_k, init_v, padded_ids, starts,
                              true_lens, *, n_heads, page_tokens):
    """``_prefill_batch_jit`` with the banded block-sparse backend:
    every query attends only its canonical window + anchor page —
    O(Sb*pt) attention instead of O(Sb*S_max), which is what makes 16k+
    prompts admissible at interactive TTFT. Callers pad ``padded_ids``
    to a page-multiple width; pad queries write garbage KV past the true
    length, which decode overwrites in order before it is ever
    attendable (the same write-before-attend argument dense prefill
    uses for its pad region)."""
    h, (k, v) = _forward_chunk(params, n_heads, (init_k, init_v),
                               padded_ids, starts, attn_impl="sparse_xla",
                               page_tokens=page_tokens)
    return k, v, _prefill_tail(params, h, starts, true_lens)


@partial(jax.jit, static_argnames=("n_heads", "page_tokens", "kernel_impl",
                                   "kernel_interpret"),
         donate_argnums=(1, 2))
def _prefill_batch_kernel_jit(params, init_k, init_v, padded_ids, starts,
                              true_lens, *, n_heads, page_tokens,
                              kernel_impl, kernel_interpret):
    """``_prefill_batch_jit`` through the fused decode-attention kernel
    (``pallas_decode`` lanes): the chunk attends via ``chunk_attend`` —
    the contiguous-cache adapter over the SAME paged kernel the decode
    step runs — so prefill and decode share one math path and the
    per-backend oracle holds bitwise. ``kernel_impl``/``kernel_interpret``
    are the registry's resolved statics (part of the cache key: a
    selection change can never serve a stale program)."""
    h, (k, v) = _forward_chunk(params, n_heads, (init_k, init_v),
                               padded_ids, starts, attn_impl="pallas_decode",
                               page_tokens=page_tokens,
                               kernel_impl=kernel_impl,
                               kernel_interpret=kernel_interpret)
    return k, v, _prefill_tail(params, h, starts, true_lens)


@partial(jax.jit, static_argnames=("n_heads", "page_tokens", "kernel_impl",
                                   "kernel_interpret"),
         donate_argnums=(1, 2))
def _prefill_batch_kernel_window_jit(params, init_k, init_v, padded_ids,
                                     starts, true_lens, *, n_heads,
                                     page_tokens, kernel_impl,
                                     kernel_interpret):
    """``_prefill_batch_window_jit`` with the band math fused into the
    Pallas band kernel (``pallas_sparse`` lanes): same canonical
    window + anchor key set, same page-multiple chunk-width contract."""
    h, (k, v) = _forward_chunk(params, n_heads, (init_k, init_v),
                               padded_ids, starts, attn_impl="pallas_sparse",
                               page_tokens=page_tokens,
                               kernel_impl=kernel_impl,
                               kernel_interpret=kernel_interpret)
    return k, v, _prefill_tail(params, h, starts, true_lens)


def _sample(logits, tokens, positions, active):
    """Shared tail of every decode program: the greedy token of each
    active lane and its advanced position (inactive lanes keep theirs)."""
    with jax.named_scope("sample"):
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        tokens = jnp.where(active, nxt, tokens)
        positions = jnp.where(active, positions + 1, positions)
    return tokens, positions


@partial(jax.jit, static_argnames=("n_heads",), donate_argnums=(1, 2, 4, 5))
def _decode_step_jit(params, pool_k, pool_v, page_tables, tokens, positions,
                     active, *, n_heads):
    """One masked batched decode step over every pool lane.

    Each lane's pages are gathered into the EXACT contiguous stripe the
    old layout stored (unmapped pages read masked-invisible garbage),
    its last token runs through the one-shot path's ``_step`` (vmapped
    as a B=1 lane), and only the freshly-written row is scattered back
    by page index — untouched positions keep their bits, so the step is
    bitwise the contiguous step. Inactive lanes compute garbage routed
    to the null page and keep their token via the ``active`` mask; pool
    buffers, tokens and positions are donated, page tables and the mask
    are NOT (they live on device across steps), so steady-state decode
    still needs no per-step host->device upload at all."""
    pt = pool_k.shape[3]
    with jax.named_scope("kv_gather"):
        lanes_k = _gather_lanes(pool_k, page_tables)
        lanes_v = _gather_lanes(pool_v, page_tables)

    def lane(ck, cv, tok, pos):
        logits, (ck2, cv2) = _step(params, n_heads, (ck[:, None], cv[:, None]),
                                   tok[None], pos)
        return logits[0], ck2[:, 0], cv2[:, 0]

    with jax.named_scope("attend"):
        logits, lanes_k, lanes_v = jax.vmap(
            lane, in_axes=(1, 1, 0, 0), out_axes=(0, 1, 1))(
            lanes_k, lanes_v, tokens, positions)
    with jax.named_scope("kv_scatter"):
        pool_k = _scatter_rows(pool_k, page_tables,
                               _lane_rows(lanes_k, positions),
                               positions, active, pt)
        pool_v = _scatter_rows(pool_v, page_tables,
                               _lane_rows(lanes_v, positions),
                               positions, active, pt)
    return _sample(logits, tokens, positions, active) + (pool_k, pool_v)


@partial(jax.jit, static_argnames=("n_heads", "qmode"),
         donate_argnums=(1, 2, 6, 7))
def _decode_step_quant_jit(params, pool_k, pool_v, k_scale, v_scale,
                           page_tables, tokens, positions, active, *,
                           n_heads, qmode):
    """``_decode_step_jit`` over a QUANTIZED paged pool: each lane's
    gathered stripe dequantizes at use (int8 * per-head scale, or a
    bf16 cast), runs the same vmapped ``_step``, and the written row is
    re-stored against its FIXED install-time scales — idempotent on
    untouched positions (see ``requantize_kv``), so the step still only
    logically appends one token per lane. Scales are NOT donated: they
    are returned unchanged and the host keeps its reference. ``qmode``
    is static — one program per storage mode, no traced branching (for
    "bf16" the scale operands are None)."""
    dtype = _cache_dtype(params)
    pt = pool_k.shape[3]
    with jax.named_scope("kv_gather"):
        lanes_k = _gather_lanes(pool_k, page_tables)
        lanes_v = _gather_lanes(pool_v, page_tables)

    if qmode == "int8":
        def lane(ck, cv, sk, sv, tok, pos):
            logits, (ck2, cv2) = _step(
                params, n_heads,
                (dequantize_kv(ck, sk, dtype)[:, None],
                 dequantize_kv(cv, sv, dtype)[:, None]),
                tok[None], pos)
            return (logits[0], requantize_kv(ck2[:, 0], sk),
                    requantize_kv(cv2[:, 0], sv))

        with jax.named_scope("attend"):
            logits, lanes_k, lanes_v = jax.vmap(
                lane, in_axes=(1, 1, 1, 1, 0, 0), out_axes=(0, 1, 1))(
                lanes_k, lanes_v, k_scale, v_scale, tokens, positions)
    else:
        def lane(ck, cv, tok, pos):
            logits, (ck2, cv2) = _step(
                params, n_heads,
                (ck.astype(dtype)[:, None], cv.astype(dtype)[:, None]),
                tok[None], pos)
            return (logits[0], ck2[:, 0].astype(jnp.bfloat16),
                    cv2[:, 0].astype(jnp.bfloat16))

        with jax.named_scope("attend"):
            logits, lanes_k, lanes_v = jax.vmap(
                lane, in_axes=(1, 1, 0, 0), out_axes=(0, 1, 1))(
                lanes_k, lanes_v, tokens, positions)
    with jax.named_scope("kv_scatter"):
        pool_k = _scatter_rows(pool_k, page_tables,
                               _lane_rows(lanes_k, positions),
                               positions, active, pt)
        pool_v = _scatter_rows(pool_v, page_tables,
                               _lane_rows(lanes_v, positions),
                               positions, active, pt)
    return _sample(logits, tokens, positions, active) + (pool_k, pool_v)


@partial(jax.jit, static_argnames=("n_heads", "page_tokens", "qmode",
                                   "kernel_impl", "kernel_interpret"),
         donate_argnums=(1, 2, 6, 7))
def _decode_step_window_jit(params, pool_k, pool_v, k_scale, v_scale,
                            page_tables, tokens, positions, active, *,
                            n_heads, page_tokens, qmode, kernel_impl=None,
                            kernel_interpret=False):
    """Banded block-sparse decode over the paged pool. Unlike the dense
    step, it never reassembles whole lanes: each lane touches only its
    canonical window pages (SPARSE_BAND+1 pages ending at the query)
    plus the anchor page — O(page_tokens) KV traffic per token per lane
    instead of O(S_max), which is where the 16k-bucket speedup lives.
    Per layer: project qkv, store the written row into its page, gather
    the window/anchor pages, attend with the SAME ``_attend_window_one``
    the one-shot sparse ``generate()`` path uses (write-then-attend,
    matching ``_decode_one_window``) — the per-lane key set is identical
    by construction, so fp32 storage keeps the bitwise oracle. Window
    lanes use their own ``active`` mask; the pool and the token/position
    vectors are threaded through both class programs each step.

    ``kernel_impl`` (static, ``pallas_sparse`` lanes) swaps the band
    MATH for the fused Pallas band kernel (``kernels.band_attend``) —
    the window/anchor gather stays on the XLA side either way, so the
    per-lane key set (hence the oracle) is backend-identical."""
    dtype = _cache_dtype(params)
    pt = page_tokens
    B, mp = page_tables.shape
    tr = params["params"]["transformer"]
    layer_p = _layer_tree(params)

    h = embed_rows(tr["wte"], tokens) + tr["wpe"]["embedding"][positions]

    pp = jnp.clip(positions // pt, 0, mp - 1)          # each query's page
    lo = jnp.maximum(pp - SPARSE_BAND, 0)              # window's first page
    base = lo * pt
    win_logical = jnp.clip(
        lo[:, None] + jnp.arange(SPARSE_BAND + 1)[None, :], 0, mp - 1)
    win_phys = jnp.take_along_axis(page_tables, win_logical, axis=1)
    sink_phys = page_tables[:, 0]
    dp = _row_pages(page_tables, positions, active, pt)
    off = positions % pt

    def layer_body(h, inputs):
        lp, pk_l, pv_l, sk_l, sv_l = inputs
        q, kk, vv = _window_qkv(lp, h, n_heads)        # each [B, nh, hd]
        if qmode == "int8":
            krow = requantize_kv(kk[:, :, None, :], sk_l)[:, :, 0]
            vrow = requantize_kv(vv[:, :, None, :], sv_l)[:, :, 0]
        elif qmode == "bf16":
            krow, vrow = kk.astype(jnp.bfloat16), vv.astype(jnp.bfloat16)
        else:
            krow, vrow = kk, vv
        with jax.named_scope("kv_scatter"):
            pk_l = pk_l.at[dp, :, off].set(krow)
            pv_l = pv_l.at[dp, :, off].set(vrow)

        def stripe(buf, scale):
            def dq(x):
                if qmode == "int8":
                    return dequantize_kv(x, scale, dtype)
                if qmode == "bf16":
                    return x.astype(dtype)
                return x
            win = jnp.moveaxis(buf[win_phys], 1, 2)    # [B, nh, bw, pt, hd]
            win = win.reshape(B, n_heads, (SPARSE_BAND + 1) * pt, -1)
            return dq(win), dq(buf[sink_phys])

        with jax.named_scope("kv_gather"):
            k_win, k_sink = stripe(pk_l, sk_l)
            v_win, v_sink = stripe(pv_l, sv_l)
        with jax.named_scope("attend"):
            if kernel_impl is not None:
                ctx = kernels.band_attend(
                    q, k_win, v_win, k_sink, v_sink, positions, base,
                    dtype=dtype, impl=kernel_impl,
                    interpret=kernel_interpret)
            else:
                ctx = jax.vmap(_attend_window_one,
                               in_axes=(0, 0, 0, 0, 0, 0, 0, None))(
                    q, k_win, v_win, k_sink, v_sink, positions, base, dtype)
        h = _window_finish(lp, h, ctx)
        return h, (pk_l, pv_l)

    h, (pool_k, pool_v) = jax.lax.scan(
        layer_body, h, (layer_p, pool_k, pool_v, k_scale, v_scale))
    h = _ln(h, tr["ln_f"])
    logits = h @ logits_table(tr["wte"], h.dtype).T
    return _sample(logits, tokens, positions, active) + (pool_k, pool_v)


@partial(jax.jit, static_argnames=("n_heads", "page_tokens", "qmode",
                                   "kernel_impl", "kernel_interpret"),
         donate_argnums=(1, 2, 6, 7))
def _decode_step_kernel_jit(params, pool_k, pool_v, k_scale, v_scale,
                            page_tables, tokens, positions, active, *,
                            n_heads, page_tokens, qmode, kernel_impl,
                            kernel_interpret):
    """Fused-kernel decode for ``pallas_decode`` lanes. Unlike the dense
    step it never reassembles contiguous stripes on the XLA side: each
    layer writes the lane's fresh KV row into its page, then hands the
    POOL ITSELF (storage dtype — int8 pages included) plus the lane page
    tables to ``kernels.decode_attend``, whose scalar-prefetch index map
    performs the paged gather inside the kernel's DMA schedule. int8
    pools pass per-page scales (the lane's fixed install scale scattered
    to its pages) so dequantization fuses into the QK/PV matmuls —
    no dequantized pool copy ever exists. The online-softmax recurrence
    is bitwise invariant to trailing fully-masked pages, so fp32 pools
    keep the bitwise continuous-vs-``generate()`` oracle even though
    ``generate()`` runs a shorter identity-table cache."""
    dtype = _cache_dtype(params)
    pt = page_tokens
    B, mp = page_tables.shape
    P = pool_k.shape[1]
    tr = params["params"]["transformer"]
    layer_p = _layer_tree(params)

    h = embed_rows(tr["wte"], tokens) + tr["wpe"]["embedding"][positions]
    dp = _row_pages(page_tables, positions, active, pt)
    off = positions % pt
    qpos = positions[:, None]

    def page_scales(sl):
        # per-(slot, head) install scales -> per-physical-page scales the
        # kernel gathers alongside each page block. Lanes never share
        # data pages; the null page takes whatever lane scatters last,
        # which only ever scales masked (exact-zero-probability) keys.
        s = jnp.broadcast_to(sl.reshape(B, 1, n_heads), (B, mp, n_heads))
        return jnp.zeros((P, n_heads), jnp.float32).at[page_tables].set(s)

    def layer_body(h, inputs):
        lp, pk_l, pv_l, sk_l, sv_l = inputs
        q, kk, vv = _window_qkv(lp, h, n_heads)        # each [B, nh, hd]
        if qmode == "int8":
            krow = requantize_kv(kk[:, :, None, :], sk_l)[:, :, 0]
            vrow = requantize_kv(vv[:, :, None, :], sv_l)[:, :, 0]
            ksp, vsp = page_scales(sk_l), page_scales(sv_l)
        elif qmode == "bf16":
            krow, vrow = kk.astype(jnp.bfloat16), vv.astype(jnp.bfloat16)
            ksp = vsp = None
        else:
            krow, vrow = kk, vv
            ksp = vsp = None
        with jax.named_scope("kv_scatter"):
            pk_l = pk_l.at[dp, :, off].set(krow)
            pv_l = pv_l.at[dp, :, off].set(vrow)
        # the paged gather happens inside the kernel's DMA schedule
        with jax.named_scope("attend"):
            ctx = kernels.decode_attend(
                q[:, None], pk_l, pv_l, page_tables, qpos, page_tokens=pt,
                dtype=dtype, impl=kernel_impl, interpret=kernel_interpret,
                k_scale=ksp, v_scale=vsp)[:, 0]
        h = _window_finish(lp, h, ctx)
        return h, (pk_l, pv_l)

    h, (pool_k, pool_v) = jax.lax.scan(
        layer_body, h, (layer_p, pool_k, pool_v, k_scale, v_scale))
    h = _ln(h, tr["ln_f"])
    logits = h @ logits_table(tr["wte"], h.dtype).T
    return _sample(logits, tokens, positions, active) + (pool_k, pool_v)


def _attend_window_chunk(q, cache_k, cache_v, qpos, pt, dtype):
    """Per-query canonical window attention for a SMALL chunk of queries
    (the k+1-wide speculative verify): no page-multiple chunk-width
    requirement — each query dynamic-slices its own window from the full
    lane stripe and attends with the same ``_attend_window_one`` every
    other sparse path uses, so the per-query key set (and hence the
    fp32 result, bitwise) matches the blocked prefill formulation."""
    def one(qi, p, ck, cv):
        b = _window_base(p, pt)
        k_win, v_win, k_sink, v_sink = _window_slice_one(ck, cv, b, pt)
        return _attend_window_one(qi, k_win, v_win, k_sink, v_sink, p, b,
                                  dtype)

    return jax.vmap(lambda qrow, prow, ck, cv: jax.vmap(
        lambda qi, p: one(qi, p, ck, cv))(qrow, prow))(
        q, qpos, cache_k, cache_v)


def _forward_chunk_window(params, n_heads, caches, ids, starts, pt):
    """The sparse-backend twin of ``_forward_chunk`` for the speculative
    verify: same embed/scan shell and cache writes, attention via
    ``_attend_window_chunk`` (verify chunks are k+1 wide — not a page
    multiple, so the blocked ``_chunk_attend_window`` cannot be used)."""
    tr = params["params"]["transformer"]
    layer_p = _layer_tree(params)
    C = ids.shape[1]
    pos = starts[:, None] + jnp.arange(C)[None, :]
    h = embed_rows(tr["wte"], ids) + tr["wpe"]["embedding"][pos]

    def layer_body(h, inputs):
        lp, ck_l, cv_l = inputs
        h, ck_l, cv_l = _chunk_layer_with(
            lp, h, ck_l, cv_l, starts, n_heads,
            lambda q, ck, cv, qpos: _attend_window_chunk(q, ck, cv, qpos,
                                                         pt, h.dtype))
        return h, (ck_l, cv_l)

    h, caches = jax.lax.scan(layer_body, h, (layer_p,) + tuple(caches))
    return h, caches


def _speculative_verify_window(params, n_heads, caches, tokens, drafts,
                               positions, pt):
    """``_speculative_verify`` with windowed attention: identical
    draft/oracle/acceptance logic, the one-forward verify runs the
    sparse key set. See ``_speculative_verify`` for the rollback-free
    stale-KV argument (it is backend-independent: the stale range sits
    inside the next step's write window either way)."""
    tr = params["params"]["transformer"]
    k = drafts.shape[1]
    ids = jnp.concatenate([tokens[:, None], drafts], axis=1)     # [B, k+1]
    h, caches = _forward_chunk_window(params, n_heads, caches, ids,
                                      positions, pt)
    h = _ln(h, tr["ln_f"])
    logits = h @ logits_table(tr["wte"], h.dtype).T
    oracle = jnp.argmax(logits, axis=-1).astype(jnp.int32)       # [B, k+1]
    ok = (drafts == oracle[:, :k]).astype(jnp.int32)
    accepted = jnp.sum(jnp.cumprod(ok, axis=1), axis=1)          # [B]
    return oracle, accepted, caches


def _spec_core(params, n_heads, caches, history, tokens, positions, active,
               draft_noise, k, window_pt=None, kernel_backend=None,
               kernel_impl=None, kernel_interpret=False):
    """Shared body of the speculative step programs: draft -> (optional
    noise) -> one-forward verify -> advance. Operates on COMPUTE-dtype
    caches; the quantized wrapper handles storage conversion.
    ``kernel_backend`` (static) routes the k+1-wide verify forward
    through the kernel tier ("pallas_decode"/"pallas_sparse" with
    ``window_pt`` as its page size) instead of the dense/window XLA
    verifies."""
    S_max = history.shape[1]
    V = vocab_size(params["params"]["transformer"]["wte"])
    drafts = jax.vmap(partial(_ngram_draft, k=k))(history, positions)
    # fault-injection hook: draft_noise is normally all-zeros (the mod-V
    # add is then the identity, bitwise) — the corrupt_draft arm swaps in
    # nonzero values without changing shapes, so scrambling never
    # recompiles
    drafts = (drafts + draft_noise) % V
    if kernel_backend is not None:
        oracle, accepted, caches = _speculative_verify(
            params, n_heads, caches, tokens, drafts, positions,
            attn_impl=kernel_backend, page_tokens=window_pt,
            kernel_impl=kernel_impl, kernel_interpret=kernel_interpret)
    elif window_pt is None:
        oracle, accepted, caches = _speculative_verify(
            params, n_heads, caches, tokens, drafts, positions)
    else:
        oracle, accepted, caches = _speculative_verify_window(
            params, n_heads, caches, tokens, drafts, positions, window_pt)
    # append all k+1 oracle tokens to the history at the lane's write
    # window; positions past the accepted point hold speculative
    # continuations the next step overwrites — the drafter's bigram scan
    # only trusts positions below its pending one, and emitted output
    # never comes from history, so they cannot corrupt anything
    idx = jnp.where(active[:, None],
                    positions[:, None] + 1 + jnp.arange(k + 1)[None, :],
                    S_max)                                   # OOB -> dropped
    history = jax.vmap(
        lambda h, i, t: h.at[i].set(t, mode="drop"))(history, idx, oracle)
    last = jnp.take_along_axis(oracle, accepted[:, None], axis=1)[:, 0]
    tokens = jnp.where(active, last, tokens)
    positions = jnp.where(active,
                          jnp.minimum(positions + accepted + 1, S_max - 1),
                          positions)
    return tokens, positions, caches, history, oracle, accepted


@partial(jax.jit, static_argnames=("n_heads", "k"),
         donate_argnums=(1, 2, 4, 5, 6))
def _spec_step_jit(params, pool_k, pool_v, page_tables, history, tokens,
                   positions, active, draft_noise, *, n_heads, k):
    """One SPECULATIVE masked batched decode step over every pool lane.

    Per lane: gather the lane's pages into its contiguous stripe, draft
    ``k`` tokens (n-gram lookup over ``history``), feed pending-token +
    drafts through ONE k+1-wide causal forward against the stripes
    (``_forward_chunk`` — the gathered pool IS the chunk cache), accept
    the longest draft prefix the greedy oracle confirms, advance
    position by accepted+1, and scatter the k+1 written rows back by
    page index (overflow past a lane's pages drops to the null sink —
    only reachable after the request's retirement point, see
    ``_alloc_tokens``). ``k`` and the lane count are static; drafts,
    acceptance and noise are traced, so acceptance variation and slot
    churn reuse one compiled program. Returns the full oracle [B, k+1]
    and per-lane accepted counts for the host emit loop."""
    pt = pool_k.shape[3]
    lanes = (_gather_lanes(pool_k, page_tables),
             _gather_lanes(pool_v, page_tables))
    written = positions[:, None] + jnp.arange(k + 1)[None, :]
    tokens, positions, (lk, lv), history, oracle, accepted = \
        _spec_core(params, n_heads, lanes, history, tokens,
                   positions, active, draft_noise, k)
    pool_k = _scatter_rows(pool_k, page_tables, _lane_rows(lk, written),
                           written, active, pt)
    pool_v = _scatter_rows(pool_v, page_tables, _lane_rows(lv, written),
                           written, active, pt)
    return tokens, positions, pool_k, pool_v, history, oracle, accepted


@partial(jax.jit, static_argnames=("n_heads", "k", "qmode"),
         donate_argnums=(1, 2, 6, 7, 8))
def _spec_step_quant_jit(params, pool_k, pool_v, k_scale, v_scale,
                         page_tables, history, tokens, positions, active,
                         draft_noise, *, n_heads, k, qmode):
    """Speculative step over a quantized paged pool: dequantize the
    gathered stripes at use, run the same draft/verify core in the
    compute dtype, then requantize against the FIXED per-(slot, head)
    install scales (or a bf16 cast) and scatter back the k+1 written
    rows. Untouched positions round-trip bitwise (idempotent requant),
    so only the freshly-written rows actually change."""
    dtype = _cache_dtype(params)
    pt = pool_k.shape[3]
    lk = _gather_lanes(pool_k, page_tables)
    lv = _gather_lanes(pool_v, page_tables)
    if qmode == "int8":
        kf = dequantize_kv(lk, k_scale, dtype)
        vf = dequantize_kv(lv, v_scale, dtype)
    else:
        kf, vf = lk.astype(dtype), lv.astype(dtype)
    written = positions[:, None] + jnp.arange(k + 1)[None, :]
    tokens, positions, (kf, vf), history, oracle, accepted = _spec_core(
        params, n_heads, (kf, vf), history, tokens, positions, active,
        draft_noise, k)
    if qmode == "int8":
        rows_k = _lane_rows(requantize_kv(kf, k_scale), written)
        rows_v = _lane_rows(requantize_kv(vf, v_scale), written)
    else:
        rows_k = _lane_rows(kf, written).astype(jnp.bfloat16)
        rows_v = _lane_rows(vf, written).astype(jnp.bfloat16)
    pool_k = _scatter_rows(pool_k, page_tables, rows_k, written, active, pt)
    pool_v = _scatter_rows(pool_v, page_tables, rows_v, written, active, pt)
    return tokens, positions, pool_k, pool_v, history, oracle, accepted


@partial(jax.jit, static_argnames=("n_heads", "k", "page_tokens", "qmode"),
         donate_argnums=(1, 2, 6, 7, 8))
def _spec_step_window_jit(params, pool_k, pool_v, k_scale, v_scale,
                          page_tables, history, tokens, positions, active,
                          draft_noise, *, n_heads, k, page_tokens, qmode):
    """Speculative step for sparse-backend lanes: same draft/accept core,
    with the k+1-wide verify forward attending the windowed key set
    (``_speculative_verify_window``). The verify gathers full lane
    stripes like the dense spec step — speculation is a latency
    trade-off knob, not the steady-state path the windowed decode
    optimizes — and scatters the k+1 written rows back by page index.
    ``qmode`` is static; scale operands are None unless int8."""
    dtype = _cache_dtype(params)
    pt = pool_k.shape[3]
    lk = _gather_lanes(pool_k, page_tables)
    lv = _gather_lanes(pool_v, page_tables)
    if qmode == "int8":
        kf = dequantize_kv(lk, k_scale, dtype)
        vf = dequantize_kv(lv, v_scale, dtype)
    elif qmode == "bf16":
        kf, vf = lk.astype(dtype), lv.astype(dtype)
    else:
        kf, vf = lk, lv
    written = positions[:, None] + jnp.arange(k + 1)[None, :]
    tokens, positions, (kf, vf), history, oracle, accepted = _spec_core(
        params, n_heads, (kf, vf), history, tokens, positions, active,
        draft_noise, k, window_pt=page_tokens)
    if qmode == "int8":
        rows_k = _lane_rows(requantize_kv(kf, k_scale), written)
        rows_v = _lane_rows(requantize_kv(vf, v_scale), written)
    elif qmode == "bf16":
        rows_k = _lane_rows(kf, written).astype(jnp.bfloat16)
        rows_v = _lane_rows(vf, written).astype(jnp.bfloat16)
    else:
        rows_k = _lane_rows(kf, written)
        rows_v = _lane_rows(vf, written)
    pool_k = _scatter_rows(pool_k, page_tables, rows_k, written, active, pt)
    pool_v = _scatter_rows(pool_v, page_tables, rows_v, written, active, pt)
    return tokens, positions, pool_k, pool_v, history, oracle, accepted


@partial(jax.jit, static_argnames=("n_heads", "k", "page_tokens", "qmode",
                                   "attn_backend", "kernel_impl",
                                   "kernel_interpret"),
         donate_argnums=(1, 2, 6, 7, 8))
def _spec_step_kernel_jit(params, pool_k, pool_v, k_scale, v_scale,
                          page_tables, history, tokens, positions, active,
                          draft_noise, *, n_heads, k, page_tokens, qmode,
                          attn_backend, kernel_impl, kernel_interpret):
    """Speculative step for kernel-tier lanes: same draft/accept core as
    ``_spec_step_window_jit``, with the k+1-wide verify forward routed
    through the resolved kernel backend (``attn_backend`` is the static
    ``pallas_decode``/``pallas_sparse`` name; the verify gathers full
    lane stripes like every spec step — speculation trades gather
    traffic for acceptance throughput) and the k+1 written rows
    scattered back by page index. ``qmode`` is static; scale operands
    are None unless int8."""
    dtype = _cache_dtype(params)
    pt = pool_k.shape[3]
    lk = _gather_lanes(pool_k, page_tables)
    lv = _gather_lanes(pool_v, page_tables)
    if qmode == "int8":
        kf = dequantize_kv(lk, k_scale, dtype)
        vf = dequantize_kv(lv, v_scale, dtype)
    elif qmode == "bf16":
        kf, vf = lk.astype(dtype), lv.astype(dtype)
    else:
        kf, vf = lk, lv
    written = positions[:, None] + jnp.arange(k + 1)[None, :]
    tokens, positions, (kf, vf), history, oracle, accepted = _spec_core(
        params, n_heads, (kf, vf), history, tokens, positions, active,
        draft_noise, k, window_pt=page_tokens, kernel_backend=attn_backend,
        kernel_impl=kernel_impl, kernel_interpret=kernel_interpret)
    if qmode == "int8":
        rows_k = _lane_rows(requantize_kv(kf, k_scale), written)
        rows_v = _lane_rows(requantize_kv(vf, v_scale), written)
    elif qmode == "bf16":
        rows_k = _lane_rows(kf, written).astype(jnp.bfloat16)
        rows_v = _lane_rows(vf, written).astype(jnp.bfloat16)
    else:
        rows_k = _lane_rows(kf, written)
        rows_v = _lane_rows(vf, written)
    pool_k = _scatter_rows(pool_k, page_tables, rows_k, written, active, pt)
    pool_v = _scatter_rows(pool_v, page_tables, rows_v, written, active, pt)
    return tokens, positions, pool_k, pool_v, history, oracle, accepted


class _ChunkedPrefill:
    """In-flight chunked prefill: the request, its private cache pair
    (carried across engine steps between chunk calls), how far it has
    prefilled, and the pool slot reserved for it at start."""

    __slots__ = ("req", "k", "v", "pos", "reuse", "slot", "prefill_s",
                 "positions_run")

    def __init__(self, req, k, v, pos, reuse, slot):
        self.req = req
        self.k = k
        self.v = v
        self.pos = pos
        self.reuse = reuse
        self.slot = slot
        self.prefill_s = 0.0
        self.positions_run = 0


class _EngineLadderShim:
    """Ladder facade handed to MemoryPressureGuard: the engine creates
    its DegradeLadder lazily (configure_degrade), so the guard must not
    capture the ladder object at construction — it reads/writes through
    the engine, which creates the ladder on first set_rung."""

    __slots__ = ("_engine",)

    def __init__(self, engine):
        self._engine = engine

    @property
    def rung(self):
        return self._engine._degrade_rung

    def set_rung(self, rung, reason="forced"):
        return self._engine.set_degrade_rung(rung, reason=reason)


class ServingEngine:
    """Request queue + KV pool + the single compiled decode loop.

    Drive it synchronously (``step()`` / ``drain()`` — deterministic, what
    the tests do) or as a background thread (``start()`` / ``stop()``)
    with ``submit()`` from any thread."""

    def __init__(self, params, model_config, serving_config=None,
                 monitor=None, injector=None, sentinel_config=None,
                 telemetry_config=None, rank=None):
        cfg = serving_config or ServingConfig()
        self.params = params
        self.model_config = model_config
        self.config = cfg
        # the one seam between the shared loop and a model family: what a
        # lane's state is, which programs fill and advance it, and which
        # options it cannot honour (those raise here, by name)
        self.family = family_for(model_config)
        self.family.check_options(cfg, params)
        self.n_layers = model_config.num_hidden_layers
        self.n_heads = model_config.num_attention_heads
        self.head_dim = model_config.hidden_size // self.n_heads

        mpe = model_config.max_position_embeddings
        self.max_seq_len = cfg.max_seq_len or mpe
        if self.max_seq_len > mpe:
            raise ValueError(
                f"serving.max_seq_len={self.max_seq_len} exceeds "
                f"max_position_embeddings={mpe}")
        buckets = cfg.prompt_buckets or default_buckets(self.max_seq_len - 1)
        if buckets[-1] > self.max_seq_len - 1:
            raise ValueError(
                f"largest prompt bucket ({buckets[-1]}) must leave room for "
                f"one generated token (max_seq_len={self.max_seq_len})")
        if cfg.prefill_chunk_tokens < 0:
            raise ValueError(
                f"serving.prefill_chunk_tokens must be >= 0 "
                f"(0 disables chunked prefill), got {cfg.prefill_chunk_tokens}")
        if cfg.prefix_cache_mb < 0:
            raise ValueError(
                f"serving.prefix_cache_mb must be >= 0 "
                f"(0 disables the prefix cache), got {cfg.prefix_cache_mb}")
        if cfg.prefix_spill_mb < 0:
            raise ValueError(
                f"serving.prefix_spill_mb must be >= 0 "
                f"(0 disables the spill tier), got {cfg.prefix_spill_mb}")
        if cfg.prefix_spill_mb > 0 and cfg.prefix_cache_mb <= 0:
            raise ValueError(
                f"serving.prefix_spill_mb={cfg.prefix_spill_mb} needs a "
                f"live prefix cache (prefix_cache_mb > 0) to spill from")
        if cfg.prefix_spill_dir is not None and cfg.prefix_spill_mb <= 0:
            raise ValueError(
                f"serving.prefix_spill_dir={cfg.prefix_spill_dir!r} needs "
                f"a spill tier (prefix_spill_mb > 0) above it")
        if cfg.host_mem_watermark_mb < 0:
            raise ValueError(
                f"serving.host_mem_watermark_mb must be >= 0 "
                f"(0 disables the memory-pressure guard), "
                f"got {cfg.host_mem_watermark_mb}")
        if (isinstance(cfg.speculative_k, bool)
                or not isinstance(cfg.speculative_k, int)
                or cfg.speculative_k < 0):
            raise ValueError(
                f"serving.speculative_k must be an int >= 0 "
                f"(0 disables speculative decoding), "
                f"got {cfg.speculative_k!r}")
        if cfg.speculative_k >= self.max_seq_len:
            raise ValueError(
                f"serving.speculative_k={cfg.speculative_k} must be "
                f"smaller than max_seq_len={self.max_seq_len}")
        if cfg.kv_cache_dtype not in KV_CACHE_DTYPES:
            raise ValueError(
                f"serving.kv_cache_dtype must be one of {KV_CACHE_DTYPES}, "
                f"got {cfg.kv_cache_dtype!r}")
        if cfg.kv_page_tokens is not None and (
                isinstance(cfg.kv_page_tokens, bool)
                or not isinstance(cfg.kv_page_tokens, int)
                or cfg.kv_page_tokens < 1):
            raise ValueError(
                f"serving.kv_page_tokens must be an int >= 1 "
                f"(None = {DEFAULT_PAGE_TOKENS}), got {cfg.kv_page_tokens!r}")
        if cfg.kv_pool_tokens is not None and (
                isinstance(cfg.kv_pool_tokens, bool)
                or not isinstance(cfg.kv_pool_tokens, int)
                or cfg.kv_pool_tokens < 1):
            raise ValueError(
                f"serving.kv_pool_tokens must be an int >= 1 (None = "
                f"max_slots * max_seq_len, the contiguous-equivalent "
                f"budget), got {cfg.kv_pool_tokens!r}")
        self._impl_default, self._impl_map = _parse_attention_impl(
            cfg.attention_impl, buckets)
        impls = set(self._impl_map.values())
        impls.add(self._impl_default)
        self._any_window = "sparse_xla" in impls
        self._any_flash = "flash" in impls
        self._any_kfull = "pallas_decode" in impls
        self._any_kwin = "pallas_sparse" in impls
        page_tokens = resolve_page_tokens(
            cfg.kv_page_tokens or DEFAULT_PAGE_TOKENS, self.max_seq_len)
        if ((self._any_window or self._any_kwin)
                and self.max_seq_len < (SPARSE_BAND + 1) * page_tokens):
            raise ValueError(
                f"serving.attention_impl='sparse_xla'/'pallas_sparse' needs "
                f"at least {SPARSE_BAND + 1} pages per lane: max_seq_len="
                f"{self.max_seq_len} < {(SPARSE_BAND + 1) * page_tokens} "
                f"(kv_page_tokens={page_tokens})")
        # kernel-tier backends: resolve the (impl, interpret) statics ONCE
        # here, through the registry's availability probe — on a TPU a
        # failed probe fails construction (KernelProbeError); off-TPU it
        # degrades the whole engine to the XLA twin (same oracle).
        kernel_backends = sorted(impls & set(kernels.KERNEL_BACKENDS))
        if cfg.attention_kernel is not None and not kernel_backends:
            raise ValueError(
                f"serving.attention_kernel={cfg.attention_kernel!r} applies "
                f"only when a kernel-tier attention_impl "
                f"({tuple(sorted(kernels.KERNEL_BACKENDS))}) is armed")
        if (cfg.kernel_interpret is not None
                and not isinstance(cfg.kernel_interpret, bool)):
            raise ValueError(
                f"serving.kernel_interpret must be a bool or None "
                f"(None = auto: interpret off-TPU), "
                f"got {cfg.kernel_interpret!r}")
        self._kernel_impl = {}
        self._kernel_interpret = {}
        for be in kernel_backends:
            ki, kint = kernels.resolve(be, requested=cfg.attention_kernel,
                                       interpret=cfg.kernel_interpret)
            self._kernel_impl[be] = ki
            self._kernel_interpret[be] = kint

        # Tensor-parallel mesh (serving.mesh_shape / the ds_config
        # `parallel` block): build the mesh and the shared sharding
        # registry ONCE, shard the params per the registry rules, and
        # hand both to the pool so KV pages split their heads dim over
        # the `model` axis. The decode/prefill/spec programs are
        # unchanged — jit compiles them SPMD from the operand shardings
        # (GSPMD), so each lane class still compiles exactly once.
        # mesh_shape=None keeps the single-device engine byte-identical.
        self.mesh = None
        self.registry = None
        self._replicated_sharding = None
        self._prefill_kv_sharding = None
        if cfg.mesh_shape is not None:
            self.registry = serving_registry(
                extra_rules=cfg.partition_rules,
                replicate_unmatched=cfg.replicate_unmatched)
            self.mesh = create_serving_mesh(cfg.mesh_shape)
            self.registry.validate_axes(self.mesh)
            mp = mp_world_size(self.mesh)
            if self.n_heads % mp != 0:
                raise ValueError(
                    f"serving.mesh_shape model axis {mp} must divide "
                    f"num_attention_heads={self.n_heads} (the KV pool "
                    f"shards heads)")
            native = [be for be in kernel_backends
                      if self._kernel_impl[be] == "pallas"
                      and not self._kernel_interpret[be]]
            if mp > 1 and native:
                # GSPMD cannot partition a Mosaic call, and these programs
                # do not shard_map their kernels over the model axis yet:
                # say so here, not from inside the first prefill
                raise NotImplementedError(
                    f"serving.attention_impl {native} compiles Pallas "
                    f"kernels natively, which a tensor-parallel mesh "
                    f"(model axis {mp}) cannot partition yet; use a "
                    f"dense/flash/sparse_xla backend or "
                    f"attention_kernel='xla' on this mesh")
            self.params = self.registry.shard(self.mesh, params)
            self._replicated_sharding = serving_sharding(
                self.mesh, "serving/lane_state", registry=self.registry)
            self._prefill_kv_sharding = serving_sharding(
                self.mesh, "serving/prefill_kv", registry=self.registry)

        self._qmode = None
        self.metrics = ServingMetrics(monitor)
        self.pool = self.family.build_pool(self, cfg)
        self._spec_k = int(cfg.speculative_k)
        # degraded-mode ladder: armed by configure_degrade() (from_config
        # wires the fleet.degrade block) or lazily by set_degrade_rung()
        # (the replica "degrade" socket op / the autoscaler's push).
        # _degrade_rung is the hot-path mirror — one int read per check.
        self._degrade = None
        self._degrade_rung = 0
        self.scheduler = ContinuousBatchingScheduler(
            max_queue=cfg.max_queue, buckets=buckets,
            default_max_new_tokens=cfg.default_max_new_tokens,
            request_timeout_s=cfg.request_timeout_s)
        self.metrics.record_kv_pool_bytes(self.pool.nbytes())
        if injector is None and cfg.fault_injection:
            injector = ServingFaultInjector(cfg.fault_injection)
        self.injector = injector
        self.prefix_cache = (
            PrefixKVCache(max(1, int(cfg.prefix_cache_mb * 2 ** 20)),
                          spill_budget_bytes=int(
                              cfg.prefix_spill_mb * 2 ** 20),
                          spill_dir=cfg.prefix_spill_dir,
                          listener=self._on_spill_event)
            if cfg.prefix_cache_mb > 0 else None)
        if (self.prefix_cache is not None
                and self.prefix_cache.spill is not None):
            # torn-write fault surface: consulted per disk write, False
            # while unarmed — re-wired live if an injector arrives later
            # over the replica inject op (same object, arm-time only)
            if self.injector is not None:
                self.prefix_cache.spill.torn_write_hook = (
                    self.injector.torn_spill_write)
            self.metrics.set_spill_sources(
                spill_stats_fn=self.prefix_cache.spill.stats,
                host_rss_mb_fn=self._host_rss_mb)
        elif cfg.host_mem_watermark_mb > 0:
            self.metrics.set_spill_sources(host_rss_mb_fn=self._host_rss_mb)
        # host-memory watchdog: one check per step(); sheds the spill
        # tier, pauses prefix inserts, then climbs the degrade ladder
        self._mem_guard = (
            MemoryPressureGuard(cfg.host_mem_watermark_mb,
                                cache=self.prefix_cache,
                                ladder=_EngineLadderShim(self),
                                read_rss_mb=self._guard_rss_mb,
                                listener=self._on_mem_pressure_level)
            if cfg.host_mem_watermark_mb > 0 else None)
        # edge-trigger memo for the serving/spill_corrupt instant
        self._spill_corrupt_seen = 0
        # pool-pressure relief: one evict+shed attempt per exhaustion
        # event (satellite: requeue-after-relief instead of plain requeue)
        self._pool_relief_attempts = 0

        self._active = {}                                   # slot -> Request
        self._lane_tokens = np.zeros(cfg.max_slots, np.int32)
        self._lane_active = np.zeros(cfg.max_slots, bool)
        # which active lanes run the windowed (sparse) decode program;
        # the complement runs the full-gather (dense/flash) program.
        # Each program masks with its own class vector, so threading the
        # shared token/position/pool operands through both leaves every
        # lane with exactly its own class's result.
        self._lane_impl_window = np.zeros(cfg.max_slots, bool)
        # which active lanes route through the kernel tier: pallas_decode
        # lanes are (kernel & ~window), pallas_sparse (kernel & window) —
        # four lane classes total, each masked by its own class vector
        self._lane_impl_kernel = np.zeros(cfg.max_slots, bool)
        # device-resident decode operands: uploaded ONLY on lane churn
        # (_lane_dirty), advanced in-jit otherwise — steady-state decode
        # performs exactly one explicit transfer per step (the EOS read)
        self._dev_tokens = None
        self._dev_positions = None
        self._dev_active = None
        self._dev_active_win = None
        self._dev_active_kfull = None
        self._dev_active_kwin = None
        self._dev_page_tables = None
        self._lane_dirty = True
        # speculative state: per-lane token-by-position history feeding
        # the n-gram drafter (host mirror for churn re-upload, device
        # buffer advanced in-jit between churns) and the corrupt_draft
        # noise operand (all-zeros = bitwise no-op)
        self._lane_history = (
            np.zeros((cfg.max_slots, self.max_seq_len), np.int32)
            if self._spec_k > 0 else None)
        self._dev_history = None
        self._dev_noise = None
        self._noise_armed = False
        if sentinel_config is not None and sentinel_config.enabled:
            budget = sentinel_config.compile_budget
            decode_prog, prefill_prog = self.family.sentinel_programs(self)
            self.decode_sentinel = CompileSentinel(
                decode_prog, budget, name="serving decode step")
            self.prefill_sentinel = CompileSentinel(
                prefill_prog, budget, name="serving batched prefill")
            # backend programs get their own pins only when armed — an
            # all-dense config keeps the exact legacy sentinel set
            self.decode_window_sentinel = (
                CompileSentinel(
                    _spec_step_window_jit if self._spec_k > 0
                    else _decode_step_window_jit,
                    budget, name="serving window decode step")
                if self._any_window else None)
            self.prefill_window_sentinel = (
                CompileSentinel(_prefill_batch_window_jit, budget,
                                name="serving window prefill")
                if self._any_window else None)
            self.prefill_flash_sentinel = (
                CompileSentinel(_prefill_batch_flash_jit, budget,
                                name="serving flash prefill")
                if self._any_flash else None)
            # kernel-class decode pins: pallas_decode lanes always run a
            # kernel-tier program; pallas_sparse lanes run the kernel spec
            # step under speculation but the (kernel-static) window
            # program otherwise, so non-spec kwin pins that instead
            self.decode_kernel_sentinel = (
                CompileSentinel(
                    _spec_step_kernel_jit if self._spec_k > 0
                    else _decode_step_kernel_jit,
                    budget, name="serving kernel decode step")
                if (self._any_kfull
                    or (self._any_kwin and self._spec_k > 0)) else None)
            if (self._any_kwin and self._spec_k == 0
                    and self.decode_window_sentinel is None):
                self.decode_window_sentinel = CompileSentinel(
                    _decode_step_window_jit, budget,
                    name="serving window decode step")
            self.prefill_kernel_sentinel = (
                CompileSentinel(_prefill_batch_kernel_jit, budget,
                                name="serving kernel prefill")
                if self._any_kfull else None)
            self.prefill_kernel_window_sentinel = (
                CompileSentinel(_prefill_batch_kernel_window_jit, budget,
                                name="serving kernel window prefill")
                if self._any_kwin else None)
            self._transfer_guard = bool(sentinel_config.transfer_guard)
        else:
            self.decode_sentinel = None
            self.prefill_sentinel = None
            self.decode_window_sentinel = None
            self.prefill_window_sentinel = None
            self.prefill_flash_sentinel = None
            self.decode_kernel_sentinel = None
            self.prefill_kernel_sentinel = None
            self.prefill_kernel_window_sentinel = None
            self._transfer_guard = False
        # batched prefill always runs at the pool width: the batch dim is
        # STATIC, so any admission-group size shares one program per bucket
        self._prefill_batch = cfg.max_slots
        self._chunking = None               # at most one chunked prefill
        self._step_count = 0
        self._busy_steps = 0                # steps that had active lanes
        # prefills (and chunks) run so far: a request remembers the count
        # at each token it emits, so the next gap knows whether a prefill
        # ran inside it (stalled by cause, not by a threshold)
        self._prefill_seq = 0
        self._loop_thread = None
        self._stop = threading.Event()
        self._draining = False              # planned restart: admit nothing
        # the pool has no lock: every mutation happens on the serving-loop
        # thread. Handoff pool ops (claim/install/free/resume) arrive on
        # replica connection threads and are marshaled here, drained at
        # the top of step().
        self._loop_ops = _queue_mod.Queue()

        # telemetry: an explicit block arms the process-global tracer and
        # registry; an absent block leaves them untouched. Hot-path guard
        # is one attribute read (self._tracer.enabled). rank/role become
        # the trace's process identity (the fleet collector's merge key);
        # rank=None falls back to the launcher-exported RANK env var.
        telemetry.configure_from_config(telemetry_config, rank=rank,
                                        role="serve")
        self._tracer = telemetry.get_tracer()
        # an armed span is also a TraceMe event: under a jax.profiler
        # session the host spans land in the same .xplane.pb, on the same
        # clock, as the device's programs (telemetry itself never imports
        # jax, so the engine hands it the annotation class)
        self._tracer.set_annotation_factory(jax.profiler.TraceAnnotation)
        self._trace_file = None
        self.telemetry_server = None
        self.slo = None
        if telemetry_config is not None and telemetry_config.enabled:
            self._trace_file = telemetry_config.trace_file
            self.metrics.export_to(telemetry.get_registry())
            if (self.prefix_cache is not None
                    and self.prefix_cache.spill is not None):
                telemetry.get_registry().gauge_fn(
                    "Serving/SpillTier", self.prefix_cache.spill.stats,
                    help="host-RAM/disk spill tier occupancy")
            if self._mem_guard is not None or cfg.host_mem_watermark_mb > 0:
                telemetry.get_registry().gauge_fn(
                    "Serving/HostRssMb", self._host_rss_mb,
                    help="process resident set size (MiB)")
            if self._kernel_impl:
                # per-kernel selected-backend gauges next to the
                # Kernels/<name>/calls counters at /metrics
                kernels.get_registry().export_gauges(telemetry.get_registry())
            # explicit http_port wins; a supervised worker with a null
            # port inherits DSTPU_TELEMETRY_PORT so the fleet collector
            # can scrape it without per-worker config edits
            http_port = telemetry.resolve_http_port(telemetry_config)
            if http_port is not None:
                self.telemetry_server = self._build_telemetry_server(
                    http_port)
            self.slo = telemetry.SloEngine.from_config(
                telemetry_config, tracer=self._tracer,
                registry=telemetry.get_registry())
            if self.slo is not None and self.telemetry_server is not None:
                self.slo.attach(self.telemetry_server)

    # -- the gpt2 family's side of the seam (family.py: GPT2Family) --------
    def _build_kv_pool(self, cfg):
        dtype = _cache_dtype(self.params)
        pool = KVCachePool(self.n_layers, cfg.max_slots, self.n_heads,
                           self.max_seq_len, self.head_dim, dtype=dtype,
                           kv_cache_dtype=cfg.kv_cache_dtype,
                           page_tokens=cfg.kv_page_tokens,
                           pool_tokens=cfg.kv_pool_tokens,
                           mesh=self.mesh, registry=self.registry)
        # _qmode: storage<->compute conversion the decode programs need.
        # "fp32" stores the compute dtype directly, and "bf16" on a bf16
        # checkpoint is ALSO storage==compute — both take the plain
        # (bitwise) programs; only a real narrowing pays the quant path.
        if cfg.kv_cache_dtype == "int8":
            self._qmode = "int8"
        elif jnp.dtype(pool.k.dtype) != jnp.dtype(dtype):
            self._qmode = "bf16"
        return pool

    def _gpt2_sentinel_programs(self):
        if self._spec_k > 0:
            decode_prog = (_spec_step_quant_jit if self._qmode
                           else _spec_step_jit)
        else:
            decode_prog = (_decode_step_quant_jit if self._qmode
                           else _decode_step_jit)
        return decode_prog, _prefill_batch_jit

    def _build_telemetry_server(self, port):
        srv = telemetry.TelemetryServer(
            registry=telemetry.get_registry(), tracer=self._tracer, port=port)
        srv.add_snapshot_provider("serving", self.metrics.snapshot)
        srv.add_snapshot_provider("kv_pool", self.occupancy)
        srv.add_snapshot_provider("prefix_cache", self.prefix_stats)
        srv.add_snapshot_provider("memtier", self.memtier_stats)
        srv.add_snapshot_provider("kernels", kernels.registry_snapshot)
        srv.add_health_provider("serving_loop", self._loop_health)
        return srv.start()

    def _loop_health(self):
        """Healthy unless a background loop was started and then died
        (synchronous step()/drain() driving is always healthy)."""
        t = self._loop_thread
        return {"healthy": t is None or t.is_alive(),
                "background_loop": t is not None,
                "steps": self._step_count,
                "active_requests": len(self._active),
                "queue_depth": self.scheduler.queue_depth(),
                "draining": self._draining,
                "degrade_rung": self._degrade_rung}

    # -- degraded-mode ladder -------------------------------------------
    def configure_degrade(self, degrade_config):
        """Arm the degraded-mode ladder (fleet.degrade block or a
        DegradeLadder). Rung 1 disables speculation (k -> 0 — safe
        mid-flight: emitted tokens always come from the verify oracle,
        so the classic program continues the exact same sequence);
        rung 2 additionally pauses prefix-cache inserts and halves the
        admission queue budget. Rung 3 is router-side (class shedding).
        """
        if isinstance(degrade_config, DegradeLadder):
            self._degrade = degrade_config
            self._degrade._on_change = self._on_degrade_change
        else:
            self._degrade = DegradeLadder(
                degrade_config, on_change=self._on_degrade_change,
                name="engine")
        self._degrade_rung = self._degrade.rung
        self._degrade.export_gauges(telemetry.get_registry())
        return self._degrade

    def set_degrade_rung(self, rung, reason="forced"):
        """External rung override (the replica's ``degrade`` socket op,
        the autoscaler's no-headroom push). Arms a default ladder when
        none is configured, so the op always works."""
        if self._degrade is None:
            self.configure_degrade(None)
        return self._degrade.set_rung(rung, reason=reason)

    @property
    def degrade_rung(self):
        return self._degrade_rung

    def _effective_spec_k(self):
        """Speculation knob after the ladder: rung >= 1 runs the classic
        one-token decode program (which always exists — it IS the k=0
        path), so toggling never recompiles anything new per rung flip."""
        return 0 if self._degrade_rung >= 1 else self._spec_k

    def _on_degrade_change(self, old, new, reason):
        self._degrade_rung = new
        # crossing the speculation boundary switches decode programs;
        # re-upload lane state so the program about to run sees fresh
        # operands (spec needs the host history mirror, which the classic
        # path keeps warm — see step()).
        if self._spec_k > 0 and (old >= 1) != (new >= 1):
            self._lane_dirty = True

    def _degrade_queue_budget(self):
        """Effective admission-queue budget under the ladder: rung >= 2
        halves it (earlier backpressure, less queued work to carry)."""
        if self._degrade_rung >= 2:
            return max(1, self.config.max_queue // 2)
        return self.config.max_queue

    # -- memory tiering & pressure (spill tier + guard) ------------------
    def _host_rss_mb(self):
        """Current host RSS (MiB) — the snapshot/gauge source."""
        return read_host_rss_mb()

    def _guard_rss_mb(self):
        """RSS reader the MemoryPressureGuard ticks on: the
        host_mem_pressure fault arm substitutes a fake over-watermark
        value while armed, so chaos drives the escalation path without
        actually ballooning the process."""
        if (self.injector is not None
                and self.injector.host_mem_pressure_active()):
            return self.config.host_mem_watermark_mb * 4.0
        return read_host_rss_mb()

    def _on_spill_event(self, event):
        """Spill-tier listener (fires under the cache lock — metrics and
        tracer only, never back into the cache)."""
        if event == "spill_hit":
            self.metrics.record_spill_lookup(True)
        elif event == "spill_miss":
            self.metrics.record_spill_lookup(False)
        elif event == "spill_corrupt":
            self.metrics.record_spill_corrupt()
            tracer = getattr(self, "_tracer", None)
            if tracer is not None and tracer.enabled:
                tracer.instant("serving/spill_corrupt", args={
                    "total": self.metrics.spill_corrupt_total})

    def _on_mem_pressure_level(self, level, rss_mb):
        """Edge-triggered on every MemoryPressureGuard level change."""
        tracer = getattr(self, "_tracer", None)
        if tracer is not None and tracer.enabled:
            tracer.instant("serving/mem_pressure", args={
                "level": level,
                "level_name": MemoryPressureGuard.LEVELS[level],
                "rss_mb": None if rss_mb is None else round(rss_mb, 1)})

    def _relieve_memory_pressure(self):
        """One-shot relief when admission hits pool/page exhaustion:
        evict every unreferenced live prefix entry (demoting to spill)
        and shed the spill tier, so transient pressure self-heals before
        the request round-trips through requeue backpressure. Returns
        True when anything was actually released."""
        if self.prefix_cache is None:
            return False
        self._pool_relief_attempts += 1
        evicted = self.prefix_cache.evict_unreferenced()
        shed = self.prefix_cache.shed_spill()
        return bool(evicted or shed)

    def memtier_stats(self):
        """Spill-tier + pressure-guard snapshot (telemetry provider)."""
        out = {"pool_relief_attempts": self._pool_relief_attempts}
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        if self._mem_guard is not None:
            out["mem_guard"] = self._mem_guard.stats()
        return out

    @classmethod
    def from_config(cls, params, model_config, ds_config, rank=0,
                    injector=None):
        """Build from a ds_config (dict or DeepSpeedConfig): the validated
        ``serving`` block plus the shared monitor construction path."""
        from deepspeed_tpu.monitor import monitor_from_config
        from deepspeed_tpu.runtime.config import DeepSpeedConfig

        if isinstance(ds_config, dict):
            ds_config = DeepSpeedConfig(ds_config, world_size=1)
        serving_cfg = ds_config.serving_config
        parallel = getattr(ds_config, "parallel_config", None)
        if parallel is not None and parallel.enabled:
            # the validated `parallel` block arms tensor parallelism for
            # the serving engine; replace() keeps the frozen-ish config
            # object semantics (serving_cfg may be shared across engines)
            import dataclasses
            serving_cfg = dataclasses.replace(
                serving_cfg, mesh_shape=parallel.mesh_shape,
                partition_rules=parallel.partition_rules,
                replicate_unmatched=parallel.replicate_unmatched)
        eng = cls(params, model_config,
                  serving_config=serving_cfg,
                  monitor=monitor_from_config(ds_config, rank),
                  injector=injector,
                  sentinel_config=ds_config.sentinel_config,
                  telemetry_config=ds_config.telemetry_config,
                  rank=rank)
        fleet = getattr(ds_config, "fleet_config", None)
        if fleet is not None and fleet.enabled and fleet.degrade.enabled:
            eng.configure_degrade(fleet.degrade)
        return eng

    # -- request intake -------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens=None, eos_token_id=None,
               timeout_s=None, stream_cb=None, age_s=0.0):
        """Queue one request; returns its ``ServingFuture``.

        ``prompt_ids`` is a 1-D token sequence. Raises ``QueueFullError``
        when the admission queue is at capacity (backpressure),
        ``EngineDrainingError`` during a planned drain, and ``ValueError``
        for requests that can never fit. ``stream_cb`` (optional) is
        called as ``stream_cb(request_id, token)`` for every generated
        token, including the first. ``age_s`` backdates the enqueue
        timestamp by that many seconds — a re-routed or requeued request
        keeps its original deadline/TTFT clock instead of resetting it."""
        if self._draining:
            raise EngineDrainingError(
                "engine is draining for a planned restart; "
                "route this request to another replica")
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if len(prompt) < 1:
            raise ValueError("prompt must contain at least one token")
        if max_new_tokens is None:
            max_new_tokens = self.config.default_max_new_tokens
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        bucket_for(len(prompt), self.scheduler.buckets)  # raises if too long
        total = len(prompt) + int(max_new_tokens)
        if total > self.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"= {total} exceeds serving max_seq_len={self.max_seq_len}")
        if eos_token_id is not None and not (
                0 <= int(eos_token_id) < self.model_config.vocab_size):
            raise ValueError(
                f"eos_token_id={eos_token_id} outside vocab "
                f"[0, {self.model_config.vocab_size})")
        if self._degrade_rung >= 2:
            # budget_shrink rung: earlier backpressure at half the queue
            budget = self._degrade_queue_budget()
            if self.scheduler.queue_depth() >= budget:
                raise QueueFullError(
                    f"admission queue shrunk to {budget} at degrade rung "
                    f"{self._degrade_rung}")
        submitted_at = (time.monotonic() - float(age_s)
                        if age_s and age_s > 0 else None)
        req = self.scheduler.submit(
            prompt, max_new_tokens=int(max_new_tokens),
            eos_token_id=None if eos_token_id is None else int(eos_token_id),
            timeout_s=timeout_s, stream_cb=stream_cb,
            submitted_at=submitted_at)
        return req.future

    # -- disaggregated prefill/decode handoff ---------------------------
    def submit_handoff(self, prompt_ids, reserve_new_tokens,
                       eos_token_id=None, timeout_s=None, stream_cb=None,
                       age_s=0.0):
        """Prefill-only submit: run prefill for ``prompt_ids``, emit the
        first token, then retire immediately (``max_new_tokens=1``) while
        exporting the lane's KV pages as ``req.export_payload`` for a
        decode-worker handoff.

        ``reserve_new_tokens`` is the ORIGINAL request's generation
        budget — the page allocation spans the full request so the
        exported layout (and int8 scales, which quantize over the whole
        allocated span) is bit-identical to what a mixed-mode admission
        would have produced. Returns the Request (the caller reads
        ``export_payload`` after ``future.result()``)."""
        self.family.refuse_handoff()
        if self._draining:
            raise EngineDrainingError(
                "engine is draining for a planned restart; "
                "route this request to another replica")
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if len(prompt) < 1:
            raise ValueError("prompt must contain at least one token")
        reserve = int(reserve_new_tokens)
        if reserve < 1:
            raise ValueError(
                f"reserve_new_tokens must be >= 1, got {reserve}")
        bucket_for(len(prompt), self.scheduler.buckets)
        total = len(prompt) + reserve
        if total > self.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + reserve_new_tokens ({reserve}) "
                f"= {total} exceeds serving max_seq_len={self.max_seq_len}")
        if eos_token_id is not None and not (
                0 <= int(eos_token_id) < self.model_config.vocab_size):
            raise ValueError(
                f"eos_token_id={eos_token_id} outside vocab "
                f"[0, {self.model_config.vocab_size})")
        if self._degrade_rung >= 2:
            budget = self._degrade_queue_budget()
            if self.scheduler.queue_depth() >= budget:
                raise QueueFullError(
                    f"admission queue shrunk to {budget} at degrade rung "
                    f"{self._degrade_rung}")
        submitted_at = (time.monotonic() - float(age_s)
                        if age_s and age_s > 0 else None)
        req = self.scheduler.adopt(
            prompt, max_new_tokens=1,
            eos_token_id=None if eos_token_id is None else int(eos_token_id),
            timeout_s=timeout_s, stream_cb=stream_cb,
            submitted_at=submitted_at)
        # flags set BEFORE the request becomes loop-visible
        req.handoff_export = True
        req.alloc_tokens_override = min(total, self.max_seq_len)
        self.scheduler.enqueue(req)
        return req

    def handoff_claim(self, n_tokens):
        """Decode-side phase 1: allocate a pool slot sized for the full
        request span. Raises PoolExhaustedError under pressure. Mirrors
        ``_alloc_tokens``: an armed injector forces full-lane claims, so
        the claim always holds at least as many pages as the (also
        full-lane) prefill-side export ships."""
        self.family.refuse_handoff()
        n = None if self.injector is not None else int(n_tokens)
        return self._run_on_loop(lambda: self.pool.allocate(n))

    def handoff_install(self, slot, meta, frames, handoff_key=None):
        """Decode-side phase 2: install transferred pages into the
        claimed slot. Returns False on an idempotent duplicate."""
        self.family.refuse_handoff()
        def _do():
            fresh = self.pool.install_raw(slot, meta, frames,
                                          handoff_key=handoff_key)
            self.metrics.record_handoff("install" if fresh else "dup_install")
            return fresh
        return self._run_on_loop(_do)

    def handoff_release(self, slot):
        """Free a claimed/installed slot (orphan reap, failed resume)."""
        return self._run_on_loop(lambda: self.pool.free(slot))

    def resume_handoff(self, slot, prompt_ids, first_token, max_new_tokens,
                       eos_token_id=None, timeout_s=None, stream_cb=None,
                       age_s=0.0):
        """Activate a lane whose KV pages were installed by a handoff and
        continue decoding exactly where prefill left off. The first
        generated token was already delivered by the prefill worker, so
        it is recorded (``emitted=1``, appended to the future) but NOT
        re-streamed through ``stream_cb``. Returns the Request."""
        self.family.refuse_handoff()
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        submitted_at = (time.monotonic() - float(age_s)
                        if age_s and age_s > 0 else None)
        req = self.scheduler.adopt(
            prompt, max_new_tokens=int(max_new_tokens),
            eos_token_id=None if eos_token_id is None else int(eos_token_id),
            timeout_s=timeout_s, stream_cb=stream_cb,
            submitted_at=submitted_at)

        def _do():
            req.attn_impl = self._impl_for_len(len(prompt))
            now = time.monotonic()
            req.first_token_time = now
            self._activate(req, slot, int(first_token), emit=False)
            req.future._append(int(first_token))
            req.emitted = 1
            self._stamp_token(req, now)
            self.metrics.record_handoff("resume")
            # defensively retire right away if the first token already
            # ended the request (the router short-circuits these, but a
            # direct caller may not)
            self._maybe_retire(req, int(first_token), now)
            return None
        self._run_on_loop(_do)
        return req

    # -- the serving loop ----------------------------------------------
    def step(self):
        """One scheduler iteration: expire, advance any chunked prefill,
        admit (batched per bucket), one batched decode step, retire.
        Returns an activity dict (all zeros = idle)."""
        top = now = time.monotonic()
        stats = {"admitted": 0, "decoded": 0, "retired": 0,
                 "prefill_chunks": 0}
        read_back = None        # instant the decode step's tokens landed
        espan = telemetry.NULL_SPAN

        self._drain_loop_ops()

        for req in self.scheduler.pop_expired(now):
            self._finish_timeout(req, phase="queued")
            stats["retired"] += 1

        # one chunk per step: a long prompt makes progress without ever
        # stalling the in-flight lanes' inter-token latency
        now = self.family.advance_prefill(self, stats, now)

        # admission is timed from the last stamp the iteration holds
        self._admit_from_queue(stats, now)

        if self.injector is not None:
            self.injector.maybe_evict_prefix(self._step_count,
                                             self.prefix_cache)
            self.injector.maybe_corrupt_spill(self._step_count,
                                              self.prefix_cache)
        if self._mem_guard is not None:
            self._mem_guard.check()
        if self._active:
            # busy steps (not raw _step_count, which idles forward between
            # requests in background mode): the kill_replica arm's at_step
            # must mean "the Nth decode step that had work" to be
            # reproducible against a live server
            self._busy_steps += 1
            if self.injector is not None:
                self.injector.maybe_slow_decode(self._step_count)
                self.injector.maybe_kill_replica(self._busy_steps)
            # span args (request ids) are built ONLY when tracing is armed:
            # disabled-mode cost is this one attribute read. The dict is
            # kept so the spec path can fill in `accepted` post-step (the
            # tracer renders args lazily, at write time).
            span_args = None
            if self._tracer.enabled:
                span_args = {
                    "request_ids": [r.id for r in self._active.values()],
                    "active": len(self._active), "accepted": 0}
                dspan = self._tracer.span("serving/decode_step",
                                          cat="serving", args=span_args)
            else:
                dspan = telemetry.NULL_SPAN
            dspan.__enter__()
            t0 = time.monotonic()
            if self._lane_dirty:
                with (self._tracer.span("serving/upload_lanes",
                                        cat="serving",
                                        args={"active": len(self._active)})
                      if self._tracer.enabled else telemetry.NULL_SPAN):
                    self.family.upload_lanes(self)
            guard = transfer_free() if self._transfer_guard else nullcontext()
            # host-side np masks: np.bool_ drives the dispatch branches
            # directly (a bool() cast here reads as a device sync to JL002)
            lw, lk = self._lane_impl_window, self._lane_impl_kernel
            full_mask = self._lane_active & ~lw & ~lk
            win_mask = self._lane_active & lw & ~lk
            kfull_mask = self._lane_active & ~lw & lk
            kwin_mask = self._lane_active & lw & lk
            full_any = np.any(full_mask)
            win_any = np.any(win_mask)
            kfull_any = np.any(kfull_mask)
            kwin_any = np.any(kwin_mask)
            if self._effective_spec_k() > 0:
                self._maybe_update_noise()
                with guard:
                    got = []           # (class mask, oracle, accepted)
                    if full_any:
                        (self._dev_tokens, self._dev_positions, self.pool.k,
                         self.pool.v, self._dev_history, oracle_dev,
                         accepted_dev) = self._call_spec_step()
                        got.append((full_mask, oracle_dev, accepted_dev))
                    if win_any:
                        (self._dev_tokens, self._dev_positions, self.pool.k,
                         self.pool.v, self._dev_history, oracle_dev,
                         accepted_dev) = self._call_spec_step_window()
                        got.append((win_mask, oracle_dev, accepted_dev))
                    if kfull_any:
                        (self._dev_tokens, self._dev_positions, self.pool.k,
                         self.pool.v, self._dev_history, oracle_dev,
                         accepted_dev) = self._call_spec_step_kernel(
                            "pallas_decode")
                        got.append((kfull_mask, oracle_dev, accepted_dev))
                    if kwin_any:
                        (self._dev_tokens, self._dev_positions, self.pool.k,
                         self.pool.v, self._dev_history, oracle_dev,
                         accepted_dev) = self._call_spec_step_kernel(
                            "pallas_sparse")
                        got.append((kwin_mask, oracle_dev, accepted_dev))
                self._check_decode_sentinels()
                # the step's single deliberate sync: the emit loop needs
                # the oracle tokens and per-lane acceptance counts (one
                # tuple read even when several class programs ran)
                host = jax.device_get(tuple((o, a) for _, o, a in got))  # jaxlint: disable=JL002(one explicit host read per step)
                oracle, accepted = host[0]
                if len(got) > 1:
                    # overlay each later class's lanes onto the first's
                    # result (every active lane is in exactly one class);
                    # device_get already landed host numpy — no copies here
                    oracle = oracle.copy()
                    accepted = accepted.copy()
                    for (mask, _, _), (o, a) in zip(got[1:], host[1:]):
                        oracle[mask] = o[mask]
                        accepted[mask] = a[mask]
                step_s = time.monotonic() - t0
                read_back = t0 + step_s
                oracle = oracle.tolist()        # host numpy -> python ints
                accepted = accepted.tolist()
                acc_total = sum(accepted[s] for s in self._active)
                if span_args is not None:
                    span_args["accepted"] = acc_total
                dspan.__exit__(None, None, None)
                espan = (self._tracer.span(
                             "serving/emit", cat="serving",
                             args={"active": len(self._active)})
                         if self._tracer.enabled else telemetry.NULL_SPAN)
                espan.__enter__()
                now = time.monotonic()
                n_active = len(self._active)
                decoded_before = stats["decoded"]
                for slot in list(self._active):
                    req = self._active[slot]
                    acc = accepted[slot]
                    # mirror the device lane state: the pending token is
                    # now the oracle's post-acceptance token
                    self._lane_tokens[slot] = oracle[slot][acc]
                    base = self.pool.positions[slot]    # host-side counter
                    for j in range(acc + 1):
                        tok = oracle[slot][j]
                        self.pool.advance(slot)
                        if base + 1 + j < self.max_seq_len:
                            self._lane_history[slot, base + 1 + j] = tok
                        self._stamp_token(req, now)
                        self._emit(req, tok)
                        stats["decoded"] += 1
                        if self._maybe_retire(req, tok, now):
                            # EOS/length/deadline truncates the step's
                            # remaining oracle tokens — exactly where a
                            # non-speculative server would have stopped
                            stats["retired"] += 1
                            break
                occ = self.pool.occupancy()
                self.metrics.record_step(
                    queue_depth=self.scheduler.queue_depth(),
                    active_slots=n_active, max_slots=self.pool.max_slots,
                    tokens_this_step=stats["decoded"] - decoded_before,
                    step_s=step_s, accepted_tokens=acc_total,
                    proposed_tokens=self._spec_k * n_active,
                    pages_in_use=occ["pages_in_use"],
                    page_fragmentation=occ["page_fragmentation"])
            else:
                # ``lanes``: the slots whose token this is (every active
                # lane, but for a family that keeps one step in flight and
                # hands back the step BEFORE the one it just dispatched)
                host_tokens, lanes = self.family.decode_step(
                    self, guard, (full_any, win_any, kfull_any, kwin_any))
                step_s = time.monotonic() - t0
                read_back = t0 + step_s
                dspan.__exit__(None, None, None)
                espan = (self._tracer.span(
                             "serving/emit", cat="serving",
                             args={"active": len(self._active)})
                         if self._tracer.enabled else telemetry.NULL_SPAN)
                espan.__enter__()
                self._lane_tokens = host_tokens.copy()
                toks = host_tokens.tolist()
                now = time.monotonic()
                n_active = len(lanes)
                for slot in lanes:
                    req = self._active[slot]
                    base = self.pool.positions[slot]
                    self.pool.advance(slot)
                    if (self._lane_history is not None
                            and base + 1 < self.max_seq_len):
                        # speculation is configured but ladder-disabled:
                        # keep the host history mirror warm so recovery
                        # back to the spec program re-uploads fresh
                        # drafter context (stale history would only cost
                        # accept rate, but fresh is free here)
                        self._lane_history[slot, base + 1] = toks[slot]
                    self._stamp_token(req, now)
                    self._emit(req, toks[slot])
                    stats["decoded"] += 1
                    stats["retired"] += self._maybe_retire(req, toks[slot],
                                                           now)
                occ = self.pool.occupancy()
                self.metrics.record_step(
                    queue_depth=self.scheduler.queue_depth(),
                    active_slots=n_active, max_slots=self.pool.max_slots,
                    tokens_this_step=n_active, step_s=step_s,
                    pages_in_use=occ["pages_in_use"],
                    page_fragmentation=occ["page_fragmentation"])
        self._step_count += 1
        if self._degrade is not None and self._degrade.config.enabled:
            # host-only pressure signal, evaluated once per step: a
            # sustained near-full admission queue climbs the ladder one
            # rung; sustained quiet walks it back down
            threshold = max(1, int(self._degrade.config.pressure_queue_frac
                                   * self.config.max_queue))
            self._degrade.update(self.scheduler.queue_depth() >= threshold)
        if self.slo is not None:
            # host-only snapshot + pushed gauges; under policy="fail" a
            # firing rule raises SloViolationError out of step()
            self.slo.evaluate(self._slo_values())
        if (stats["decoded"] or stats["admitted"] or stats["retired"]
                or stats["prefill_chunks"]):
            # the iteration's one new clock read: it closes the busy time
            # and the host phase behind the token read-back
            end = time.monotonic()
            self.metrics.record_iteration(
                end - top, end - read_back if read_back is not None else 0.0)
        espan.__exit__(None, None, None)
        return stats

    def _gpt2_decode_programs(self, guard, classes):
        """One decode step of the gpt2 family: (at most) one jitted call
        per armed lane class, then the step's one host read."""
        full_any, win_any, kfull_any, kwin_any = classes
        with guard:
            if full_any:
                if self._qmode is not None:
                    (self._dev_tokens, self._dev_positions,
                     self.pool.k, self.pool.v) = \
                        _decode_step_quant_jit(
                            self.params, self.pool.k, self.pool.v,
                            self.pool.k_scale, self.pool.v_scale,
                            self._dev_page_tables, self._dev_tokens,
                            self._dev_positions, self._dev_active,
                            n_heads=self.n_heads, qmode=self._qmode)
                else:
                    (self._dev_tokens, self._dev_positions,
                     self.pool.k, self.pool.v) = _decode_step_jit(
                        self.params, self.pool.k, self.pool.v,
                        self._dev_page_tables, self._dev_tokens,
                        self._dev_positions, self._dev_active,
                        n_heads=self.n_heads)
            if win_any:
                (self._dev_tokens, self._dev_positions, self.pool.k,
                 self.pool.v) = _decode_step_window_jit(
                    self.params, self.pool.k, self.pool.v,
                    self.pool.k_scale, self.pool.v_scale,
                    self._dev_page_tables, self._dev_tokens,
                    self._dev_positions, self._dev_active_win,
                    n_heads=self.n_heads,
                    page_tokens=self.pool.page_tokens,
                    qmode=self._qmode)
            if kfull_any:
                kernels.record_call(
                    "decode_attention",
                    self._kernel_impl["pallas_decode"])
                (self._dev_tokens, self._dev_positions, self.pool.k,
                 self.pool.v) = _decode_step_kernel_jit(
                    self.params, self.pool.k, self.pool.v,
                    self.pool.k_scale, self.pool.v_scale,
                    self._dev_page_tables, self._dev_tokens,
                    self._dev_positions, self._dev_active_kfull,
                    n_heads=self.n_heads,
                    page_tokens=self.pool.page_tokens,
                    qmode=self._qmode,
                    kernel_impl=self._kernel_impl["pallas_decode"],
                    kernel_interpret=self._kernel_interpret[
                        "pallas_decode"])
            if kwin_any:
                kernels.record_call(
                    "sparse_attention",
                    self._kernel_impl["pallas_sparse"])
                (self._dev_tokens, self._dev_positions, self.pool.k,
                 self.pool.v) = _decode_step_window_jit(
                    self.params, self.pool.k, self.pool.v,
                    self.pool.k_scale, self.pool.v_scale,
                    self._dev_page_tables, self._dev_tokens,
                    self._dev_positions, self._dev_active_kwin,
                    n_heads=self.n_heads,
                    page_tokens=self.pool.page_tokens,
                    qmode=self._qmode,
                    kernel_impl=self._kernel_impl["pallas_sparse"],
                    kernel_interpret=self._kernel_interpret[
                        "pallas_sparse"])
        self._check_decode_sentinels()
        # the step's single deliberate sync: EOS checks need the
        # tokens
        return jax.device_get(self._dev_tokens)  # jaxlint: disable=JL002(one explicit host read per step)

    def _slo_values(self):
        """SLO inputs: the live serving snapshot under ``Serving/*`` plus
        pushed registry metrics. Pull gauges are skipped — the snapshot is
        already here, and re-polling every callback each step would double
        the work for no fresher data."""
        vals = {k: v
                for k, v in telemetry.get_registry().as_dict(pulled=False).items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}
        for k, v in self.metrics.snapshot().items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                vals[f"Serving/{k}"] = v
        return vals

    def _put_host(self, tree):
        """Sharding-aware host upload: on a mesh, commit to the
        registry's replicated lane-state sharding — a default-device
        put on a >1-device mesh would land on device 0 and force a
        reshard inside the next jitted step, breaking the
        ``transfer_free()`` steady-state contract."""
        if self._replicated_sharding is None:
            return jax.device_put(tree)
        return jax.device_put(tree, self._replicated_sharding)

    def _upload_lane_state(self):
        """Lane churn: ONE explicit upload of the lane vectors, both
        per-class active masks, the page tables, and the drafter history
        when speculation is armed; between churn events they live on
        device and never move. Page-table churn rides the same dirty
        flag lane churn already sets (allocate/free happen exactly
        there), so paging adds no extra steady-state transfers."""
        pos = np.ascontiguousarray(self.pool.positions, dtype=np.int32)
        lw, lk = self._lane_impl_window, self._lane_impl_kernel
        full = self._lane_active & ~lw & ~lk
        win = self._lane_active & lw & ~lk
        kfull = self._lane_active & ~lw & lk
        kwin = self._lane_active & lw & lk
        tables = np.ascontiguousarray(self.pool.page_tables)
        if self._spec_k > 0:
            (self._dev_tokens, self._dev_positions, self._dev_active,
             self._dev_active_win, self._dev_active_kfull,
             self._dev_active_kwin, self._dev_page_tables,
             self._dev_history) = self._put_host(
                (self._lane_tokens, pos, full, win, kfull, kwin, tables,
                 self._lane_history))
            if self._dev_noise is None:
                self._dev_noise = self._put_host(
                    np.zeros((self.pool.max_slots, self._spec_k), np.int32))
        else:
            (self._dev_tokens, self._dev_positions, self._dev_active,
             self._dev_active_win, self._dev_active_kfull,
             self._dev_active_kwin, self._dev_page_tables) = self._put_host(
                (self._lane_tokens, pos, full, win, kfull, kwin, tables))
        self._lane_dirty = False

    def _call_spec_step(self):
        """Dispatch the full-gather speculative step program (dense and
        flash lanes) for the pool's storage mode. Both return (tokens,
        positions, k, v, history, oracle, accepted)."""
        if self._qmode is not None:
            return _spec_step_quant_jit(
                self.params, self.pool.k, self.pool.v,
                self.pool.k_scale, self.pool.v_scale,
                self._dev_page_tables, self._dev_history,
                self._dev_tokens, self._dev_positions, self._dev_active,
                self._dev_noise, n_heads=self.n_heads, k=self._spec_k,
                qmode=self._qmode)
        return _spec_step_jit(  # jaxlint: disable=JL005(exclusive branch: the quant dispatch above never ran)
            self.params, self.pool.k, self.pool.v, self._dev_page_tables,
            self._dev_history, self._dev_tokens, self._dev_positions,
            self._dev_active, self._dev_noise, n_heads=self.n_heads,
            k=self._spec_k)

    def _call_spec_step_window(self):
        """Dispatch the windowed speculative step program (sparse lanes;
        one program handles every storage mode via the static qmode —
        scale operands are None unless int8)."""
        return _spec_step_window_jit(
            self.params, self.pool.k, self.pool.v,
            self.pool.k_scale, self.pool.v_scale, self._dev_page_tables,
            self._dev_history, self._dev_tokens, self._dev_positions,
            self._dev_active_win, self._dev_noise,
            n_heads=self.n_heads, k=self._spec_k,
            page_tokens=self.pool.page_tokens, qmode=self._qmode)

    def _call_spec_step_kernel(self, backend):
        """Dispatch the kernel-tier speculative step program for one lane
        class (``pallas_decode`` = kfull mask, ``pallas_sparse`` = kwin)
        with that backend's resolved registry statics."""
        kernels.record_call(kernels.kernel_for_backend(backend),
                            self._kernel_impl[backend])
        mask = (self._dev_active_kwin if backend == "pallas_sparse"
                else self._dev_active_kfull)
        return _spec_step_kernel_jit(
            self.params, self.pool.k, self.pool.v,
            self.pool.k_scale, self.pool.v_scale, self._dev_page_tables,
            self._dev_history, self._dev_tokens, self._dev_positions,
            mask, self._dev_noise, n_heads=self.n_heads, k=self._spec_k,
            page_tokens=self.pool.page_tokens, qmode=self._qmode,
            attn_backend=backend,
            kernel_impl=self._kernel_impl[backend],
            kernel_interpret=self._kernel_interpret[backend])

    def _check_decode_sentinels(self):
        """Post-dispatch budget asserts for every armed decode pin (the
        per-class programs share the step, so they share the check)."""
        for s in (self.decode_sentinel, self.decode_window_sentinel,
                  self.decode_kernel_sentinel):
            if s is not None:
                s.check()

    def _maybe_update_noise(self):
        """Swap the device-resident draft-noise operand when the
        corrupt_draft fault arm fires (and restore zeros after). The
        operand always exists with the same shape, so firing the fault
        can never recompile the step."""
        if self.injector is None:
            return
        noise = self.injector.corrupt_draft_noise(
            self._step_count, self._spec_k, self.model_config.vocab_size)
        if noise is not None:
            self._dev_noise = self._put_host(np.ascontiguousarray(
                np.broadcast_to(np.asarray(noise, np.int32),
                                (self.pool.max_slots, self._spec_k))))
            self._noise_armed = True
        elif self._noise_armed:
            self._dev_noise = self._put_host(
                np.zeros((self.pool.max_slots, self._spec_k), np.int32))
            self._noise_armed = False

    def drain(self, max_steps=None):
        """Step until no request is queued, prefilling, or in flight.
        ``max_steps`` bounds the loop (a deadline-less stuck request
        would otherwise spin forever under fault injection)."""
        steps = 0
        while self.pending():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return steps

    def pending(self):
        """Requests still owed work: queued + chunking + in flight."""
        return (len(self._active) + self.family.prefilling(self)
                + self.scheduler.queue_depth())

    def _put_prefill_kv(self, arr):
        """Host prefix-KV seed -> device, heads-sharded on a mesh (dims
        [L, B, nh, S, hd] split at nh like the pool) so prefill starts
        from the layout its outputs and the pool install already use."""
        if self._prefill_kv_sharding is None:
            return jnp.asarray(arr)
        return jax.device_put(np.asarray(arr), self._prefill_kv_sharding)

    def _zeros_prefill_kv(self, shape, dtype):
        if self._prefill_kv_sharding is None:
            return jnp.zeros(shape, dtype)
        return jnp.zeros(shape, dtype, device=self._prefill_kv_sharding)

    @property
    def draining(self):
        return self._draining

    def begin_drain(self):
        """Planned-restart drain: stop admitting (``submit`` raises
        ``EngineDrainingError``), keep stepping accepted work to
        completion. The SIGTERM path: a replica flips this, finishes its
        in-flight lanes, then exits ``EXIT_PREEMPTED`` so the supervisor
        restarts it without backoff while the router re-routes around
        it."""
        self._draining = True

    # -- loop-thread marshaling -----------------------------------------
    def _drain_loop_ops(self):
        """Run pool ops posted by other threads (handoff claim/install/
        free/resume) on the serving-loop thread, where all pool mutation
        belongs."""
        while True:
            try:
                fn, done, box = self._loop_ops.get_nowait()
            except _queue_mod.Empty:
                return
            try:
                box.append(("ok", fn()))
            except BaseException as exc:  # marshal, don't kill the loop
                box.append(("err", exc))
            finally:
                done.set()

    def _run_on_loop(self, fn, timeout_s=30.0):
        """Execute ``fn`` on the serving-loop thread and return its
        result (re-raising its exception here). Runs inline when no
        background loop is active or when already on the loop thread."""
        t = self._loop_thread
        if t is None or not t.is_alive() \
                or t is threading.current_thread():
            return fn()
        done = threading.Event()
        box = []
        self._loop_ops.put((fn, done, box))
        if not done.wait(timeout_s):
            raise TimeoutError(
                f"serving loop did not service a marshaled op within "
                f"{timeout_s}s (loop stalled?)")
        kind, val = box[0]
        if kind == "err":
            raise val
        return val

    # -- background mode ------------------------------------------------
    def start(self, idle_sleep_s=0.001):
        """Run the serving loop on a daemon thread until ``stop()``."""
        if self._loop_thread is not None:
            raise RuntimeError("serving loop already running")
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                busy = self.step()
                if not any(busy.values()) and not self._active:
                    time.sleep(idle_sleep_s)

        self._loop_thread = threading.Thread(
            target=loop, name="serving-loop", daemon=True)
        self._loop_thread.start()

    def stop(self, timeout_s=5.0):
        if self._loop_thread is None:
            return
        self._stop.set()
        self._loop_thread.join(timeout_s)
        self._loop_thread = None
        self._drain_loop_ops()   # release any waiter the loop left behind

    def close(self):
        self.stop()
        self.metrics.close()
        if self.telemetry_server is not None:
            self.telemetry_server.stop()
            self.telemetry_server = None
        if self._trace_file:
            self._tracer.write(self._trace_file)

    # -- admission ------------------------------------------------------
    def _admit_from_queue(self, stats, now):
        """Join-at-free-slot admission, batched per bucket: pop the FIFO
        head, gather every queued request sharing its (prefix-adjusted)
        bucket up to the free-slot count, and prefill them as ONE call.
        Long prompts divert to the chunked path (one at a time).
        ``now`` is the latest stamp the iteration holds: an admission
        that admitted anything is timed from it to its end."""
        admitted = stats["admitted"]
        if self._tracer.enabled and self.scheduler.queue_depth() > 0:
            with self._tracer.span(
                    "serving/admission", cat="serving",
                    args={"queue_depth": self.scheduler.queue_depth()}):
                self.family.admit(self, stats)
        else:
            self.family.admit(self, stats)
        if stats["admitted"] > admitted:
            self.metrics.admit_time_s += time.monotonic() - now

    def _impl_for_len(self, prompt_len):
        """Attention backend for a request, selected by its FULL prompt
        length's bucket (not the prefix-adjusted suffix bucket — the
        prefix lookup itself is backend-filtered, so selection must not
        depend on it)."""
        return self._impl_map.get(
            bucket_for(prompt_len, self.scheduler.buckets),
            self._impl_default)

    def _alloc_tokens(self, req):
        """Page budget claimed for a request at admission: the exact
        prompt + generation span (rounded up to whole pages by the
        allocator). Under fault injection, stuck/runaway lanes may
        decode past their natural length, so claim the full lane.

        A handoff-export request overrides the budget with the ORIGINAL
        request's full reserve: int8 install quantizes over the whole
        allocated span, so the exported pages must be laid out exactly
        as a mixed-mode admission of the original request would lay
        them out — bit-for-bit."""
        if self.injector is not None:
            return None
        override = getattr(req, "alloc_tokens_override", None)
        if override is not None:
            return int(override)
        return min(len(req.prompt) + req.max_new_tokens, self.max_seq_len)

    def _admit_from_queue_now(self, stats):
        while self.pool.free_slots > 0:
            head = self.scheduler.pop_next()
            if head is None:
                return
            if not self.pool.can_allocate(self._alloc_tokens(head)):
                # page-pool backpressure: release host-side ballast once
                # (unreferenced prefix entries demote to spill, spill
                # tier sheds) before parking the FIFO head — transient
                # memory pressure self-heals instead of round-tripping
                # through requeue backpressure
                if (not self._relieve_memory_pressure()
                        or not self.pool.can_allocate(
                            self._alloc_tokens(head))):
                    self.scheduler.requeue_front(head)
                    return
            if self._needs_chunking(head):
                if self._chunking is not None:
                    self.scheduler.requeue_front(head)   # chunk lane is busy
                    return
                if not self._start_chunked(head):
                    return                   # pages raced away (requeued)
                stats["admitted"] += 1
                continue
            bucket = bucket_for(self._suffix_len(head), self.scheduler.buckets)
            impl = self._impl_for_len(len(head.prompt))
            group = [head]
            room = min(self.pool.free_slots - 1, self._prefill_batch - 1)
            if room > 0:
                group += self.scheduler.pop_matching(
                    lambda r: (not self._needs_chunking(r)
                               and self._impl_for_len(len(r.prompt)) == impl
                               and bucket_for(self._suffix_len(r),
                                              self.scheduler.buckets)
                               == bucket),
                    room)
            admitted, retired = self._admit_batch(group, bucket, impl)
            stats["admitted"] += admitted
            stats["retired"] += retired
            if admitted < len(group):
                return                       # pages ran out mid-group

    def _admit_batch(self, group, bucket, impl):
        """Prefill ``group`` (same bucket AND attention backend) as one
        [MaxSlots, Sb] call and install each lane into its slot. Slots
        and pages are claimed FIRST: members the page pool cannot hold
        are requeued in FIFO order before any compute runs. Returns
        (admitted, retired-on-their-very-first-token) counts."""
        pspan = (self._tracer.span(
                     "serving/prefill_batch", cat="serving",
                     args={"request_ids": [r.id for r in group],
                           "bucket": bucket, "group": len(group)})
                 if self._tracer.enabled else telemetry.NULL_SPAN)
        pspan.__enter__()
        B, total = self._prefill_batch, self.max_seq_len
        pt = self.pool.page_tokens
        # the sparse prefills' blocked attention needs a page-multiple
        # chunk width; pad queries are invisible (outputs discarded,
        # their garbage KV is overwritten by decode before attendable)
        Sb = (_round_up(bucket, pt)
              if impl in ("sparse_xla", "pallas_sparse") else bucket)
        ids = np.zeros((B, Sb), np.int32)
        starts = np.zeros(B, np.int32)
        lens = np.ones(B, np.int32)        # dummy lanes: 1-token no-ops
        plan = []
        any_hit = False
        for req in group:
            try:
                slot = self.pool.allocate(self._alloc_tokens(req))
            except PoolExhaustedError:
                if not self._relieve_memory_pressure():
                    break
                try:        # one retry after shedding host-side ballast
                    slot = self.pool.allocate(self._alloc_tokens(req))
                except PoolExhaustedError:
                    break
            i = len(plan)
            req.attn_impl = impl
            reuse, entry = self._acquire_prefix(req)
            suffix = req.prompt[reuse:]
            ids[i, :len(suffix)] = suffix
            starts[i] = reuse
            lens[i] = len(req.prompt)
            plan.append((req, reuse, entry, slot))
            any_hit = any_hit or reuse > 0
            self.metrics.record_admission(bucket, len(req.prompt))
        for req in reversed(group[len(plan):]):
            self.scheduler.requeue_front(req)    # pages exhausted mid-group
        if not plan:
            pspan.__exit__(None, None, None)
            return 0, 0
        # prefill runs in the COMPUTE dtype regardless of pool storage:
        # the quantize happens once, at lane install
        shape = (self.n_layers, B, self.n_heads, total, self.head_dim)
        cdtype = self.pool.compute_dtype
        if any_hit:
            # seed hit lanes from host-resident prefix KV; one transfer
            init_k = np.zeros(shape, cdtype)
            init_v = np.zeros(shape, cdtype)
            for i, (req, reuse, entry, _slot) in enumerate(plan):
                if reuse > 0:
                    ek, ev = self._entry_prefix_kv(entry, reuse)
                    init_k[:, i, :, :reuse] = ek
                    init_v[:, i, :, :reuse] = ev
            init_k = self._put_prefill_kv(init_k)
            init_v = self._put_prefill_kv(init_v)
        else:
            init_k = self._zeros_prefill_kv(shape, cdtype)
            init_v = self._zeros_prefill_kv(shape, cdtype)

        t0 = time.monotonic()
        k, v, first = self._run_prefill(impl, init_k, init_v,
                                        self._put_host(ids),
                                        self._put_host(starts),
                                        self._put_host(lens))
        first_host = np.asarray(first)             # sync: TTFT endpoint
        prefill_s = time.monotonic() - t0
        self._prefill_seq += 1
        # every row of the bucket runs, whatever the group's size
        self.metrics.record_prefill(
            tokens=sum(len(r.prompt) - re for r, re, _, _ in plan),
            reused_tokens=sum(re for _, re, _, _ in plan),
            requests=len(plan), prefill_s=prefill_s, positions_run=B * Sb)
        self.metrics.record_queue_wait(
            sum(t0 - r.submit_time for r, _, _, _ in plan), len(plan))

        ispan = (self._tracer.span("serving/install", cat="serving",
                                   args={"group": len(plan)})
                 if self._tracer.enabled else telemetry.NULL_SPAN)
        ispan.__enter__()
        now = time.monotonic()
        retired = 0
        for i, (req, reuse, entry, slot) in enumerate(plan):
            self._maybe_insert_prefix(req, reuse, k, v, lane=i)
            self.pool.install_lane(k, v, lane=i, slot=slot,
                                   position=len(req.prompt))
            req.prefix_entry = entry
            retired += self._first_token(req, slot, int(first_host[i]), now)
        # settle the queued lane installs here so they are accounted to
        # admission, not silently absorbed into the next decode step's
        # measured latency
        self.pool.k.block_until_ready()
        ispan.__exit__(None, None, None)
        pspan.__exit__(None, None, None)
        return len(plan), retired

    def _run_prefill(self, impl, init_k, init_v, ids, starts, lens):
        """Dispatch the per-backend batched prefill program (each with
        its own CompileSentinel pin when armed)."""
        if impl == "sparse_xla":
            out = _prefill_batch_window_jit(
                self.params, init_k, init_v, ids, starts, lens,
                n_heads=self.n_heads, page_tokens=self.pool.page_tokens)
            sentinel = self.prefill_window_sentinel
        elif impl == "pallas_decode":
            kernels.record_call("decode_attention",
                                self._kernel_impl["pallas_decode"])
            out = _prefill_batch_kernel_jit(
                self.params, init_k, init_v, ids, starts, lens,
                n_heads=self.n_heads, page_tokens=self.pool.page_tokens,
                kernel_impl=self._kernel_impl["pallas_decode"],
                kernel_interpret=self._kernel_interpret["pallas_decode"])
            sentinel = self.prefill_kernel_sentinel
        elif impl == "pallas_sparse":
            kernels.record_call("sparse_attention",
                                self._kernel_impl["pallas_sparse"])
            out = _prefill_batch_kernel_window_jit(
                self.params, init_k, init_v, ids, starts, lens,
                n_heads=self.n_heads, page_tokens=self.pool.page_tokens,
                kernel_impl=self._kernel_impl["pallas_sparse"],
                kernel_interpret=self._kernel_interpret["pallas_sparse"])
            sentinel = self.prefill_kernel_window_sentinel
        elif impl == "flash":
            out = _prefill_batch_flash_jit(
                self.params, init_k, init_v, ids, starts, lens,
                n_heads=self.n_heads, page_tokens=self.pool.page_tokens)
            sentinel = self.prefill_flash_sentinel
        else:
            out = _prefill_batch_jit(
                self.params, init_k, init_v, ids, starts, lens,
                n_heads=self.n_heads)
            sentinel = self.prefill_sentinel
        if sentinel is not None:
            sentinel.check()
        return out

    # -- chunked prefill ------------------------------------------------
    def _needs_chunking(self, req):
        chunk = self.config.prefill_chunk_tokens
        return chunk > 0 and self._suffix_len(req) > chunk

    def _start_chunked(self, req):
        """Reserve a slot+pages and a private cache for ``req`` and let
        ``_advance_chunk`` feed it one chunk per engine step. Returns
        False (request requeued) if the page pool cannot hold it."""
        req.attn_impl = self._impl_for_len(len(req.prompt))
        reuse, entry = self._acquire_prefix(req)
        req.prefix_entry = entry
        try:
            # reserved up front: completion can't stall on a full pool
            slot = self.pool.allocate(self._alloc_tokens(req))
        except PoolExhaustedError:
            slot = None
            if self._relieve_memory_pressure():
                try:    # one retry after shedding host-side ballast
                    slot = self.pool.allocate(self._alloc_tokens(req))
                except PoolExhaustedError:
                    slot = None
            if slot is None:
                if entry is not None and self.prefix_cache is not None:
                    self.prefix_cache.release(entry)
                    req.prefix_entry = None
                self.scheduler.requeue_front(req)
                return False
        self.metrics.record_admission(
            bucket_for(self._suffix_len(req), self.scheduler.buckets),
            len(req.prompt))
        shape = (self.n_layers, 1, self.n_heads, self.max_seq_len,
                 self.head_dim)
        cdtype = self.pool.compute_dtype
        if reuse > 0:
            k0 = np.zeros(shape, cdtype)
            v0 = np.zeros(shape, cdtype)
            ek, ev = self._entry_prefix_kv(entry, reuse)
            k0[:, 0, :, :reuse] = ek
            v0[:, 0, :, :reuse] = ev
            k0, v0 = self._put_prefill_kv(k0), self._put_prefill_kv(v0)
        else:
            k0 = self._zeros_prefill_kv(shape, cdtype)
            v0 = self._zeros_prefill_kv(shape, cdtype)
        self._chunking = _ChunkedPrefill(req, k0, v0, pos=reuse, reuse=reuse,
                                         slot=slot)
        return True

    def _advance_chunk(self, stats):
        """Run the next chunk of the in-flight chunked prefill (same
        compiled program as batched prefill, at B=1/Sb=chunk); install
        and activate on the final chunk. Mid chunks never block the host
        — only the final chunk syncs, for its first token. Returns the
        last clock stamp it took; a chunk's own time is admission time."""
        st = self._chunking
        req = st.req
        top = now = time.monotonic()
        if req.deadline_exceeded(now):
            req.slot = st.slot             # hand the reserved slot back
            self._finish_timeout(req, phase="prefill")
            self._chunking = None
            stats["retired"] += 1
            return now
        impl = getattr(req, "attn_impl", "dense")
        chunk_len = self.config.prefill_chunk_tokens
        # sparse chunks pad to a page multiple (blocked attention width
        # constraint); a chunk's pad garbage is overwritten by the next
        # chunk's real writes before it is ever attendable, and the
        # final chunk's by decode — same write-before-attend argument
        # as batched prefill padding
        cw = (_round_up(chunk_len, self.pool.page_tokens)
              if impl in ("sparse_xla", "pallas_sparse") else chunk_len)
        chunk = req.prompt[st.pos:st.pos + chunk_len]
        ids = np.zeros((1, cw), np.int32)
        ids[0, :len(chunk)] = chunk
        cspan = (self._tracer.span("serving/prefill_chunk", cat="serving",
                                   args={"request_id": req.id, "pos": st.pos,
                                         "chunk": len(chunk)})
                 if self._tracer.enabled else telemetry.NULL_SPAN)
        t0 = time.monotonic()
        if st.pos == st.reuse:                     # the first chunk
            self.metrics.record_queue_wait(t0 - req.submit_time)
        with cspan:
            st.k, st.v, first = self._run_prefill(
                impl, st.k, st.v, self._put_host(ids),
                self._put_host(np.asarray([st.pos], np.int32)),
                self._put_host(np.asarray([len(req.prompt)], np.int32)))
        st.pos += len(chunk)
        st.positions_run += cw
        stats["prefill_chunks"] += 1
        self._prefill_seq += 1
        if st.pos < len(req.prompt):
            now = time.monotonic()
            st.prefill_s += now - t0
            self.metrics.admit_time_s += now - top
            return now
        first_tok = int(np.asarray(first)[0])      # sync: TTFT endpoint
        st.prefill_s += time.monotonic() - t0
        now = time.monotonic()
        self.metrics.admit_time_s += now - top
        self.metrics.record_prefill(
            tokens=len(req.prompt) - st.reuse, reused_tokens=st.reuse,
            requests=1, prefill_s=st.prefill_s,
            positions_run=st.positions_run)
        self._maybe_insert_prefix(req, st.reuse, st.k, st.v, lane=0)
        self.pool.install(st.k, st.v, st.slot, position=len(req.prompt))
        stats["retired"] += self._first_token(req, st.slot, first_tok, now)
        self._chunking = None
        return now

    # -- prefix cache ---------------------------------------------------
    def _suffix_len(self, req):
        """Tokens a prefill would actually compute for ``req`` after
        prefix-cache reuse (always >= 1: the last prompt position is
        recomputed to produce the first token's logits)."""
        if self.prefix_cache is None:
            return len(req.prompt)
        length, _ = self.prefix_cache.match(
            req.prompt, impl=self._impl_for_len(len(req.prompt)))
        return len(req.prompt) - min(length, len(req.prompt) - 1)

    def _acquire_prefix(self, req):
        """Counted, ref-taking lookup at admission time. Returns
        (reused_tokens, entry-or-None); the ref is released at the
        request's retirement (any path)."""
        if self.prefix_cache is None:
            return 0, None
        length, entry = self.prefix_cache.acquire(
            req.prompt, impl=getattr(req, "attn_impl", "dense"))
        reuse = min(length, len(req.prompt) - 1)
        if entry is not None and reuse <= 0:
            self.prefix_cache.release(entry)
            entry, reuse = None, 0
        self.metrics.record_prefix_lookup(hit=reuse > 0)
        return reuse, entry

    def _maybe_insert_prefix(self, req, reuse, k, v, lane):
        """Store the freshly-prefilled prompt's KV for future requests
        (skipped when an existing entry already covers the whole prompt
        — nothing new to add). In int8 pool mode entries are stored
        QUANTIZED (per-(layer, head) scales over the cached positions):
        the trie's byte budget buys ~4x the prefix positions, same
        at-use-dequant contract as the pool itself."""
        if self.prefix_cache is None:
            return
        if self._degrade_rung >= 2:
            # budget_shrink rung: stop growing the host-RAM trie under
            # overload (lookups/hits still work — reuse stays free)
            return
        if self._mem_guard is not None and self._mem_guard.inserts_paused:
            # host-RSS watermark breached: stop allocating host memory
            # for new entries until the guard recovers (hits still work)
            return
        n = len(req.prompt)
        if reuse >= n - 1:
            return
        # entries are tagged with the backend that produced them: for
        # L >= 2 layers the backends' hidden states (hence deep-layer
        # KV) differ in low bits, so cross-backend seeding would break
        # the per-backend bitwise oracle
        impl = getattr(req, "attn_impl", "dense")
        pk = np.asarray(k[:, lane, :, :n])
        pv = np.asarray(v[:, lane, :, :n])
        if self.pool.kv_cache_dtype == "int8":
            pk, k_scale = quantize_kv_np(pk)
            pv, v_scale = quantize_kv_np(pv)
            self.prefix_cache.insert(req.prompt, pk, pv,
                                     k_scale=k_scale, v_scale=v_scale,
                                     impl=impl)
            return
        self.prefix_cache.insert(req.prompt, pk, pv, impl=impl)

    def _entry_prefix_kv(self, entry, reuse):
        """A prefix entry's first ``reuse`` positions in the pool's
        COMPUTE dtype (int8-mode entries dequantize here, at seed
        time — never inside the prefill program)."""
        ek = entry.k[:, :, :reuse]
        ev = entry.v[:, :, :reuse]
        if entry.k_scale is not None:
            dt = np.dtype(self.pool.compute_dtype)
            return (dequantize_kv_np(ek, entry.k_scale, dt),
                    dequantize_kv_np(ev, entry.v_scale, dt))
        return ek, ev

    # -- internals ------------------------------------------------------
    def _activate(self, req, slot, first_tok, emit=True):
        req.slot = slot
        self._active[slot] = req
        self._lane_tokens[slot] = first_tok
        self._lane_active[slot] = True
        impl = getattr(req, "attn_impl", "dense")
        self._lane_impl_window[slot] = impl in ("sparse_xla", "pallas_sparse")
        self._lane_impl_kernel[slot] = impl in ("pallas_decode",
                                                "pallas_sparse")
        if self._lane_history is not None:
            # seed the drafter: prompt tokens by position, then the
            # PENDING first generated token at position len(prompt)
            row = self._lane_history[slot]
            row[:] = 0
            row[:len(req.prompt)] = req.prompt
            row[len(req.prompt)] = first_tok
        self._lane_dirty = True
        if emit:
            self._emit(req, first_tok)

    def _first_token(self, req, slot, first_tok, now):
        """A prompt's prefill is done and its state is in ``slot``: stamp
        and hand out the first token and join the decode lanes. Returns 1
        if that token already ended the request, else 0."""
        req.first_token_time = now
        self.metrics.record_first_token(now - req.submit_time)
        self._stamp_token(req, now)
        self._activate(req, slot, first_tok)
        return self._maybe_retire(req, first_tok, now)

    def _stamp_token(self, req, now):
        """Beside each token handed out: ``now`` is the stamp the iteration
        already holds (taken after the read-back that produced the token).
        The gap to the request's previous token is counted from it, and
        counted stalled when a prefill ran since that token."""
        if req.last_emit_time is not None:
            self.metrics.record_token_gap(
                now - req.last_emit_time,
                req.last_emit_prefill_seq != self._prefill_seq)
        req.last_emit_time = now
        req.last_emit_prefill_seq = self._prefill_seq

    def _emit(self, req, token):
        req.emitted += 1
        req.future._append(token)
        if req.stream_cb is not None:
            try:
                req.stream_cb(req.id, token)
            except Exception:  # a broken callback must not kill the loop
                pass

    def _maybe_retire(self, req, token, now):
        stuck = (self.injector is not None
                 and self.injector.request_is_stuck(req.id))
        if req.deadline_exceeded(now):
            self._finish_timeout(req, phase="decoding")
            return 1
        if self.scheduler.should_retire(req, token, stuck=stuck) is not None:
            self._release_slot(req)
            req.future._finish()
            self.scheduler.completed += 1
            self.metrics.record_completion()
            if self._tracer.enabled:
                self._tracer.instant("serving/retire", cat="serving",
                                     args={"request_id": req.id,
                                           "tokens": req.emitted})
            return 1
        return 0

    def _finish_timeout(self, req, phase):
        self._release_slot(req)
        if self._tracer.enabled:
            self._tracer.instant("serving/retire_timeout", cat="serving",
                                 args={"request_id": req.id, "phase": phase,
                                       "tokens": req.emitted})
        req.future._finish(RequestTimeoutError(
            req.id, req.timeout_s, phase, tokens_done=req.emitted))
        self.scheduler.timed_out += 1
        self.metrics.record_timeout()

    def _release_slot(self, req):
        if req.slot is not None and getattr(req, "handoff_export", False):
            # snapshot the lane's pages before the slot is freed; the
            # replica's handoff sender ships them to the decode worker
            try:
                req.export_payload = self.pool.export_lane(req.slot)
                self.metrics.record_handoff("export")
            except Exception as exc:
                req.export_error = exc
        if req.slot is not None:
            self._lane_active[req.slot] = False
            self._lane_impl_window[req.slot] = False
            self._lane_impl_kernel[req.slot] = False
            self._lane_dirty = True
            self._active.pop(req.slot, None)
            self.pool.free(req.slot)
            req.slot = None
        if req.prefix_entry is not None and self.prefix_cache is not None:
            self.prefix_cache.release(req.prefix_entry)
            req.prefix_entry = None

    # -- introspection ---------------------------------------------------
    def occupancy(self):
        return self.pool.occupancy()

    def prefix_stats(self):
        """Prefix-cache counters, or None when the cache is disabled."""
        return None if self.prefix_cache is None else self.prefix_cache.stats()
