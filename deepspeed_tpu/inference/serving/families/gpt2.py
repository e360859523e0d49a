"""GPT-2 behind the serving loop: its thirteen jitted programs, the paged
``KVCachePool``, attention backends, pool quantization, speculation, chunked
prefill, batch admission and the prefix cache's acquire/insert. The contract
it is called through is ``serving/family.py``.

The decode step is ONE jitted program for the life of the server: a
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
Pallas attention resolved ONCE at construction through the
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

import time
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
from deepspeed_tpu.profiling.sentinels import CompileSentinel
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
from deepspeed_tpu.inference.serving.family import ServingFamily
from deepspeed_tpu.inference.serving.kv_pool import (
    KVCachePool,
    PoolExhaustedError,
)
from deepspeed_tpu.inference.serving.scheduler import bucket_for


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


@partial(jax.jit, static_argnames=("n_heads",),
         donate_argnums=(1, 2))  # jaxlint: hot
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
    h, (k, v) = _forward_chunk(params, n_heads, (init_k, init_v),
                               padded_ids, starts)
    return k, v, _prefill_tail(params, h, starts, true_lens)


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
         donate_argnums=(1, 2))  # jaxlint: hot
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
         donate_argnums=(1, 2))  # jaxlint: hot
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
         donate_argnums=(1, 2))  # jaxlint: hot
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
         donate_argnums=(1, 2))  # jaxlint: hot
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


@partial(jax.jit, static_argnames=("n_heads",),
         donate_argnums=(1, 2, 4, 5))  # jaxlint: hot
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
         donate_argnums=(1, 2, 6, 7))  # jaxlint: hot
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
         donate_argnums=(1, 2, 6, 7))  # jaxlint: hot
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
         donate_argnums=(1, 2, 6, 7))  # jaxlint: hot
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


def _spec_step_paged(params, pool_k, pool_v, k_scale, v_scale, page_tables,
                     history, tokens, positions, active, draft_noise, *,
                     n_heads, k, qmode, **verify):
    """Shared body of the speculative step programs that take scale
    operands (the plain one is this with no conversion): gather every
    lane's pages into its contiguous stripe, dequantize at use (``qmode``:
    int8 * per-head scale, a bf16 cast, or None for storage == compute),
    run the draft/verify core in the compute dtype (``verify`` holds the
    core's backend statics), then requantize against the FIXED
    per-(slot, head) install scales (or cast) and scatter back the k+1
    written rows by page index. Untouched positions round-trip bitwise
    (idempotent requant), so only the freshly-written rows actually
    change."""
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
        draft_noise, k, **verify)
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


@partial(jax.jit, static_argnames=("n_heads", "k"),
         donate_argnums=(1, 2, 4, 5, 6))  # jaxlint: hot
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
    only reachable after the request's retirement point, see the loop's
    ``alloc_tokens``). ``k`` and the lane count are static; drafts,
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
         donate_argnums=(1, 2, 6, 7, 8))  # jaxlint: hot
def _spec_step_quant_jit(params, pool_k, pool_v, k_scale, v_scale,
                         page_tables, history, tokens, positions, active,
                         draft_noise, *, n_heads, k, qmode):
    """Speculative step over a quantized paged pool (``qmode`` is static;
    scale operands are None unless int8)."""
    return _spec_step_paged(
        params, pool_k, pool_v, k_scale, v_scale, page_tables, history,
        tokens, positions, active, draft_noise, n_heads=n_heads, k=k,
        qmode=qmode)


@partial(jax.jit, static_argnames=("n_heads", "k", "page_tokens", "qmode"),
         donate_argnums=(1, 2, 6, 7, 8))  # jaxlint: hot
def _spec_step_window_jit(params, pool_k, pool_v, k_scale, v_scale,
                          page_tables, history, tokens, positions, active,
                          draft_noise, *, n_heads, k, page_tokens, qmode):
    """Speculative step for sparse-backend lanes: same draft/accept core,
    with the k+1-wide verify forward attending the windowed key set
    (``_speculative_verify_window``). The verify gathers full lane
    stripes like the dense spec step — speculation is a latency
    trade-off knob, not the steady-state path the windowed decode
    optimizes."""
    return _spec_step_paged(
        params, pool_k, pool_v, k_scale, v_scale, page_tables, history,
        tokens, positions, active, draft_noise, n_heads=n_heads, k=k,
        qmode=qmode, window_pt=page_tokens)


@partial(jax.jit, static_argnames=("n_heads", "k", "page_tokens", "qmode",
                                   "attn_backend", "kernel_impl",
                                   "kernel_interpret"),
         donate_argnums=(1, 2, 6, 7, 8))  # jaxlint: hot
def _spec_step_kernel_jit(params, pool_k, pool_v, k_scale, v_scale,
                          page_tables, history, tokens, positions, active,
                          draft_noise, *, n_heads, k, page_tokens, qmode,
                          attn_backend, kernel_impl, kernel_interpret):
    """Speculative step for kernel-tier lanes: same draft/accept core as
    ``_spec_step_window_jit``, with the k+1-wide verify forward routed
    through the resolved kernel backend (``attn_backend`` is the static
    ``pallas_decode``/``pallas_sparse`` name; speculation trades gather
    traffic for acceptance throughput)."""
    return _spec_step_paged(
        params, pool_k, pool_v, k_scale, v_scale, page_tables, history,
        tokens, positions, active, draft_noise, n_heads=n_heads, k=k,
        qmode=qmode, window_pt=page_tokens, kernel_backend=attn_backend,
        kernel_impl=kernel_impl, kernel_interpret=kernel_interpret)


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


class GPT2Family(ServingFamily):
    """GPT-2's programs over a ``KVCachePool``: every option of
    ``ServingConfig`` is supported."""

    name = "gpt2"

    def __init__(self, model_config):
        self.cfg = model_config
        self.n_layers = model_config.num_hidden_layers
        self.n_heads = model_config.num_attention_heads
        self.head_dim = model_config.hidden_size // self.n_heads

    def check_options(self, cfg, params):
        pass

    def build(self, loop, params):
        self.loop = loop
        cfg = loop.config
        self._impl_default, self._impl_map = _parse_attention_impl(
            cfg.attention_impl, loop.scheduler.buckets)
        impls = set(self._impl_map.values())
        impls.add(self._impl_default)
        self._any_window = "sparse_xla" in impls
        self._any_flash = "flash" in impls
        self._any_kfull = "pallas_decode" in impls
        self._any_kwin = "pallas_sparse" in impls
        page_tokens = resolve_page_tokens(
            cfg.kv_page_tokens or DEFAULT_PAGE_TOKENS, loop.max_seq_len)
        if ((self._any_window or self._any_kwin)
                and loop.max_seq_len < (SPARSE_BAND + 1) * page_tokens):
            raise ValueError(
                f"serving.attention_impl='sparse_xla'/'pallas_sparse' needs "
                f"at least {SPARSE_BAND + 1} pages per lane: max_seq_len="
                f"{loop.max_seq_len} < {(SPARSE_BAND + 1) * page_tokens} "
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
            params = self.registry.shard(self.mesh, params)
            self._replicated_sharding = serving_sharding(
                self.mesh, "serving/lane_state", registry=self.registry)
            self._prefill_kv_sharding = serving_sharding(
                self.mesh, "serving/prefill_kv", registry=self.registry)

        dtype = _cache_dtype(params)
        pool = KVCachePool(self.n_layers, cfg.max_slots, self.n_heads,
                           loop.max_seq_len, self.head_dim, dtype=dtype,
                           kv_cache_dtype=cfg.kv_cache_dtype,
                           page_tokens=cfg.kv_page_tokens,
                           pool_tokens=cfg.kv_pool_tokens,
                           mesh=self.mesh, registry=self.registry)
        # _qmode: storage<->compute conversion the decode programs need.
        # "fp32" stores the compute dtype directly, and "bf16" on a bf16
        # checkpoint is ALSO storage==compute — both take the plain
        # (bitwise) programs; only a real narrowing pays the quant path.
        self._qmode = None
        if cfg.kv_cache_dtype == "int8":
            self._qmode = "int8"
        elif jnp.dtype(pool.k.dtype) != jnp.dtype(dtype):
            self._qmode = "bf16"

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
        self._dev_active_win = None
        self._dev_active_kfull = None
        self._dev_active_kwin = None
        # speculative state: per-lane token-by-position history feeding
        # the n-gram drafter (host mirror for churn re-upload, device
        # buffer advanced in-jit between churns) and the corrupt_draft
        # noise operand (all-zeros = bitwise no-op)
        self._spec_k = int(cfg.speculative_k)
        self._spec_on = True                # the degrade ladder's rung 1
        self._lane_history = (
            np.zeros((cfg.max_slots, loop.max_seq_len), np.int32)
            if self._spec_k > 0 else None)
        self._dev_history = None
        self._dev_noise = None
        self._noise_armed = False
        self.decode_window_sentinel = None
        self.prefill_window_sentinel = None
        self.prefill_flash_sentinel = None
        self.decode_kernel_sentinel = None
        self.prefill_kernel_sentinel = None
        self.prefill_kernel_window_sentinel = None
        # batched prefill always runs at the pool width: the batch dim is
        # STATIC, so any admission-group size shares one program per bucket
        self._prefill_batch = cfg.max_slots
        self._chunking = None               # at most one chunked prefill
        return params, pool

    def sentinel_programs(self):
        if self._spec_k > 0:
            decode_prog = (_spec_step_quant_jit if self._qmode
                           else _spec_step_jit)
        else:
            decode_prog = (_decode_step_quant_jit if self._qmode
                           else _decode_step_jit)
        return decode_prog, _prefill_batch_jit

    def arm_sentinels(self, budget):
        super().arm_sentinels(budget)
        # backend programs get their own pins only when armed — an
        # all-dense config keeps the exact legacy sentinel set
        if self._any_window:
            self.decode_window_sentinel = CompileSentinel(
                _spec_step_window_jit if self._spec_k > 0
                else _decode_step_window_jit,
                budget, name="serving window decode step")
            self.prefill_window_sentinel = CompileSentinel(
                _prefill_batch_window_jit, budget,
                name="serving window prefill")
        if self._any_flash:
            self.prefill_flash_sentinel = CompileSentinel(
                _prefill_batch_flash_jit, budget,
                name="serving flash prefill")
        # kernel-class decode pins: pallas_decode lanes always run a
        # kernel-tier program; pallas_sparse lanes run the kernel spec
        # step under speculation but the (kernel-static) window
        # program otherwise, so non-spec kwin pins that instead
        if self._any_kfull or (self._any_kwin and self._spec_k > 0):
            self.decode_kernel_sentinel = CompileSentinel(
                _spec_step_kernel_jit if self._spec_k > 0
                else _decode_step_kernel_jit,
                budget, name="serving kernel decode step")
        if (self._any_kwin and self._spec_k == 0
                and self.decode_window_sentinel is None):
            self.decode_window_sentinel = CompileSentinel(
                _decode_step_window_jit, budget,
                name="serving window decode step")
        if self._any_kfull:
            self.prefill_kernel_sentinel = CompileSentinel(
                _prefill_batch_kernel_jit, budget,
                name="serving kernel prefill")
        if self._any_kwin:
            self.prefill_kernel_window_sentinel = CompileSentinel(
                _prefill_batch_kernel_window_jit, budget,
                name="serving kernel window prefill")

    def export_telemetry(self, registry, server):
        if self._kernel_impl:
            # per-kernel selected-backend gauges next to the
            # Kernels/<name>/calls counters at /metrics
            kernels.get_registry().export_gauges(registry)
        if server is not None:
            server.add_snapshot_provider("kernels", kernels.registry_snapshot)

    def prefilling(self):
        return 1 if self._chunking is not None else 0

    # -- lanes ------------------------------------------------------------
    def lane_joined(self, req, slot, first_tok):
        # also where a handoff-resumed request, which no admission saw,
        # learns its backend
        impl = req.attn_impl = self._impl_for_len(len(req.prompt))
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

    def lane_left(self, slot):
        self._lane_impl_window[slot] = False
        self._lane_impl_kernel[slot] = False

    def set_speculation(self, on):
        """Rung >= 1 runs the classic one-token decode program (which always
        exists — it IS the k=0 path), so toggling never recompiles anything
        new per rung flip. Crossing the boundary switches decode programs:
        re-upload lane state so the program about to run sees fresh operands
        (spec needs the host history mirror, which the classic path keeps
        warm — see ``decode_step``)."""
        if self._spec_k > 0 and on != self._spec_on:
            self._spec_on = on
            self.loop.lanes.dirty = True

    def _lane_classes(self):
        """The four backend lane classes' host masks, in dispatch order:
        full-gather, window, kernel full, kernel window."""
        act = self.loop.lanes.active
        lw, lk = self._lane_impl_window, self._lane_impl_kernel
        return act & ~lw & ~lk, act & lw & ~lk, act & ~lw & lk, act & lw & lk

    def _put_host(self, tree):
        """Sharding-aware host upload: on a mesh, commit to the
        registry's replicated lane-state sharding — a default-device
        put on a >1-device mesh would land on device 0 and force a
        reshard inside the next jitted step, breaking the
        ``transfer_free()`` steady-state contract."""
        if self._replicated_sharding is None:
            return jax.device_put(tree)
        return jax.device_put(tree, self._replicated_sharding)

    def upload_lanes(self):
        """Lane churn: ONE explicit upload of the lane vectors, the
        per-class active masks, the page tables, and the drafter history
        when speculation is armed; between churn events they live on
        device and never move. Page-table churn rides the same dirty
        flag lane churn already sets (allocate/free happen exactly
        there), so paging adds no extra steady-state transfers."""
        pool, lanes = self.loop.pool, self.loop.lanes
        host = (lanes.tokens,
                np.ascontiguousarray(pool.positions, dtype=np.int32),
                *self._lane_classes(),
                np.ascontiguousarray(pool.page_tables))
        if self._spec_k > 0:
            host += (self._lane_history,)
        dev = self._put_host(host)
        (lanes.dev_tokens, lanes.dev_positions, lanes.dev_active,
         self._dev_active_win, self._dev_active_kfull,
         self._dev_active_kwin, lanes.dev_page_tables) = dev[:7]
        if self._spec_k > 0:
            self._dev_history = dev[7]
            if self._dev_noise is None:
                self._dev_noise = self._put_host(
                    np.zeros((pool.max_slots, self._spec_k), np.int32))
        lanes.dirty = False

    # -- decode -----------------------------------------------------------
    def _dispatch(self, program, active, scales=True, **statics):
        """One plain decode program over one lane class's mask: the pool
        and the token/position vectors are donated and rebound."""
        loop = self.loop
        pool, lanes = loop.pool, loop.lanes
        scale_ops = (pool.k_scale, pool.v_scale) if scales else ()
        (lanes.dev_tokens, lanes.dev_positions, pool.k, pool.v) = program(
            loop.params, pool.k, pool.v, *scale_ops, lanes.dev_page_tables,
            lanes.dev_tokens, lanes.dev_positions, active,
            n_heads=self.n_heads, **statics)

    def _dispatch_spec(self, program, active, scales=True, **statics):
        """One speculative step program over one lane class's mask.
        Returns the class's device (oracle [B, k+1], accepted [B])."""
        loop = self.loop
        pool, lanes = loop.pool, loop.lanes
        scale_ops = (pool.k_scale, pool.v_scale) if scales else ()
        (lanes.dev_tokens, lanes.dev_positions, pool.k, pool.v,
         self._dev_history, oracle, accepted) = program(
            loop.params, pool.k, pool.v, *scale_ops, lanes.dev_page_tables,
            self._dev_history, lanes.dev_tokens, lanes.dev_positions, active,
            self._dev_noise, n_heads=self.n_heads, k=self._spec_k, **statics)
        return oracle, accepted

    def _kernel_statics(self, backend):
        """Count the call and hand back ``backend``'s resolved statics."""
        kernels.record_call(kernels.kernel_for_backend(backend),
                            self._kernel_impl[backend])
        return {"kernel_impl": self._kernel_impl[backend],
                "kernel_interpret": self._kernel_interpret[backend]}

    def _check_decode_sentinels(self):
        """Post-dispatch budget asserts for every armed decode pin (the
        per-class programs share the step, so they share the check)."""
        for s in (self.decode_sentinel, self.decode_window_sentinel,
                  self.decode_kernel_sentinel):
            if s is not None:
                s.check()

    def decode_step(self, guard):  # jaxlint: hot
        """(At most) one jitted call per armed lane class, then the step's
        one host read. Also keeps the drafter's host history mirror: with
        speculation configured but ladder-disabled it stays warm, so recovery
        back to the spec program re-uploads fresh drafter context (stale
        history would only cost accept rate, but fresh is free here)."""
        if self._spec_k > 0 and self._spec_on:
            return self._spec_decode_step(guard)
        loop = self.loop
        pool, lanes = loop.pool, loop.lanes
        # host-side np masks: np.bool_ drives the dispatch branches
        # directly (a bool() cast here reads as a device sync to JL002)
        full, win, kfull, kwin = self._lane_classes()
        pt, qmode = pool.page_tokens, self._qmode
        loop.launched("decode")
        with guard:
            if np.any(full):
                if qmode is not None:
                    self._dispatch(_decode_step_quant_jit, lanes.dev_active,
                                   qmode=qmode)
                else:
                    self._dispatch(_decode_step_jit, lanes.dev_active,
                                   scales=False)
            if np.any(win):
                self._dispatch(_decode_step_window_jit, self._dev_active_win,
                               page_tokens=pt, qmode=qmode)
            if np.any(kfull):
                self._dispatch(_decode_step_kernel_jit,
                               self._dev_active_kfull, page_tokens=pt,
                               qmode=qmode,
                               **self._kernel_statics("pallas_decode"))
            if np.any(kwin):
                self._dispatch(_decode_step_window_jit, self._dev_active_kwin,
                               page_tokens=pt, qmode=qmode,
                               **self._kernel_statics("pallas_sparse"))
        self._check_decode_sentinels()
        # the step's single deliberate sync: EOS checks need the tokens
        host_tokens = loop.read_back(lanes.dev_tokens, "decode", newest=True)
        lanes.tokens = host_tokens.copy()
        slots = list(lanes.requests)
        if self._lane_history is not None:
            for slot in slots:
                at = pool.positions[slot] + 1
                if at < loop.max_seq_len:
                    self._lane_history[slot, at] = host_tokens[slot]
        return slots, host_tokens[:, None].tolist(), 0, 0

    def _spec_decode_step(self, guard):  # jaxlint: hot
        """The speculative step: each armed class's program hands back its
        oracle tokens and acceptance counts; a lane emits its accepted
        drafts plus the oracle's own next token."""
        loop = self.loop
        pool, lanes = loop.pool, loop.lanes
        self._maybe_update_noise()
        full, win, kfull, kwin = self._lane_classes()
        pt, qmode = pool.page_tokens, self._qmode
        got = []                        # (class mask, (oracle, accepted))
        loop.launched("decode")
        with guard:
            if np.any(full):
                if qmode is not None:
                    got.append((full, self._dispatch_spec(
                        _spec_step_quant_jit, lanes.dev_active,
                        qmode=qmode)))
                else:
                    got.append((full, self._dispatch_spec(
                        _spec_step_jit, lanes.dev_active, scales=False)))
            if np.any(win):
                got.append((win, self._dispatch_spec(
                    _spec_step_window_jit, self._dev_active_win,
                    page_tokens=pt, qmode=qmode)))
            for backend, mask, dev_mask in (
                    ("pallas_decode", kfull, self._dev_active_kfull),
                    ("pallas_sparse", kwin, self._dev_active_kwin)):
                if np.any(mask):
                    got.append((mask, self._dispatch_spec(
                        _spec_step_kernel_jit, dev_mask, page_tokens=pt,
                        qmode=qmode, attn_backend=backend,
                        **self._kernel_statics(backend))))
        self._check_decode_sentinels()
        # the step's single deliberate sync: the emit loop needs the
        # oracle tokens and per-lane acceptance counts (one tuple read
        # even when several class programs ran)
        host = loop.read_back(tuple(out for _, out in got), "decode",
                              newest=True)
        oracle, accepted = host[0]
        if len(got) > 1:
            # overlay each later class's lanes onto the first's result
            # (every active lane is in exactly one class); the read-back
            # already landed host numpy — no copies here
            oracle = oracle.copy()
            accepted = accepted.copy()
            for (mask, _), (o, a) in zip(got[1:], host[1:]):
                oracle[mask] = o[mask]
                accepted[mask] = a[mask]
        oracle = oracle.tolist()            # host numpy -> python ints
        accepted = accepted.tolist()
        slots = list(lanes.requests)
        rows = [None] * pool.max_slots
        for slot in slots:
            acc = accepted[slot]
            row = rows[slot] = oracle[slot][:acc + 1]
            # mirror the device lane state: the pending token is now the
            # oracle's post-acceptance token
            lanes.tokens[slot] = row[acc]
            base = pool.positions[slot] + 1     # host-side counter
            n = max(0, min(acc + 1, loop.max_seq_len - base))
            self._lane_history[slot, base:base + n] = row[:n]
        return (slots, rows, sum(accepted[s] for s in slots),
                self._spec_k * len(slots))

    def _maybe_update_noise(self):
        """Swap the device-resident draft-noise operand when the
        corrupt_draft fault arm fires (and restore zeros after). The
        operand always exists with the same shape, so firing the fault
        can never recompile the step."""
        loop = self.loop
        if loop.injector is None:
            return
        noise = loop.injector.corrupt_draft_noise(
            loop.step_count, self._spec_k, self.cfg.vocab_size)
        shape = (loop.pool.max_slots, self._spec_k)
        if noise is not None:
            self._dev_noise = self._put_host(np.ascontiguousarray(
                np.broadcast_to(np.asarray(noise, np.int32), shape)))
            self._noise_armed = True
        elif self._noise_armed:
            self._dev_noise = self._put_host(np.zeros(shape, np.int32))
            self._noise_armed = False

    # -- admission ------------------------------------------------------
    def _impl_for_len(self, prompt_len):
        """Attention backend for a request, selected by its FULL prompt
        length's bucket (not the prefix-adjusted suffix bucket — the
        prefix lookup itself is backend-filtered, so selection must not
        depend on it)."""
        return self._impl_map.get(
            bucket_for(prompt_len, self.loop.scheduler.buckets),
            self._impl_default)

    def _allocate(self, req):
        """A slot and pages for ``req``, or None. Page-pool backpressure
        releases host-side ballast once (unreferenced prefix entries
        demote to spill, the spill tier sheds) and retries, so transient
        memory pressure self-heals instead of round-tripping through
        requeue backpressure."""
        loop = self.loop
        try:
            return loop.pool.allocate(loop.alloc_tokens(req))
        except PoolExhaustedError:
            if not loop.relieve_memory_pressure():
                return None
        try:
            return loop.pool.allocate(loop.alloc_tokens(req))
        except PoolExhaustedError:
            return None

    def admit(self, stats):
        """Join-at-free-slot admission, batched per bucket: pop the FIFO
        head, gather every queued request sharing its (prefix-adjusted)
        bucket up to the free-slot count, and prefill them as ONE call.
        Long prompts divert to the chunked path (one at a time)."""
        loop = self.loop
        pool, scheduler = loop.pool, loop.scheduler
        while pool.free_slots > 0:
            head = scheduler.pop_next()
            if head is None:
                return
            if not pool.can_allocate(loop.alloc_tokens(head)):
                # the same one-shot relief ``_allocate`` makes, before
                # parking the FIFO head
                if (not loop.relieve_memory_pressure()
                        or not pool.can_allocate(loop.alloc_tokens(head))):
                    scheduler.requeue_front(head)
                    return
            if self._needs_chunking(head):
                if self._chunking is not None:
                    scheduler.requeue_front(head)   # chunk lane is busy
                    return
                if not self._start_chunked(head):
                    return                   # pages raced away (requeued)
                stats["admitted"] += 1
                continue
            bucket = bucket_for(self._suffix_len(head), scheduler.buckets)
            impl = self._impl_for_len(len(head.prompt))
            group = [head]
            room = min(pool.free_slots - 1, self._prefill_batch - 1)
            if room > 0:
                group += scheduler.pop_matching(
                    lambda r: (not self._needs_chunking(r)
                               and self._impl_for_len(len(r.prompt)) == impl
                               and bucket_for(self._suffix_len(r),
                                              scheduler.buckets)
                               == bucket),
                    room)
            admitted, retired = self._admit_batch(group, bucket, impl)
            stats["admitted"] += admitted
            stats["retired"] += retired
            if admitted < len(group):
                return                       # pages ran out mid-group

    def _seed_prefill_kv(self, batch, hits):
        """The cache pair a prefill starts from, ``[L, batch, nh, S_max,
        hd]`` in the COMPUTE dtype regardless of pool storage (the quantize
        happens once, at lane install): zeros, or for ``hits`` ((lane,
        entry, reuse) triples) host-resident prefix KV, one transfer.
        Heads-sharded on a mesh (split at nh like the pool), so prefill
        starts from the layout its outputs and the pool install use."""
        shape = (self.n_layers, batch, self.n_heads, self.loop.max_seq_len,
                 self.head_dim)
        cdtype = self.loop.pool.compute_dtype
        sharding = self._prefill_kv_sharding
        if not hits:
            return (jnp.zeros(shape, cdtype, device=sharding),
                    jnp.zeros(shape, cdtype, device=sharding))
        init_k = np.zeros(shape, cdtype)
        init_v = np.zeros(shape, cdtype)
        for lane, entry, reuse in hits:
            ek, ev = self._entry_prefix_kv(entry, reuse)
            init_k[:, lane, :, :reuse] = ek
            init_v[:, lane, :, :reuse] = ev
        return jax.device_put((init_k, init_v), sharding)

    def _admit_batch(self, group, bucket, impl):
        """Prefill ``group`` (same bucket AND attention backend) as one
        [MaxSlots, Sb] call and install each lane into its slot. Slots
        and pages are claimed FIRST: members the page pool cannot hold
        are requeued in FIFO order before any compute runs. Returns
        (admitted, retired-on-their-very-first-token) counts."""
        loop = self.loop
        pool, metrics, tracer = loop.pool, loop.metrics, loop.tracer
        pspan = (tracer.span(
                     "serving/prefill_batch", cat="serving",
                     args={"request_ids": [r.id for r in group],
                           "bucket": bucket, "group": len(group)})
                 if tracer.enabled else telemetry.NULL_SPAN)
        pspan.__enter__()
        B = self._prefill_batch
        # the sparse prefills' blocked attention needs a page-multiple
        # chunk width; pad queries are invisible (outputs discarded,
        # their garbage KV is overwritten by decode before attendable)
        Sb = (_round_up(bucket, pool.page_tokens)
              if impl in ("sparse_xla", "pallas_sparse") else bucket)
        ids = np.zeros((B, Sb), np.int32)
        starts = np.zeros(B, np.int32)
        lens = np.ones(B, np.int32)        # dummy lanes: 1-token no-ops
        plan = []
        for req in group:
            slot = self._allocate(req)
            if slot is None:
                break
            i = len(plan)
            req.attn_impl = impl
            reuse, entry = self._acquire_prefix(req)
            suffix = req.prompt[reuse:]
            ids[i, :len(suffix)] = suffix
            starts[i] = reuse
            lens[i] = len(req.prompt)
            plan.append((req, reuse, entry, slot))
            metrics.record_admission(bucket, len(req.prompt))
        for req in reversed(group[len(plan):]):
            loop.scheduler.requeue_front(req)    # pages exhausted mid-group
        if not plan:
            pspan.__exit__(None, None, None)
            return 0, 0
        init_k, init_v = self._seed_prefill_kv(
            B, [(i, entry, reuse)
                for i, (_, reuse, entry, _) in enumerate(plan) if reuse > 0])

        t0 = time.monotonic()
        loop.launched("prefill")
        k, v, first = self._run_prefill(impl, init_k, init_v,
                                        self._put_host(ids),
                                        self._put_host(starts),
                                        self._put_host(lens))
        # sync: TTFT endpoint
        first_host = loop.read_back(first, "prefill", newest=True)
        prefill_s = time.monotonic() - t0
        loop.prefill_ran()
        # every row of the bucket runs, whatever the group's size
        metrics.record_prefill(
            tokens=sum(len(r.prompt) - re for r, re, _, _ in plan),
            reused_tokens=sum(re for _, re, _, _ in plan),
            requests=len(plan), prefill_s=prefill_s, positions_run=B * Sb)
        metrics.record_queue_wait(
            sum(t0 - r.submit_time for r, _, _, _ in plan), len(plan))

        ispan = (tracer.span("serving/install", cat="serving",
                             args={"group": len(plan)})
                 if tracer.enabled else telemetry.NULL_SPAN)
        ispan.__enter__()
        now = time.monotonic()
        retired = 0
        for i, (req, reuse, entry, slot) in enumerate(plan):
            self._maybe_insert_prefix(req, reuse, k, v, lane=i)
            pool.install_lane(k, v, lane=i, slot=slot,
                              position=len(req.prompt))
            req.prefix_entry = entry
            retired += loop.first_token(req, slot, int(first_host[i]), now)
        # settle the queued lane installs here so they are accounted to
        # admission, not silently absorbed into the next decode step's
        # measured latency
        loop.read_back(pool.k, "prefill", newest=True, fetch=False)
        ispan.__exit__(None, None, None)
        pspan.__exit__(None, None, None)
        return len(plan), retired

    def _run_prefill(self, impl, init_k, init_v, ids, starts, lens):
        """Dispatch the per-backend batched prefill program (each with
        its own CompileSentinel pin when armed)."""
        program, sentinel = {
            "sparse_xla": (_prefill_batch_window_jit,
                           self.prefill_window_sentinel),
            "pallas_decode": (_prefill_batch_kernel_jit,
                              self.prefill_kernel_sentinel),
            "pallas_sparse": (_prefill_batch_kernel_window_jit,
                              self.prefill_kernel_window_sentinel),
            "flash": (_prefill_batch_flash_jit, self.prefill_flash_sentinel),
        }.get(impl, (_prefill_batch_jit, self.prefill_sentinel))
        statics = (self._kernel_statics(impl) if impl in self._kernel_impl
                   else {})
        if program is not _prefill_batch_jit:
            statics["page_tokens"] = self.loop.pool.page_tokens
        out = program(self.loop.params, init_k, init_v, ids, starts, lens,
                      n_heads=self.n_heads, **statics)
        if sentinel is not None:
            sentinel.check()
        return out

    # -- chunked prefill ------------------------------------------------
    def _needs_chunking(self, req):
        chunk = self.loop.config.prefill_chunk_tokens
        return chunk > 0 and self._suffix_len(req) > chunk

    def _start_chunked(self, req):
        """Reserve a slot+pages and a private cache for ``req`` and let
        ``advance_prefill`` feed it one chunk per engine step. Returns
        False (request requeued) if the page pool cannot hold it."""
        loop = self.loop
        req.attn_impl = self._impl_for_len(len(req.prompt))
        reuse, entry = self._acquire_prefix(req)
        req.prefix_entry = entry
        # reserved up front: completion can't stall on a full pool
        slot = self._allocate(req)
        if slot is None:
            if entry is not None:
                loop.prefix_cache.release(entry)
                req.prefix_entry = None
            loop.scheduler.requeue_front(req)
            return False
        loop.metrics.record_admission(
            bucket_for(self._suffix_len(req), loop.scheduler.buckets),
            len(req.prompt))
        k0, v0 = self._seed_prefill_kv(
            1, [(0, entry, reuse)] if reuse > 0 else [])
        self._chunking = _ChunkedPrefill(req, k0, v0, pos=reuse, reuse=reuse,
                                         slot=slot)
        return True

    def advance_prefill(self, stats, now):
        """One chunk per step, so a long prompt makes progress without ever
        stalling the in-flight lanes' inter-token latency: run the next
        chunk of the in-flight chunked prefill (same compiled program as
        batched prefill, at B=1/Sb=chunk); install and activate on the
        final chunk. Mid chunks never block the host — only the final
        chunk syncs, for its first token. Returns the last clock stamp it
        took; a chunk's own time is admission time."""
        st = self._chunking
        if st is None:
            return now
        loop = self.loop
        pool, metrics = loop.pool, loop.metrics
        req = st.req
        top = now = time.monotonic()
        if req.deadline_exceeded(now):
            req.slot = st.slot             # hand the reserved slot back
            loop.finish_timeout(req, phase="prefill")
            self._chunking = None
            stats["retired"] += 1
            return now
        impl = req.attn_impl
        chunk_len = loop.config.prefill_chunk_tokens
        # sparse chunks pad to a page multiple (blocked attention width
        # constraint); a chunk's pad garbage is overwritten by the next
        # chunk's real writes before it is ever attendable, and the
        # final chunk's by decode — same write-before-attend argument
        # as batched prefill padding
        cw = (_round_up(chunk_len, pool.page_tokens)
              if impl in ("sparse_xla", "pallas_sparse") else chunk_len)
        chunk = req.prompt[st.pos:st.pos + chunk_len]
        ids = np.zeros((1, cw), np.int32)
        ids[0, :len(chunk)] = chunk
        cspan = (loop.tracer.span("serving/prefill_chunk", cat="serving",
                                  args={"request_id": req.id, "pos": st.pos,
                                        "chunk": len(chunk)})
                 if loop.tracer.enabled else telemetry.NULL_SPAN)
        t0 = time.monotonic()
        if st.pos == st.reuse:                     # the first chunk
            metrics.record_queue_wait(t0 - req.submit_time)
        with cspan:
            loop.launched("prefill")
            st.k, st.v, first = self._run_prefill(
                impl, st.k, st.v, self._put_host(ids),
                self._put_host(np.asarray([st.pos], np.int32)),
                self._put_host(np.asarray([len(req.prompt)], np.int32)))
        st.pos += len(chunk)
        st.positions_run += cw
        stats["prefill_chunks"] += 1
        loop.prefill_ran()
        if st.pos < len(req.prompt):
            now = time.monotonic()
            st.prefill_s += now - t0
            metrics.admit_time_s += now - top
            return now
        # sync: TTFT endpoint
        first_tok = int(loop.read_back(first, "prefill", newest=True)[0])
        st.prefill_s += time.monotonic() - t0
        now = time.monotonic()
        metrics.admit_time_s += now - top
        metrics.record_prefill(
            tokens=len(req.prompt) - st.reuse, reused_tokens=st.reuse,
            requests=1, prefill_s=st.prefill_s,
            positions_run=st.positions_run)
        self._maybe_insert_prefix(req, st.reuse, st.k, st.v, lane=0)
        pool.install(st.k, st.v, st.slot, position=len(req.prompt))
        stats["retired"] += loop.first_token(req, st.slot, first_tok, now)
        self._chunking = None
        return now

    # -- prefix cache ---------------------------------------------------
    def _suffix_len(self, req):
        """Tokens a prefill would actually compute for ``req`` after
        prefix-cache reuse (always >= 1: the last prompt position is
        recomputed to produce the first token's logits)."""
        cache = self.loop.prefix_cache
        if cache is None:
            return len(req.prompt)
        length, _ = cache.match(
            req.prompt, impl=self._impl_for_len(len(req.prompt)))
        return len(req.prompt) - min(length, len(req.prompt) - 1)

    def _acquire_prefix(self, req):
        """Counted, ref-taking lookup at admission time. Returns
        (reused_tokens, entry-or-None); the ref is released at the
        request's retirement (any path)."""
        cache = self.loop.prefix_cache
        if cache is None:
            return 0, None
        length, entry = cache.acquire(req.prompt, impl=req.attn_impl)
        reuse = min(length, len(req.prompt) - 1)
        if entry is not None and reuse <= 0:
            cache.release(entry)
            entry, reuse = None, 0
        self.loop.metrics.record_prefix_lookup(hit=reuse > 0)
        return reuse, entry

    def _maybe_insert_prefix(self, req, reuse, k, v, lane):
        """Store the freshly-prefilled prompt's KV for future requests
        (skipped when an existing entry already covers the whole prompt
        — nothing new to add, and while the degrade ladder or the memory
        guard has inserts paused). In int8 pool mode entries are stored
        QUANTIZED (per-(layer, head) scales over the cached positions):
        the trie's byte budget buys ~4x the prefix positions, same
        at-use-dequant contract as the pool itself."""
        cache = self.loop.prefix_cache
        if cache is None or self.loop.prefix_inserts_paused():
            return
        n = len(req.prompt)
        if reuse >= n - 1:
            return
        # entries are tagged with the backend that produced them: for
        # L >= 2 layers the backends' hidden states (hence deep-layer
        # KV) differ in low bits, so cross-backend seeding would break
        # the per-backend bitwise oracle
        # a copy, not a program's output: the helper's wait and span, but
        # no read in the prefill reads' mean
        pk, pv = self.loop.read_back(
            (k[:, lane, :, :n], v[:, lane, :, :n]), "prefix_kv", newest=True)
        if self.loop.pool.kv_cache_dtype == "int8":
            pk, k_scale = quantize_kv_np(pk)
            pv, v_scale = quantize_kv_np(pv)
            cache.insert(req.prompt, pk, pv, k_scale=k_scale,
                         v_scale=v_scale, impl=req.attn_impl)
            return
        cache.insert(req.prompt, pk, pv, impl=req.attn_impl)

    def _entry_prefix_kv(self, entry, reuse):
        """A prefix entry's first ``reuse`` positions in the pool's
        COMPUTE dtype (int8-mode entries dequantize here, at seed
        time — never inside the prefill program)."""
        ek = entry.k[:, :, :reuse]
        ev = entry.v[:, :, :reuse]
        if entry.k_scale is not None:
            dt = np.dtype(self.loop.pool.compute_dtype)
            return (dequantize_kv_np(ek, entry.k_scale, dt),
                    dequantize_kv_np(ev, entry.v_scale, dt))
        return ek, ev
