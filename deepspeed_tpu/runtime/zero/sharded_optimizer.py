"""ZeRO stages 1/2 as mesh shardings over a flat fp32 master shard.

Capability parity with the reference's ``FP16_DeepSpeedZeroOptimizer_Stage1``
(``runtime/zero/stage1.py:105``) and ``FP16_DeepSpeedZeroOptimizer``
(``runtime/zero/stage2.py:92``), re-designed TPU-first:

- The reference retrofits ZeRO onto eager autograd: backward hooks fill IPG
  buckets, async ``dist.reduce`` sends slices to owner ranks, the owner updates
  its fp32 sub-partitions, then a sharded sequential all-gather rebuilds fp16
  params. Here the same *capability* is a sharding decision inside one XLA
  program: all params flatten into a single fp32 master vector laid out along
  the ``data`` mesh axis; grads flatten and take a ``P('data')`` sharding
  constraint (stage 2 → XLA emits reduce-scatter over ICI; stage 1 keeps the
  all-reduce + local slice); the inner optimizer (Adam/LAMB) runs elementwise on
  the local shard; the updated master re-assembles via XLA's all-gather when the
  replicated params are rebuilt (an all-gather only because the flat vector is
  padded to whole lane tiles a rank: ``flat_pad_multiple``).
- Optimizer state (m, v) lives only on the shard — the stage-1/2 memory win.
- ``cpu_offload=True`` (ZeRO-Offload, reference stage2.py:743-900,1416-1427)
  runs the inner step on host over pinned numpy buffers via
  ``DeepSpeedCPUAdam`` (C++ kernel when built), overlapping D2H/H2D at the
  shard granularity.
- Elastic checkpoints: each dp rank's logical (unpadded) shard is saved
  separately and re-partitioning on load handles a different dp degree
  (reference stage2.py:1648-1841).
"""

import queue
import threading
import time
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu import telemetry
from deepspeed_tpu.ops.utils_op import (
    flatten_dense_tensors,
    pad_to_multiple,
    tree_spec,
    unflatten_dense_tensors,
)
from deepspeed_tpu.parallel.mesh import dp_world_size
from deepspeed_tpu.parallel.sharding_registry import (
    train_sharding,
    train_spec,
)
from deepspeed_tpu.profiling.sentinels import (
    allowed_transfer,
    register_allowed_transfer,
)
from deepspeed_tpu.utils.logging import log_dist


# reference default (stage2.py); the warn loop below keys off this constant
DEFAULT_BUCKET_SIZE = 500000000

# The ONLY sanctioned paging sites of the ZeRO-Offload host step: grad
# buckets stream D2H and updated param buckets stream H2D through these
# named windows, so a transfer_free() region around the training step stays
# honest — offload traffic is explicit and greppable, never implicit.
OFFLOAD_D2H = register_allowed_transfer("zero/offload_d2h")
OFFLOAD_H2D = register_allowed_transfer("zero/offload_h2d")

# Trace-time gauges of the stage-1/2 parameter gather, recorded when
# ``update()`` is traced: the bytes a rank receives a step and the element
# size that travels (2 where the shard is cast to bf16 ahead of the gather).
PARAM_GATHER_BYTES = "ZeRO/param_gather_bytes"
PARAM_GATHER_ITEMSIZE = "ZeRO/param_gather_itemsize"

# lanes of a TPU vector tile's minor dimension
LANE_TILE = 128


def flat_pad_multiple(dp):
    """The multiple ZeRO's flat vector is padded to: ``dp`` whole 128-lane
    tiles, so that a rank's shard is a whole number of lane tiles.

    A shard that is not, the TPU compiler does not gather: it writes each
    rank's shard into a zero-filled buffer of the whole vector's length and
    all-reduces that (twice an all-gather's bytes on the wire, plus the fill
    and the update-slice). Compiled for four described v5e chips, BERT-large's
    flat vector padded to ``dp`` (336,232,260 elements, shards of 84,058,065)
    gathers as ``all-reduce bf16[336232260]``; padded to ``dp x 128``
    (336,232,448, shards of 84,058,112 = 128 x 656,704) as ``all-gather
    bf16[336232448]``. Shards that are multiples of 2, 8, 16 or 64 and not of
    128 still all-reduce; 128 and not 256 gathers. The rule reads ``dp`` and
    nothing else."""
    return dp * LANE_TILE


# Edge-triggered, per process: flips on the FIRST grad leaf whose async D2H
# could not be kicked, so benches on backends without copy_to_host_async
# are visibly honest instead of silently degrading to sync fetches.
_SYNC_FALLBACK_SEEN = False


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def _span(tracer, name, **args):
    return tracer.span(name, cat="offload", args=args) if tracer.enabled \
        else _NULL_SPAN


def _start_async_copy(leaf):
    """Kick ``leaf``'s async D2H; False means the later ``device_get`` will
    be a synchronous fetch (no ``copy_to_host_async``, or the backend
    refused it)."""
    fn = getattr(leaf, "copy_to_host_async", None)
    if fn is None:
        return False
    try:
        fn()
    except Exception:  # noqa: BLE001 — backend without async copy
        return False
    return True


def _kick_async_copies(leaves):
    """Start D2H for every grad leaf up front (transfers run while earlier
    buckets compute); returns how many leaves will fall back to a
    synchronous fetch. CSR leaves kick their index/value components."""
    from deepspeed_tpu.runtime.csr_tensor import CSRTensor

    sync = 0
    for leaf in leaves:
        if isinstance(leaf, CSRTensor):
            ok = _start_async_copy(leaf.indices)
            ok = _start_async_copy(leaf.values) and ok
        else:
            ok = _start_async_copy(leaf)
        if not ok:
            sync += 1
    return sync


def _note_sync_fetches(count, total):
    """Account the silent-degrade path: a monotonic counter every step it
    happens, plus ONE edge-triggered trace instant per process."""
    global _SYNC_FALLBACK_SEEN
    if count <= 0:
        return
    telemetry.get_registry().counter(
        "Train/offload_sync_fetch_total",
        help="offload grad fetches that fell back to a synchronous "
             "device_get (copy_to_host_async unavailable or refused)",
    ).inc(count)
    if not _SYNC_FALLBACK_SEEN:
        _SYNC_FALLBACK_SEEN = True
        telemetry.instant(
            "train/offload_sync_fallback", cat="train",
            args={"leaves": count, "total": total})


def _fetch_flat_grad(leaf, out):
    """device_get one grad leaf into ``out`` (a flat fp32 staging slice of
    exactly the leaf's numel). CSR leaves (sparse embedding grads) rebuild
    their dense layout host-side — only touched rows cross D2H."""
    from deepspeed_tpu.runtime.csr_tensor import CSRTensor

    if isinstance(leaf, CSRTensor):
        out[:] = 0.0
        idx = np.asarray(jax.device_get(leaf.indices))
        if idx.size:
            dense = out.reshape(leaf.dense_size)
            dense[idx] = np.asarray(jax.device_get(leaf.values), np.float32)
    else:
        out[:] = np.asarray(jax.device_get(leaf), np.float32).reshape(-1)


def _offload_stage_loop(q):
    """Generic stage loop of the offload pipeline workers ('zero-offload-
    adam', 'zero-offload-h2d'): tasks are closures that trap their own
    errors into the per-call state, so the loop itself never dies; ``None``
    shuts the worker down."""
    while True:
        task = q.get()
        if task is None:
            return
        task()


def compute_bucket_ranges(sizes, bucket_size):
    """Greedy split of the flat leaf order into contiguous buckets holding at
    most ``bucket_size`` elements each (a single oversized leaf still gets its
    own bucket — leaves are never split across buckets, so every bucket's
    segment of the flat master is a plain concat of whole leaves).

    Returns ``[(lo, hi), ...]`` half-open leaf-index ranges covering every
    leaf exactly once, in leaf order. This is the overlap_comm analogue of the
    reference's IPG buckets (stage2.py:904-940): each range becomes one
    backward-interleaved reduce collective instead of one eager NCCL call.
    """
    bucket_size = max(1, int(bucket_size))
    ranges = []
    start, acc = 0, 0
    for i, n in enumerate(sizes):
        n = max(1, int(n))
        if acc > 0 and acc + n > bucket_size:
            ranges.append((start, i))
            start, acc = i, 0
        acc += n
    if start < len(sizes):
        ranges.append((start, len(sizes)))
    return ranges


class ZeroState(NamedTuple):
    flat_master: jnp.ndarray  # fp32, padded, sharded along data axis
    inner_state: object  # inner optimizer state over the flat vector (sharded)


def zero3_param_shardings(mesh, params):
    """Stage-3 storage layout: each leaf's leading dim sharded along ``data``
    when divisible (small/indivisible leaves stay replicated — their memory
    is negligible). This is the TPU-native form of the reference's never-
    shipped stage 3 (param partitioning with gather-on-use): params LIVE
    sharded between steps; the training step constrains them to replicated at
    use, so XLA inserts the all-gather exactly where the reference would have
    issued its prefetch all-gathers, and re-shards on update output."""
    dp = dp_world_size(mesh)
    # leading-dim axis comes from the shared sharding registry
    # (parallel/sharding_registry.py) — the one spec table both engines
    # resolve placements from
    lead = train_spec("zero3/stacked_leading")

    def spec(p):
        shape = getattr(p, "shape", ())
        if len(shape) >= 1 and shape[0] >= dp and shape[0] % dp == 0:
            return NamedSharding(
                mesh, PartitionSpec(*lead, *([None] * (len(shape) - 1))))
        return train_sharding(mesh, "zero/gathered")

    return jax.tree_util.tree_map(spec, params)


class ZeroShardedOptimizer:
    """Optimizer wrapper implementing ZeRO-1/2 semantics on a mesh."""

    def __init__(self, inner, stage=1, mesh=None, cpu_offload=False, reduce_scatter=True,
                 reduce_bucket_size=DEFAULT_BUCKET_SIZE,
                 allgather_bucket_size=DEFAULT_BUCKET_SIZE,
                 elastic_checkpoint=True, clip_grad=0.0, postscale_gradients=True,
                 gradient_predivide_factor=1.0, keep_master=True,
                 param_shardings=None, overlap_comm=False,
                 offload_stream_buckets=1, offload_pin_host=True):
        assert mesh is not None, "ZeroShardedOptimizer requires a mesh"
        self.inner = inner
        self.stage = stage
        self.mesh = mesh
        self.dp = dp_world_size(mesh)
        self.cpu_offload = cpu_offload
        # offload_stream_buckets >= 2 turns the host step into the three-
        # stage per-bucket pipeline (_update_host_streamed); 1 keeps the
        # sequential leaf-at-a-time path bit-for-bit.
        self.offload_stream_buckets = max(1, int(offload_stream_buckets))
        self.offload_pin_host = bool(offload_pin_host)
        self._offload_streaming = bool(cpu_offload) and self.offload_stream_buckets > 1
        self.reduce_scatter = reduce_scatter
        # overlap_comm=False (default): bucket-size knobs are accepted for
        # config parity but are NO-OPS, by design rather than omission — the
        # reference buckets grads to bound transient memory because its
        # reduce/all-gather are eager NCCL calls issued from backward hooks
        # (stage2.py:904-940,1444-1477); here the whole step is ONE XLA
        # program whose collectives the scheduler bounds on its own. Each
        # ignored non-default knob logs once, loudly.
        #
        # overlap_comm=True (DeepCompile-style): reduce_bucket_size becomes
        # REAL — the param leaves split into contiguous buckets of at most
        # that many elements, and grad_overlap_tap() pins each bucket's
        # post-reduce layout INSIDE the backward pass, so XLA emits one
        # collective per bucket as soon as that bucket's grads exist and
        # schedules it against the remaining backward compute.
        # Under cpu_offload, overlap_comm only survives when the offload
        # stream is on: the streamed host step reuses grad_overlap_tap's
        # per-bucket backward pins (tap buckets == stream buckets), so each
        # bucket's grads are reduced AND ready to page out mid-backward.
        self.overlap_comm = overlap_comm and (not cpu_offload or self._offload_streaming)
        self.reduce_bucket_size = reduce_bucket_size
        self.allgather_bucket_size = allgather_bucket_size
        if self.overlap_comm and not self._offload_streaming:
            ignored = (("allgather_bucket_size", allgather_bucket_size),)
        else:
            # offload streaming derives its bucket plan from
            # offload_stream_buckets, not reduce_bucket_size
            ignored = (
                ("reduce_bucket_size", reduce_bucket_size),
                ("allgather_bucket_size", allgather_bucket_size),
            )
        for knob, val in ignored:
            if val != DEFAULT_BUCKET_SIZE:
                log_dist(
                    f"ZeRO: '{knob}'={val} is accepted for parity but IGNORED "
                    "on TPU — collectives are compiler-scheduled inside one "
                    "XLA program (see ZeroShardedOptimizer docstring)",
                    ranks=[0],
                )
        if overlap_comm and cpu_offload and not self._offload_streaming:
            log_dist(
                "ZeRO: overlap_comm is IGNORED under cpu_offload — the host "
                "step fetches whole grad leaves; there is no in-program "
                "backward to interleave collectives into (set "
                "offload_stream_buckets >= 2 to stream the host step against "
                "the backward)", ranks=[0],
            )
        self._buckets = None       # [(lo, hi)] leaf ranges, set by init()
        self.bucket_numels = None  # per-bucket element counts (telemetry)
        self.elastic_checkpoint = elastic_checkpoint
        self.clip_grad = clip_grad
        # keep_master=False (fp32 compute): the replicated params ARE fp32, so
        # a persistent sharded master would double-store them — the step
        # re-derives the local master slice from params instead.
        self.keep_master = keep_master
        self._spec = None  # (treedef, shapes, dtypes, sizes)
        self._numel = None
        self._padded = None
        self._param_shardings = param_shardings  # stage-3 storage layout
        # streamed-offload pipeline state (workers start lazily, daemonized;
        # constructing an optimizer never spawns threads)
        self._offload_queues = None
        self._offload_threads = None
        # ping-pong partner for the streamed out-of-place host step; kept
        # across steps under offload_pin_host (steady-state zero allocation)
        self._offload_master_next = None
        self.last_offload_stats = None  # per-step stage timings + overlap_frac
        self.lr = getattr(inner, "lr", 1e-3)
        self.name = getattr(inner, "name", "zero")

    # -- layout -----------------------------------------------------------
    def _shard_sharding(self):
        return train_sharding(self.mesh, "zero/flat_shard")

    def _ensure_buckets(self, params=None):
        """Leaf-range bucket plan (lazily derivable from a params pytree
        before ``init`` runs, e.g. at trace time). Under offload streaming
        the plan is ``offload_stream_buckets`` near-equal element splits —
        and it is the SAME plan ``grad_overlap_tap`` pins, so the backward's
        reduce buckets line up 1:1 with the host pipeline's stream buckets."""
        if self._buckets is not None:
            return self._buckets
        spec = self._spec if self._spec is not None else tree_spec(params)
        _, _, _, sizes = spec
        if self._offload_streaming:
            total = int(sum(int(s) for s in sizes))
            bucket_size = max(1, -(-total // self.offload_stream_buckets))
        else:
            bucket_size = self.reduce_bucket_size
        self._buckets = compute_bucket_ranges(sizes, bucket_size)
        self.bucket_numels = [int(sum(sizes[lo:hi])) for lo, hi in self._buckets]
        return self._buckets

    def grad_overlap_tap(self):
        """Per-bucket identity taps that pin gradient-reduce layout INSIDE the
        backward pass (DeepCompile's overlapped reduce, expressed to GSPMD).

        Returns a ``params -> params`` function to apply at the TOP of the
        loss function, or ``None`` when overlap is off. Forward is the
        identity; each bucket's custom-vjp backward takes that bucket's
        cotangents (the final grads w.r.t. the tapped leaves), flattens them
        to one fp32 vector, pads to the dp multiple, and pins a REPLICATED
        sharding constraint before slicing/reshaping back. Numerically this
        is the identity — but the constraint forces XLA to complete the
        data-parallel reduction of that bucket at the point in the backward
        where its grads are produced, free to overlap the remaining backward
        compute, instead of one monolithic reduce after the whole backward.

        The pin is replicated (all-reduce) rather than ``P('data')`` on
        purpose, for BOTH stages: the tapped leaves re-enter the graph
        replicated either way, so a sharded pin would force reduce-scatter
        immediately followed by all-gather — identical total comm volume to
        one all-reduce (RS + AG == AR) plus a layout round-trip the compiler
        cannot always elide. Stage>=2's scatter still happens: ``update()``
        constrains the flat grads to ``P('data')``, which against an
        already-reduced replicated buffer is a free local slice.

        With or without the tap, the engine pins the whole gradient tree to
        its parameters' layout where the backward returns it
        (``engine._fwd_bwd_core``): replicated for the stages this tap
        serves, the same layout a bucket's pin asks for. The tap moves WHEN a
        bucket's reduction runs (inside the backward, a bucket at a time);
        the engine's pin fixes the layout in which every gradient arrives at
        ``update()``, so the two never disagree.
        """
        if not self.overlap_comm:
            return None
        dp = self.dp
        out_sharding = train_sharding(self.mesh, "zero/grad_bucket")

        @jax.custom_vjp
        def _bucket_tap(*leaves):
            return leaves

        def _tap_fwd(*leaves):
            # no residuals: the cotangents carry the leaf shapes/dtypes
            return leaves, None

        def _tap_bwd(_, cts):
            flat = jnp.concatenate(
                [c.astype(jnp.float32).reshape(-1) for c in cts])
            n = flat.shape[0]
            padded, _ = pad_to_multiple(flat, dp)
            padded = jax.lax.with_sharding_constraint(padded, out_sharding)
            flat = padded[:n]
            outs, off = [], 0
            for c in cts:
                outs.append(
                    flat[off:off + c.size].reshape(c.shape).astype(c.dtype))
                off += c.size
            return tuple(outs)

        _bucket_tap.defvjp(_tap_fwd, _tap_bwd)

        def apply(params):
            buckets = self._ensure_buckets(params)
            leaves, treedef = jax.tree_util.tree_flatten(params)
            out = list(leaves)
            for b, (lo, hi) in enumerate(buckets):
                with jax.named_scope(f"grad_reduce_bucket{b}"):
                    out[lo:hi] = list(_bucket_tap(*leaves[lo:hi]))
            return jax.tree_util.tree_unflatten(treedef, out)

        return apply

    def init(self, params):
        self._spec = tree_spec(params)
        if self.overlap_comm or self._offload_streaming:
            self._ensure_buckets(params)
            if self._offload_streaming:
                log_dist(
                    f"ZeRO-Offload stream: {len(self._buckets)} bucket(s) "
                    f"(requested {self.offload_stream_buckets}, "
                    f"numels={self.bucket_numels}, "
                    f"pin_host={self.offload_pin_host}, "
                    f"backward taps={'on' if self.overlap_comm else 'off'})",
                    ranks=[0])
            else:
                log_dist(
                    f"ZeRO overlap_comm: {len(self._buckets)} reduce bucket(s) of "
                    f"at most {self.reduce_bucket_size} elements "
                    f"(numels={self.bucket_numels})", ranks=[0])
        if getattr(self.inner, "no_decay_names", None):
            if self.cpu_offload:
                # ValueError, not assert: must fire under python -O too (a
                # silently-uniform decay would be wrong training, not a bug)
                raise ValueError(
                    "no_decay_names is not supported with cpu_offload (the "
                    "host C++ Adam applies decay uniformly); drop one of the two")
            from deepspeed_tpu.ops.adam.fused_adam import decay_scales

            self._leaf_decay_scales = jax.tree_util.tree_leaves(
                decay_scales(params, self.inner.no_decay_names))
        if self.stage >= 3:
            assert not self.cpu_offload, (
                "ZeRO-3 + cpu_offload is not supported: stage 3's win is "
                "sharded on-device param storage; combine offload with stage 2"
            )
            # the engine passes ITS storage layout so there is exactly one
            # definition of where stage-3 params live (engine.py builds it
            # via zero3_param_shardings and device_puts params accordingly)
            if self._param_shardings is None:
                self._param_shardings = zero3_param_shardings(self.mesh, params)
        flat = flatten_dense_tensors(params, jnp.float32)
        self._numel = int(flat.shape[0])
        flat = self._pad_flat(flat)
        self._padded = int(flat.shape[0])
        if self.cpu_offload:
            # ZeRO-Offload: master AND optimizer state live on host only — no
            # device-side copies (that HBM is exactly what offload frees).
            # np.array (not asarray): device_get can hand back a READ-ONLY
            # zero-copy view of the runtime's buffer; the master must be an
            # owned writable array (in-place sequential steps, ping-pong)
            self._host_master = np.array(jax.device_get(flat), np.float32)
            self._host_inner = self.inner.init_host(self._host_master) if hasattr(self.inner, "init_host") else None
            log_dist(f"ZeRO-Offload: {self._host_master.nbytes/1e6:.1f} MB master on host", ranks=[0])
            return ZeroState(flat_master=jnp.zeros((0,), jnp.float32), inner_state=None)
        flat = jax.device_put(flat, self._shard_sharding())
        inner_state = self.inner.init(flat)
        if not self.keep_master:
            return ZeroState(flat_master=jnp.zeros((0,), jnp.float32), inner_state=inner_state)
        return ZeroState(flat_master=flat, inner_state=inner_state)

    def _flat_decay_mask(self):
        """Per-element decay multiplier aligned with the flat master layout
        (padding decays-0). Built in-trace from scalar broadcasts — XLA
        keeps it as fused broadcast+concat, never a materialized literal."""
        _, _, _, sizes = self._spec
        parts = [jnp.full((n,), s, jnp.float32)
                 for n, s in zip(sizes, self._leaf_decay_scales)]
        mask = jnp.concatenate(parts) if parts else jnp.zeros((0,), jnp.float32)
        mask = self._pad_flat(mask)
        return jax.lax.with_sharding_constraint(mask, self._shard_sharding())

    def _pad_flat(self, flat):
        """``flat`` zero-padded to the flat vector's length (whole lane
        tiles a rank: ``flat_pad_multiple``)."""
        return pad_to_multiple(flat, flat_pad_multiple(self.dp))[0]

    def _note_param_gather(self, travels):
        """Trace-time gauges of the stage-1/2 parameter gather: the bytes a
        rank receives a step (the other ranks' shards) and the element size
        that travels."""
        itemsize = jnp.dtype(travels).itemsize
        gauges = telemetry.get_registry()
        gauges.gauge(
            PARAM_GATHER_BYTES, help="bytes a rank receives a step in ZeRO-1/2's "
            "parameter gather, as the last traced update() laid it out"
        ).set((self.dp - 1) * (self._padded // self.dp) * itemsize)
        gauges.gauge(
            PARAM_GATHER_ITEMSIZE, help="bytes an element of ZeRO-1/2's "
            "parameter gather travels as (2: cast to bf16 ahead of it)"
        ).set(itemsize)

    # -- device path (jit-traceable) --------------------------------------
    def update(self, grads, opt_state, params, lr=None):
        """One sharded step. ``grads`` arrive as the engine hands them over:
        each leaf already reduced and pinned to its parameter's layout
        (``engine._fwd_bwd_core``; replicated for stages 1/2, the storage
        split for stage 3), so the ``zero/flat_shard`` constraint below is a
        local slice of an already reduced vector (stage >= 2: only the
        owner's shard persists). The pin upstream is what keeps this
        ``P('data')`` from travelling backwards through the concatenate and
        the accumulator into the carries of the model's backward loops,
        where it once made the loss reduce-scatter its kernel gradient once
        a chunk.

        Stages 1/2 hand the parameters back whole on every rank: the updated
        master, one shard a rank, is gathered once. Two things keep that one
        all-gather of the parameters' own dtype and not an all-reduce of a
        zero-padded copy of the vector, or a gather of float32: the flat
        vector's length (``flat_pad_multiple``: whole lane tiles a rank) and
        the cast ahead of the gather, pinned to the shard's layout
        (``tests/unit/test_step_fusion.py`` reads both in the step compiled
        for four described v5e chips)."""
        treedef, shapes, dtypes, _ = self._spec

        flat_grads = flatten_dense_tensors(grads, jnp.float32)
        flat_grads = self._pad_flat(flat_grads)
        if self.stage >= 2 and self.reduce_scatter:
            # Stage 2: gradient partitioning — only the owner shard persists.
            flat_grads = jax.lax.with_sharding_constraint(flat_grads, self._shard_sharding())

        if self.keep_master:
            master = opt_state.flat_master
        else:
            # fp32 compute: derive the local master slice from the (fp32)
            # params — XLA materializes only this rank's shard transiently.
            master = flatten_dense_tensors(params, jnp.float32)
            master = self._pad_flat(master)
            master = jax.lax.with_sharding_constraint(master, self._shard_sharding())
        if getattr(self.inner, "no_decay_names", None) and \
                getattr(self.inner, "weight_decay", 0.0) != 0.0:
            # key paths are gone after flattening — rebuild the per-element
            # decay mask as a concat of scalar broadcasts (no materialized
            # literal; XLA fuses it) in the SAME leaf order as the master
            new_master, new_inner = self.inner.update(
                flat_grads, opt_state.inner_state, master, lr=lr,
                decay_mask=self._flat_decay_mask())
        else:
            new_master, new_inner = self.inner.update(flat_grads, opt_state.inner_state, master, lr=lr)
        new_master = jax.lax.with_sharding_constraint(new_master, self._shard_sharding())

        # Rebuild params in their original dtypes (compute dtype under mixed
        # precision — the fp32 master stays only in the shard).
        out_dtypes = [l.dtype for l in jax.tree_util.tree_leaves(params)]
        if self.stage >= 3:
            # Stage 3: params STAY sharded between steps — each rebuilt leaf
            # is constrained to its storage sharding, so the only replicated
            # copy ever materialized is the transient one the forward gathers.
            new_params = unflatten_dense_tensors(
                new_master[: self._numel], treedef, shapes, out_dtypes
            )
            new_params = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, new_params, self._param_shardings
            )
        else:
            # Stages 1/2: the whole padded vector goes from one shard a rank
            # to replicated, and XLA writes that as ONE all-gather over ICI
            # (the reference's sharded sequential all_gather,
            # stage2.py:1444-1477) because the shard is whole lane tiles
            # (``flat_pad_multiple``; a ragged shard is all-reduced inside a
            # zero-filled copy of the vector instead). Where every leaf has
            # one dtype the shard is cast to it BEFORE it travels, pinned as
            # a shard so the cast cannot slide behind the gather: bf16 moves
            # half of float32's bytes, and the cast is elementwise, so every
            # parameter gets the bits gather-then-cast gives. Mixed leaves
            # gather in float32 and cast a leaf at a time.
            travels = out_dtypes[0] if len(set(out_dtypes)) == 1 else jnp.float32
            self._note_param_gather(travels)
            shard = jax.lax.with_sharding_constraint(
                new_master.astype(travels), self._shard_sharding())
            full = jax.lax.with_sharding_constraint(
                shard, train_sharding(self.mesh, "zero/gathered"))
            new_params = unflatten_dense_tensors(full, treedef, shapes, out_dtypes)
        if not self.keep_master:
            new_master = jnp.zeros((0,), jnp.float32)
        return new_params, ZeroState(flat_master=new_master, inner_state=new_inner)

    # -- host path (ZeRO-Offload) -----------------------------------------
    def update_host(self, grads, opt_state, params, lr=None):
        """Host-side step (ZeRO-Offload). ``offload_stream_buckets >= 2``
        runs the three-stage per-bucket pipeline (_update_host_streamed);
        the default collapses to the sequential leaf-at-a-time path — the
        two are bitwise-identical because slice-stepping the host Adam over
        any disjoint cover of [0, numel) equals the full-vector step
        (pinned by tests/unit/test_cpu_adam.py)."""
        if self._offload_streaming:
            return self._update_host_streamed(grads, opt_state, params, lr=lr)
        return self._update_host_sequential(grads, opt_state, params, lr=lr)

    def _update_host_sequential(self, grads, opt_state, params, lr=None):
        """Sequential host step with a pipelined D2H / compute / H2D boundary
        (reference overlaps via pinned double buffers, csrc/adam/cpu_adam.cpp):

        1. async D2H is kicked off for EVERY dense grad leaf up front
           (``copy_to_host_async``) — transfers run while earlier leaves
           compute; leaves that cannot kick one are counted
           (Train/offload_sync_fetch_total) and flagged once per process
           (train/offload_sync_fallback) instead of degrading silently;
        2. leaves step the host master slice-by-slice (C++ Adam on the leaf's
           [lo, hi) range; one shared Adam step counter per logical step);
        3. each leaf's updated params start their async H2D (``device_put``)
           immediately, overlapping the remaining leaves' host compute.

        Grad leaves may be ``CSRTensor``s (sparse embedding gradients,
        reference engine.py:1186-1242): only the touched rows cross the
        device→host boundary; the dense layout is rebuilt host-side."""
        from deepspeed_tpu.runtime.csr_tensor import CSRTensor

        treedef, shapes, dtypes, _ = self._spec
        leaves = jax.tree_util.tree_leaves(grads)

        # (1) start all D2H transfers before any host compute
        _note_sync_fetches(_kick_async_copies(leaves), len(leaves))

        repl = train_sharding(self.mesh, "zero/gathered")
        lr_f = lr
        master = self._host_master
        new_leaves = []
        offset = 0
        for i, (leaf, shape, dtype) in enumerate(zip(leaves, shapes, dtypes)):
            n = int(np.prod(shape)) if shape else 1
            with allowed_transfer(OFFLOAD_D2H):
                if isinstance(leaf, CSRTensor):
                    g = np.zeros(leaf.dense_size, np.float32)
                    idx = np.asarray(jax.device_get(leaf.indices))
                    if idx.size:
                        g[idx] = np.asarray(jax.device_get(leaf.values), np.float32)
                    g = g.reshape(-1)
                else:
                    g = np.asarray(jax.device_get(leaf), np.float32).reshape(-1)
            # (2) C++/numpy Adam on this leaf's master range
            self.inner.step_host(
                master, g, lr=lr_f, lo=offset, hi=offset + n, advance_step=(i == 0)
            )
            # (3) async H2D of the updated leaf while later leaves compute
            # (numpy straight into device_put: one transfer, async; routing
            # through jnp.asarray would commit a second, synchronous copy).
            # The copy=True is load-bearing: on the CPU backend device_put can
            # adopt an aligned numpy buffer zero-copy, and a VIEW into
            # self._host_master would silently mutate these params on the
            # NEXT in-place step_host.
            upd = np.array(
                master[offset:offset + n].reshape(shape), dtype=dtype, copy=True
            )
            with allowed_transfer(OFFLOAD_H2D):
                new_leaves.append(jax.device_put(upd, repl))
            offset += n
        # padding tail (if any) never holds real params; leave it untouched
        new_params = jax.tree_util.tree_unflatten(treedef, new_leaves)
        return new_params, opt_state

    def _ensure_offload_pipeline(self):
        """The two persistent daemon stage workers of the streamed host step:
        'zero-offload-adam' (stage 2, host optimizer) and 'zero-offload-h2d'
        (stage 3, param commit). Started lazily on the first streamed step;
        restarted if a previous worker died with the interpreter shutdown."""
        if self._offload_queues is not None and \
                all(t.is_alive() for t in self._offload_threads):
            return self._offload_queues
        adam_q, h2d_q = queue.Queue(), queue.Queue()
        threads = (
            threading.Thread(target=_offload_stage_loop, args=(adam_q,),
                             name="zero-offload-adam", daemon=True),
            threading.Thread(target=_offload_stage_loop, args=(h2d_q,),
                             name="zero-offload-h2d", daemon=True),
        )
        for t in threads:
            t.start()
        self._offload_queues = (adam_q, h2d_q)
        self._offload_threads = threads
        return self._offload_queues

    def _update_host_streamed(self, grads, opt_state, params, lr=None):
        """Three-stage per-bucket pipeline (ZeRO-Offload/ZeRO-Infinity's
        overlapped optimizer traffic, reference stage2.py:743-900 plus the
        csrc pinned double buffers):

          stage 1 (training thread): per-bucket D2H — async copies were
            kicked for every leaf up front, so each fetch materializes a
            host view/copy of an already-landed buffer (on CPU backends a
            zero-copy view);
          stage 2 ('zero-offload-adam' worker): host Adam over each leaf's
            [lo, hi) master range — bitwise identical to the sequential
            path (slice-stepping == full-vector stepping; shared step
            counter advances once, on the first leaf). The step is OUT-OF-
            PLACE (``master_out``): params for this step land in the ping-
            pong partner buffer while the current master stays untouched;
          stage 3 ('zero-offload-h2d' worker): the partner buffer's leaf
            views committed back via sharding-aware device_put with NO
            snapshot copy — the runtime may adopt the buffer zero-copy,
            which is safe exactly because the out-of-place step never
            rewrites it until two steps later, when the adopted arrays
            are dead. (The in-place sequential path must pay a full
            master copy per step for the same safety; eliminating that
            copy is the streamed path's single-core win, on top of the
            multi-core stage overlap.)

        Host Adam for bucket i overlaps the D2H of bucket i+1 AND the H2D
        of bucket i-1. A two-token semaphore bounds stage 1 to two buckets
        in flight, so host grad staging high-water stays bounded on
        backends where device_get materializes copies. After the last
        commit the buffers swap: the partner becomes the master. Under
        ``offload_pin_host`` the pair is persistent (steady-state zero
        allocation; param arrays from two updates ago alias the recycled
        buffer — the engine never reads that old generation, but external
        holders of stale param trees must copy); with it off a fresh
        partner is allocated every step (no aliasing across updates, one
        full-master allocation per step). Every transfer goes through the
        named allowlist (zero/offload_d2h, zero/offload_h2d) — a
        surrounding transfer_free() region stays honest. The call is
        synchronous: it returns only after every bucket committed, so
        checkpoint/rollback state is always step-consistent."""
        treedef, shapes, dtypes, _ = self._spec
        leaves = jax.tree_util.tree_leaves(grads)
        buckets = self._ensure_buckets()
        nleaf = [int(np.prod(s)) if s else 1 for s in shapes]  # jaxlint: disable=JL002(static host-side shape arithmetic)
        ele_off = [0]
        for n in nleaf:
            ele_off.append(ele_off[-1] + n)

        tracer = telemetry.get_tracer()
        t_wall = time.perf_counter()
        _note_sync_fetches(_kick_async_copies(leaves), len(leaves))

        adam_q, h2d_q = self._ensure_offload_pipeline()
        src = self._host_master
        if self.offload_pin_host and self._offload_master_next is not None \
                and self._offload_master_next.shape == src.shape \
                and self._offload_master_next.flags.writeable:
            dst = self._offload_master_next
        else:
            dst = np.empty_like(src)
        # buckets cover [0, numel); carry the alignment-padding tail over so
        # the swapped-in master stays bitwise-equal to the sequential one
        if ele_off[-1] < src.shape[0]:
            dst[ele_off[-1]:] = src[ele_off[-1]:]

        repl = train_sharding(self.mesh, "zero/gathered")
        lr_f = lr
        fetched = [None] * len(leaves)
        new_leaves = [None] * len(leaves)
        state = {"error": None, "host_s": 0.0, "h2d_s": 0.0}
        slot_free = threading.Semaphore(2)
        done = threading.Event()

        def h2d_task(b, lo_l, hi_l):
            if state["error"] is not None:
                return
            t0 = time.perf_counter()
            try:
                with _span(tracer, "train/offload_h2d",
                           bucket=b, leaves=hi_l - lo_l,
                           numel=ele_off[hi_l] - ele_off[lo_l]):
                    with allowed_transfer(OFFLOAD_H2D):
                        for i in range(lo_l, hi_l):
                            # a VIEW of dst, deliberately: dst is written
                            # out-of-place and not recycled until these
                            # arrays are dead, so zero-copy adoption is
                            # safe and the per-leaf snapshot copy the
                            # sequential path pays is eliminated
                            upd = dst[ele_off[i]:ele_off[i + 1]].reshape(shapes[i])
                            if upd.dtype != dtypes[i]:
                                upd = np.asarray(upd, dtype=dtypes[i])  # jaxlint: disable=JL002(host-side dtype cast, no device traffic)
                            new_leaves[i] = jax.device_put(upd, repl)  # jaxlint: disable=JL002(the offload H2D commit itself, allowlisted zero/offload_h2d)
            except BaseException as e:  # noqa: BLE001 — re-raised on the training thread
                state["error"] = e
            finally:
                state["h2d_s"] += time.perf_counter() - t0

        def adam_task(b, lo_l, hi_l, first):
            t0 = time.perf_counter()
            try:
                if state["error"] is None:
                    with _span(tracer, "train/offload_host_step",
                               bucket=b,
                               numel=ele_off[hi_l] - ele_off[lo_l]):
                        for i in range(lo_l, hi_l):
                            self.inner.step_host(
                                src, fetched[i], lr=lr_f,
                                lo=ele_off[i], hi=ele_off[i + 1],
                                advance_step=first and i == lo_l,
                                master_out=dst)
                            fetched[i] = None  # release the grad buffer
            except BaseException as e:  # noqa: BLE001 — re-raised on the training thread
                state["error"] = e
            finally:
                state["host_s"] += time.perf_counter() - t0
                # stage 2 consumed this bucket's grads; stage 1 may advance
                slot_free.release()
            h2d_q.put(lambda: h2d_task(b, lo_l, hi_l))

        # stage 1: per-bucket D2H on the training thread
        d2h_s = 0.0
        for b, (lo_l, hi_l) in enumerate(buckets):
            slot_free.acquire()
            if state["error"] is not None:
                slot_free.release()
                break
            # timed AFTER the slot wait: blocking on backpressure is hidden
            # time, not D2H work — counting it would inflate overlap_frac
            t0 = time.perf_counter()
            with _span(tracer, "train/offload_d2h", bucket=b,
                       numel=ele_off[hi_l] - ele_off[lo_l]):
                with allowed_transfer(OFFLOAD_D2H):
                    for i in range(lo_l, hi_l):
                        leaf = leaves[i]
                        if hasattr(leaf, "dense_size"):  # CSR: densify
                            buf = np.empty(nleaf[i], np.float32)
                            _fetch_flat_grad(leaf, buf)
                            fetched[i] = buf
                        else:
                            fetched[i] = np.asarray(  # jaxlint: disable=JL002(the offload D2H fetch itself, allowlisted zero/offload_d2h)
                                jax.device_get(leaf), np.float32).reshape(-1)  # jaxlint: disable=JL002(async copy kicked up front; zero-copy view on CPU)
            d2h_s += time.perf_counter() - t0
            adam_q.put(lambda b=b, lo=lo_l, hi=hi_l,
                       first=(b == 0): adam_task(b, lo, hi, first))
        # flush: FIFO queues + single workers mean this runs strictly after
        # every bucket's stage 2, which enqueued every bucket's stage 3
        adam_q.put(lambda: h2d_q.put(done.set))
        done.wait()

        wall_s = time.perf_counter() - t_wall
        if state["error"] is not None:
            raise state["error"]
        # commit the ping-pong swap only on success: on error the master is
        # untouched (out-of-place step) and dst is next step's scratch
        self._host_master = dst
        self._offload_master_next = src if self.offload_pin_host else None
        busy = d2h_s + state["host_s"] + state["h2d_s"]
        overlap = max(0.0, min(1.0, (busy - wall_s) / busy)) if busy > 0 else 0.0
        self.last_offload_stats = {
            "buckets": len(buckets),
            "d2h_ms": d2h_s * 1000.0,
            "host_step_ms": state["host_s"] * 1000.0,
            "h2d_ms": state["h2d_s"] * 1000.0,
            "wall_ms": wall_s * 1000.0,
            "overlap_frac": overlap,
        }
        # padding tail (if any) never holds real params; leave it untouched
        new_params = jax.tree_util.tree_unflatten(treedef, new_leaves)
        return new_params, opt_state

    # -- elastic checkpointing --------------------------------------------
    def shard_state_dicts(self, opt_state):
        """Per-dp-rank logical shards + metadata (unpadded), so a later run at a
        different dp degree can re-partition (reference 'lean' states)."""
        if self.cpu_offload:
            return self._host_shard_state_dicts()
        has_master = self.keep_master
        flat = np.asarray(jax.device_get(opt_state.flat_master), np.float32) if has_master else None
        inner_leaves, inner_treedef = jax.tree_util.tree_flatten(jax.device_get(opt_state.inner_state))
        shard_size = self._padded // self.dp
        shards = []
        for r in range(self.dp):
            lo, hi = r * shard_size, (r + 1) * shard_size
            hi_logical = min(hi, self._numel)
            shard = {
                "rank": r,
                "dp_world_size": self.dp,
                "numel": self._numel,
                # fp32 compute: master == params; the module checkpoint carries it.
                "master_from_params": not has_master,
                "flat_master": flat[lo:hi_logical] if has_master else None,
                "inner": [
                    np.asarray(l[lo:hi_logical]) if getattr(l, "ndim", 0) == 1 and l.shape[0] == self._padded else np.asarray(l)
                    for l in inner_leaves
                ],
            }
            shards.append(shard)
        return shards

    def _host_shard_state_dicts(self):
        """Offload variant: shards come from the HOST master + host Adam state
        (the device copy does not exist under cpu_offload)."""
        flat = self._host_master
        hs = getattr(self.inner, "_host_state", None)
        shard_size = flat.shape[0] // self.dp
        shards = []
        for r in range(self.dp):
            lo, hi = r * shard_size, (r + 1) * shard_size
            hi_logical = min(hi, self._numel)
            shard = {
                "rank": r,
                "dp_world_size": self.dp,
                "numel": self._numel,
                "cpu_offload": True,
                "flat_master": flat[lo:hi_logical].copy(),
                "inner": [] if hs is None else [
                    np.asarray([hs.step]), hs.exp_avg[lo:hi_logical].copy(), hs.exp_avg_sq[lo:hi_logical].copy(),
                ],
            }
            shards.append(shard)
        return shards

    def _host_load_shard_state_dicts(self, opt_state, shards):
        shards = sorted(shards, key=lambda s: s["rank"])
        numel = shards[0]["numel"]
        assert numel == self._numel, f"checkpoint numel {numel} != model numel {self._numel}"
        full = np.concatenate([s["flat_master"] for s in shards])[:numel]
        pad = self._host_master.shape[0] - numel
        self._host_master = np.concatenate([full, np.zeros(pad, np.float32)]) if pad > 0 else full
        # drop the ping-pong partner: it may still back param arrays from the
        # abandoned timeline, and the loaded master deserves a clean pair
        self._offload_master_next = None
        if shards[0]["inner"]:
            hs = self.inner.init_host(self._host_master)
            hs.step = int(shards[0]["inner"][0][0])
            ea = np.concatenate([s["inner"][1] for s in shards])[:numel]
            es = np.concatenate([s["inner"][2] for s in shards])[:numel]
            hs.exp_avg = np.concatenate([ea, np.zeros(pad, np.float32)]) if pad > 0 else ea
            hs.exp_avg_sq = np.concatenate([es, np.zeros(pad, np.float32)]) if pad > 0 else es
        return opt_state

    def load_shard_state_dicts(self, opt_state, shards):
        """Merge shards from any dp degree, re-partition for the current one."""
        if self.cpu_offload or shards[0].get("cpu_offload"):
            return self._host_load_shard_state_dicts(opt_state, shards)
        shards = sorted(shards, key=lambda s: s["rank"])
        numel = shards[0]["numel"]
        assert numel == self._numel, (
            f"checkpoint numel {numel} != model numel {self._numel}"
        )

        inner_leaves_t, inner_treedef = jax.tree_util.tree_flatten(opt_state.inner_state)
        n_inner = len(shards[0]["inner"])
        merged_inner = []
        for i in range(n_inner):
            tmpl = inner_leaves_t[i]
            if getattr(tmpl, "ndim", 0) == 1 and tmpl.shape[0] == self._padded:
                merged = np.concatenate([s["inner"][i] for s in shards])[:numel]
                pad = tmpl.shape[0] - numel
                if pad > 0:
                    merged = np.concatenate([merged, np.zeros(pad, merged.dtype)])
                merged_inner.append(jax.device_put(jnp.asarray(merged, tmpl.dtype), tmpl.sharding))
            else:
                merged_inner.append(jnp.asarray(shards[0]["inner"][i], tmpl.dtype))
        new_inner = jax.tree_util.tree_unflatten(inner_treedef, merged_inner)

        if shards[0].get("master_from_params"):
            if self.keep_master:
                # Saved under fp32 compute (no stored master), loading under
                # fp16/bf16 which requires one. Failing here is better than an
                # empty master crashing mid-step far from the load site.
                raise ValueError(
                    "This ZeRO checkpoint was saved with fp32 compute (the fp32 "
                    "params serve as the master; none is stored). Loading it into "
                    "a mixed-precision run needs a stored master — resume with "
                    "fp32 compute, or re-save the checkpoint from a mixed-"
                    "precision run."
                )
            return ZeroState(flat_master=jnp.zeros((0,), jnp.float32), inner_state=new_inner)
        if not self.keep_master:
            # Mixed-precision checkpoint into an fp32 run: the stored master is
            # simply ignored (params from the module checkpoint are the master).
            return ZeroState(flat_master=jnp.zeros((0,), jnp.float32), inner_state=new_inner)
        full_master = np.concatenate([s["flat_master"] for s in shards])[:numel]
        pad = self._padded - numel
        if pad > 0:
            full_master = np.concatenate([full_master, np.zeros(pad, np.float32)])
        new_master = jax.device_put(jnp.asarray(full_master, jnp.float32), self._shard_sharding())
        return ZeroState(flat_master=new_master, inner_state=new_inner)
