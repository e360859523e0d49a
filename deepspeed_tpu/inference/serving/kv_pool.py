"""Paged KV-cache pool for continuous-batching inference.

The pool stores keys/values as FIXED-SIZE PAGES of ``page_tokens``
positions each — ``[L, n_pages, nh, page_tokens, hd]`` — instead of one
contiguous ``S_max`` stripe per slot. A *slot* is still one admission
lane (the engine's compiled programs are shaped by ``max_slots``), but a
lane's tokens now live wherever its PAGE TABLE points: ``page_tables``
is a host-side ``[max_slots, pages_per_lane]`` int32 map from a lane's
logical page index to a physical page, uploaded to the device only when
lane membership changes. The jitted decode/prefill programs gather and
scatter BY PAGE INDEX, so:

- the bucket ladder extends into 16k–64k without paying
  ``MaxSlots x S_max`` bytes up front — short requests claim few pages,
  long requests claim many, all against ONE shared ``pool_tokens``
  budget (the ZeRO-Infinity tiering shape: fixed-size units under a
  single budget, no fragmentation classes);
- slot churn moves host integers around, never recompiles (shapes are
  fixed at construction, exactly as before).

Physical page 0 is the NULL page: it is never allocated, page-table
rows are zeroed on free, and every jitted scatter routes inactive /
out-of-range writes to it. A freed lane's masked decode step may keep
writing garbage — it lands on the null page, so a page reallocated to a
new request can never be corrupted by its previous owner. That plus
install overwriting every mapped page preserves the old slot-hygiene
contract verbatim.

``page_tokens`` always DIVIDES ``max_seq_len`` (``resolve_page_tokens``
falls back to the gcd), so a full lane is exactly ``pages_per_lane``
pages and gathering a lane's pages back-to-back reproduces the old
contiguous ``[nh, S_max, hd]`` stripe bit-for-bit — which is how the
dense decode programs stay bitwise-identical to the contiguous pool.

Storage dtype (``kv_cache_dtype``): "fp32" stores the compute dtype,
"bf16" halves the bytes, "int8" quarters them with per-(layer, slot,
head) symmetric fp32 scales. Scales stay PER-LANE (pages are never
shared between lanes), set once at install from the prefilled lane's
amax and fixed while the lane decodes — re-storing an untouched row is
a bitwise no-op, as before.
"""

import numpy as np

import jax
import jax.numpy as jnp

from ..generation import DEFAULT_PAGE_TOKENS, resolve_page_tokens
from ..quantization import quantize_kv
from deepspeed_tpu.parallel.mesh import mp_world_size
from deepspeed_tpu.parallel.sharding_registry import serving_sharding

KV_CACHE_DTYPES = ("fp32", "bf16", "int8")


class PoolExhaustedError(RuntimeError):
    """allocate() found no free slot or not enough free pages. The
    scheduler treats this as "keep the request queued", never as a hard
    failure — it is an error type so direct pool users cannot mistake
    -1 style sentinels for a slot id."""


class PageStateError(ValueError):
    """A page/slot lifecycle violation: freeing a slot that is already
    free, installing into a slot that was never allocated, or raw-
    installing over a live lane under a DIFFERENT handoff key. Named so
    the disaggregated handoff path can distinguish a state-machine bug
    from silent free-list corruption (the failure mode it replaces).
    Subclasses ValueError so pre-existing double-free callers keep
    their except clauses."""


def _install_pages(pool_k, pool_v, new_k, new_v, dest_pages, page_tokens):
    """Scatter a prefilled single-request cache ([L, 1, nh, S, hd],
    S >= pages_per_lane * page_tokens) into the pool's pages at
    ``dest_pages`` [pages_per_lane] (traced — any page assignment reuses
    one compiled program). Unallocated logical pages carry dest 0 and
    land harmlessly on the null page. The cast covers the "bf16" storage
    mode and is a no-op when dtypes already match."""
    L, _, nh, _, hd = new_k.shape
    mp = dest_pages.shape[0]
    span = mp * page_tokens

    def paged(buf):
        lane = buf[:, 0, :, :span]                       # [L, nh, span, hd]
        pages = lane.reshape(L, nh, mp, page_tokens, hd)
        return jnp.moveaxis(pages, 2, 1)                 # [L, mp, nh, pt, hd]

    with jax.named_scope("install_pages"):
        pool_k = pool_k.at[:, dest_pages].set(
            paged(new_k).astype(pool_k.dtype))
        pool_v = pool_v.at[:, dest_pages].set(
            paged(new_v).astype(pool_v.dtype))
    return pool_k, pool_v


def _install_pages_int8(pool_k, pool_v, k_scale, v_scale, new_k, new_v,
                        dest_pages, slot, page_tokens):
    """int8-mode install: quantize the prefilled lane with fresh
    per-(layer, head) scales, page it, and overwrite both the mapped
    pages and the lane's scale rows — a reallocated slot never inherits
    the previous occupant's scale range."""
    L, _, nh, _, hd = new_k.shape
    mp = dest_pages.shape[0]
    span = mp * page_tokens

    def quant_paged(buf):
        q, s = quantize_kv(buf[:, 0, :, :span])          # [L, nh, span, hd]
        pages = q.reshape(L, nh, mp, page_tokens, hd)
        return jnp.moveaxis(pages, 2, 1), s

    with jax.named_scope("install_pages"):
        qk, sk = quant_paged(new_k)
        qv, sv = quant_paged(new_v)
        pool_k = pool_k.at[:, dest_pages].set(qk)
        pool_v = pool_v.at[:, dest_pages].set(qv)
        k_scale = jax.lax.dynamic_update_index_in_dim(k_scale, sk, slot,
                                                      axis=1)
        v_scale = jax.lax.dynamic_update_index_in_dim(v_scale, sv, slot,
                                                      axis=1)
    return pool_k, pool_v, k_scale, v_scale


# Donate the pool buffers: the install is an in-place page overwrite, the
# old pool is dead the moment the new one exists. (Scales are donated too
# in the int8 path — the install REPLACES the slot's scale rows.)
_install_pages_jit = jax.jit(_install_pages, donate_argnums=(0, 1),
                             static_argnums=(5,))
_install_pages_int8_jit = jax.jit(_install_pages_int8,
                                  donate_argnums=(0, 1, 2, 3),
                                  static_argnums=(8,))


# -- host entry frame export / import (prefix-cache spill tier) ---------
def export_entry_frames(k, v, k_scale=None, v_scale=None):
    """Serialize a host-side KV entry (numpy ``[L, nh, P, hd]`` pair in
    its STORAGE dtype — fp32/bf16/int8 — plus optional per-(layer, head)
    fp32 scales) into ``(meta, frames)``: raw ``bytes`` payloads the
    spill tier can frame/checksum individually, and the meta dict
    ``import_entry_frames`` needs to rebuild the arrays bit-for-bit.
    The generalization of ``export_lane``'s tobytes/frombuffer discipline
    to entries that never lived in the pool."""
    meta = {
        "dtype": str(np.dtype(k.dtype)),
        "shape": list(k.shape),
        "scales": k_scale is not None,
    }
    frames = [k.tobytes(), v.tobytes()]
    if k_scale is not None:
        meta["scale_shape"] = list(k_scale.shape)
        frames.append(np.ascontiguousarray(k_scale, np.float32).tobytes())
        frames.append(np.ascontiguousarray(v_scale, np.float32).tobytes())
    return meta, frames


def import_entry_frames(meta, frames):
    """Inverse of ``export_entry_frames``: rebuild ``(k, v, k_scale,
    v_scale)`` from a meta dict and its byte frames. Raises ValueError
    when a frame's byte count disagrees with the advertised shape/dtype
    (a framing-level corruption the crc missed structurally)."""
    dtype = np.dtype(str(meta["dtype"]))
    shape = tuple(int(d) for d in meta["shape"])
    expect = dtype.itemsize * int(np.prod(shape))
    if len(frames[0]) != expect or len(frames[1]) != expect:
        raise ValueError(
            f"entry frame carries {len(frames[0])}/{len(frames[1])} bytes "
            f"but shape {shape} x {dtype} needs {expect}")
    k = np.frombuffer(frames[0], dtype).reshape(shape)
    v = np.frombuffer(frames[1], dtype).reshape(shape)
    k_scale = v_scale = None
    if meta.get("scales"):
        sshape = tuple(int(d) for d in meta["scale_shape"])
        sexpect = 4 * int(np.prod(sshape))
        if len(frames[2]) != sexpect or len(frames[3]) != sexpect:
            raise ValueError(
                f"scale frame carries {len(frames[2])}/{len(frames[3])} "
                f"bytes but shape {sshape} x float32 needs {sexpect}")
        k_scale = np.frombuffer(frames[2], np.float32).reshape(sshape)
        v_scale = np.frombuffer(frames[3], np.float32).reshape(sshape)
    return k, v, k_scale, v_scale


class PagedSlots:
    """The host-side allocator every serving pool shares: ``max_slots``
    admission lanes, and pages of ``page_tokens`` positions under one
    ``pool_tokens`` budget, mapped per lane by ``page_tables`` (physical
    page 0 is the null page). No device memory: a subclass holds what the
    pages and the slots index (keys and values, latent rows, recurrent
    state)."""

    def __init__(self, max_slots, max_seq_len, page_tokens=None,
                 pool_tokens=None):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if max_seq_len < 2:
            raise ValueError(f"max_seq_len must be >= 2, got {max_seq_len}")
        self.max_slots = int(max_slots)
        self.max_seq_len = int(max_seq_len)
        self.page_tokens = resolve_page_tokens(
            page_tokens or DEFAULT_PAGE_TOKENS, self.max_seq_len)
        self.pages_per_lane = self.max_seq_len // self.page_tokens
        # Shared token budget across all lanes. The default keeps the old
        # every-lane-can-be-full capacity; a smaller budget is where the
        # paged layout beats the contiguous MaxSlots x S_max footprint
        # (long and short requests share it instead of each reserving
        # S_max). Floor of one full lane so a single max-length request
        # always fits.
        if pool_tokens is None:
            pool_tokens = self.max_slots * self.max_seq_len
        if int(pool_tokens) < 1:
            raise ValueError(f"pool_tokens must be >= 1, got {pool_tokens}")
        self.pool_tokens = max(int(pool_tokens),
                               self.pages_per_lane * self.page_tokens)
        self.n_data_pages = self.pool_tokens // self.page_tokens
        self.n_pages = self.n_data_pages + 1             # + null page 0
        # lowest-index-first allocation keeps slot/page assignment
        # deterministic for a given arrival order (oracle tests replay
        # schedules)
        self._free = sorted(range(self.max_slots), reverse=True)
        self._free_pages = sorted(range(1, self.n_pages), reverse=True)
        # logical->physical page map per lane; 0 (the null page) means
        # unmapped. The engine mirrors this to the device only on churn.
        self.page_tables = np.zeros((self.max_slots, self.pages_per_lane),
                                    np.int32)
        self._lane_pages = [[] for _ in range(self.max_slots)]
        # per-slot NEXT write/read position (== tokens cached so far)
        self.positions = np.zeros(self.max_slots, np.int32)
        self.allocations = 0
        self.frees = 0
        self.peak_in_use = 0
        self.peak_pages_in_use = 0

    # -- slot lifecycle -------------------------------------------------
    @property
    def slots_in_use(self):
        return self.max_slots - len(self._free)

    @property
    def free_slots(self):
        return len(self._free)

    @property
    def pages_in_use(self):
        return self.n_data_pages - len(self._free_pages)

    @property
    def free_pages(self):
        return len(self._free_pages)

    def _pages_needed(self, n_tokens):
        if n_tokens is None:
            n_tokens = self.max_seq_len
        n_tokens = min(max(int(n_tokens), 1), self.max_seq_len)
        return -(-n_tokens // self.page_tokens)

    def can_allocate(self, n_tokens=None):
        """True iff allocate(n_tokens) would succeed right now."""
        return (bool(self._free)
                and self._pages_needed(n_tokens) <= len(self._free_pages))

    def allocate(self, n_tokens=None):
        """Claim the lowest free slot plus enough pages for ``n_tokens``
        positions (default: a full ``max_seq_len`` lane — the contiguous
        pool's behavior). PoolExhaustedError when out of slots or pages;
        the pool is untouched on failure, so callers can requeue."""
        if not self._free:
            raise PoolExhaustedError(
                f"all {self.max_slots} KV-cache slots are in use")
        need = self._pages_needed(n_tokens)
        if need > len(self._free_pages):
            raise PoolExhaustedError(
                f"KV page pool exhausted: need {need} pages, "
                f"{len(self._free_pages)} of {self.n_data_pages} free "
                f"({self.page_tokens} tokens/page)")
        slot = self._free.pop()
        pages = [self._free_pages.pop() for _ in range(need)]
        self.page_tables[slot] = 0
        self.page_tables[slot, :need] = pages
        self._lane_pages[slot] = pages
        self.allocations += 1
        self.peak_in_use = max(self.peak_in_use, self.slots_in_use)
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.pages_in_use)
        self.positions[slot] = 0
        return slot

    def free(self, slot):
        if not 0 <= slot < self.max_slots:
            raise ValueError(f"slot {slot} outside [0, {self.max_slots})")
        if slot in self._free:
            raise PageStateError(
                f"slot {slot} is already free (double free)")
        self._on_free(slot)
        self.frees += 1
        self.positions[slot] = 0
        # zero the table row BEFORE returning pages: the freed lane's
        # masked decode writes must route to the null page from the next
        # uploaded table on, never to a page someone else now owns
        self.page_tables[slot] = 0
        self._free_pages.extend(self._lane_pages[slot])
        self._free_pages.sort(reverse=True)
        self._lane_pages[slot] = []
        self._free.append(slot)
        self._free.sort(reverse=True)

    def lane_tokens(self, slot):
        """Token capacity actually backed by this lane's pages."""
        return len(self._lane_pages[slot]) * self.page_tokens

    def _on_free(self, slot):
        """Hook: what a subclass forgets about a slot being freed."""

    def advance(self, slot):
        """Bump a slot's position after a decode step wrote its token.
        Clamped at the last cache index: a (injected-fault) runaway
        request keeps overwriting the final position instead of relying
        on silent OOB-scatter behavior."""
        self.positions[slot] = min(self.positions[slot] + 1,
                                   self.max_seq_len - 1)

    def occupancy(self):
        """The allocator's occupancy snapshot for metrics/debugging."""
        in_use = self.slots_in_use
        covered = self.pages_in_use * self.page_tokens
        return {
            "max_slots": self.max_slots,
            "in_use": in_use,
            "free": self.free_slots,
            "utilization": in_use / self.max_slots,
            "allocations": self.allocations,
            "frees": self.frees,
            "peak_in_use": self.peak_in_use,
            "cached_tokens": int(self.positions.sum()),
            "page_tokens": self.page_tokens,
            "pages_total": self.n_data_pages,
            "pages_in_use": self.pages_in_use,
            "pages_free": self.free_pages,
            "peak_pages_in_use": self.peak_pages_in_use,
            # tokens reserved by claimed pages but not (yet) cached —
            # internal fragmentation of the page granularity
            "page_fragmentation": ((covered - int(self.positions.sum()))
                                   / max(covered, 1)),
        }


class KVCachePool(PagedSlots):
    """Fixed-capacity paged KV storage over the shared allocator."""

    def __init__(self, n_layers, max_slots, n_heads, max_seq_len, head_dim,
                 dtype=jnp.float32, kv_cache_dtype="fp32",
                 page_tokens=None, pool_tokens=None, mesh=None,
                 registry=None):
        if kv_cache_dtype not in KV_CACHE_DTYPES:
            raise ValueError(
                f"kv_cache_dtype must be one of {KV_CACHE_DTYPES}, "
                f"got {kv_cache_dtype!r}")
        super().__init__(max_slots, max_seq_len, page_tokens, pool_tokens)
        self.n_layers = int(n_layers)
        self.n_heads = int(n_heads)
        self.head_dim = int(head_dim)
        # ``dtype`` is the model's COMPUTE dtype ("fp32" mode stores it
        # directly); quantized modes store narrower and dequant at use.
        self.compute_dtype = dtype
        self.kv_cache_dtype = kv_cache_dtype
        n_pages = self.n_pages
        shape = (self.n_layers, n_pages, self.n_heads,
                 self.page_tokens, self.head_dim)
        storage = {"fp32": dtype, "bf16": jnp.bfloat16,
                   "int8": jnp.int8}[kv_cache_dtype]
        # Tensor-parallel pool: the heads dim splits over the mesh's
        # `model` axis (specs resolved through the sharding registry —
        # the single source both engines consume). mesh=None keeps the
        # single-device layout byte-identical.
        self.mesh = mesh
        self.kv_sharding = None
        self.replicated_sharding = None
        if mesh is not None:
            mp = mp_world_size(mesh)
            if self.n_heads % mp != 0:
                raise ValueError(
                    f"n_heads={self.n_heads} not divisible by the mesh's "
                    f"model axis size {mp}; the KV pool shards heads")
            self.kv_sharding = serving_sharding(mesh, "serving/kv_pool",
                                                registry=registry)
            self.replicated_sharding = serving_sharding(
                mesh, "serving/lane_state", registry=registry)
            self.k = jnp.zeros(shape, storage, device=self.kv_sharding)
            self.v = jnp.zeros(shape, storage, device=self.kv_sharding)
        else:
            self.k = jnp.zeros(shape, storage)
            self.v = jnp.zeros(shape, storage)
        if kv_cache_dtype == "int8":
            # one symmetric scale per (layer, slot, head) — per LANE, not
            # per page: pages are never shared across lanes, and keeping
            # the old shape keeps dequantize_kv broadcasting unchanged
            sshape = (self.n_layers, self.max_slots, self.n_heads, 1, 1)
            if mesh is not None:
                scale_sh = serving_sharding(mesh, "serving/kv_scale",
                                            registry=registry)
                self.k_scale = jnp.ones(sshape, jnp.float32,
                                        device=scale_sh)
                self.v_scale = jnp.ones(sshape, jnp.float32,
                                        device=scale_sh)
            else:
                self.k_scale = jnp.ones(sshape, jnp.float32)
                self.v_scale = jnp.ones(sshape, jnp.float32)
        else:
            self.k_scale = None
            self.v_scale = None
        # handoff idempotency: key -> slot for lanes installed via
        # install_raw(); a re-sent handoff under a live key is a no-op
        self._handoff_keys = {}
        self._slot_handoff_key = {}

    def host_put(self, x, dtype=None, sharded=False):
        """Sharding-aware host->device placement: on a mesh, commit to
        the registry-resolved sharding (replicated lane state, or the
        pool's heads-sharded layout when ``sharded``) instead of the
        default device — a default-device put on a >1-device mesh would
        force a reshard inside the next jitted step."""
        arr = np.asarray(x, dtype) if dtype is not None else np.asarray(x)
        if self.mesh is None:
            return jnp.asarray(arr)
        target = self.kv_sharding if sharded else self.replicated_sharding
        return jax.device_put(arr, target)

    def _on_free(self, slot):
        key = self._slot_handoff_key.pop(slot, None)
        if key is not None:
            self._handoff_keys.pop(key, None)

    def install(self, new_k, new_v, slot, position):
        """Install a prefilled request cache ([L, 1, nh, S, hd] with
        S >= max_seq_len) into ``slot``'s pages and set its position
        counter (= prompt length: the next decode write index)."""
        if not 0 <= position < self.max_seq_len:
            raise ValueError(
                f"position {position} outside [0, {self.max_seq_len})")
        if slot in self._free:
            raise PageStateError(
                f"install into slot {slot} which is not allocated")
        dest = self.host_put(self.page_tables[slot], jnp.int32)
        if self.kv_cache_dtype == "int8":
            (self.k, self.v, self.k_scale,
             self.v_scale) = _install_pages_int8_jit(
                self.k, self.v, self.k_scale, self.v_scale,
                new_k, new_v, dest, slot, self.page_tokens)
        else:
            self.k, self.v = _install_pages_jit(
                self.k, self.v, new_k, new_v, dest, self.page_tokens)
        self.positions[slot] = position

    def install_lane(self, batch_k, batch_v, lane, slot, position):
        """Install lane ``lane`` of a BATCHED prefill result
        ([L, B, nh, S, hd]) into ``slot``. Reuses the single-lane
        install program (the lane slice is a static index; the dest
        pages and slot stay traced), so batched admission adds no
        install compiles."""
        self.install(batch_k[:, lane:lane + 1], batch_v[:, lane:lane + 1],
                     slot, position)

    # -- raw page export / install (disaggregated handoff) --------------
    def export_lane(self, slot):
        """Snapshot a live lane's pages AS STORED (storage dtype bytes,
        no dequant — the transfer must be bitwise) into host memory.
        Returns ``(meta, frames)``: ``frames`` is one ``bytes`` payload
        per logical page (k-page bytes then v-page bytes, fixed length),
        plus one trailing scales frame in int8 mode; ``meta`` carries
        everything install_raw() needs to rebuild the lane bit-for-bit
        on another pool with the same geometry."""
        if slot in self._free:
            raise PageStateError(
                f"export from slot {slot} which is not allocated")
        pages = self._lane_pages[slot]
        idx = np.asarray(pages, np.int32)
        lane_k = np.asarray(self.k[:, idx])   # [L, n, nh, pt, hd]
        lane_v = np.asarray(self.v[:, idx])
        frames = [lane_k[:, i].tobytes() + lane_v[:, i].tobytes()
                  for i in range(len(pages))]
        meta = {
            "pages": len(pages),
            "position": int(self.positions[slot]),
            "page_tokens": self.page_tokens,
            "kv_cache_dtype": self.kv_cache_dtype,
            "page_nbytes": len(frames[0]) if frames else 0,
            "scales": self.k_scale is not None,
        }
        if self.k_scale is not None:
            sk = np.asarray(self.k_scale[:, slot], np.float32)
            sv = np.asarray(self.v_scale[:, slot], np.float32)
            frames.append(sk.tobytes() + sv.tobytes())
        return meta, frames

    def install_raw(self, slot, meta, frames, handoff_key=None):
        """Install exported pages into an allocated ``slot`` WITHOUT
        re-quantizing — the bytes land in storage exactly as the sender
        stored them, so the resumed lane is bit-identical to the lane
        the prefill worker built. Idempotent under ``handoff_key``: a
        re-sent handoff whose key is already live returns False and
        touches nothing (never double-installs); installing over a live
        lane registered under a DIFFERENT key raises PageStateError."""
        if slot in self._free:
            raise PageStateError(
                f"install_raw into slot {slot} which is not allocated")
        if handoff_key is not None and handoff_key in self._handoff_keys:
            return False                         # idempotent re-send
        held = self._slot_handoff_key.get(slot)
        if held is not None and held != handoff_key:
            raise PageStateError(
                f"slot {slot} already holds handoff key {held!r}; "
                f"refusing install over a live lane under "
                f"{handoff_key!r}")
        n = int(meta["pages"])
        if meta["kv_cache_dtype"] != self.kv_cache_dtype:
            raise PageStateError(
                f"handoff dtype {meta['kv_cache_dtype']!r} does not "
                f"match pool dtype {self.kv_cache_dtype!r}")
        if n > len(self._lane_pages[slot]):
            raise PageStateError(
                f"handoff carries {n} pages but slot {slot} has only "
                f"{len(self._lane_pages[slot])} allocated")
        position = int(meta["position"])
        if not 0 <= position < self.max_seq_len:
            raise ValueError(
                f"position {position} outside [0, {self.max_seq_len})")
        storage = np.dtype(self.k.dtype)
        pshape = (self.n_layers, self.n_heads, self.page_tokens,
                  self.head_dim)
        half = storage.itemsize * int(np.prod(pshape))
        ks, vs = [], []
        for payload in frames[:n]:
            ks.append(np.frombuffer(payload[:half], storage)
                      .reshape(pshape))
            vs.append(np.frombuffer(payload[half:], storage)
                      .reshape(pshape))
        dest = np.asarray(self._lane_pages[slot][:n], np.int32)
        lane_k = np.stack(ks, axis=1)            # [L, n, nh, pt, hd]
        lane_v = np.stack(vs, axis=1)
        self.k = self.k.at[:, dest].set(self.host_put(lane_k, sharded=True))
        self.v = self.v.at[:, dest].set(self.host_put(lane_v, sharded=True))
        if meta.get("scales"):
            if self.k_scale is None:
                raise PageStateError(
                    "handoff carries scales but pool is not int8")
            sshape = (self.n_layers, self.n_heads, 1, 1)
            shalf = 4 * int(np.prod(sshape))
            sbuf = frames[n]
            sk = np.frombuffer(sbuf[:shalf], np.float32).reshape(sshape)
            sv = np.frombuffer(sbuf[shalf:], np.float32).reshape(sshape)
            self.k_scale = self.k_scale.at[:, slot].set(self.host_put(sk))
            self.v_scale = self.v_scale.at[:, slot].set(self.host_put(sv))
        self.positions[slot] = position
        if handoff_key is not None:
            self._handoff_keys[handoff_key] = slot
            self._slot_handoff_key[slot] = handoff_key
        return True

    def handoff_slot(self, handoff_key):
        """Slot currently holding ``handoff_key``, or None."""
        return self._handoff_keys.get(handoff_key)

    # -- stats ----------------------------------------------------------
    def nbytes(self):
        """Device bytes held by the pool's KV storage (+ scales in int8
        mode) — the number ``Serving/kv_pool_bytes`` reports, and the one
        that halves/quarters when kv_cache_dtype narrows."""
        total = self.k.nbytes + self.v.nbytes
        if self.k_scale is not None:
            total += self.k_scale.nbytes + self.v_scale.nbytes
        return int(total)

    def contiguous_equiv_bytes(self):
        """Bytes the OLD contiguous layout ([L, MaxSlots, nh, S_max, hd]
        per cache side, same storage dtype) would spend for the same
        slot count — the footprint the paged pool beats when
        ``pool_tokens`` undercuts ``max_slots * max_seq_len``."""
        itemsize = {"fp32": jnp.dtype(self.compute_dtype).itemsize,
                    "bf16": 2, "int8": 1}[self.kv_cache_dtype]
        elems = (self.n_layers * self.max_slots * self.n_heads
                 * self.max_seq_len * self.head_dim)
        total = 2 * elems * itemsize
        if self.k_scale is not None:
            total += self.k_scale.nbytes + self.v_scale.nbytes
        return int(total)

    def occupancy(self):
        """Occupancy snapshot for metrics/debugging."""
        return dict(super().occupancy(),
                    kv_cache_dtype=self.kv_cache_dtype,
                    pool_bytes=self.nbytes())


def _zero_slot(arrays, slot):
    return {name: a.at[:, slot].set(0) for name, a in arrays.items()}


_zero_slot_jit = jax.jit(_zero_slot, donate_argnums=(0,))


class HybridStatePool(PagedSlots):
    """State of two kinds behind the one allocator, for models whose layers
    do not all cache keys and values:

    - *paged* arrays ``[layers, n_pages, width, page_tokens]`` (no head
      axis): ``width`` values a token, a page's tokens along the last
      axis (they fill the chip's 128-wide tiles exactly, where a width
      such as 576 would be padded), reached through the lane's page table
      like the KV pool's pages (a latent-attention cache); or, described
      by a token's shape instead of a width, ``[layers, n_pages,
      page_tokens, *shape]``: a page's tokens first, each a block of its
      own (``(8, 128)`` is one tile of the chip's memory), for a model
      that fetches single tokens out of its pages and not whole pages;
    - *slot* arrays ``[layers, max_slots, ...]``: a fixed-size state a
      lane (recurrent state, convolution tails, a window layer's ring of
      keys and values).

    So state of two lifetimes lives behind the one allocator: pages, which
    a request claims for its own span out of the ``pool_tokens`` budget and
    gives back when it retires, and a slot's arrays, which are the lane's
    for as long as the pool stands. Admission needs a free slot AND pages
    (``allocate``, inherited), and then ``reset_slot`` for the slot arrays
    named in ``reset`` (default: all of them): a recurrent layer has no
    position mask that could hide the previous occupant, so its rows are
    zeroed when a lane is reused; a ring that is read behind a position
    mask needs no reset, and its family says so. ``state`` is the ``{name:
    array}`` dict the programs take and give back whole (donated)."""

    def __init__(self, max_slots, max_seq_len, paged, slotted,
                 page_tokens=None, pool_tokens=None, reset=None):
        """``paged``: {name: (layers, width or a token's shape, dtype)};
        ``slotted``: {name: (layers, per-slot shape, dtype)}, which may be
        empty (a model whose state is pages only); ``reset``: the slot
        arrays ``reset_slot`` zeroes (None: all)."""
        super().__init__(max_slots, max_seq_len, page_tokens, pool_tokens)
        self.paged_names = tuple(paged)
        self.slot_names = tuple(slotted)
        self.reset_names = (self.slot_names if reset is None
                            else tuple(reset))
        if set(self.reset_names) - set(self.slot_names):
            raise ValueError(f"reset={self.reset_names} names no slot array "
                             f"of {self.slot_names}")
        self.state = {}
        for name, (layers, width, dtype) in paged.items():
            page = ((width, self.page_tokens) if isinstance(width, int)
                    else (self.page_tokens,) + tuple(width))
            self.state[name] = jnp.zeros((layers, self.n_pages) + page, dtype)
        for name, (layers, shape, dtype) in slotted.items():
            self.state[name] = jnp.zeros(
                (layers, self.max_slots) + tuple(shape), dtype)
        self.slot_resets = 0
        # fixed at construction (the arrays are donated and replaced, never
        # resized), so the loop's gauges do not sum them every step
        self._paged_bytes = int(sum(self.state[n].nbytes
                                    for n in self.paged_names))
        self._slot_bytes = int(sum(self.state[n].nbytes
                                   for n in self.slot_names))

    def reset_slot(self, slot):
        """Zero ``slot``'s rows of the slot arrays in ``reset_names`` (in
        place: the arrays are donated)."""
        if slot in self._free:
            raise PageStateError(
                f"reset of slot {slot} which is not allocated")
        if not self.reset_names:
            return
        zeroed = _zero_slot_jit(
            {n: self.state[n] for n in self.reset_names}, jnp.int32(slot))
        self.state.update(zeroed)
        self.slot_resets += 1

    @property
    def state_slots_in_use(self):
        """Slots whose slot arrays hold an occupant's state: the lanes in
        use, or none where the pool has no slot array (pages only)."""
        return self.slots_in_use if self.slot_names else 0

    def paged_bytes(self):
        return self._paged_bytes

    def slot_bytes(self):
        return self._slot_bytes

    def nbytes(self):
        return self.paged_bytes() + self.slot_bytes()

    def occupancy(self):
        return dict(super().occupancy(), pool_bytes=self.nbytes(),
                    paged_bytes=self.paged_bytes(),
                    slot_bytes=self.slot_bytes(),
                    slot_resets=self.slot_resets)

    def delete(self):
        """Free the device arrays (the benchmark's reference runs after
        the program, in the memory it leaves)."""
        for a in self.state.values():
            a.delete()
        self.state = {}
