"""Kimi-Linear behind the serving loop (``models/kimi_linear.py``): one
chunked prefill program for every prompt length and one decode program, over
a ``HybridStatePool`` (latent pages beside recurrent state slots). The
contract it is called through is ``serving/family.py``."""

import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.generation import (
    DEFAULT_PAGE_TOKENS,
    resolve_page_tokens,
)
from deepspeed_tpu.inference.serving.family import (
    ServingFamily,
    UnsupportedOptionError,
)
from deepspeed_tpu.inference.serving.kv_pool import (
    HybridStatePool,
    PoolExhaustedError,
)
from deepspeed_tpu.models import kimi_linear as kl


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))  # jaxlint: hot
def _kimi_prefill_chunk_jit(params, state, ids, slots, starts, lens,
                            page_tables, *, cfg, page_tokens, keep_logits):
    state, first, logits = kl.prefill_chunk(
        params, cfg, state, ids, slots, starts, lens, page_tables,
        page_tokens=page_tokens)
    return state, first, logits if keep_logits else None


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))  # jaxlint: hot
def _kimi_decode_step_jit(params, state, tokens, positions, active,
                          page_tables, *, cfg, page_tokens, keep_logits):
    state, tokens, positions, logits, moe = kl.decode_step(
        params, cfg, state, tokens, positions, active, page_tables,
        page_tokens=page_tokens)
    return state, tokens, positions, logits if keep_logits else None, moe


@jax.jit  # jaxlint: hot
def _kimi_patch_lanes_jit(tokens, positions, joined, new_tokens,
                          new_positions):
    """Lane churn: the lanes that ``joined`` take the host's token and
    position; every other lane keeps what the device has, which is a step
    ahead of the host while a decode step is in flight."""
    return (jnp.where(joined, new_tokens, tokens),
            jnp.where(joined, new_positions, positions))


class _Prefilling:
    """A request that holds a lane while its prompt is read in chunks."""

    __slots__ = ("req", "slot", "pos", "prefill_s", "positions_run")

    def __init__(self, req, slot):
        self.req = req
        self.slot = slot
        self.pos = 0
        self.prefill_s = 0.0
        self.positions_run = 0


class KimiLinearFamily(ServingFamily):
    """Kimi-Linear through the shared loop: latent pages and KDA state
    slots in one ``HybridStatePool``; one prefill program of one prompt's
    next ``prefill_chunk_tokens`` tokens, which carries state from chunk to
    chunk (so every prompt length runs it and a prompt is padded by less
    than one chunk); one decode program that returns, beside the tokens,
    three integers of the expert layers' load, read in the same transfer.

    One decode step is kept in flight: a call dispatches step N and then
    reads back step N - 1, which finished while the host emitted N - 2, so
    the device does not wait for the host between steps. (Read back at
    once, a twelfth of every step is dispatch and read-back latency on a
    shared host, and six runs of the benchmark's cell spread by 0.65% of
    their rate where 0.5% admits a cell: PERF.md, PR 27.) The device's
    lane vectors are therefore the truth for lanes that go on; lane churn
    patches only the lanes that joined. A lane that retires on token N - 1
    has already been given step N: its row of N is never emitted, its
    writes go to pages and a slot that are its own until a later program (a
    reset, a prefill: the device runs them in order) makes them someone
    else's.

    ``keep_logits`` (tests set it before the first step) makes both programs
    hand back the logits their token was taken from, in ``last_logits`` and
    ``last_prefill_logits``; otherwise they are never materialised."""

    name = "kimi_linear"

    def __init__(self, model_config):
        self.cfg = model_config
        self.keep_logits = False
        self.last_logits = None
        self.last_prefill_logits = None
        self._prefilling = []       # requests that hold a lane, in order
        self._in_flight = None      # (tokens, moe counts, request ids) of N
        self._on_device = {}        # slot -> request id the device decodes

    def check_options(self, cfg, params):
        def no(option, why):
            raise UnsupportedOptionError(
                f"serving.{option}: the kimi_linear family {why}")

        if cfg.prefix_cache_mb > 0:
            no(f"prefix_cache_mb={cfg.prefix_cache_mb}",
               "has no snapshot of recurrent state to seed a prefix from")
        if cfg.prefix_spill_mb > 0 or cfg.prefix_spill_dir is not None:
            no("prefix_spill_mb/prefix_spill_dir",
               "has no spill codec (the codecs frame keys and values)")
        if cfg.speculative_k:
            no(f"speculative_k={cfg.speculative_k}",
               "cannot roll recurrent state back over rejected drafts")
        if cfg.attention_impl not in (None, "dense"):
            no(f"attention_impl={cfg.attention_impl!r}",
               "has one attention path (expanded prefill, absorbed decode)")
        if cfg.attention_kernel is not None or cfg.kernel_interpret is not None:
            no("attention_kernel/kernel_interpret",
               "has no kernel-tier backend")
        if cfg.mesh_shape is not None:
            no(f"mesh_shape={cfg.mesh_shape}",
               "has no tensor-parallel sharding rules")
        if cfg.partition_rules:
            no("partition_rules", "has no tensor-parallel sharding rules")
        dtype = jnp.dtype(params["embed_tokens"]["embedding"].dtype)
        stored = {"bfloat16": "bf16", "float32": "fp32"}.get(dtype.name)
        if cfg.kv_cache_dtype != stored:
            no(f"kv_cache_dtype={cfg.kv_cache_dtype!r}",
               f"stores latent rows in the compute type only "
               f"({stored!r} for {dtype.name} parameters)")
        if (cfg.prefill_chunk_tokens < kl.KDA_CHUNK
                or cfg.prefill_chunk_tokens % kl.KDA_CHUNK):
            no(f"prefill_chunk_tokens={cfg.prefill_chunk_tokens}",
               f"prefills in chunks only: a positive multiple of "
               f"{kl.KDA_CHUNK} tokens")
        page = resolve_page_tokens(cfg.kv_page_tokens or DEFAULT_PAGE_TOKENS,
                                   cfg.max_seq_len or 2 ** 20)
        if cfg.prefill_chunk_tokens % page:
            no(f"prefill_chunk_tokens={cfg.prefill_chunk_tokens}",
               f"writes a chunk's latent rows as whole pages: a multiple "
               f"of kv_page_tokens={page}")
        if cfg.fault_injection:
            no("fault_injection", "has no fault-injection points")

    def refuse_handoff(self):
        raise UnsupportedOptionError(
            "handoff: the kimi_linear family has no handoff codec (the "
            "codec frames keys and values, not recurrent state)")

    def build(self, loop, params):
        self.loop = loop
        m, cfg = self.cfg, loop.config
        dtype = jnp.dtype(params["embed_tokens"]["embedding"].dtype)
        n_kda, n_mla = len(m.kda_index), len(m.mla_index)
        H, D = m.linear_num_heads, m.linear_head_dim
        pool = HybridStatePool(
            cfg.max_slots, loop.max_seq_len,
            paged={"latent": (n_mla, m.latent_width, dtype)},
            slotted={"kda": (n_kda, (H, D, D), jnp.float32),
                     "conv": (n_kda, (m.short_conv_kernel_size - 1,
                                      3 * m.kda_width), dtype)},
            page_tokens=cfg.kv_page_tokens, pool_tokens=cfg.kv_pool_tokens)
        self.chunk = int(cfg.prefill_chunk_tokens)
        loop.metrics.record_state_pool(0, 0, pool.slot_bytes(),
                                       pool.paged_bytes())
        return params, pool

    def sentinel_programs(self):
        return _kimi_decode_step_jit, _kimi_prefill_chunk_jit

    def prefilling(self):
        return len(self._prefilling)

    # -- admission and prefill -------------------------------------------
    def admit(self, stats):
        """Give each queued request a free slot and its pages, zero the
        slot's recurrent state, and let ``advance_prefill`` read its prompt
        a chunk a step."""
        loop = self.loop
        pool = loop.pool
        while pool.free_slots > 0:
            req = loop.scheduler.pop_next()
            if req is None:
                return
            try:
                slot = pool.allocate(loop.alloc_tokens(req))
            except PoolExhaustedError:
                loop.scheduler.requeue_front(req)
                return
            with (loop.tracer.span("serving/state_reset", cat="serving",
                                   args={"slot": slot})
                  if loop.tracer.enabled else telemetry.NULL_SPAN):
                pool.reset_slot(slot)
            loop.metrics.record_admission(loop.scheduler.buckets[-1],
                                          len(req.prompt))
            req.slot = slot
            self._prefilling.append(_Prefilling(req, slot))
            stats["admitted"] += 1

    def advance_prefill(self, stats, now):
        """One call of the chunked prefill program: the next chunk of the
        request that has waited longest. A request whose prompt ends in
        this chunk takes its first token and joins the decode lanes."""
        if not self._prefilling:
            return now
        top = now
        loop = self.loop
        pool = loop.pool
        for st in [s for s in self._prefilling
                   if s.req.deadline_exceeded(now)]:
            self._prefilling.remove(st)
            loop.finish_timeout(st.req, phase="prefill")
            stats["retired"] += 1
        if not self._prefilling:
            return now
        st = self._prefilling[0]
        req, Tc = st.req, self.chunk
        part = req.prompt[st.pos:st.pos + Tc]
        ids = np.zeros((1, Tc), np.int32)
        ids[0, :len(part)] = part
        ends = st.pos + Tc >= len(req.prompt)
        cspan = (loop.tracer.span(
                     "serving/prefill_chunk", cat="serving",
                     args={"request_ids": [req.id], "tokens": len(part)})
                 if loop.tracer.enabled else telemetry.NULL_SPAN)
        t0 = time.monotonic()
        if st.pos == 0:
            loop.metrics.record_queue_wait(t0 - req.submit_time)
        with cspan:
            pool.state, first, self.last_prefill_logits = (
                _kimi_prefill_chunk_jit(
                    loop.params, pool.state, *jax.device_put(
                        (ids, np.array([st.slot], np.int32),
                         np.array([st.pos], np.int32),
                         np.array([len(part)], np.int32),
                         pool.page_tables[st.slot][None])),
                    cfg=self.cfg, page_tokens=pool.page_tokens,
                    keep_logits=self.keep_logits))
            if self.prefill_sentinel is not None:
                self.prefill_sentinel.check()
            # the one read-back of a chunk, and only of a chunk that ends
            # a prompt: the first token is the TTFT endpoint
            first_host = int(np.asarray(first)[0]) if ends else None
        now = time.monotonic()
        loop.prefill_ran()
        stats["prefill_chunks"] += 1
        loop.metrics.record_prefill_chunk()
        st.pos += len(part)
        st.prefill_s += now - t0
        st.positions_run += Tc
        if ends:
            self._prefilling.remove(st)
            loop.metrics.record_prefill(
                tokens=len(req.prompt), reused_tokens=0, requests=1,
                prefill_s=st.prefill_s, positions_run=st.positions_run)
            pool.positions[st.slot] = len(req.prompt)
            stats["retired"] += loop.first_token(req, st.slot, first_host,
                                                 now)
        loop.metrics.admit_time_s += now - top
        return now

    # -- decode ----------------------------------------------------------
    def upload_lanes(self):
        """Lane churn. The active mask and the page tables are the host's
        to say; tokens and positions are patched for the lanes that joined
        since the last upload and left alone for the rest."""
        pool, lanes = self.loop.pool, self.loop.lanes
        joined = np.zeros(pool.max_slots, bool)
        for slot, req in lanes.requests.items():
            joined[slot] = self._on_device.get(slot) != req.id
        self._on_device = {s: r.id for s, r in lanes.requests.items()}
        host = jax.device_put(
            (joined, lanes.tokens,
             np.ascontiguousarray(pool.positions, dtype=np.int32),
             lanes.active.copy(),
             np.ascontiguousarray(pool.page_tables)))
        if lanes.dev_tokens is None:
            lanes.dev_tokens, lanes.dev_positions = host[1], host[2]
        else:
            lanes.dev_tokens, lanes.dev_positions = _kimi_patch_lanes_jit(
                lanes.dev_tokens, lanes.dev_positions, *host[:3])
        lanes.dev_active, lanes.dev_page_tables = host[3], host[4]
        lanes.dirty = False

    def decode_step(self, guard):  # jaxlint: hot
        loop = self.loop
        pool, lanes = loop.pool, loop.lanes
        # whose step this is: a slot may change hands before it is read
        riders = {slot: req.id for slot, req in lanes.requests.items()}
        with guard:
            (pool.state, lanes.dev_tokens, lanes.dev_positions,
             self.last_logits, moe) = _kimi_decode_step_jit(
                loop.params, pool.state, lanes.dev_tokens,
                lanes.dev_positions, lanes.dev_active,
                lanes.dev_page_tables, cfg=self.cfg,
                page_tokens=pool.page_tokens, keep_logits=self.keep_logits)
        if self.decode_sentinel is not None:
            self.decode_sentinel.check()
        before, self._in_flight = self._in_flight, (lanes.dev_tokens, moe,
                                                    riders)
        if before is None:
            return (), (), 0, 0
        # the step's single deliberate sync, on the step BEFORE the one just
        # dispatched: its tokens and, in the same transfer, the three
        # integers of its expert layers
        host_tokens, moe = jax.device_get(before[:2])  # jaxlint: disable=JL002(one explicit host read per step)
        loop.metrics.record_moe(self.cfg.n_moe_layers, *moe.tolist())
        loop.metrics.record_state_pool(
            pool.slots_in_use, pool.pages_in_use, pool.slot_bytes(),
            pool.paged_bytes())
        lanes.tokens = host_tokens.copy()
        return ([slot for slot, req in lanes.requests.items()
                 if before[2].get(slot) == req.id],
                host_tokens[:, None].tolist(), 0, 0)
