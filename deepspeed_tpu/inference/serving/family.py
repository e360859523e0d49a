"""The seam between ``ServingEngine`` and a model family.

The engine's loop, ``submit``, futures, ``_emit``, ``_maybe_retire``,
``ServingMetrics``, the tracer, the scheduler and the page allocator are the
same for every model. What differs is what a lane's state is and which
jitted programs fill and advance it. A ``ServingFamily`` is that difference,
as one small object the engine consults at five points:

- ``check_options(cfg, params)``: the ``ServingConfig`` options the family
  cannot honour raise here, at construction, by the option's name;
- ``build_pool(engine, cfg)``: the state behind the shared allocator;
- ``admit(engine, stats)`` / ``advance_prefill(engine, stats, now)``:
  admission and the prefill program(s);
- ``upload_lanes(engine)`` / ``decode_step(engine, guard, classes)``: the
  decode program(s) and the step's one host read. Returns ``(tokens on the
  host, slots)``: the slots whose token this is, every active lane for a
  family that reads the step it dispatched.

``GPT2Family`` stands in front of the thirteen GPT-2 programs of
``engine.py`` as they are. ``KimiLinearFamily`` runs
``models/kimi_linear.py``: one chunked prefill program for every prompt
length and one decode program, over a ``HybridStatePool`` (latent pages
beside recurrent state slots).
"""

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
from deepspeed_tpu.inference.serving.kv_pool import (
    HybridStatePool,
    PoolExhaustedError,
)
from deepspeed_tpu.models import kimi_linear as kl


class UnsupportedOptionError(NotImplementedError):
    """A ``ServingConfig`` option (named in the message) that the model's
    family cannot honour yet. Raised at construction: no silent fallback."""


class ServingFamily:
    """What the engine asks of a family. See the module docstring."""

    name = None

    def check_options(self, cfg, params):
        raise NotImplementedError

    def build_pool(self, engine, cfg):
        raise NotImplementedError

    def sentinel_programs(self, engine):
        """(decode program, prefill program) for the compile sentinels."""
        raise NotImplementedError

    def prefilling(self, engine):
        """Requests that hold a lane but are not decoding yet."""
        return 0

    def refuse_handoff(self):
        """Raise if the family has no handoff codec for its state."""


class GPT2Family(ServingFamily):
    """GPT-2's programs and ``KVCachePool``, as ``engine.py`` has them:
    every option of ``ServingConfig`` is supported, and each hook runs the
    engine's own method."""

    name = "gpt2"

    def check_options(self, cfg, params):
        pass

    def build_pool(self, engine, cfg):
        return engine._build_kv_pool(cfg)

    def sentinel_programs(self, engine):
        return engine._gpt2_sentinel_programs()

    def prefilling(self, engine):
        return 1 if engine._chunking is not None else 0

    def advance_prefill(self, engine, stats, now):
        if engine._chunking is not None:
            return engine._advance_chunk(stats)
        return now

    def admit(self, engine, stats):
        engine._admit_from_queue_now(stats)

    def upload_lanes(self, engine):
        engine._upload_lane_state()

    def decode_step(self, engine, guard, classes):
        return engine._gpt2_decode_programs(guard, classes), list(
            engine._active)


# -- Kimi-Linear ------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))
def _kimi_prefill_chunk_jit(params, state, ids, slots, starts, lens,
                            page_tables, *, cfg, page_tokens, keep_logits):
    state, first, logits = kl.prefill_chunk(
        params, cfg, state, ids, slots, starts, lens, page_tables,
        page_tokens=page_tokens)
    return state, first, logits if keep_logits else None


@partial(jax.jit, static_argnames=("cfg", "page_tokens", "keep_logits"),
         donate_argnums=(1,))
def _kimi_decode_step_jit(params, state, tokens, positions, active,
                          page_tables, *, cfg, page_tokens, keep_logits):
    state, tokens, positions, logits, moe = kl.decode_step(
        params, cfg, state, tokens, positions, active, page_tables,
        page_tokens=page_tokens)
    return state, tokens, positions, logits if keep_logits else None, moe


@jax.jit
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

    def build_pool(self, engine, cfg):
        m = self.cfg
        dtype = jnp.dtype(engine.params["embed_tokens"]["embedding"].dtype)
        n_kda, n_mla = len(m.kda_index), len(m.mla_index)
        H, D = m.linear_num_heads, m.linear_head_dim
        pool = HybridStatePool(
            cfg.max_slots, engine.max_seq_len,
            paged={"latent": (n_mla, m.latent_width, dtype)},
            slotted={"kda": (n_kda, (H, D, D), jnp.float32),
                     "conv": (n_kda, (m.short_conv_kernel_size - 1,
                                      3 * m.kda_width), dtype)},
            page_tokens=cfg.kv_page_tokens, pool_tokens=cfg.kv_pool_tokens)
        self.chunk = int(cfg.prefill_chunk_tokens)
        engine.metrics.record_state_pool(0, 0, pool.slot_bytes(),
                                         pool.paged_bytes())
        return pool

    def sentinel_programs(self, engine):
        return _kimi_decode_step_jit, _kimi_prefill_chunk_jit

    def prefilling(self, engine):
        return len(self._prefilling)

    # -- admission and prefill -------------------------------------------
    def admit(self, engine, stats):
        """Give each queued request a free slot and its pages, zero the
        slot's recurrent state, and let ``advance_prefill`` read its prompt
        a chunk a step."""
        pool = engine.pool
        while pool.free_slots > 0:
            req = engine.scheduler.pop_next()
            if req is None:
                return
            try:
                slot = pool.allocate(engine._alloc_tokens(req))
            except PoolExhaustedError:
                engine.scheduler.requeue_front(req)
                return
            with (engine._tracer.span("serving/state_reset", cat="serving",
                                      args={"slot": slot})
                  if engine._tracer.enabled else telemetry.NULL_SPAN):
                pool.reset_slot(slot)
            engine.metrics.record_admission(engine.scheduler.buckets[-1],
                                            len(req.prompt))
            req.slot = slot
            self._prefilling.append(_Prefilling(req, slot))
            stats["admitted"] += 1

    def advance_prefill(self, engine, stats, now):
        """One call of the chunked prefill program: the next chunk of the
        request that has waited longest. A request whose prompt ends in
        this chunk takes its first token and joins the decode lanes."""
        if not self._prefilling:
            return now
        top = now
        pool = engine.pool
        for st in [s for s in self._prefilling
                   if s.req.deadline_exceeded(now)]:
            self._prefilling.remove(st)
            engine._finish_timeout(st.req, phase="prefill")
            stats["retired"] += 1
        if not self._prefilling:
            return now
        st = self._prefilling[0]
        req, Tc = st.req, self.chunk
        part = req.prompt[st.pos:st.pos + Tc]
        ids = np.zeros((1, Tc), np.int32)
        ids[0, :len(part)] = part
        ends = st.pos + Tc >= len(req.prompt)
        cspan = (engine._tracer.span(
                     "serving/prefill_chunk", cat="serving",
                     args={"request_ids": [req.id], "tokens": len(part)})
                 if engine._tracer.enabled else telemetry.NULL_SPAN)
        t0 = time.monotonic()
        if st.pos == 0:
            engine.metrics.record_queue_wait(t0 - req.submit_time)
        with cspan:
            pool.state, first, self.last_prefill_logits = (
                _kimi_prefill_chunk_jit(
                    engine.params, pool.state, *jax.device_put(
                        (ids, np.array([st.slot], np.int32),
                         np.array([st.pos], np.int32),
                         np.array([len(part)], np.int32),
                         pool.page_tables[st.slot][None])),
                    cfg=self.cfg, page_tokens=pool.page_tokens,
                    keep_logits=self.keep_logits))
            if engine.prefill_sentinel is not None:
                engine.prefill_sentinel.check()
            # the one read-back of a chunk, and only of a chunk that ends
            # a prompt: the first token is the TTFT endpoint
            first_host = int(np.asarray(first)[0]) if ends else None
        now = time.monotonic()
        engine._prefill_seq += 1
        stats["prefill_chunks"] += 1
        engine.metrics.record_prefill_chunk()
        st.pos += len(part)
        st.prefill_s += now - t0
        st.positions_run += Tc
        if ends:
            self._prefilling.remove(st)
            engine.metrics.record_prefill(
                tokens=len(req.prompt), reused_tokens=0, requests=1,
                prefill_s=st.prefill_s, positions_run=st.positions_run)
            pool.positions[st.slot] = len(req.prompt)
            stats["retired"] += engine._first_token(req, st.slot,
                                                    first_host, now)
        engine.metrics.admit_time_s += now - top
        return now

    # -- decode ----------------------------------------------------------
    def upload_lanes(self, engine):
        """Lane churn. The active mask and the page tables are the host's
        to say; tokens and positions are patched for the lanes that joined
        since the last upload and left alone for the rest."""
        pool = engine.pool
        joined = np.zeros(pool.max_slots, bool)
        for slot, req in engine._active.items():
            joined[slot] = self._on_device.get(slot) != req.id
        self._on_device = {s: r.id for s, r in engine._active.items()}
        host = jax.device_put(
            (joined, engine._lane_tokens,
             np.ascontiguousarray(pool.positions, dtype=np.int32),
             engine._lane_active.copy(),
             np.ascontiguousarray(pool.page_tables)))
        if engine._dev_tokens is None:
            engine._dev_tokens, engine._dev_positions = host[1], host[2]
        else:
            engine._dev_tokens, engine._dev_positions = _kimi_patch_lanes_jit(
                engine._dev_tokens, engine._dev_positions, *host[:3])
        engine._dev_active, engine._dev_page_tables = host[3], host[4]
        engine._lane_dirty = False

    def decode_step(self, engine, guard, classes):
        pool = engine.pool
        # whose step this is: a slot may change hands before it is read
        riders = {slot: req.id for slot, req in engine._active.items()}
        with guard:
            (pool.state, engine._dev_tokens, engine._dev_positions,
             self.last_logits, moe) = _kimi_decode_step_jit(
                engine.params, pool.state, engine._dev_tokens,
                engine._dev_positions, engine._dev_active,
                engine._dev_page_tables, cfg=self.cfg,
                page_tokens=pool.page_tokens, keep_logits=self.keep_logits)
        if engine.decode_sentinel is not None:
            engine.decode_sentinel.check()
        before, self._in_flight = self._in_flight, (engine._dev_tokens, moe,
                                                    riders)
        if before is None:
            return engine._lane_tokens, []
        # the step's single deliberate sync, on the step BEFORE the one just
        # dispatched: its tokens and, in the same transfer, the three
        # integers of its expert layers
        host_tokens, moe = jax.device_get(before[:2])  # jaxlint: disable=JL002(one explicit host read per step)
        engine.metrics.record_moe(self.cfg.n_moe_layers, int(moe[0]),
                                  int(moe[1]), int(moe[2]))
        engine.metrics.record_state_pool(
            pool.slots_in_use, pool.pages_in_use, pool.slot_bytes(),
            pool.paged_bytes())
        return host_tokens, [slot for slot, req in engine._active.items()
                             if before[2].get(slot) == req.id]


def family_for(model_config):
    """The family of a model configuration."""
    if isinstance(model_config, kl.KimiLinearConfig):
        return KimiLinearFamily(model_config)
    return GPT2Family()
