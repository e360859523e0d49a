"""Continuous-batching serving loop: the loop and nothing else.

``ServingEngine`` owns ``submit`` and the futures, the scheduler, expiry,
which request rides which slot, stamp/emit/retire, ``ServingMetrics``, the
spans, the degrade ladder, the SLO engine, the memory guard, the injector
hooks, drain and the threads, and the surface of the handoff API. It knows
no model: what a lane's state is and which jitted programs fill and advance
it is a ``ServingFamily`` (``family.py`` is the contract,
``families/`` the models), which the loop calls through that contract and
which calls back only the loop's public names.

Greedy only: serving argmax-decodes (temperature-0), the mode with a
bitwise oracle. Sampling needs per-request RNG streams and is future
work.
"""

import queue as _queue_mod
import threading
import time
from contextlib import nullcontext

import numpy as np

import jax

from deepspeed_tpu.profiling.sentinels import transfer_free
from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.serving.config import ServingConfig
from deepspeed_tpu.inference.serving.family import LaneState, family_for
from deepspeed_tpu.inference.serving.fault_injection import ServingFaultInjector
from deepspeed_tpu.inference.serving.kv_pool import (
    DEFAULT_PAGE_TOKENS,
    KV_CACHE_DTYPES,
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

        self.metrics = ServingMetrics(monitor)
        self.scheduler = ContinuousBatchingScheduler(
            max_queue=cfg.max_queue, buckets=buckets,
            default_max_new_tokens=cfg.default_max_new_tokens,
            request_timeout_s=cfg.request_timeout_s)
        # which request rides which slot and every lane's decode operands:
        # the loop's to own, the family's to upload and advance
        self.lanes = LaneState(cfg.max_slots)
        # the family binds to the loop's public names, places the
        # parameters as its programs take them (sharded, on a mesh) and
        # builds the state behind the shared allocator
        self.params, self.pool = self.family.build(self, params)
        # degraded-mode ladder: armed by configure_degrade() (from_config
        # wires the fleet.degrade block) or lazily by set_degrade_rung()
        # (the replica "degrade" socket op / the autoscaler's push).
        # _degrade_rung is the hot-path mirror — one int read per check.
        self._degrade = None
        self._degrade_rung = 0
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
        # pool-pressure relief: one evict+shed attempt per exhaustion
        # event (satellite: requeue-after-relief instead of plain requeue)
        self._pool_relief_attempts = 0

        if sentinel_config is not None and sentinel_config.enabled:
            self.family.arm_sentinels(sentinel_config.compile_budget)
            self._transfer_guard = bool(sentinel_config.transfer_guard)
        else:
            self._transfer_guard = False
        self.step_count = 0
        self._busy_steps = 0                # steps that had active lanes
        # prefills (and chunks) run so far: a request remembers the count
        # at each token it emits, so the next gap knows whether a prefill
        # ran inside it (stalled by cause, not by a threshold)
        self._prefill_seq = 0
        # the loop's waits for the device (``launched`` / ``read_back``):
        # the stamp of a decode launch no read has closed yet, the stamp
        # and kind of the read that left the device with nothing to run,
        # and whether a prefill program stands before the newest decode
        # program dispatched and before the one ahead of it
        self._launched_at = None
        self._dry_since = None
        self._dry_kind = None
        self._prefill_unread = False
        self._behind_newest = False
        self._behind_earlier = False
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
        # is one attribute read (self.tracer.enabled). rank/role become
        # the trace's process identity (the fleet collector's merge key);
        # rank=None falls back to the launcher-exported RANK env var.
        telemetry.configure_from_config(telemetry_config, rank=rank,
                                        role="serve")
        self.tracer = telemetry.get_tracer()
        # an armed span is also a TraceMe event: under a jax.profiler
        # session the host spans land in the same .xplane.pb, on the same
        # clock, as the device's programs (telemetry itself never imports
        # jax, so the engine hands it the annotation class)
        self.tracer.set_annotation_factory(jax.profiler.TraceAnnotation)
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
            # explicit http_port wins; a supervised worker with a null
            # port inherits DSTPU_TELEMETRY_PORT so the fleet collector
            # can scrape it without per-worker config edits
            http_port = telemetry.resolve_http_port(telemetry_config)
            if http_port is not None:
                self.telemetry_server = self._build_telemetry_server(
                    http_port)
            self.slo = telemetry.SloEngine.from_config(
                telemetry_config, tracer=self.tracer,
                registry=telemetry.get_registry())
            if self.slo is not None and self.telemetry_server is not None:
                self.slo.attach(self.telemetry_server)

            self.family.export_telemetry(telemetry.get_registry(),
                                         self.telemetry_server)

    @property
    def decode_sentinel(self):
        return self.family.decode_sentinel

    @property
    def prefill_sentinel(self):
        return self.family.prefill_sentinel

    def _build_telemetry_server(self, port):
        srv = telemetry.TelemetryServer(
            registry=telemetry.get_registry(), tracer=self.tracer, port=port)
        srv.add_snapshot_provider("serving", self.metrics.snapshot)
        srv.add_snapshot_provider("kv_pool", self.occupancy)
        srv.add_snapshot_provider("prefix_cache", self.prefix_stats)
        srv.add_snapshot_provider("memtier", self.memtier_stats)
        srv.add_health_provider("serving_loop", self._loop_health)
        return srv.start()

    def _loop_health(self):
        """Healthy unless a background loop was started and then died
        (synchronous step()/drain() driving is always healthy)."""
        t = self._loop_thread
        return {"healthy": t is None or t.is_alive(),
                "background_loop": t is not None,
                "steps": self.step_count,
                "active_requests": len(self.lanes.requests),
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
        self._on_degrade_change(self._degrade_rung, self._degrade.rung,
                                "configured")
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

    def _on_degrade_change(self, old, new, reason):
        self._degrade_rung = new
        self.family.set_speculation(new < 1)

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
            tracer = getattr(self, "tracer", None)
            if tracer is not None and tracer.enabled:
                tracer.instant("serving/spill_corrupt", args={
                    "total": self.metrics.spill_corrupt_total})

    def _on_mem_pressure_level(self, level, rss_mb):
        """Edge-triggered on every MemoryPressureGuard level change."""
        tracer = getattr(self, "tracer", None)
        if tracer is not None and tracer.enabled:
            tracer.instant("serving/mem_pressure", args={
                "level": level,
                "level_name": MemoryPressureGuard.LEVELS[level],
                "rss_mb": None if rss_mb is None else round(rss_mb, 1)})

    def relieve_memory_pressure(self):
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

    def prefix_inserts_paused(self):
        """The prefix trie stops growing (lookups still hit, reuse stays
        free) at degrade rung 2 (budget_shrink: no new host RAM under
        overload) and while the host-RSS watermark is breached."""
        return self._degrade_rung >= 2 or (
            self._mem_guard is not None and self._mem_guard.inserts_paused)

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
        if max_new_tokens is None:
            max_new_tokens = self.config.default_max_new_tokens
        prompt, _, eos, submitted_at = self._intake(
            prompt_ids, max_new_tokens, "max_new_tokens", eos_token_id, age_s)
        req = self.scheduler.submit(
            prompt, max_new_tokens=int(max_new_tokens), eos_token_id=eos,
            timeout_s=timeout_s, stream_cb=stream_cb,
            submitted_at=submitted_at)
        return req.future

    def _intake(self, prompt_ids, n_new, what, eos_token_id, age_s):
        """What ``submit`` and ``submit_handoff`` check alike before a
        request is queued; ``what`` names the caller's token budget
        ``n_new`` in the messages. Returns (prompt, prompt + budget, eos,
        backdated enqueue stamp)."""
        if self._draining:
            raise EngineDrainingError(
                "engine is draining for a planned restart; "
                "route this request to another replica")
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if len(prompt) < 1:
            raise ValueError("prompt must contain at least one token")
        if n_new < 1:
            raise ValueError(f"{what} must be >= 1, got {n_new}")
        bucket_for(len(prompt), self.scheduler.buckets)  # raises if too long
        total = len(prompt) + int(n_new)
        if total > self.max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + {what} ({n_new}) "
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
        return (prompt, total,
                None if eos_token_id is None else int(eos_token_id),
                self._backdated(age_s))

    @staticmethod
    def _backdated(age_s):
        """The enqueue stamp of a request that is already ``age_s`` old."""
        return time.monotonic() - float(age_s) if age_s and age_s > 0 else None

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
        prompt, total, eos, submitted_at = self._intake(
            prompt_ids, int(reserve_new_tokens), "reserve_new_tokens",
            eos_token_id, age_s)
        req = self.scheduler.adopt(
            prompt, max_new_tokens=1, eos_token_id=eos, timeout_s=timeout_s,
            stream_cb=stream_cb, submitted_at=submitted_at)
        # flags set BEFORE the request becomes loop-visible
        req.handoff_export = True
        req.alloc_tokens_override = min(total, self.max_seq_len)
        self.scheduler.enqueue(req)
        return req

    def handoff_claim(self, n_tokens):
        """Decode-side phase 1: allocate a pool slot sized for the full
        request span. Raises PoolExhaustedError under pressure. Mirrors
        ``alloc_tokens``: an armed injector forces full-lane claims, so
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
        req = self.scheduler.adopt(
            prompt, max_new_tokens=int(max_new_tokens),
            eos_token_id=None if eos_token_id is None else int(eos_token_id),
            timeout_s=timeout_s, stream_cb=stream_cb,
            submitted_at=self._backdated(age_s))

        def _do():
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
    def step(self):  # jaxlint: hot
        """One scheduler iteration: expire, advance any prefill in flight,
        admit, one batched decode step, retire. Returns an activity dict
        (all zeros = idle)."""
        top = now = time.monotonic()
        stats = {"admitted": 0, "decoded": 0, "retired": 0,
                 "prefill_chunks": 0}
        read_back = None        # instant the decode step's tokens landed
        espan = telemetry.NULL_SPAN
        lanes = self.lanes

        self._drain_loop_ops()

        for req in self.scheduler.pop_expired(now):
            self.finish_timeout(req, phase="queued")
            stats["retired"] += 1

        now = self.family.advance_prefill(stats, now)

        # admission is timed from the last stamp the iteration holds
        self._admit_from_queue(stats, now)

        if self.injector is not None:
            self.injector.maybe_evict_prefix(self.step_count,
                                             self.prefix_cache)
            self.injector.maybe_corrupt_spill(self.step_count,
                                              self.prefix_cache)
        if self._mem_guard is not None:
            self._mem_guard.check()
        if lanes.requests:
            # busy steps (not raw step_count, which idles forward between
            # requests in background mode): the kill_replica arm's at_step
            # must mean "the Nth decode step that had work" to be
            # reproducible against a live server
            self._busy_steps += 1
            if self.injector is not None:
                self.injector.maybe_slow_decode(self.step_count)
                self.injector.maybe_kill_replica(self._busy_steps)
            # span args (request ids) are built ONLY when tracing is armed:
            # disabled-mode cost is this one attribute read. The dict is
            # kept so a speculative step can fill in `accepted` post-step
            # (the tracer renders args lazily, at write time).
            span_args = None
            if self.tracer.enabled:
                span_args = {
                    "request_ids": [r.id for r in lanes.requests.values()],
                    "active": len(lanes.requests), "accepted": 0}
                dspan = self.tracer.span("serving/decode_step",
                                         cat="serving", args=span_args)
            else:
                dspan = telemetry.NULL_SPAN
            dspan.__enter__()
            t0 = time.monotonic()
            if lanes.dirty:
                with (self.tracer.span("serving/upload_lanes",
                                       cat="serving",
                                       args={"active": len(lanes.requests)})
                      if self.tracer.enabled else telemetry.NULL_SPAN):
                    self.family.upload_lanes()
            guard = transfer_free() if self._transfer_guard else nullcontext()
            # ``slots``: whose tokens these are; ``rows[slot]``: the tokens
            # to emit for it, in order (one, or a speculative step's
            # accepted drafts and the oracle's own next token)
            slots, rows, accepted, proposed = self.family.decode_step(guard)
            step_s = time.monotonic() - t0
            read_back = t0 + step_s
            # a step in flight with nothing to read yet
            self._dispatched(read_back)
            if span_args is not None:
                span_args["accepted"] = accepted
            dspan.__exit__(None, None, None)
            espan = (self.tracer.span(
                         "serving/emit", cat="serving",
                         args={"active": len(lanes.requests)})
                     if self.tracer.enabled else telemetry.NULL_SPAN)
            espan.__enter__()
            now = time.monotonic()
            decoded_before = stats["decoded"]
            for slot in slots:
                req = lanes.requests[slot]
                for tok in rows[slot]:
                    self.pool.advance(slot)
                    self._stamp_token(req, now)
                    self._emit(req, tok)
                    stats["decoded"] += 1
                    if self._maybe_retire(req, tok, now):
                        # EOS/length/deadline truncates a speculative
                        # step's remaining tokens — exactly where a
                        # non-speculative server would have stopped
                        stats["retired"] += 1
                        break
            occ = self.pool.occupancy()
            self.metrics.record_step(
                queue_depth=self.scheduler.queue_depth(),
                active_slots=len(slots), max_slots=self.pool.max_slots,
                tokens_this_step=stats["decoded"] - decoded_before,
                step_s=step_s, accepted_tokens=accepted,
                proposed_tokens=proposed,
                pages_in_use=occ["pages_in_use"],
                page_fragmentation=occ["page_fragmentation"])
        self.step_count += 1
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
        else:
            # out of work: from here on the device is dry for want of
            # requests, which is not the loop's to account for
            self._dry_spell_ends(top)
        espan.__exit__(None, None, None)
        return stats

    # -- the loop's waits for the device --------------------------------
    def launched(self, kind):
        """A family calls this directly before it calls a decode or a
        prefill program (``kind`` "decode" or "prefill"; once before the
        several programs of one step): a decode step's dispatch, the
        upload of the call's arguments included, is timed from here, and
        a dry spell ends here."""
        now = time.monotonic()
        self._dispatched(now)
        self._dry_spell_ends(now)
        if kind == "prefill":
            self._prefill_unread = True
        else:
            self._launched_at = now
            self._behind_earlier = self._behind_newest
            self._behind_newest = self._prefill_unread
            self._prefill_unread = False

    def read_back(self, tree, kind, newest, fetch=True):  # jaxlint: hot
        """The one place where the loop thread waits for the device:
        ``jax.device_get(tree)``, or with ``fetch=False`` only the wait
        until ``tree`` is ready (the settle after installs), timed on both
        sides. ``kind`` is the program's whose output this is ("prefix_kv"
        for the prefix cache's copy of a prompt's K/V, which is waited for
        here and counted as no program's read); ``newest`` says it is the
        output of the last program dispatched, so that on return nothing
        dispatched is left to run and the device is dry until the next
        ``launched`` (the slices, installs, lane patches and slot resets
        dispatched meanwhile are well under a millisecond of device time
        each and count as dry). A decode read that is not the
        newest reads the step ahead of the one just dispatched; it counts
        as behind a prefill when a prefill program that nothing has read
        back was dispatched ahead of the step it reads."""
        t0 = time.monotonic()
        self._dispatched(t0)
        with (self.tracer.span("serving/read_back", cat="serving",
                               args={"kind": kind, "newest": newest})
              if self.tracer.enabled else telemetry.NULL_SPAN):
            if fetch:
                tree = jax.device_get(tree)  # jaxlint: disable=JL002(the loop's one explicit host read)
            else:
                jax.block_until_ready(tree)  # jaxlint: disable=JL002(the settle after installs, accounted to admission)
        t1 = time.monotonic()
        self.metrics.record_read(
            kind, t1 - t0,
            self._behind_newest if newest else self._behind_earlier)
        if newest:
            # everything dispatched has run: no prefill stands before
            # anything, and unless a spell is open already one begins
            self._prefill_unread = False
            self._behind_newest = self._behind_earlier = False
            if self._dry_since is None:
                self._dry_since, self._dry_kind = t1, kind
        return tree

    def _dispatched(self, at):
        """Close the open decode launch's dispatch at the stamp ``at``."""
        if self._launched_at is not None:
            self.metrics.decode_dispatch_s += at - self._launched_at
            self._launched_at = None

    def _dry_spell_ends(self, at):
        """Close the open dry spell, if there is one, at the stamp ``at``."""
        if self._dry_since is not None:
            self.metrics.record_dry_spell(self._dry_kind,
                                          at - self._dry_since)
            self._dry_since = None

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
        return (len(self.lanes.requests) + self.family.prefilling()
                + self.scheduler.queue_depth())

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
                if not any(busy.values()) and not self.lanes.requests:
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
            self.tracer.write(self._trace_file)

    # -- admission ------------------------------------------------------
    def _admit_from_queue(self, stats, now):
        """Join-at-free-slot admission, batched per bucket: pop the FIFO
        head, gather every queued request sharing its (prefix-adjusted)
        bucket up to the free-slot count, and prefill them as ONE call.
        Long prompts divert to the chunked path (one at a time).
        ``now`` is the latest stamp the iteration holds: an admission
        that admitted anything is timed from it to its end."""
        admitted = stats["admitted"]
        if self.tracer.enabled and self.scheduler.queue_depth() > 0:
            with self.tracer.span(
                    "serving/admission", cat="serving",
                    args={"queue_depth": self.scheduler.queue_depth()}):
                self.family.admit(stats)
        else:
            self.family.admit(stats)
        if stats["admitted"] > admitted:
            self.metrics.admit_time_s += time.monotonic() - now

    def alloc_tokens(self, req):
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

    # -- internals ------------------------------------------------------
    def _activate(self, req, slot, first_tok, emit=True):
        req.slot = slot
        lanes = self.lanes
        lanes.requests[slot] = req
        lanes.tokens[slot] = first_tok
        lanes.active[slot] = True
        lanes.dirty = True
        self.family.lane_joined(req, slot, first_tok)
        if emit:
            self._emit(req, first_tok)

    def first_token(self, req, slot, first_tok, now):
        """A prompt's prefill is done and its state is in ``slot``: stamp
        and hand out the first token and join the decode lanes. Returns 1
        if that token already ended the request, else 0."""
        req.first_token_time = now
        self.metrics.record_first_token(now - req.submit_time)
        self._stamp_token(req, now)
        self._activate(req, slot, first_tok)
        return self._maybe_retire(req, first_tok, now)

    def prefill_ran(self):
        """A prefill program (a batch or a chunk) ran: a token gap it sits
        in counts as stalled."""
        self._prefill_seq += 1

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
            self.finish_timeout(req, phase="decoding")
            return 1
        if self.scheduler.should_retire(req, token, stuck=stuck) is not None:
            self._release_slot(req)
            req.future._finish()
            self.scheduler.completed += 1
            self.metrics.record_completion()
            if self.tracer.enabled:
                self.tracer.instant("serving/retire", cat="serving",
                                     args={"request_id": req.id,
                                           "tokens": req.emitted})
            return 1
        return 0

    def finish_timeout(self, req, phase):
        self._release_slot(req)
        if self.tracer.enabled:
            self.tracer.instant("serving/retire_timeout", cat="serving",
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
            self.lanes.active[req.slot] = False
            self.lanes.dirty = True
            self.lanes.requests.pop(req.slot, None)
            self.family.lane_left(req.slot)
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
