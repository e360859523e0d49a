"""Typed views of the ``serving`` and ``fleet`` config blocks.

Parsed and validated by ``runtime/config.py::get_serving_config`` /
``get_fleet_config`` (key strings and defaults live in
``runtime/constants.py`` next to the checkpoint/resilience blocks).
Import-light on purpose: the config layer must not drag jax in; device
work lives in engine.py/kv_pool.py.
"""

from dataclasses import dataclass, field


@dataclass
class ServingConfig:
    # Master switch: True once a `serving` section exists, False when the
    # section is absent (see get_serving_config).
    enabled: bool = False
    # KV-cache slots = max concurrent requests mid-decode. STATIC: fixes
    # the decode program's batch dimension, so slot churn never
    # recompiles. Sized to HBM: pool bytes = 2·L·max_slots·nh·S·hd·dtype.
    max_slots: int = 8
    # Bounded admission queue; submit() past this raises QueueFullError.
    max_queue: int = 64
    # KV-cache length per slot (prompt + generated). None = the model's
    # max_position_embeddings.
    max_seq_len: int = None
    # Ascending prompt-length bucket ladder; a prompt is padded up to its
    # bucket so XLA compiles at most len(buckets) prefill programs.
    # None = powers of two up to max_seq_len - 1.
    prompt_buckets: tuple = None
    # max_new_tokens for submit() calls that don't specify one.
    default_max_new_tokens: int = 64
    # Default per-request deadline (queued + decoding); 0 = none. A
    # request past it is retired with RequestTimeoutError.
    request_timeout_s: float = 0.0
    # Chunked prefill: prompts whose to-be-computed length exceeds this
    # are prefilled in fixed-size chunks of this many tokens, interleaved
    # with decode steps, so one long prompt cannot stall every in-flight
    # request's inter-token latency. 0 = always single-pass.
    prefill_chunk_tokens: int = 0
    # Prefix KV cache budget in MiB (host RAM): stores served prompts'
    # KV keyed by token prefix so shared system-prompt prefixes skip
    # recomputation. 0 = disabled.
    prefix_cache_mb: float = 0.0
    # Spill tier for evicted prefix-cache entries: instead of destroying
    # cold entries, demote their (already-quantized) bytes into a
    # crc32-framed host-RAM store under this MiB budget; a later hit
    # verifies the checksum and promotes the entry back, paying one host
    # decode instead of re-prefilling the shared prefix. 0 = disabled
    # (eviction destroys, the pre-tiering behavior).
    prefix_spill_mb: float = 0.0
    # Optional disk tier under the spill tier: RAM-overflow spill
    # records are written here with the checkpoint atomic-write
    # discipline (tmp -> fsync -> rename). None = RAM-only spill.
    prefix_spill_dir: str = None
    # Host-RSS watermark (MiB) for the MemoryPressureGuard: sustained
    # RSS at/above it sheds the spill tier, then pauses prefix inserts,
    # then climbs the fleet DegradeLadder — staged degradation instead
    # of an OOM kill. 0 = guard disabled.
    host_mem_watermark_mb: float = 0.0
    # Speculative decoding: propose up to this many self-drafted tokens
    # per lane per step (n-gram lookup over the lane's own history) and
    # verify them all in ONE batched forward — each step emits 1..k+1
    # tokens per lane, output-identical to k=0. STATIC like max_slots:
    # varying per-lane acceptance never recompiles. 0 = classic
    # one-token decode (the bitwise-oracle path).
    speculative_k: int = 0
    # KV-pool storage dtype: "fp32" (the model's compute dtype —
    # bitwise-transparent default), "bf16" (half the pool bytes, cast at
    # use), or "int8" (quarter, per-(slot, head) symmetric fp32 scales,
    # dequantized at use; threshold-based parity instead of bitwise).
    kv_cache_dtype: str = "fp32"
    # Serving/step/I-O fault-injection spec (tests only): see
    # serving/fault_injection.py for the accepted points.
    fault_injection: dict = field(default=None)
    # Attention backend selection: None/"dense" (bitwise oracle path),
    # "flash" (online-softmax, math-equal dense), "sparse_xla" (banded
    # block-sparse window — the long-context backend), or a
    # {bucket: impl} dict with an optional "default" key so e.g. only
    # the 16k bucket goes sparse. Validated in engine.py against the
    # bucket ladder.
    attention_impl: object = None
    # Kernel-tier implementation for the "pallas_decode"/"pallas_sparse"
    # attention backends: None or "pallas" (the fused kernels; a failed
    # execution probe raises KernelProbeError on a TPU backend and
    # degrades to the XLA twin with a telemetry instant elsewhere), or
    # "xla" (run the composed-XLA twin on purpose — the parity-oracle
    # side of every kernel test).
    attention_kernel: str = None
    # Pallas interpret mode: None = auto (interpret everywhere but a
    # real TPU backend, so CPU CI executes the same kernel bodies
    # eagerly), True/False to force; True is refused on a TPU backend.
    # Static in every jitted program.
    kernel_interpret: object = None
    # Tokens per KV page. None = 128 (clamped/adjusted to divide
    # max_seq_len — see resolve_page_tokens). Smaller pages = finer
    # allocation granularity + smaller sparse windows.
    kv_page_tokens: int = None
    # Total KV-pool token budget shared by all lanes. None =
    # max_slots * max_seq_len (the contiguous-equivalent footprint);
    # set LOWER to serve a 16k-bucket ladder without paying
    # MaxSlots × S_max bytes — admission backpressures when pages
    # run out instead of over-allocating.
    kv_pool_tokens: int = None
    # Tensor-parallel mesh shape as (data, model) — e.g. (1, 4) shards
    # attention heads and MLP columns over 4 devices. None = the
    # single-device engine (no mesh, byte-identical to the pre-mesh
    # layout). Parsed/validated from the ds_config `parallel` block by
    # runtime/config.py::get_parallel_config.
    mesh_shape: tuple = None
    # Ordered (path-regex, spec-elements) overrides consulted BEFORE
    # the registry's built-in SERVING_PARTITION_RULES (first match
    # wins). Spec elements are axis names / None, e.g.
    # (("wte/embedding$", ("model", None)),). None/() = built-ins only.
    partition_rules: tuple = None
    # Unmatched param-tree paths replicate instead of raising
    # UnmatchedPathError (the built-in table ends in a catch-all, so
    # this only matters for custom partition_rules tables).
    replicate_unmatched: bool = True


@dataclass
class AutoscaleConfig:
    """The ``fleet.autoscale`` sub-block: the SLO-driven control loop
    (inference/serving/autoscaler.py). Opt-in: the sub-block's presence
    enables it."""

    enabled: bool = False
    # Fleet-size bounds the control loop may move between. Scale-down
    # never drains below min_replicas; scale-up never attaches past
    # max_replicas (past it, pressure escalates the degrade ladder
    # instead).
    min_replicas: int = 1
    max_replicas: int = 4
    # Pre-spawned replica processes kept listening but NOT routed to:
    # scale-up is attach-not-cold-start (the pool refills in the
    # background after an attach). 0 = cold-start scale-up.
    warm_spares: int = 1
    # Hysteresis: an alert must fire this long before a scale-up acts...
    up_after_s: float = 1.0
    # ...and the fleet must be alert-quiet this long before a scale-down.
    down_after_s: float = 5.0
    # Minimum gap between ANY two scaling actions (flap damping).
    cooldown_s: float = 2.0
    # Control-loop tick interval for the background thread.
    poll_interval_s: float = 0.25


@dataclass
class DegradeConfig:
    """The ``fleet.degrade`` sub-block: the degraded-mode ladder
    (inference/serving/degrade.py). Opt-in: presence enables."""

    enabled: bool = False
    # Sustained pressure before climbing ONE rung...
    escalate_after_s: float = 0.5
    # ...and sustained quiet before descending ONE rung (rung-by-rung
    # recovery; never a jump back to healthy).
    recover_after_s: float = 2.0
    # Engine-side pressure signal: queue_depth >= this fraction of
    # serving.max_queue counts as pressure for the automatic ladder.
    pressure_queue_frac: float = 0.75
    # Request classes the router sheds at rung 3. Empty = every class
    # EXCEPT "default" (the protected class).
    shed_classes: tuple = ()


@dataclass
class BreakerConfig:
    """The ``fleet.breaker`` sub-block: per-replica crash-loop circuit
    breakers (launcher/supervisor.py). Opt-in: presence enables."""

    enabled: bool = False
    # Failure exits (crash/hung/fatal — NOT clean or preempted) within
    # window_s that open the breaker.
    threshold: int = 3
    window_s: float = 30.0
    # Quarantine length while open: the worker stays down (the router
    # routes around its dead port), then ONE half-open probe restart is
    # allowed; a probe failure re-opens with a fresh cooldown.
    cooldown_s: float = 5.0


@dataclass
class RolloutConfig:
    """The ``fleet.rollout`` sub-block: zero-downtime weight rollout
    (inference/serving/rollout.py). Opt-in: presence enables."""

    enabled: bool = False
    # Fraction of NEW requests routed onto the canary generation while
    # the rollout is in its canary phase (deterministic prefix-hash
    # slice, so cache affinity survives the split).
    canary_fraction: float = 0.1
    # Replicas booted on the new weights for the canary phase.
    canary_replicas: int = 1
    # Fraction of completed live requests replayed against the canary as
    # shadow traffic (output-diffed against the incumbent's answer).
    # 0 = shadow mode off.
    shadow_sample_rate: float = 0.25
    # Bounded shadow backlog; beyond it, samples are dropped (shadowing
    # must never apply backpressure to live traffic).
    shadow_max_pending: int = 64
    # Canary soak gates before promotion: hold at least this long AND
    # carry at least this many canary-routed attempts AND (with
    # shadowing on) compare at least this many shadow replays.
    canary_hold_s: float = 5.0
    min_canary_requests: int = 8
    min_shadow_compared: int = 4
    # Shadow diff rate (diffs / compared) ABOVE this triggers rollback.
    # 0.0 = any diff at all rolls back (the bitwise-oracle default).
    shadow_diff_threshold: float = 0.0
    # Canary process deaths during canary/promote that trigger rollback.
    max_canary_crashes: int = 1
    # Which regression signals may trigger automatic rollback; subset of
    # {"slo_alert", "shadow_diff", "canary_crash"}.
    rollback_on: tuple = ("slo_alert", "shadow_diff", "canary_crash")
    # Manifest poll cadence of the background watch loop.
    poll_interval_s: float = 0.5
    # Rollback must restore a healthy single-generation fleet within
    # this bound (the chaos harness asserts it).
    recovery_bound_s: float = 30.0


@dataclass
class RolesConfig:
    """The ``fleet.roles`` sub-block: disaggregated prefill/decode
    role pools (router scoring + per-role autoscaling). Opt-in:
    presence enables."""

    enabled: bool = False
    # Replicas launched per role pool. A replica's own role still comes
    # from its spawn (--role / spec["role"]); these size launch/bench
    # wiring and the per-role autoscaler floors.
    prefill_replicas: int = 1
    decode_replicas: int = 1
    # Per-role autoscaler ceilings: TTFT pressure grows the prefill
    # pool, decode-throughput pressure grows the decode pool — two
    # control loops on two SLO signals.
    max_prefill_replicas: int = 4
    max_decode_replicas: int = 4


@dataclass
class HandoffConfig:
    """The ``fleet.handoff`` sub-block: the crash-safe KV-page transfer
    between prefill and decode workers (inference/serving/handoff.py).
    Opt-in: presence enables (role routing works without it via the
    defaults)."""

    enabled: bool = False
    # Hard cap on one binary page frame; an oversize length prefix is
    # refused (HandoffSizeError) before any payload is read.
    max_frame_bytes: int = 8 << 20
    # Per-attempt deadline over the whole claim→transfer→ack exchange.
    attempt_timeout_s: float = 30.0
    # Bounded retry: total attempts per handoff (>= 1), with exponential
    # backoff + jitter between them.
    retries: int = 3
    backoff_s: float = 0.05
    backoff_max_s: float = 2.0
    # Orphan-reaper TTLs on the decode side: a claim whose transfer
    # never finished (prefill death mid-handoff) is freed after
    # claim_ttl_s; an installed lane the router never resumed after
    # resume_ttl_s.
    claim_ttl_s: float = 30.0
    resume_ttl_s: float = 60.0


@dataclass
class FleetConfig:
    """The ``fleet`` block: router + replica-fleet policy
    (inference/serving/router.py, replica.py). Opt-in like ``serving``:
    the block's presence enables it."""

    # Master switch: True once a `fleet` section exists (see
    # get_fleet_config), False when absent.
    enabled: bool = False
    # Replica processes the launch path spawns (the router itself
    # accepts any endpoint list; this sizes launch/bench wiring).
    replicas: int = 2
    # Re-route attempts per request after a replica FAILURE (death, EOF,
    # attempt timeout). Rejections (queue-full / draining / injected) do
    # NOT consume the budget — they re-route immediately. Exhausting it
    # quarantines the request with RequestPoisonedError.
    retry_budget: int = 2
    # Exponential backoff between failure retries: base * 2^attempt,
    # jittered, capped at retry_backoff_max_s.
    retry_backoff_s: float = 0.05
    retry_backoff_max_s: float = 2.0
    # Per-attempt socket inactivity deadline (no token / reply for this
    # long = the replica is wedged; fail the attempt and re-route).
    # 0 = wait forever. Must exceed worst-case cold prefill compile.
    attempt_timeout_s: float = 120.0
    # Replica-side drain deadline on SIGTERM: finish in-flight work for
    # at most this long, then exit EXIT_PREEMPTED regardless.
    drain_timeout_s: float = 30.0
    # Router-side health probe cache TTL: /healthz + /snapshot scrapes
    # are at most this stale when scoring replicas.
    health_ttl_s: float = 0.25
    # Prefix-affinity hash length (tokens): requests sharing their first
    # N tokens route to the same replica so the prefix KV cache keeps
    # hitting after scale-out. 0 disables affinity (pure least-loaded).
    affinity_prefix_tokens: int = 16
    # A replica with queue_depth + active_requests >= this is saturated:
    # affinity falls back to least-loaded, and when EVERY healthy
    # replica is saturated the router sheds with FleetOverloadError.
    saturation_queue_depth: int = 32
    # Admission-controller token budgets (prompt + max_new_tokens of
    # everything in flight through the router): an int caps every
    # request class; a {class: budget} dict (optional "default" key)
    # caps per class. 0 = unbounded.
    max_inflight_tokens: object = 0
    # retry-after hint carried by FleetOverloadError on shed.
    shed_retry_after_s: float = 0.5
    # Self-healing sub-blocks (autoscaler control loop, degraded-mode
    # ladder, crash-loop breakers). Each is opt-in by presence, like the
    # fleet block itself.
    autoscale: AutoscaleConfig = field(default_factory=AutoscaleConfig)
    degrade: DegradeConfig = field(default_factory=DegradeConfig)
    breaker: BreakerConfig = field(default_factory=BreakerConfig)
    rollout: RolloutConfig = field(default_factory=RolloutConfig)
    roles: RolesConfig = field(default_factory=RolesConfig)
    handoff: HandoffConfig = field(default_factory=HandoffConfig)
