# Developer entry points.

.PHONY: chip-smoke test test-fast test-faults test-cluster test-serving test-router test-disagg test-memtier test-sharding lint-jax lint-jax-diff lint-jax-baseline ops bench bench-serving bench-longdoc bench-fleet bench-kernels bench-train bench-offload trace-smoke bench-gate chaos-smoke bench-rollout bench-disagg bench-memtier bench-mesh

# Unit tests run on a virtual 8-device CPU mesh and never touch an
# accelerator (tests/conftest.py pins JAX_PLATFORMS=cpu).
test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -x -q

test-fast:
	JAX_PLATFORMS=cpu python -m pytest tests/ -x -q -m "not slow"

# Fault-injection suites: checkpoint I/O faults (crash/torn-write/EIO at every
# protocol point) + step-level resilience (divergence guard, watchdog,
# rollback recovery) + cluster fault tolerance (supervised kill/preempt with
# subprocess workers, comm deadlines, gossip). Deterministic on the CPU mesh.
test-faults:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m faults

# Just the job-level (cluster) suite: worker supervision, preemption,
# comm deadlines, health gossip, elastic resume.
test-cluster:
	JAX_PLATFORMS=cpu python -m pytest tests/unit/test_cluster_resilience.py -q

# Continuous-batching serving engine: bitwise oracle vs generate(),
# batched/chunked prefill, prefix KV cache, speculative decoding,
# int8/bf16 KV quantization, recompile pins,
# backpressure/deadline/fault-injection recovery.
test-serving:
	JAX_PLATFORMS=cpu python -m pytest tests/unit/test_serving.py tests/unit/test_prefix_cache.py tests/unit/test_speculative.py -q

# Fleet router + replica suite, BOTH tiers: the fast stub-replica tests
# (routing policy, exactly-once retry accounting, shedding, affinity,
# fleet fault arms) and the slow multi-process tests that spawn real
# replica workers (kill_replica mid-decode, SIGTERM drain, prefix
# affinity surviving scale-out).
test-router:
	JAX_PLATFORMS=cpu python -m pytest tests/unit/test_router.py -q

# Disaggregated prefill/decode suite, BOTH tiers: the fast tests (frame
# codec, pool page-state guards, handoff sender/receiver state machines,
# role routing + degraded fallback, role-pool autoscaler, bitwise
# engine/socket roundtrips) and the slow multi-process chaos tests that
# kill a prefill worker mid-handoff and a decode worker post-ack.
test-disagg:
	JAX_PLATFORMS=cpu python -m pytest tests/unit/test_disagg.py -q

# Memory-tier suite: spill blob codec round-trips (fp32/bf16/int8 +
# scales, bitwise), checksum/torn-write detection dropping — never
# serving — corrupt entries, RAM->disk demotion + promotion, the
# host-RSS pressure guard (shed -> pause inserts -> degrade ladder,
# with hysteresis), OOM-safe admission relief, and the bitwise oracle
# with the spill tier on, off, and under the three memory fault arms.
test-memtier:
	JAX_PLATFORMS=cpu python -m pytest tests/unit/test_memtier.py -q

# Sharding-spec registry: ordered first-match rules, named validation
# errors, the bitwise shard->gather round-trip on the virtual CPU mesh,
# and the `parallel` ds_config block that feeds it.
test-sharding:
	JAX_PLATFORMS=cpu python -m pytest tests/unit/test_sharding_registry.py -q

# Static JAX hazard analysis (tools/jaxlint): recompile, host-sync,
# leaked-tracer, donation, fp16-dtype, collective-axis, RNG-reuse,
# quantized-dtype and sharding-consistency rules. AST-only — no jax
# import, the two-pass analyzer covers the repo in well under 3 s. Fails
# on any finding not in jaxlint_baseline.json (see docs/static_analysis.md
# for rules, suppressions, and the workflow).
lint-jax:
	python -m tools.jaxlint deepspeed_tpu tools --baseline jaxlint_baseline.json

# The PR gate: only findings on lines changed vs origin/main fail, so new
# code lands at zero findings while untouched debt stays the baseline's
# problem. Works on a shallow checkout (tree-vs-worktree diff).
lint-jax-diff:
	python -m tools.jaxlint deepspeed_tpu tools --diff origin/main

# Regenerate the baseline after intentionally fixing findings (shrinking it).
# Never use this to absorb NEW findings — fix or suppress them with a reason.
lint-jax-baseline:
	python -m tools.jaxlint deepspeed_tpu tools --baseline jaxlint_baseline.json --write-baseline

# End-to-end telemetry smoke on the CPU backend: short train loop +
# serving burst + a real supervisor restart with the telemetry block
# enabled, then validates the merged Chrome trace (train/serving spans,
# request ids, lifecycle instants) and the live /metrics//healthz
# endpoint. Writes trace_smoke.json (see docs/observability.md).
trace-smoke:
	python -m tools.trace_smoke

ops:
	$(MAKE) -C csrc

# Continuous-batching serving throughput + TTFT on the CPU backend;
# runs the decode leg with speculation off AND on (BENCH_SERVE_SPEC_K,
# default 4; BENCH_SERVE_KV_DTYPE picks fp32|bf16|int8 KV storage) and
# writes SERVING_BENCH_CPU.json with both rates + accept_rate
# (see docs/serving.md).
bench-serving:
	JAX_PLATFORMS=cpu BENCH_MODEL=serving python bench.py --child

# Long-document serving leg: two shared-prefix 16k prompts mixed with
# short chat, served with the 16384 bucket on dense then sparse_xla
# over the paged KV pool. Writes LONGDOC_BENCH_CPU.json with per-backend
# tokens/sec + TTFT, the sparse-vs-dense speedup, and the paged-vs-
# contiguous footprint ratio; the bitwise generate() oracle is asserted
# in-run (see docs/serving.md). Takes a few minutes on CPU — the dense
# 16k prefills ARE the story.
bench-longdoc:
	JAX_PLATFORMS=cpu BENCH_MODEL=longdoc python bench.py --child

# Fleet serving leg: 1 -> 2 -> 4 real replica processes behind the
# Router, plus a kill-one-replica recovery measurement. Writes
# FLEET_BENCH_CPU.json with per-fleet-size tokens/sec, the 2x/4x
# scaling factors (CPU-time-normalized on core-starved boxes — see
# scaling_mode), and kill_recovery_s; the bitwise cross-fleet oracle is
# asserted in-run (see docs/serving.md).
bench-fleet:
	JAX_PLATFORMS=cpu BENCH_MODEL=fleet python bench.py --child

# Chaos harness: a seeded 20-episode randomized fault schedule
# (kill/drain/slow/reject/overload composed) against 2 live replica
# processes behind the Router. Writes CHAOS_BENCH_CPU.json with
# recovery-time p50/p95 and the four invariant flags (bitwise
# exactly-once, no stuck requests, bounded recovery, convergence back
# to healthy) that the bench gate's schema check refuses when false.
# Knobs: BENCH_CHAOS_SEED (default 0), BENCH_CHAOS_EPISODES (default
# 20), BENCH_CHAOS_OUT (redirects the artifact).
chaos-smoke:
	JAX_PLATFORMS=cpu BENCH_MODEL=chaos python bench.py --child
	python -m tools.bench_gate --check-schema CHAOS_BENCH_CPU.json

# Zero-downtime weight rollout: live checkpoint hot-swap against 2
# incumbent replica processes — roll-forward on identical weights
# (canary + shadow traffic + promote) and a forced-regression rollback
# on different weights, both under continuous traffic with a streamed
# exactly-once oracle. Writes ROLLOUT_BENCH_CPU.json; the bench gate's
# schema check refuses any dropped/duplicated request, a rollback
# exceeding the recovery bound, or a canary that never carried traffic.
# Knobs: BENCH_ROLLOUT_SEED (default 0), BENCH_ROLLOUT_REQUESTS (per
# phase, default 48), BENCH_ROLLOUT_OUT (redirects the artifact).
bench-rollout:
	JAX_PLATFORMS=cpu BENCH_MODEL=rollout python bench.py --child
	python -m tools.bench_gate --check-schema ROLLOUT_BENCH_CPU.json

# Disaggregated prefill/decode leg: the same seeded longdoc+chat
# workload against 2 interleaved mixed replicas vs 1 prefill + 1 decode
# worker with KV-page handoff, plus a chaos mini-leg (kill prefill
# mid-handoff, kill decode post-ack, corrupt a page frame). Writes
# DISAGG_BENCH_CPU.json with chat TTFT p95 both legs, the improvement
# ratio, decode tok/s, and the exactly-once / zero-orphan counters the
# bench gate's schema check refuses when nonzero. Knobs:
# BENCH_DISAGG_SEED, BENCH_DISAGG_ROUNDS (default 5), BENCH_DISAGG_OUT.
bench-disagg:
	JAX_PLATFORMS=cpu BENCH_MODEL=disagg python bench.py --child
	python -m tools.bench_gate --check-schema DISAGG_BENCH_CPU.json

# Memory-tier leg: two long shared prompts alternate through a live
# prefix cache sized for ONE entry, so every serve after the first two
# promotes its KV from the host-RAM spill tier — spilled-hit TTFT vs
# the cold re-prefill TTFT of disjoint same-length prompts, decode
# tok/s held equal, bitwise generate() oracle asserted in-run, plus a
# corrupt-a-spilled-blob mini-leg (dropped + re-prefilled, never
# served). Writes MEMTIER_BENCH_CPU.json; the bench gate's schema
# check refuses a false integrity flag, a served corrupt entry, or a
# TTFT ratio at/below 1.0. Knobs: BENCH_MEMTIER_ROUNDS (default 6),
# BENCH_MEMTIER_NEW_TOKENS (default 16), BENCH_MEMTIER_OUT.
bench-memtier:
	JAX_PLATFORMS=cpu BENCH_MODEL=memtier python bench.py --child
	python -m tools.bench_gate --check-schema MEMTIER_BENCH_CPU.json

# Mesh-sharded serving: tensor-parallel engine at mesh shapes (1,1),
# (1,2), (1,4) on a 4-device virtual CPU mesh; asserts the bitwise
# continuous-vs-generate() oracle SHARDED (dense + pallas decode tier,
# speculation off/on) and writes MESH_BENCH_CPU.json with per-shape
# tok/s, TTFT and per-device KV-pool bytes. The gate's schema check
# refuses a false sharded_oracle_ok, a retention collapse vs (1,1), and
# a pool that doesn't shrink per device. Knobs: BENCH_MESH_REQUESTS /
# BENCH_MESH_NEW_TOKENS / BENCH_MESH_SPEC_K / BENCH_MESH_OUT.
bench-mesh:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" BENCH_MODEL=mesh python bench.py --child
	python -m tools.bench_gate --check-schema MESH_BENCH_CPU.json

# Kernel-tier microbench: Pallas (interpret on CPU) vs the composed-XLA
# fallback for the fused paged decode (fp32 + int8) and banded sparse
# kernels, parity asserted per sample. Writes KERNEL_BENCH_CPU.json
# (see docs/kernels.md).
bench-kernels:
	JAX_PLATFORMS=cpu BENCH_MODEL=kernels python bench.py --child

# Train-step fusion bench: overlapped per-bucket backward/reduce vs the
# sequential post-backward reduce (bitwise parity asserted in-run) plus
# interleaved-1F1B bubble accounting on a simulated 4-device CPU mesh.
# Writes TRAIN_BENCH_CPU.json (see docs/training_perf.md).
bench-train:
	JAX_PLATFORMS=cpu BENCH_MODEL=train python bench.py --child
	python -m tools.bench_gate --check-schema TRAIN_BENCH_CPU.json

# Bucket-streamed ZeRO-Offload bench: the three-stage host-optimizer
# pipeline (per-bucket D2H -> ping-pong out-of-place host Adam -> H2D
# commit of adopted views) vs the sequential offload step — losses,
# params AND host master bitwise-asserted in-run, one compile enforced.
# Writes OFFLOAD_BENCH_CPU.json (see docs/training_perf.md).
bench-offload:
	JAX_PLATFORMS=cpu BENCH_MODEL=offload python bench.py --child
	python -m tools.bench_gate --check-schema OFFLOAD_BENCH_CPU.json

# BERT-large throughput on the TPU. Chip or nothing: with no TPU it exits
# non-zero and prints no number.
bench:
	python bench.py

# The quickest proof the system still starts on the chip: every Pallas
# kernel compiled natively and checked, BERT-large train steps, GPT-2-large
# serving, in ONE process on every TPU device of the host. Refuses a CPU.
chip-smoke:
	python chip_smoke.py

# Perf-regression gate: run the CPU serving bench into a scratch file
# (BENCH_SERVE_OUT keeps the committed baseline untouched), then diff it
# against SERVING_BENCH_CPU.json under per-key tolerance bands
# (tools/bench_gate.py). Nonzero exit on regression. Tune with
# BENCH_GATE_SCALE (e.g. 2.0 on a loaded machine).
bench-gate:
	JAX_PLATFORMS=cpu BENCH_MODEL=serving \
		BENCH_SERVE_OUT=/tmp/bench_gate_serving.json python bench.py --child
	python -m tools.bench_gate compare /tmp/bench_gate_serving.json SERVING_BENCH_CPU.json
	JAX_PLATFORMS=cpu BENCH_MODEL=longdoc \
		BENCH_LONGDOC_OUT=/tmp/bench_gate_longdoc.json python bench.py --child
	python -m tools.bench_gate compare /tmp/bench_gate_longdoc.json LONGDOC_BENCH_CPU.json
	JAX_PLATFORMS=cpu BENCH_MODEL=fleet \
		BENCH_FLEET_OUT=/tmp/bench_gate_fleet.json python bench.py --child
	python -m tools.bench_gate compare /tmp/bench_gate_fleet.json FLEET_BENCH_CPU.json
	JAX_PLATFORMS=cpu BENCH_MODEL=kernels \
		BENCH_KERNELS_OUT=/tmp/bench_gate_kernels.json python bench.py --child
	python -m tools.bench_gate compare /tmp/bench_gate_kernels.json KERNEL_BENCH_CPU.json
	JAX_PLATFORMS=cpu BENCH_MODEL=train \
		BENCH_TRAIN_OUT=/tmp/bench_gate_train.json python bench.py --child
	python -m tools.bench_gate compare /tmp/bench_gate_train.json TRAIN_BENCH_CPU.json
