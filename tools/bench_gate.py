#!/usr/bin/env python3
"""Perf-regression gate over bench.py JSON artifacts.

Two modes:

``--check-schema [files...]``
    Validate that bench artifacts are structurally sound (required keys,
    numeric types, ``complete: true``). Defaults to the committed
    baselines (``SERVING_BENCH_CPU.json`` +
    ``LONGDOC_BENCH_CPU.json`` + ``FLEET_BENCH_CPU.json`` +
    ``KERNEL_BENCH_CPU.json`` + ``CHAOS_BENCH_CPU.json`` +
    ``ROLLOUT_BENCH_CPU.json`` + ``DISAGG_BENCH_CPU.json`` +
    ``MEMTIER_BENCH_CPU.json`` + ``TRAIN_BENCH_CPU.json`` +
    ``MESH_BENCH_CPU.json`` + ``OFFLOAD_BENCH_CPU.json``). This is the
    CI step: it needs no jax and takes milliseconds.

``compare FRESH BASELINE``
    Diff a fresh bench run against a committed baseline under per-key
    tolerance bands and exit nonzero on regression. Run locally via
    ``make bench-gate`` (which produces FRESH with ``BENCH_SERVE_OUT`` so
    the committed artifact is never clobbered).

Artifact kinds are auto-detected: a dict with a ``parsed`` key is a
driver wrapper around ``bench.py``'s one JSON line and is unwrapped;
``speedup_sparse_vs_dense_16k`` marks a long-document serving artifact
(``LONGDOC_BENCH_CPU.json``); ``fleet_scaling_2x`` marks a fleet
scale-out artifact (``FLEET_BENCH_CPU.json``); ``disagg_ttft_p95_s``
marks a disaggregated prefill/decode artifact
(``DISAGG_BENCH_CPU.json``); ``spilled_hit_ttft_s`` marks a
memory-tier spill artifact (``MEMTIER_BENCH_CPU.json``);
``chaos_episodes`` marks
a chaos-harness artifact (``CHAOS_BENCH_CPU.json``);
``canary_routed_total`` marks a weight-rollout artifact
(``ROLLOUT_BENCH_CPU.json``);
``decode_pallas_us`` marks a kernel-tier microbench artifact
(``KERNEL_BENCH_CPU.json``); ``train_fusion`` marks a train-step
fusion artifact (``TRAIN_BENCH_CPU.json``); ``streamed_step_ms``
marks a bucket-streamed ZeRO-Offload artifact
(``OFFLOAD_BENCH_CPU.json``); ``sharded_oracle_ok``
marks a mesh-sharded serving artifact (``MESH_BENCH_CPU.json``);
``tokens_per_sec`` marks
a serving artifact; ``metric`` marks a train artifact. Contexts
must match before numbers are compared — platform, model and workload
knobs for serving; the metric string for train — otherwise the compare
is skipped with exit 0 (a CPU artifact is not a regression signal for a
TPU baseline) unless ``--require-comparable`` makes that an error.

Tolerances are deliberately generous: bench.py numbers on a shared CPU
runner are noisy, and the gate's job is catching real regressions (a
2x TTFT blowup, halved decode throughput), not 5% jitter. Override
per key with ``--tolerance key=frac`` or scale all bands with
``--tolerance-scale`` / ``BENCH_GATE_SCALE``.

Exit codes: 0 ok / skipped-not-comparable, 1 regression or schema
violation, 2 usage / unreadable input.

Stdlib-only: importable and runnable anywhere the repo checks out.
"""

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEFAULT_ARTIFACTS = ("SERVING_BENCH_CPU.json",
                     "LONGDOC_BENCH_CPU.json", "FLEET_BENCH_CPU.json",
                     "KERNEL_BENCH_CPU.json", "CHAOS_BENCH_CPU.json",
                     "ROLLOUT_BENCH_CPU.json", "DISAGG_BENCH_CPU.json",
                     "MEMTIER_BENCH_CPU.json", "TRAIN_BENCH_CPU.json",
                     "MESH_BENCH_CPU.json", "OFFLOAD_BENCH_CPU.json")

# -- tolerance profiles -------------------------------------------------
# key -> (direction, rel_tol). direction "higher" means bigger is better:
# fail when fresh < baseline * (1 - tol). direction "lower" means smaller
# is better: fail when fresh > baseline * (1 + tol).
SERVING_TOLERANCES = {
    "tokens_per_sec":                ("higher", 0.50),
    "decode_tokens_per_sec":         ("higher", 0.50),
    "decode_tokens_per_sec_spec_off": ("higher", 0.50),
    "prefill_tokens_per_sec":        ("higher", 0.50),
    "accept_rate":                   ("higher", 0.30),
    "tokens_per_step":               ("higher", 0.30),
    "prefix_hit_rate":               ("higher", 0.30),
    "avg_ttft_s":                    ("lower", 2.00),
    "ttft_p50_s":                    ("lower", 3.00),
    "ttft_p95_s":                    ("lower", 3.00),
    "max_ttft_s":                    ("lower", 4.00),
}

TRAIN_TOLERANCES = {
    "value":           ("higher", 0.25),
    "tflops_per_chip": ("higher", 0.25),
    "mfu":             ("higher", 0.25),
    "vs_baseline":     ("higher", 0.25),
    "step_ms":         ("lower", 0.35),
}

# Long-document leg: tokens/sec per backend are noisy CPU numbers, but
# the speedup ratio (sparse/dense on the same box, same run) is the
# gate-worthy signal — dense and sparse noise largely cancels.
LONGDOC_TOLERANCES = {
    "dense_longdoc_tokens_per_sec":  ("higher", 0.50),
    "sparse_longdoc_tokens_per_sec": ("higher", 0.50),
    "dense_mixed_tokens_per_sec":    ("higher", 0.50),
    "sparse_mixed_tokens_per_sec":   ("higher", 0.50),
    "speedup_sparse_vs_dense_16k":   ("higher", 0.40),
    "dense_avg_ttft_s":              ("lower", 2.00),
    "sparse_avg_ttft_s":             ("lower", 2.00),
    "dense_ttft_p95_s":              ("lower", 3.00),
    "sparse_ttft_p95_s":             ("lower", 3.00),
    "pool_vs_contiguous":            ("lower", 0.10),
}

# Fleet leg: absolute tokens/sec per fleet size are noisy CPU numbers;
# the scaling ratios (2 and 4 replicas vs 1, same box, same run) are the
# gate-worthy signal — per-replica noise largely cancels. kill_recovery_s
# bounds how long failover leaves re-routed work in limbo.
FLEET_TOLERANCES = {
    "fleet_tokens_per_sec_1": ("higher", 0.50),
    "fleet_tokens_per_sec_2": ("higher", 0.50),
    "fleet_tokens_per_sec_4": ("higher", 0.50),
    "fleet_scaling_2x":       ("higher", 0.25),
    "fleet_scaling_4x":       ("higher", 0.30),
    "kill_recovery_s":        ("lower", 3.00),
}

# Kernel-tier microbench: on CPU the Pallas numbers run in interpret
# mode (a correctness treadmill, not kernel perf), so the Pallas bands
# are very loose; the XLA-fallback times gate the composed path that
# actually serves CPU traffic. Parity flags are schema-checked, not
# toleranced.
KERNELS_TOLERANCES = {
    "decode_pallas_us":      ("lower", 4.00),
    "decode_xla_us":         ("lower", 2.00),
    "decode_int8_pallas_us": ("lower", 4.00),
    "decode_int8_xla_us":    ("lower", 2.00),
    "band_pallas_us":        ("lower", 4.00),
    "band_xla_us":           ("lower", 2.00),
}

# Train-step fusion leg: absolute step_ms on a shared CPU runner is
# noisy, so its bands are loose; the overlapped/sequential ratio (same
# box, same run — noise cancels) and the deterministic schedule-
# simulator bubbles are the gate-worthy signals. Parity flags are
# schema-checked, not toleranced.
TRAINSTEP_TOLERANCES = {
    "seq_step_ms":         ("lower", 1.00),
    "overlap_step_ms":     ("lower", 1.00),
    "overlap_vs_seq":      ("lower", 0.15),
    "bubble_1f1b":         ("lower", 0.01),
    "bubble_interleaved":  ("lower", 0.01),
    "comm_overlap_frac":   ("higher", 0.10),
}

# Bucket-streamed ZeRO-Offload leg: absolute step_ms on a shared CPU
# runner is noisy, so its bands are loose; the streamed/sequential ratio
# (same box, same run — noise cancels) is the gate-worthy signal, and
# the bitwise parity flags are schema-checked, not toleranced.
OFFLOAD_TOLERANCES = {
    "seq_step_ms":          ("lower", 1.00),
    "streamed_step_ms":     ("lower", 1.00),
    "streamed_vs_seq":      ("lower", 0.12),
    "offload_overlap_frac": ("higher", 0.60),
}

# Chaos leg: recovery times on a shared CPU runner are pure noise, so
# only the episode/throughput counters get (very loose) bands — the real
# gate is the schema check refusing any baseline whose invariant flags
# are false or whose schedule ran short.
CHAOS_TOLERANCES = {
    "completed_total": ("higher", 0.50),
    "recovery_p95_s":  ("lower", 10.00),
}

# Rollout leg: wall-clock on a shared CPU runner is noise; the gate-
# worthy signals are the counters (zero dropped/duplicated is enforced
# by the schema, not a band) and the rollback recovery time against its
# own committed bound.
ROLLOUT_TOLERANCES = {
    "completed_total":     ("higher", 0.50),
    "rollback_recovery_s": ("lower", 10.00),
}

# Disaggregation leg: absolute TTFTs on a shared CPU runner are noisy;
# the gate-worthy signal is the interleaved/disagg TTFT p95 ratio (same
# box, same run, same seeded workload — noise largely cancels). The
# exactly-once and zero-orphan counters are enforced by the schema, not
# a band.
DISAGG_TOLERANCES = {
    "interleaved_ttft_p95_s":  ("lower", 3.00),
    "disagg_ttft_p95_s":       ("lower", 3.00),
    "ttft_improvement":        ("higher", 0.40),
    "interleaved_decode_tok_s": ("higher", 0.50),
    "disagg_decode_tok_s":     ("higher", 0.50),
    "completed_total":         ("higher", 0.50),
}

# Memory-tier leg: absolute TTFTs on a shared CPU runner are noisy; the
# gate-worthy signal is the cold-vs-spilled-hit TTFT ratio (same box,
# same run, same prompts — noise largely cancels) plus decode tok/s
# staying flat across the two legs. The integrity flags (no corrupt
# entry ever served, bitwise oracle) are enforced by the schema, not a
# band.
MEMTIER_TOLERANCES = {
    "cold_ttft_s":                 ("lower", 3.00),
    "spilled_hit_ttft_s":          ("lower", 3.00),
    "ttft_improvement":            ("higher", 0.40),
    "decode_tokens_per_sec":       ("higher", 0.50),
    "decode_tokens_per_sec_cold":  ("higher", 0.50),
    "spill_hit_rate":              ("higher", 0.20),
}

# Mesh-sharded serving leg: CPU-emulated SPMD throughput is noisy and
# NOT expected to beat single-device (the "devices" share one socket and
# GSPMD inserts real collectives), so absolute tok/s bands are loose and
# the retention floor is low — the gate-worthy signals are the bitwise
# sharded oracle and the per-device KV-pool shrink, both enforced by the
# schema, not a band.
MESH_TOLERANCES = {
    "tokens_per_sec_1x1": ("higher", 0.50),
    "tokens_per_sec_1x2": ("higher", 0.50),
    "tokens_per_sec_1x4": ("higher", 0.50),
    "retention_1x2":      ("higher", 0.40),
    "retention_1x4":      ("higher", 0.40),
    "avg_ttft_s_1x1":     ("lower", 2.00),
    "avg_ttft_s_1x2":     ("lower", 2.00),
    "avg_ttft_s_1x4":     ("lower", 2.00),
}

# context keys that must match exactly for numbers to be comparable
SERVING_CONTEXT = ("platform", "model", "requests", "max_slots",
                   "max_new_tokens", "speculative_k", "kv_cache_dtype",
                   "prefill_chunk_tokens")
TRAIN_CONTEXT = ("metric", "device_kind", "n_devices", "global_batch")
LONGDOC_CONTEXT = ("platform", "model", "max_slots", "page_tokens",
                   "kv_pool_tokens", "longdoc_prompt_len",
                   "longdoc_new_tokens", "shared_prefix_len",
                   "requests_mixed")
# scaling_mode is load-bearing: a "wall" artifact (real cores) and a
# "cpu" artifact (CPU-time-normalized on a core-starved box) measure
# different things and must never gate each other.
FLEET_CONTEXT = ("platform", "model", "requests", "max_new_tokens",
                 "replica_counts", "scaling_mode")
# interpret is load-bearing: interpret-mode (CPU CI) and native-TPU
# kernel times are different universes and must never gate each other.
KERNELS_CONTEXT = ("platform", "interpret", "iters", "decode_shape",
                   "band_shape")
# the seed is load-bearing: two different seeds run two different fault
# schedules, so their counters are not comparable.
CHAOS_CONTEXT = ("platform", "model", "chaos_seed", "chaos_episodes")
# bucket size and the pipeline shape are load-bearing: a different
# bucket plan compiles a different collective structure, and bubbles
# are a pure function of (S, M, V).
TRAINSTEP_CONTEXT = ("platform", "model", "n_devices", "zero_stage",
                     "reduce_bucket_size", "pipe_stages",
                     "pipe_micro_batches")
# the bucket plan and model size are load-bearing: a different K (or a
# different host-optimizer tier share of the step) measures a different
# pipeline, so its ratio is not comparable.
OFFLOAD_CONTEXT = ("platform", "model", "zero_stage", "stream_buckets",
                   "params", "parity_steps")
# the seed and canary fraction are load-bearing: a different seed runs a
# different traffic schedule, and a different slice carries a different
# share of it.
ROLLOUT_CONTEXT = ("platform", "model", "requests_total", "rollout_seed",
                   "canary_fraction")
# rounds and per-kind token budgets are load-bearing: the TTFT ratio is
# only meaningful against the identical seeded longdoc+chat schedule.
DISAGG_CONTEXT = ("platform", "model", "rounds", "long_new_tokens",
                  "chat_new_tokens")
# prompt length and the cache/spill budgets are load-bearing: the TTFT
# ratio is a pure function of how much prefill the promotion avoids.
MEMTIER_CONTEXT = ("platform", "model", "rounds", "max_new_tokens",
                   "prompt_len", "prefix_cache_mb", "prefix_spill_mb")
# n_devices and the shape list are load-bearing: retention vs (1,1) is
# only meaningful on the same virtual-device layout and workload.
MESH_CONTEXT = ("platform", "model", "n_devices", "requests",
                "max_new_tokens", "speculative_k", "mesh_shapes")

# -- schema -------------------------------------------------------------
SERVING_REQUIRED = {
    "platform": str, "model": str, "requests": int, "max_slots": int,
    "max_new_tokens": int, "tokens_per_sec": (int, float),
    "decode_tokens_per_sec": (int, float),
    "prefill_tokens_per_sec": (int, float), "avg_ttft_s": (int, float),
    "ttft_p50_s": (int, float), "ttft_p95_s": (int, float),
    "decode_steps": int, "complete": bool,
}
TRAIN_REQUIRED = {
    "metric": str, "value": (int, float), "unit": str,
}
LONGDOC_REQUIRED = {
    "platform": str, "model": str, "max_slots": int, "page_tokens": int,
    "kv_pool_tokens": int, "longdoc_prompt_len": int,
    "longdoc_new_tokens": int,
    "dense_longdoc_tokens_per_sec": (int, float),
    "sparse_longdoc_tokens_per_sec": (int, float),
    "dense_mixed_tokens_per_sec": (int, float),
    "sparse_mixed_tokens_per_sec": (int, float),
    "dense_avg_ttft_s": (int, float), "sparse_avg_ttft_s": (int, float),
    "dense_oracle_ok": bool, "sparse_oracle_ok": bool,
    "speedup_sparse_vs_dense_16k": (int, float),
    "pool_bytes": int, "contiguous_equiv_bytes": int,
    "complete": bool,
}

FLEET_REQUIRED = {
    "platform": str, "model": str, "requests": int, "max_new_tokens": int,
    "scaling_mode": str,
    "fleet_tokens_per_sec_1": (int, float),
    "fleet_tokens_per_sec_2": (int, float),
    "fleet_tokens_per_sec_4": (int, float),
    "fleet_scaling_2x": (int, float), "fleet_scaling_4x": (int, float),
    "kill_recovery_s": (int, float),
    "fleet_oracle_ok": bool, "complete": bool,
}

KERNELS_REQUIRED = {
    "platform": str, "interpret": bool, "iters": int,
    "decode_pallas_us": (int, float), "decode_xla_us": (int, float),
    "decode_int8_pallas_us": (int, float),
    "decode_int8_xla_us": (int, float),
    "band_pallas_us": (int, float), "band_xla_us": (int, float),
    "decode_parity_ok": bool, "decode_int8_parity_ok": bool,
    "band_parity_ok": bool, "complete": bool,
}

TRAINSTEP_REQUIRED = {
    "platform": str, "model": str, "n_devices": int, "zero_stage": int,
    "reduce_bucket_size": int, "reduce_buckets": int, "parity_ok": bool,
    "parity_steps": int, "baseline_step_ms": (int, float),
    "seq_step_ms": (int, float),
    "overlap_step_ms": (int, float), "overlap_vs_seq": (int, float),
    "collectives_seq": int, "collectives_overlap": int,
    "pipe_stages": int, "pipe_micro_batches": int, "pipe_loss_match": bool,
    "bubble_1f1b": (int, float), "bubble_interleaved": (int, float),
    "complete": bool,
}

CHAOS_REQUIRED = {
    "platform": str, "model": str, "chaos_episodes": int, "chaos_seed": int,
    "completed_total": int, "shed_total": int,
    "recovery_p50_s": (int, float), "recovery_p95_s": (int, float),
    "invariant_bitwise_ok": bool, "invariant_no_stuck": bool,
    "invariant_recovery_bounded": bool, "invariant_converged": bool,
    "complete": bool,
}

ROLLOUT_REQUIRED = {
    "platform": str, "model": str, "rollout_seed": int,
    "canary_fraction": (int, float),
    "requests_total": int, "completed_total": int,
    "dropped_total": int, "duplicated_total": int,
    "canary_routed_total": int,
    "shadow_compared_total": int, "shadow_diff_total": int,
    "rollbacks_total": int,
    "rollforward_ok": bool, "rollback_ok": bool,
    "rollback_recovery_s": (int, float),
    "recovery_bound_s": (int, float),
    "complete": bool,
}

DISAGG_REQUIRED = {
    "platform": str, "model": str, "rounds": int, "requests_per_leg": int,
    "long_new_tokens": int, "chat_new_tokens": int,
    "interleaved_ttft_p95_s": (int, float),
    "disagg_ttft_p95_s": (int, float),
    "ttft_improvement": (int, float),
    "interleaved_decode_tok_s": (int, float),
    "disagg_decode_tok_s": (int, float),
    "handoffs_total": int, "handoffs_completed": int,
    "handoffs_failed": int,
    "completed_total": int, "dropped_total": int, "duplicated_total": int,
    "bitwise_mismatch_total": int, "leaked_pages_total": int,
    "chaos_episodes": int, "chaos_faults_fired": int,
    "chaos_bitwise_ok": bool, "chaos_no_stuck": bool,
    "chaos_recovery_bounded": bool, "chaos_pages_clean": bool,
    "complete": bool,
}

MEMTIER_REQUIRED = {
    "platform": str, "model": str, "rounds": int, "max_new_tokens": int,
    "prompt_len": int,
    "cold_ttft_s": (int, float), "spilled_hit_ttft_s": (int, float),
    "ttft_improvement": (int, float),
    "decode_tokens_per_sec": (int, float),
    "decode_tokens_per_sec_cold": (int, float),
    "spill_hits": int, "spill_promotions": int, "spill_demotions": int,
    "spill_corrupt_dropped": int, "corrupt_entries_served": int,
    "oracle_ok": bool, "spill_integrity_ok": bool,
    "complete": bool,
}

OFFLOAD_REQUIRED = {
    "platform": str, "model": str, "zero_stage": int, "cpu_offload": bool,
    "stream_buckets": int, "params": int, "parity_steps": int,
    "parity_ok": bool, "master_parity_ok": bool, "one_compile": bool,
    "seq_step_ms": (int, float), "streamed_step_ms": (int, float),
    "streamed_vs_seq": (int, float),
    "offload_overlap_frac": (int, float),
    "offload_d2h_ms": (int, float), "offload_host_step_ms": (int, float),
    "offload_h2d_ms": (int, float),
    "sync_fetch_fallbacks": int,
    "complete": bool,
}

MESH_REQUIRED = {
    "platform": str, "model": str, "n_devices": int, "requests": int,
    "max_new_tokens": int, "speculative_k": int,
    "sharded_oracle_ok": bool,
    "tokens_per_sec_1x1": (int, float),
    "tokens_per_sec_1x2": (int, float),
    "tokens_per_sec_1x4": (int, float),
    "retention_1x2": (int, float), "retention_1x4": (int, float),
    "avg_ttft_s_1x1": (int, float), "avg_ttft_s_1x2": (int, float),
    "avg_ttft_s_1x4": (int, float),
    "kv_pool_bytes_per_device_1x1": int,
    "kv_pool_bytes_per_device_1x2": int,
    "kv_pool_bytes_per_device_1x4": int,
    "complete": bool,
}

# chaos acceptance floor: the committed schedule must compose at least
# this many episodes (the issue's bar) to count as evidence
CHAOS_MIN_EPISODES = 20

# the PR's acceptance floor: sparse must beat dense end-to-end at the
# 16k bucket by at least this factor for the artifact to be a baseline
LONGDOC_MIN_SPEEDUP = 5.0

# fleet acceptance floor: 2 replicas must sustain near-linear decode
# scaling vs 1 (in the artifact's own scaling_mode) to be a baseline
FLEET_MIN_SCALING_2X = 1.8

# trainstep acceptance floor: the bucket plan must actually split the
# gradient set — a single bucket is the monolithic reduce wearing a hat
TRAINSTEP_MIN_BUCKETS = 2

# offload acceptance floor: the streamed step plan must actually split
# the host master — one bucket is the sequential path wearing a hat
OFFLOAD_MIN_BUCKETS = 2

# memtier acceptance floor: a spilled hit must actually beat a cold
# re-prefill on the same prompts — a ratio at or below 1.0 means the
# spill tier's decode+verify+promote costs more than the prefill it
# skips, and the tier is overhead wearing a hat
MEMTIER_MIN_TTFT_IMPROVEMENT = 1.0

# disagg acceptance floor: the prefill/decode split must actually beat
# the interleaved baseline's chat TTFT p95 on the same workload — a
# ratio at or below 1.0 means the handoff bought nothing
DISAGG_MIN_TTFT_IMPROVEMENT = 1.0

# mesh acceptance floor: sharded tok/s retention vs the single-device
# (1,1) leg. Deliberately low — CPU-emulated SPMD pays real collective
# costs on one socket — but a collapse below it means sharding broke
# steady-state decode (e.g. lane churn falling off the transfer-free
# path), which is exactly the regression this artifact exists to catch.
MESH_MIN_RETENTION = 0.10

TOLERANCES = {"serving": SERVING_TOLERANCES, "train": TRAIN_TOLERANCES,
              "longdoc": LONGDOC_TOLERANCES, "fleet": FLEET_TOLERANCES,
              "kernels": KERNELS_TOLERANCES, "chaos": CHAOS_TOLERANCES,
              "rollout": ROLLOUT_TOLERANCES, "disagg": DISAGG_TOLERANCES,
              "memtier": MEMTIER_TOLERANCES, "mesh": MESH_TOLERANCES,
              "trainstep": TRAINSTEP_TOLERANCES,
              "offload": OFFLOAD_TOLERANCES}
CONTEXTS = {"serving": SERVING_CONTEXT, "train": TRAIN_CONTEXT,
            "longdoc": LONGDOC_CONTEXT, "fleet": FLEET_CONTEXT,
            "kernels": KERNELS_CONTEXT, "chaos": CHAOS_CONTEXT,
            "rollout": ROLLOUT_CONTEXT, "disagg": DISAGG_CONTEXT,
            "memtier": MEMTIER_CONTEXT, "mesh": MESH_CONTEXT,
            "trainstep": TRAINSTEP_CONTEXT, "offload": OFFLOAD_CONTEXT}
REQUIRED = {"serving": SERVING_REQUIRED, "train": TRAIN_REQUIRED,
            "longdoc": LONGDOC_REQUIRED, "fleet": FLEET_REQUIRED,
            "kernels": KERNELS_REQUIRED, "chaos": CHAOS_REQUIRED,
            "rollout": ROLLOUT_REQUIRED, "disagg": DISAGG_REQUIRED,
            "memtier": MEMTIER_REQUIRED, "mesh": MESH_REQUIRED,
            "trainstep": TRAINSTEP_REQUIRED, "offload": OFFLOAD_REQUIRED}


def load_artifact(path):
    """Read + unwrap one artifact; returns (kind, payload). kind is
    "serving", "train", "longdoc", "fleet", "disagg", "memtier",
    "chaos", "rollout", "kernels" or "trainstep"."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: artifact must be a JSON object")
    if "parsed" in doc and isinstance(doc["parsed"], dict):
        doc = doc["parsed"]       # driver wrapper around bench.py's line
    # longdoc first: it carries per-backend tokens/sec but no bare
    # "tokens_per_sec", and its "metric"-shaped stdout line never lands
    # in the artifact — still, keep the most specific marker in front.
    if "speedup_sparse_vs_dense_16k" in doc:
        return "longdoc", doc
    if "fleet_scaling_2x" in doc:
        return "fleet", doc
    # disagg before chaos: its artifact embeds the chaos mini-leg's
    # "chaos_episodes" rollup, but the TTFT ratio is the kind marker
    if "disagg_ttft_p95_s" in doc:
        return "disagg", doc
    # memtier before the generic markers: its "ttft_improvement" also
    # appears in disagg artifacts, so the spilled-hit key is the marker
    if "spilled_hit_ttft_s" in doc:
        return "memtier", doc
    if "chaos_episodes" in doc:
        return "chaos", doc
    if "canary_routed_total" in doc:
        return "rollout", doc
    if "decode_pallas_us" in doc:
        return "kernels", doc
    # trainstep before the generic serving/train markers: its stdout
    # "metric" line shape must never demote the artifact to kind "train"
    if "train_fusion" in doc:
        return "trainstep", doc
    # offload before the generic "metric" marker: its artifact carries a
    # metric-shaped stdout echo but streamed_step_ms is the kind marker
    if "streamed_step_ms" in doc:
        return "offload", doc
    # mesh before serving: the mesh artifact carries per-shape
    # tokens_per_sec_* keys and must never demote to kind "serving"
    if "sharded_oracle_ok" in doc:
        return "mesh", doc
    if "tokens_per_sec" in doc:
        return "serving", doc
    if "metric" in doc:
        return "train", doc
    raise ValueError(
        f"{path}: unrecognized artifact (no 'speedup_sparse_vs_dense_16k', "
        f"'fleet_scaling_2x', 'disagg_ttft_p95_s', 'spilled_hit_ttft_s', "
        f"'chaos_episodes', "
        f"'canary_routed_total', 'decode_pallas_us', 'train_fusion', "
        f"'streamed_step_ms', "
        f"'sharded_oracle_ok', 'tokens_per_sec' or 'metric' key; "
        f"top-level keys: {sorted(doc)[:8]})")


def check_schema(path):
    """Returns a list of problem strings (empty = valid)."""
    problems = []
    try:
        kind, doc = load_artifact(path)
    except (OSError, ValueError) as e:
        return [str(e)]
    for key, types in REQUIRED[kind].items():
        if key not in doc:
            problems.append(f"{path}: missing required key '{key}' ({kind})")
            continue
        v = doc[key]
        if isinstance(v, bool) and types is not bool:
            problems.append(f"{path}: '{key}' must be {types}, got bool")
        elif not isinstance(v, types):
            problems.append(
                f"{path}: '{key}' must be {types}, got {type(v).__name__}")
    if kind == "serving":
        if doc.get("complete") is not True:
            problems.append(f"{path}: 'complete' is not true — a partial "
                            f"bench run must not be committed as a baseline")
        for key in ("tokens_per_sec", "decode_tokens_per_sec",
                    "prefill_tokens_per_sec"):
            v = doc.get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and v <= 0:
                problems.append(f"{path}: '{key}' must be > 0, got {v}")
    elif kind == "longdoc":
        if doc.get("complete") is not True:
            problems.append(f"{path}: 'complete' is not true — a partial "
                            f"bench run must not be committed as a baseline")
        for key in ("dense_oracle_ok", "sparse_oracle_ok"):
            if doc.get(key) is not True:
                problems.append(
                    f"{path}: '{key}' is not true — the bitwise "
                    f"continuous-vs-generate() oracle must hold per backend")
        for key in ("dense_longdoc_tokens_per_sec",
                    "sparse_longdoc_tokens_per_sec",
                    "dense_mixed_tokens_per_sec",
                    "sparse_mixed_tokens_per_sec"):
            v = doc.get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and v <= 0:
                problems.append(f"{path}: '{key}' must be > 0, got {v}")
        speed = doc.get("speedup_sparse_vs_dense_16k")
        if isinstance(speed, (int, float)) and not isinstance(speed, bool) \
                and speed < LONGDOC_MIN_SPEEDUP:
            problems.append(
                f"{path}: 'speedup_sparse_vs_dense_16k' is {speed}, below "
                f"the {LONGDOC_MIN_SPEEDUP}x acceptance floor")
        pool = doc.get("pool_bytes")
        contig = doc.get("contiguous_equiv_bytes")
        if isinstance(pool, int) and isinstance(contig, int) \
                and not pool < contig:
            problems.append(
                f"{path}: 'pool_bytes' ({pool}) must be strictly below "
                f"'contiguous_equiv_bytes' ({contig}) — paging must "
                f"undercut the MaxSlots x S_max footprint")
    elif kind == "fleet":
        if doc.get("complete") is not True:
            problems.append(f"{path}: 'complete' is not true — a partial "
                            f"bench run must not be committed as a baseline")
        if doc.get("fleet_oracle_ok") is not True:
            problems.append(
                f"{path}: 'fleet_oracle_ok' is not true — outputs must be "
                f"bitwise-identical across every fleet size and failover")
        for key in ("fleet_tokens_per_sec_1", "fleet_tokens_per_sec_2",
                    "fleet_tokens_per_sec_4"):
            v = doc.get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and v <= 0:
                problems.append(f"{path}: '{key}' must be > 0, got {v}")
        scaling = doc.get("fleet_scaling_2x")
        if isinstance(scaling, (int, float)) \
                and not isinstance(scaling, bool) \
                and scaling < FLEET_MIN_SCALING_2X:
            problems.append(
                f"{path}: 'fleet_scaling_2x' is {scaling}, below the "
                f"{FLEET_MIN_SCALING_2X}x near-linear acceptance floor")
        if doc.get("scaling_mode") not in ("wall", "cpu"):
            problems.append(
                f"{path}: 'scaling_mode' must be 'wall' or 'cpu', got "
                f"{doc.get('scaling_mode')!r}")
    elif kind == "chaos":
        if doc.get("complete") is not True:
            problems.append(f"{path}: 'complete' is not true — a partial "
                            f"chaos schedule must not be committed as a "
                            f"baseline")
        for key in ("invariant_bitwise_ok", "invariant_no_stuck",
                    "invariant_recovery_bounded", "invariant_converged"):
            if doc.get(key) is not True:
                problems.append(
                    f"{path}: '{key}' is not true — a chaos run with a "
                    f"failed self-healing invariant must never become a "
                    f"baseline")
        eps = doc.get("chaos_episodes")
        if isinstance(eps, int) and not isinstance(eps, bool) \
                and eps < CHAOS_MIN_EPISODES:
            problems.append(
                f"{path}: 'chaos_episodes' is {eps}, below the "
                f"{CHAOS_MIN_EPISODES}-episode acceptance floor")
        comp = doc.get("completed_total")
        if isinstance(comp, int) and not isinstance(comp, bool) and comp <= 0:
            problems.append(
                f"{path}: 'completed_total' must be > 0 — a schedule where "
                f"nothing completed proves nothing")
    elif kind == "rollout":
        if doc.get("complete") is not True:
            problems.append(f"{path}: 'complete' is not true — a partial "
                            f"rollout run must not be committed as a "
                            f"baseline")
        for key in ("rollforward_ok", "rollback_ok"):
            if doc.get(key) is not True:
                problems.append(
                    f"{path}: '{key}' is not true — both the roll-forward "
                    f"and the forced-regression rollback must succeed for "
                    f"the run to become a baseline")
        for key in ("dropped_total", "duplicated_total"):
            v = doc.get(key)
            if isinstance(v, int) and not isinstance(v, bool) and v != 0:
                problems.append(
                    f"{path}: '{key}' is {v} — a rollout that drops or "
                    f"duplicates a request breaks exactly-once and must "
                    f"never become a baseline")
        routed = doc.get("canary_routed_total")
        if isinstance(routed, int) and not isinstance(routed, bool) \
                and routed <= 0:
            problems.append(
                f"{path}: 'canary_routed_total' must be > 0 — a canary "
                f"phase that never carried traffic proves nothing")
        comp = doc.get("completed_total")
        if isinstance(comp, int) and not isinstance(comp, bool) and comp <= 0:
            problems.append(
                f"{path}: 'completed_total' must be > 0 — a rollout under "
                f"which nothing completed proves nothing")
        rec = doc.get("rollback_recovery_s")
        bound = doc.get("recovery_bound_s")
        if all(isinstance(v, (int, float)) and not isinstance(v, bool)
               for v in (rec, bound)) and rec > bound:
            problems.append(
                f"{path}: 'rollback_recovery_s' ({rec}) exceeds "
                f"'recovery_bound_s' ({bound}) — an unbounded rollback is "
                f"downtime wearing a hat")
    elif kind == "disagg":
        if doc.get("complete") is not True:
            problems.append(f"{path}: 'complete' is not true — a partial "
                            f"disagg bench run must not be committed as a "
                            f"baseline")
        for key in ("dropped_total", "duplicated_total",
                    "bitwise_mismatch_total"):
            v = doc.get(key)
            if isinstance(v, int) and not isinstance(v, bool) and v != 0:
                problems.append(
                    f"{path}: '{key}' is {v} — a disaggregated fleet that "
                    f"drops, duplicates, or corrupts a request breaks "
                    f"exactly-once and must never become a baseline")
        leaked = doc.get("leaked_pages_total")
        if isinstance(leaked, int) and not isinstance(leaked, bool) \
                and leaked != 0:
            problems.append(
                f"{path}: 'leaked_pages_total' is {leaked} — orphaned KV "
                f"pages after drain mean the handoff claim/reap contract "
                f"is broken")
        for key in ("chaos_bitwise_ok", "chaos_no_stuck",
                    "chaos_recovery_bounded", "chaos_pages_clean"):
            if doc.get(key) is not True:
                problems.append(
                    f"{path}: '{key}' is not true — a disagg chaos leg "
                    f"with a failed invariant must never become a baseline")
        imp = doc.get("ttft_improvement")
        if isinstance(imp, (int, float)) and not isinstance(imp, bool) \
                and imp <= DISAGG_MIN_TTFT_IMPROVEMENT:
            problems.append(
                f"{path}: 'ttft_improvement' is {imp}, at or below the "
                f"{DISAGG_MIN_TTFT_IMPROVEMENT}x floor — the prefill/"
                f"decode split must beat the interleaved baseline's chat "
                f"TTFT p95 on the same workload")
        done = doc.get("handoffs_completed")
        if isinstance(done, int) and not isinstance(done, bool) \
                and done <= 0:
            problems.append(
                f"{path}: 'handoffs_completed' must be > 0 — a disagg leg "
                f"that never moved a KV page proves nothing")
        comp = doc.get("completed_total")
        if isinstance(comp, int) and not isinstance(comp, bool) and comp <= 0:
            problems.append(
                f"{path}: 'completed_total' must be > 0 — a workload where "
                f"nothing completed proves nothing")
    elif kind == "memtier":
        if doc.get("complete") is not True:
            problems.append(f"{path}: 'complete' is not true — a partial "
                            f"memtier bench run must not be committed as a "
                            f"baseline")
        if doc.get("oracle_ok") is not True:
            problems.append(
                f"{path}: 'oracle_ok' is not true — spilled-hit serving "
                f"must stay bitwise-identical to one-shot generate()")
        if doc.get("spill_integrity_ok") is not True:
            problems.append(
                f"{path}: 'spill_integrity_ok' is not true — a corrupted "
                f"spill entry must be detected, dropped and re-prefilled, "
                f"never served")
        served = doc.get("corrupt_entries_served")
        if isinstance(served, int) and not isinstance(served, bool) \
                and served != 0:
            problems.append(
                f"{path}: 'corrupt_entries_served' is {served} — serving "
                f"KV from a checksum-failed blob is silent corruption and "
                f"must never become a baseline")
        imp = doc.get("ttft_improvement")
        if isinstance(imp, (int, float)) and not isinstance(imp, bool) \
                and imp <= MEMTIER_MIN_TTFT_IMPROVEMENT:
            problems.append(
                f"{path}: 'ttft_improvement' is {imp}, at or below the "
                f"{MEMTIER_MIN_TTFT_IMPROVEMENT}x floor — a spilled hit "
                f"must beat a cold re-prefill on the same prompts")
        hits = doc.get("spill_hits")
        if isinstance(hits, int) and not isinstance(hits, bool) \
                and hits <= 0:
            problems.append(
                f"{path}: 'spill_hits' must be > 0 — a run where nothing "
                f"was ever promoted from spill proves nothing")
        for key in ("decode_tokens_per_sec", "decode_tokens_per_sec_cold",
                    "cold_ttft_s", "spilled_hit_ttft_s"):
            v = doc.get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and v <= 0:
                problems.append(f"{path}: '{key}' must be > 0, got {v}")
    elif kind == "mesh":
        if doc.get("complete") is not True:
            problems.append(f"{path}: 'complete' is not true — a partial "
                            f"mesh bench run must not be committed as a "
                            f"baseline")
        if doc.get("sharded_oracle_ok") is not True:
            problems.append(
                f"{path}: 'sharded_oracle_ok' is not true — tensor-parallel "
                f"serving must stay bitwise-identical to single-device "
                f"generate() at every mesh shape")
        for key in ("tokens_per_sec_1x1", "tokens_per_sec_1x2",
                    "tokens_per_sec_1x4"):
            v = doc.get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and v <= 0:
                problems.append(f"{path}: '{key}' must be > 0, got {v}")
        for key in ("retention_1x2", "retention_1x4"):
            v = doc.get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and v < MESH_MIN_RETENTION:
                problems.append(
                    f"{path}: '{key}' is {v}, below the "
                    f"{MESH_MIN_RETENTION}x retention floor vs the "
                    f"single-device leg — sharding broke steady-state "
                    f"decode throughput")
        per1 = doc.get("kv_pool_bytes_per_device_1x1")
        for name in ("1x2", "1x4"):
            perN = doc.get(f"kv_pool_bytes_per_device_{name}")
            if all(isinstance(v, int) and not isinstance(v, bool)
                   for v in (per1, perN)) and not perN < per1:
                problems.append(
                    f"{path}: 'kv_pool_bytes_per_device_{name}' ({perN}) "
                    f"must be strictly below the single-device pool "
                    f"({per1}) — a model-axis shard that doesn't shrink "
                    f"per-device KV bytes isn't sharding anything")
        nd = doc.get("n_devices")
        if isinstance(nd, int) and not isinstance(nd, bool) and nd < 4:
            problems.append(
                f"{path}: 'n_devices' is {nd} — the leg needs >= 4 virtual "
                f"devices to exercise the (1,4) shape")
    elif kind == "trainstep":
        if doc.get("complete") is not True:
            problems.append(f"{path}: 'complete' is not true — a partial "
                            f"bench run must not be committed as a baseline")
        if doc.get("parity_ok") is not True:
            problems.append(
                f"{path}: 'parity_ok' is not true — an overlapped step that "
                f"diverges from the sequential oracle must never become a "
                f"baseline")
        if doc.get("pipe_loss_match") is not True:
            problems.append(
                f"{path}: 'pipe_loss_match' is not true — the interleaved "
                f"schedule must reproduce the 1F1B losses")
        seq_ms = doc.get("seq_step_ms")
        ovl_ms = doc.get("overlap_step_ms")
        for key, v in (("seq_step_ms", seq_ms), ("overlap_step_ms", ovl_ms)):
            if isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and v <= 0:
                problems.append(f"{path}: '{key}' must be > 0, got {v}")
        if all(isinstance(v, (int, float)) and not isinstance(v, bool)
               for v in (seq_ms, ovl_ms)) and ovl_ms > seq_ms:
            problems.append(
                f"{path}: 'overlap_step_ms' ({ovl_ms}) exceeds "
                f"'seq_step_ms' ({seq_ms}) — the overlapped step must not "
                f"be slower than the sequential reduce it replaces")
        b1, b2 = doc.get("bubble_1f1b"), doc.get("bubble_interleaved")
        if all(isinstance(v, (int, float)) and not isinstance(v, bool)
               for v in (b1, b2)) and not b2 < b1:
            problems.append(
                f"{path}: 'bubble_interleaved' ({b2}) must be strictly "
                f"below 'bubble_1f1b' ({b1}) — interleaving that doesn't "
                f"shrink the bubble proves nothing")
        nb = doc.get("reduce_buckets")
        if isinstance(nb, int) and not isinstance(nb, bool) \
                and nb < TRAINSTEP_MIN_BUCKETS:
            problems.append(
                f"{path}: 'reduce_buckets' is {nb}, below the "
                f"{TRAINSTEP_MIN_BUCKETS}-bucket acceptance floor")
    elif kind == "offload":
        if doc.get("complete") is not True:
            problems.append(f"{path}: 'complete' is not true — a partial "
                            f"offload bench run must not be committed as a "
                            f"baseline")
        if doc.get("parity_ok") is not True:
            problems.append(
                f"{path}: 'parity_ok' is not true — a streamed offload step "
                f"whose losses/params diverge from the sequential host path "
                f"must never become a baseline")
        if doc.get("master_parity_ok") is not True:
            problems.append(
                f"{path}: 'master_parity_ok' is not true — the ping-pong "
                f"host master must stay bitwise-equal to the in-place "
                f"sequential master")
        if doc.get("one_compile") is not True:
            problems.append(
                f"{path}: 'one_compile' is not true — streaming the host "
                f"optimizer must not retrace the train step")
        seq_ms = doc.get("seq_step_ms")
        str_ms = doc.get("streamed_step_ms")
        for key, v in (("seq_step_ms", seq_ms),
                       ("streamed_step_ms", str_ms)):
            if isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and v <= 0:
                problems.append(f"{path}: '{key}' must be > 0, got {v}")
        if all(isinstance(v, (int, float)) and not isinstance(v, bool)
               for v in (seq_ms, str_ms)) and str_ms >= seq_ms:
            problems.append(
                f"{path}: 'streamed_step_ms' ({str_ms}) is not below "
                f"'seq_step_ms' ({seq_ms}) — a streamed step that doesn't "
                f"beat the sequential host path it replaces proves nothing")
        nb = doc.get("stream_buckets")
        if isinstance(nb, int) and not isinstance(nb, bool) \
                and nb < OFFLOAD_MIN_BUCKETS:
            problems.append(
                f"{path}: 'stream_buckets' is {nb}, below the "
                f"{OFFLOAD_MIN_BUCKETS}-bucket acceptance floor")
    elif kind == "kernels":
        if doc.get("complete") is not True:
            problems.append(f"{path}: 'complete' is not true — a partial "
                            f"bench run must not be committed as a baseline")
        for key in ("decode_parity_ok", "decode_int8_parity_ok",
                    "band_parity_ok"):
            if doc.get(key) is not True:
                problems.append(
                    f"{path}: '{key}' is not true — a kernel that drifts "
                    f"from its XLA-fallback oracle must not be a baseline")
        for key in ("decode_pallas_us", "decode_xla_us",
                    "decode_int8_pallas_us", "decode_int8_xla_us",
                    "band_pallas_us", "band_xla_us"):
            v = doc.get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and v <= 0:
                problems.append(f"{path}: '{key}' must be > 0, got {v}")
    else:
        v = doc.get("value")
        if isinstance(v, (int, float)) and not isinstance(v, bool) and v <= 0:
            problems.append(f"{path}: 'value' must be > 0, got {v}")
    return problems


def comparable(kind, fresh, base):
    """Returns a list of context mismatches (empty = comparable)."""
    keys = CONTEXTS[kind]
    out = []
    for key in keys:
        fv, bv = fresh.get(key), base.get(key)
        if fv is not None and bv is not None and fv != bv:
            out.append(f"{key}: fresh={fv!r} baseline={bv!r}")
    return out


def compare(kind, fresh, base, tolerances, scale=1.0):
    """Returns (regressions, checked) where regressions is a list of
    problem strings and checked counts the keys actually compared."""
    regressions, checked = [], 0
    for key, (direction, tol) in sorted(tolerances.items()):
        fv, bv = fresh.get(key), base.get(key)
        if not all(isinstance(v, (int, float)) and not isinstance(v, bool)
                   for v in (fv, bv)):
            continue
        tol = tol * scale
        checked += 1
        if direction == "higher":
            floor = bv * (1.0 - tol)
            if fv < floor:
                regressions.append(
                    f"{key}: {fv:.6g} < {floor:.6g} "
                    f"(baseline {bv:.6g}, tol -{tol:.0%})")
        else:
            ceil = bv * (1.0 + tol)
            if fv > ceil:
                regressions.append(
                    f"{key}: {fv:.6g} > {ceil:.6g} "
                    f"(baseline {bv:.6g}, tol +{tol:.0%})")
    return regressions, checked


def parse_tolerance_overrides(pairs):
    out = {}
    for pair in pairs or ():
        key, _, frac = pair.partition("=")
        if not key or not frac:
            raise ValueError(f"--tolerance wants key=frac, got {pair!r}")
        out[key] = float(frac)
    return out


def run_check_schema(paths):
    paths = list(paths) or [os.path.join(REPO_ROOT, p)
                            for p in DEFAULT_ARTIFACTS]
    rc = 0
    for path in paths:
        problems = check_schema(path)
        if problems:
            rc = 1
            for p in problems:
                print(f"bench-gate: SCHEMA FAIL {p}", file=sys.stderr)
        else:
            print(f"bench-gate: schema ok {path}")
    return rc


def run_compare(args):
    try:
        fkind, fresh = load_artifact(args.fresh)
        bkind, base = load_artifact(args.baseline)
    except (OSError, ValueError) as e:
        print(f"bench-gate: {e}", file=sys.stderr)
        return 2
    if fkind != bkind:
        print(f"bench-gate: artifact kinds differ (fresh={fkind}, "
              f"baseline={bkind})", file=sys.stderr)
        return 2
    mismatches = comparable(fkind, fresh, base)
    if mismatches:
        msg = (f"bench-gate: contexts differ, numbers not comparable: "
               f"{'; '.join(mismatches)}")
        if args.require_comparable:
            print(msg, file=sys.stderr)
            return 2
        print(msg + " — SKIP")
        return 0
    tolerances = dict(TOLERANCES[fkind])
    for key, frac in parse_tolerance_overrides(args.tolerance).items():
        direction = tolerances.get(key, ("higher", 0.0))[0]
        tolerances[key] = (direction, frac)
    scale = args.tolerance_scale
    if scale is None:
        scale = float(os.environ.get("BENCH_GATE_SCALE", "1.0"))
    regressions, checked = compare(fkind, fresh, base, tolerances,
                                   scale=scale)
    if checked == 0:
        print("bench-gate: no overlapping numeric keys to compare",
              file=sys.stderr)
        return 2
    if regressions:
        for r in regressions:
            print(f"bench-gate: REGRESSION {r}", file=sys.stderr)
        print(f"bench-gate: FAIL ({len(regressions)}/{checked} keys "
              f"regressed vs {args.baseline})", file=sys.stderr)
        return 1
    print(f"bench-gate: ok ({checked} keys within tolerance vs "
          f"{args.baseline})")
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="bench_gate", description=__doc__.splitlines()[0])
    parser.add_argument("--check-schema", nargs="*", default=None,
                        metavar="FILE",
                        help="validate artifact schema(s); defaults to the "
                             "committed SERVING_BENCH_CPU.json + "
                             "LONGDOC_BENCH_CPU.json + "
                             "FLEET_BENCH_CPU.json + KERNEL_BENCH_CPU.json "
                             "+ CHAOS_BENCH_CPU.json + ROLLOUT_BENCH_CPU."
                             "json + DISAGG_BENCH_CPU.json + "
                             "MEMTIER_BENCH_CPU.json + TRAIN_BENCH_CPU.json"
                             " + MESH_BENCH_CPU.json + "
                             "OFFLOAD_BENCH_CPU.json")
    parser.add_argument("mode", nargs="?", choices=["compare"],
                        help="compare FRESH BASELINE under tolerance bands")
    parser.add_argument("fresh", nargs="?", help="fresh bench JSON")
    parser.add_argument("baseline", nargs="?", help="committed baseline JSON")
    parser.add_argument("--tolerance", action="append", metavar="KEY=FRAC",
                        help="override one key's relative tolerance")
    parser.add_argument("--tolerance-scale", type=float, default=None,
                        help="multiply every tolerance band (also "
                             "BENCH_GATE_SCALE env)")
    parser.add_argument("--require-comparable", action="store_true",
                        help="exit 2 instead of skipping when contexts differ")
    args = parser.parse_args(argv)

    if args.check_schema is not None:
        return run_check_schema(args.check_schema)
    if args.mode == "compare":
        if not args.fresh or not args.baseline:
            parser.error("compare needs FRESH and BASELINE paths")
        return run_compare(args)
    parser.error("nothing to do: use --check-schema or compare FRESH BASELINE")


if __name__ == "__main__":
    sys.exit(main())
