"""Fleet observability (telemetry/collector.py, anomaly.py, slo.py,
tools/bench_gate.py).

Contracts under test:

1. **Scrape + merge** — a FleetCollector scraping real worker
   TelemetryServers over sockets produces ONE multi-process Chrome trace
   (pid = rank, process_name metadata lanes, rebased timestamps) and
   rank-labelled metrics with min/max/mean rollups. A dead worker
   degrades to a partial merge with an edge-triggered gap marker, never
   an exception.
2. **Straggler detection** — cross-rank skew on step spans flags the
   slow rank (driven synthetically AND by the real ``slow_decode`` fault
   arm); single-step spikes against a rank's own history are counted;
   a hung step surfaces as the watchdog's resilience instant.
3. **SLO engine** — rules breach only after ``for_s`` of sustained
   violation, ``/alerts`` answers 503 while firing and 200 after
   recovery, and ``policy="fail"`` raises into the training/serving
   step.
4. **bench gate** — tools/bench_gate passes the committed baselines,
   fails synthetically regressed numbers, honors per-key tolerance
   overrides, and refuses to compare mismatched contexts.
"""

import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import (
    FleetCollector,
    MetricsRegistry,
    SloEngine,
    SloRule,
    SloViolationError,
    StragglerDetector,
    TelemetryServer,
    Tracer,
    validate_slo_rule,
)
from deepspeed_tpu.telemetry.config import DeepSpeedTelemetryConfig
from tools import bench_gate

REQUIRED_KEYS = {"ph", "ts", "pid", "tid", "name"}
REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _clean_global_telemetry():
    yield
    telemetry.configure(False)
    telemetry.get_tracer().clear()
    telemetry.get_registry().reset()


def _get(url):
    """GET url -> (status, body-str). 4xx/5xx come back as statuses —
    /alerts answers 503 by design."""
    try:
        with urllib.request.urlopen(url, timeout=5) as resp:
            return resp.status, resp.read().decode("utf-8")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8")


def _worker(rank, role="worker"):
    """A standalone worker endpoint: own tracer + registry + HTTP server."""
    tracer = Tracer(enabled=True)
    tracer.set_process_info(rank=rank, role=role)
    reg = MetricsRegistry()
    srv = TelemetryServer(registry=reg, tracer=tracer).start()
    return tracer, reg, srv


# -- collector: scrape + merge ----------------------------------------------

def test_collector_merges_ranks_over_real_sockets():
    t0, r0, s0 = _worker(0)
    t1, r1, s1 = _worker(1)
    coll = FleetCollector()
    try:
        with t0.span("serving/decode_step", cat="serving"):
            pass
        with t1.span("serving/decode_step", cat="serving"):
            pass
        r0.gauge("Serving/tps", help="t").set(100.0)
        r1.gauge("Serving/tps", help="t").set(50.0)

        coll.add_endpoint(0, s0.url)
        coll.add_endpoint(1, s1.url)
        summary = coll.scrape()
        assert summary["up"] == [0, 1] and summary["down"] == []

        merged = coll.merged_trace()
        events = merged["traceEvents"]
        assert all(REQUIRED_KEYS <= set(e) for e in events)
        assert {e["pid"] for e in events} == {0, 1}
        lanes = {e["pid"]: e["args"]["name"] for e in events
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert lanes == {0: "worker rank0", 1: "worker rank1"}
        # timestamps were rebased onto the collector's clock, not left on
        # each worker's private perf_counter epoch
        spans = [e for e in events if e["ph"] == "X"]
        assert len(spans) == 2
        assert all(e["ts"] >= 0 for e in spans)
        json.dumps(merged)

        fm = coll.fleet_metrics()
        assert fm["Fleet/rank0/Serving/tps"] == 100.0
        assert fm["Fleet/rank1/Serving/tps"] == 50.0
        assert fm["Fleet/Serving/tps/min"] == 50.0
        assert fm["Fleet/Serving/tps/max"] == 100.0
        assert fm["Fleet/Serving/tps/mean"] == 75.0
        assert fm["Fleet/alive_ranks"] == 2.0
        assert fm["Fleet/ranks_total"] == 2.0

        prom = coll.render_prometheus()
        assert "Fleet_rank0_Serving_tps 100.0" in prom
        assert "Fleet_Serving_tps_mean 75.0" in prom

        # drain semantics: a second scrape must not duplicate spans
        coll.scrape()
        n_spans = sum(1 for e in coll.merged_trace()["traceEvents"]
                      if e["ph"] == "X")
        assert n_spans == 2
    finally:
        s0.stop()
        s1.stop()


def test_collector_dead_worker_partial_merge_and_gap_marker():
    t0, r0, s0 = _worker(0)
    t1, r1, s1 = _worker(1)
    coll = FleetCollector(timeout_s=1.0)
    try:
        r0.counter("Train/steps", help="t").inc(3)
        coll.add_endpoint(0, s0.url)
        coll.add_endpoint(1, s1.url)
        coll.scrape()
        assert coll.fleet_metrics()["Fleet/alive_ranks"] == 2.0

        s1.stop()                      # rank 1 dies between scrapes
        summary = coll.scrape()
        assert summary["up"] == [0] and summary["down"] == [1]

        fm = coll.fleet_metrics()
        assert fm["Fleet/rank0/up"] == 1.0
        assert fm["Fleet/rank1/up"] == 0.0
        assert fm["Fleet/alive_ranks"] == 1.0
        assert fm["Fleet/rank0/Train/steps"] == 3.0   # live rank still merged
        assert fm["Fleet/rank1/scrape_gaps_total"] >= 1.0

        gaps = [e for e in coll.merged_trace()["traceEvents"]
                if e["ph"] == "i" and e["name"] == "fleet/scrape_gap"]
        assert len(gaps) == 1 and gaps[0]["pid"] == 1

        # edge-triggered: staying down must not flood the timeline
        coll.scrape()
        gaps = [e for e in coll.merged_trace()["traceEvents"]
                if e["name"] == "fleet/scrape_gap"]
        assert len(gaps) == 1

        snap = coll.fleet_snapshot()
        assert snap["ranks"]["1"]["status"]["up"] is False
    finally:
        s0.stop()
        s1.stop()


def test_collector_attach_local_merges_without_sockets():
    tracer = Tracer(enabled=True)
    tracer.set_process_info(rank=-1, role="supervisor")
    reg = MetricsRegistry()
    reg.gauge("Supervisor/restarts", help="t").set(2.0)
    tracer.instant("worker/restart", cat="lifecycle")
    coll = FleetCollector()
    coll.attach_local(tracer, reg, rank=-1, role="supervisor")
    coll.scrape()
    events = coll.merged_trace()["traceEvents"]
    assert any(e["name"] == "worker/restart" and e["pid"] == -1
               for e in events)
    assert coll.fleet_metrics()["Fleet/rank-1/Supervisor/restarts"] == 2.0


# -- straggler detection ----------------------------------------------------

def test_straggler_detector_flags_slow_rank():
    det = StragglerDetector(min_samples=4, skew_threshold=2.0)
    for _ in range(8):
        det.observe(0, "serving/decode_step", 0.01)
        det.observe(1, "serving/decode_step", 0.05)
    events = det.update()
    g = det.gauges()
    assert g["straggler_rank"] == 1
    assert g["step_time_skew"] == pytest.approx(5.0, rel=0.01)
    assert any(e["type"] == "straggler" and e["rank"] == 1 for e in events)
    # edge-triggered: same straggler again emits no second event
    assert not any(e["type"] == "straggler" for e in det.update())


def test_straggler_detector_needs_min_samples_and_skew():
    det = StragglerDetector(min_samples=4, skew_threshold=2.0)
    det.observe(0, "serving/decode_step", 0.01)
    det.observe(1, "serving/decode_step", 0.05)
    det.update()
    assert det.gauges()["straggler_rank"] == -1    # too few samples
    det2 = StragglerDetector(min_samples=2, skew_threshold=2.0)
    for _ in range(4):
        det2.observe(0, "serving/decode_step", 0.010)
        det2.observe(1, "serving/decode_step", 0.012)  # 1.2x: healthy jitter
    det2.update()
    assert det2.gauges()["straggler_rank"] == -1


def test_straggler_detector_counts_spikes_against_own_history():
    det = StragglerDetector(min_samples=4, spike_factor=8.0, min_spike_s=0.001)
    for _ in range(8):
        det.observe(0, "train/fwd_bwd_opt_step", 0.01)
    det.observe(0, "train/fwd_bwd_opt_step", 0.5)   # 50x the rolling median
    events = det.update()
    assert det.gauges()["step_spikes_total"] >= 1.0
    assert any(e["type"] == "step_spike" and e["rank"] == 0 for e in events)


def test_straggler_detector_consumes_chrome_events():
    det = StragglerDetector(min_samples=2, skew_threshold=2.0)
    fast = [{"ph": "X", "name": "serving/decode_step", "ts": 0, "pid": 0,
             "tid": 0, "dur": 10000} for _ in range(4)]        # 10ms
    slow = [{"ph": "X", "name": "serving/decode_step", "ts": 0, "pid": 1,
             "tid": 0, "dur": 100000} for _ in range(4)]       # 100ms
    ignored = [{"ph": "i", "name": "serving/decode_step", "ts": 0, "pid": 1,
                "tid": 0},
               {"ph": "X", "name": "serving/prefill_batch", "ts": 0,
                "pid": 1, "tid": 0, "dur": 10 ** 9}]
    det.observe_events(0, fast)
    det.observe_events(1, slow + ignored)
    det.update()
    assert det.gauges()["straggler_rank"] == 1


def test_hung_step_emits_watchdog_resilience_instant():
    from deepspeed_tpu.runtime.resilience.errors import StepTimeoutError
    from deepspeed_tpu.runtime.resilience.watchdog import timed_call

    telemetry.configure(True)
    with pytest.raises(StepTimeoutError):
        timed_call(lambda: time.sleep(5), timeout_s=0.05, what="train step")
    inst = [e for e in telemetry.get_tracer().events()
            if e["name"] == "resilience/watchdog_timeout"]
    assert inst and inst[0]["args"]["what"] == "train step"


# -- SLO engine -------------------------------------------------------------

def test_slo_rule_validation():
    rule = validate_slo_rule({"metric": "Serving/ttft_p95_s", "max": 0.5,
                              "for_s": 30})
    assert rule == {"metric": "Serving/ttft_p95_s", "min": None, "max": 0.5,
                    "for_s": 30.0}
    with pytest.raises(ValueError, match="metric"):
        validate_slo_rule({"max": 1.0})
    with pytest.raises(ValueError, match="min.*max|max.*min"):
        validate_slo_rule({"metric": "x"})
    with pytest.raises(ValueError, match="unknown"):
        validate_slo_rule({"metric": "x", "max": 1, "typo": 2})
    with pytest.raises(ValueError, match="for_s"):
        validate_slo_rule({"metric": "x", "max": 1, "for_s": -1})
    with pytest.raises(ValueError, match="slo_policy"):
        DeepSpeedTelemetryConfig({"telemetry": {
            "enabled": True, "slo_policy": "explode"}})
    with pytest.raises(ValueError, match="slo"):
        DeepSpeedTelemetryConfig({"telemetry": {
            "enabled": True, "slo": [{"max": 1.0}]}})


def test_slo_for_s_hysteresis_with_fake_clock():
    now = [1000.0]
    eng = SloEngine([{"metric": "Serving/ttft_p95_s", "max": 0.5,
                      "for_s": 30.0}], clock=lambda: now[0])
    # breach must PERSIST for_s before firing
    assert eng.evaluate({"Serving/ttft_p95_s": 0.9}) == []
    now[0] += 10
    assert eng.evaluate({"Serving/ttft_p95_s": 0.9}) == []
    assert not eng.firing()
    now[0] += 25                       # 35s sustained > for_s
    fired = eng.evaluate({"Serving/ttft_p95_s": 0.9})
    assert len(fired) == 1 and fired[0].metric == "Serving/ttft_p95_s"
    assert eng.firing()
    # already-firing rules do not re-fire every evaluation
    now[0] += 5
    assert eng.evaluate({"Serving/ttft_p95_s": 0.9}) == []
    # recovery clears BOTH the firing state and the breach clock
    assert eng.evaluate({"Serving/ttft_p95_s": 0.1}) == []
    assert not eng.firing()
    now[0] += 1
    assert eng.evaluate({"Serving/ttft_p95_s": 0.9}) == []   # clock restarted


def test_slo_min_bound_and_alias_lookup():
    now = [0.0]
    eng = SloEngine([{"metric": "Serving/tokens_per_sec", "min": 100.0,
                      "for_s": 0.0}], clock=lambda: now[0])
    # floor rules read the fleet MIN rollup: the worst rank must hold SLO
    fired = eng.evaluate({"Fleet/Serving/tokens_per_sec/min": 40.0})
    assert len(fired) == 1 and fired[0].metric == "Serving/tokens_per_sec"
    ceil = SloEngine([{"metric": "Serving/ttft_p95_s", "max": 0.5,
                       "for_s": 0.0}], clock=lambda: now[0])
    fired = ceil.evaluate({"Serving/Snapshot/ttft_p95_s": 0.8})
    assert len(fired) == 1             # Serving/* falls back to Snapshot


def test_slo_alerts_endpoint_503_while_firing():
    now = [0.0]
    eng = SloEngine([{"metric": "Serving/ttft_p95_s", "max": 0.5,
                      "for_s": 0.0}], clock=lambda: now[0])
    srv = TelemetryServer().start()
    eng.attach(srv)
    try:
        status, body = _get(srv.url + "/alerts")
        doc = json.loads(body)
        assert status == 200 and doc["firing"] == 0 and doc["status"] == "ok"

        eng.evaluate({"Serving/ttft_p95_s": 0.9})
        status, body = _get(srv.url + "/alerts")
        doc = json.loads(body)
        assert status == 503 and doc["firing"] == 1
        assert doc["status"] == "alerting"
        rule = doc["rules"][0]
        assert rule["metric"] == "Serving/ttft_p95_s" and rule["firing"]
        assert rule["last_value"] == 0.9 and rule["fired_count"] == 1

        eng.evaluate({"Serving/ttft_p95_s": 0.1})     # recover
        status, body = _get(srv.url + "/alerts")
        assert status == 200 and json.loads(body)["firing"] == 0
    finally:
        srv.stop()


def test_slo_fail_policy_raises_warn_does_not():
    warn = SloEngine([{"metric": "m", "max": 1.0, "for_s": 0.0}],
                     policy="warn", clock=lambda: 0.0)
    assert len(warn.evaluate({"m": 2.0})) == 1        # no raise
    fail = SloEngine([{"metric": "m", "max": 1.0, "for_s": 0.0}],
                     policy="fail", clock=lambda: 0.0)
    with pytest.raises(SloViolationError) as ei:
        fail.evaluate({"m": 2.0})
    assert ei.value.metric == "m" and ei.value.value == 2.0


def test_slo_from_config_and_alert_instants():
    tracer = Tracer(enabled=True)
    reg = MetricsRegistry()
    cfg = DeepSpeedTelemetryConfig({"telemetry": {
        "enabled": True,
        "slo": [{"metric": "Serving/ttft_p95_s", "max": 0.5, "for_s": 0.0}],
        "slo_policy": "warn"}})
    eng = SloEngine.from_config(cfg, tracer=tracer, registry=reg)
    assert eng is not None and eng.policy == "warn"
    assert SloEngine.from_config(
        DeepSpeedTelemetryConfig({"telemetry": {"enabled": True}})) is None

    eng.evaluate({"Serving/ttft_p95_s": 0.9})
    inst = [e for e in tracer.events() if e["name"] == "slo/alert"]
    assert len(inst) == 1 and inst[0]["args"]["metric"] == "Serving/ttft_p95_s"
    assert reg.as_dict()["Slo/alerts_total"] == 1.0
    assert reg.as_dict()["Slo/firing"] == 1.0


# -- collector + SLO + detector together ------------------------------------

def test_collector_feeds_slo_from_fleet_rollups():
    t0, r0, s0 = _worker(0, role="serve")
    coll = FleetCollector(slo=SloEngine(
        [{"metric": "Serving/ttft_p95_s", "max": 0.5, "for_s": 0.0}],
        clock=lambda: 0.0))
    try:
        r0.gauge("Serving/Snapshot/ttft_p95_s", help="t").set(0.9)
        coll.add_endpoint(0, s0.url)
        coll.scrape()
        assert coll.slo.firing()
        assert coll.slo.firing()[0]["metric"] == "Serving/ttft_p95_s"
    finally:
        s0.stop()


# -- real engines: slow_decode straggler + transfer-free hot path -----------

def _serving_pair():
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2

    cfg = GPT2Config(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=32,
                     hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    _, params = init_gpt2(cfg, batch_size=2, seq_len=4, seed=0)
    return cfg, params


def _run_burst(injector=None):
    """One tiny serving run; returns the decode_step spans it produced."""
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    cfg, params = _serving_pair()
    eng = ServingEngine(
        params, cfg,
        ServingConfig(max_slots=3, max_queue=8, max_seq_len=32,
                      prompt_buckets=(4, 8)),
        injector=injector,
        telemetry_config=DeepSpeedTelemetryConfig(
            {"telemetry": {"enabled": True}}))
    try:
        rng = np.random.RandomState(3)
        futs = [eng.submit(rng.randint(0, 64, (4,)).tolist(), max_new_tokens=6)
                for _ in range(2)]
        eng.drain(max_steps=100)
        for f in futs:
            f.result(timeout=5)
    finally:
        eng.close()
    events = telemetry.get_tracer().to_chrome_trace(drain=True)["traceEvents"]
    return [e for e in events if e["name"] == "serving/decode_step"]


@pytest.mark.slow
def test_slow_decode_fault_arm_flags_straggler():
    from deepspeed_tpu.inference.serving import ServingFaultInjector

    _run_burst()                 # warmup: pay jit compilation up front
    fast = _run_burst()
    slow_injector = ServingFaultInjector()
    slow_injector.arm_serving("slow_decode", seconds=0.03)  # every step
    slow = _run_burst(injector=slow_injector)
    assert len(fast) >= 4 and len(slow) >= 4

    det = StragglerDetector(min_samples=3, skew_threshold=2.0)
    det.observe_events(0, fast)
    det.observe_events(1, slow)
    det.update()
    g = det.gauges()
    assert g["straggler_rank"] == 1
    assert g["step_time_skew"] >= 2.0


@pytest.mark.slow
def test_decode_stays_transfer_free_with_collector_and_slo_armed():
    """The acceptance claim: arming the fleet stack (SLO evaluation per
    step + a collector scraping the engine) adds zero host<->device
    traffic to steady-state decode and stays within the CompileSentinel
    budget (sentinel check() runs on every decode step)."""
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.profiling import transfer_free
    from deepspeed_tpu.profiling.config import DeepSpeedSentinelConfig

    cfg, params = _serving_pair()
    eng = ServingEngine(
        params, cfg,
        ServingConfig(max_slots=3, max_queue=8, max_seq_len=32,
                      prompt_buckets=(4, 8)),
        sentinel_config=DeepSpeedSentinelConfig(
            {"jax_sentinels": {"enabled": True}}),
        telemetry_config=DeepSpeedTelemetryConfig({"telemetry": {
            "enabled": True, "http_port": 0,
            "slo": [{"metric": "Serving/ttft_p95_s", "max": 100.0,
                     "for_s": 0.0}]}}),
        rank=0)
    coll = FleetCollector()
    try:
        assert eng.slo is not None
        coll.add_endpoint(0, eng.telemetry_server.url, role="serve")
        rng = np.random.RandomState(1)
        futs = [eng.submit(rng.randint(0, 64, (3,)).tolist(), max_new_tokens=8)
                for _ in range(2)]
        eng.step()             # admission
        eng.step()             # flush lane churn upload
        with transfer_free():
            for _ in range(4):
                stats = eng.step()
                assert stats["decoded"] == 2
        coll.scrape()          # scraping the live engine is off-hot-path
        assert coll.fleet_metrics()["Fleet/rank0/up"] == 1.0
        assert not eng.slo.firing()        # generous bound never fired
        eng.drain(max_steps=100)
        for f in futs:
            f.result(timeout=1)
    finally:
        eng.close()


# -- bench gate -------------------------------------------------------------

SERVING_BASE = os.path.join(REPO_ROOT, "SERVING_BENCH_CPU.json")


def _write(tmp_path, name, doc):
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


@pytest.fixture()
def train_base(tmp_path):
    """A driver-wrapped ``bench.py`` train line (the shape a driver stores:
    command, exit code and the parsed JSON line). No chip record is
    committed for the gate to read, so the test brings its own."""
    return _write(tmp_path, "train_base.json", {
        "n": 1, "cmd": "python bench.py", "rc": 0,
        "parsed": {
            "metric": "bert-large pretrain samples/sec/chip @ seq128 (tpu)",
            "value": 240.05, "unit": "samples/sec", "vs_baseline": 0.883,
            "tflops_per_chip": 63.15, "mfu": 0.3206,
            "device_kind": "TPU v5 lite", "n_devices": 1, "global_batch": 64,
            "step_ms": 266.61, "params": 336232258, "micro_batch": 64}})


def test_bench_gate_schema_accepts_committed_baselines():
    assert bench_gate.main(["--check-schema"]) == 0


def test_bench_gate_schema_rejects_partial_or_broken(tmp_path):
    with open(SERVING_BASE) as f:
        doc = json.load(f)
    doc["complete"] = False
    partial = _write(tmp_path, "partial.json", doc)
    assert bench_gate.main(["--check-schema", partial]) == 1
    doc = json.loads(open(SERVING_BASE).read())
    del doc["tokens_per_sec"]
    assert bench_gate.main(
        ["--check-schema", _write(tmp_path, "broken.json", doc)]) == 1


def test_bench_gate_self_compare_passes(train_base):
    assert bench_gate.main(["compare", SERVING_BASE, SERVING_BASE]) == 0
    assert bench_gate.main(["compare", train_base, train_base]) == 0


def test_bench_gate_fails_on_regression(tmp_path, capsys):
    with open(SERVING_BASE) as f:
        doc = json.load(f)
    doc["decode_tokens_per_sec"] *= 0.3      # below the -50% floor
    doc["ttft_p95_s"] *= 10.0                # past the +300% ceiling
    fresh = _write(tmp_path, "regressed.json", doc)
    assert bench_gate.main(["compare", fresh, SERVING_BASE]) == 1
    err = capsys.readouterr().err
    assert "REGRESSION decode_tokens_per_sec" in err
    assert "REGRESSION ttft_p95_s" in err


def test_bench_gate_tolerance_override_and_scale(tmp_path):
    with open(SERVING_BASE) as f:
        doc = json.load(f)
    doc["decode_tokens_per_sec"] *= 0.3
    fresh = _write(tmp_path, "slow.json", doc)
    assert bench_gate.main(["compare", fresh, SERVING_BASE]) == 1
    # loosening just that key clears the gate
    assert bench_gate.main(["compare", fresh, SERVING_BASE,
                            "--tolerance", "decode_tokens_per_sec=0.9"]) == 0
    # scaling every band does too
    assert bench_gate.main(["compare", fresh, SERVING_BASE,
                            "--tolerance-scale", "2.0"]) == 0


def test_bench_gate_skips_mismatched_context(tmp_path):
    with open(SERVING_BASE) as f:
        doc = json.load(f)
    doc["model"] = "some-other-model"
    doc["decode_tokens_per_sec"] *= 0.01     # would be a huge regression...
    fresh = _write(tmp_path, "other.json", doc)
    # ...but a different workload is not a regression signal: skip
    assert bench_gate.main(["compare", fresh, SERVING_BASE]) == 0
    assert bench_gate.main(["compare", fresh, SERVING_BASE,
                            "--require-comparable"]) == 2


def test_bench_gate_unwraps_train_driver_artifact(tmp_path, train_base):
    with open(train_base) as f:
        wrapper = json.load(f)
    kind, doc = bench_gate.load_artifact(train_base)
    assert kind == "train" and doc == wrapper["parsed"]
    wrapper["parsed"]["step_ms"] = wrapper["parsed"].get("step_ms", 100.0) * 10
    fresh = _write(tmp_path, "slow_train.json", wrapper)
    assert bench_gate.main(["compare", fresh, train_base]) == 1
