"""Unified telemetry (deepspeed_tpu/telemetry/).

Three contracts under test:

1. **Trace validity** — spans/instants render as Chrome-trace-event JSON
   (required ``ph``/``ts``/``pid``/``tid``/``name`` keys, nested spans
   contained in their parents, bounded ring buffer with a dropped count).
2. **One registry for train + serve** — counters/gauges/histograms round-
   trip through the Prometheus text exposition, the MonitorBridge rides
   the monitor fan-out, and the HTTP endpoint serves all four routes
   over a real socket.
3. **Provably free when disabled** — a disabled tracer hands every call
   site the same NULL_SPAN singleton and records nothing; with tracing
   ARMED the serving steady-state decode loop still passes
   ``transfer_free()`` (span bookkeeping adds no host<->device traffic).
"""

import json
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import (
    MetricsRegistry,
    MonitorBridge,
    TelemetryServer,
    Tracer,
    prom_name,
)
from deepspeed_tpu.telemetry.config import DeepSpeedTelemetryConfig
from deepspeed_tpu.telemetry.trace import NULL_SPAN

REQUIRED_KEYS = {"ph", "ts", "pid", "tid", "name"}


@pytest.fixture(autouse=True)
def _clean_global_telemetry():
    """Tests arm the process-global tracer/registry; always disarm and
    empty them so telemetry never leaks into the rest of the suite."""
    yield
    telemetry.configure(False)
    telemetry.get_tracer().clear()
    telemetry.get_registry().reset()


def _get(url):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return resp.status, resp.read().decode("utf-8"), resp.headers


# -- tracer -----------------------------------------------------------------

def test_trace_events_are_valid_chrome_trace():
    t = Tracer(enabled=True)
    with t.span("outer", cat="train", args={"step": 1}):
        with t.span("inner", cat="train"):
            pass
    t.instant("lifecycle_evt", args={"why": "test"})
    doc = t.to_chrome_trace()
    assert set(doc) >= {"traceEvents", "displayTimeUnit"}
    events = doc["traceEvents"]
    assert [e["name"] for e in events] == ["inner", "outer", "lifecycle_evt"]
    for ev in events:
        assert REQUIRED_KEYS <= set(ev)
    json.dumps(doc)  # must be serializable as-is

    complete = {e["name"]: e for e in events if e["ph"] == "X"}
    assert set(complete) == {"inner", "outer"}
    assert "dur" in complete["inner"] and "dur" in complete["outer"]
    inst = [e for e in events if e["ph"] == "i"]
    assert len(inst) == 1 and inst[0]["s"] == "t"


def test_spans_nest_within_parents():
    t = Tracer(enabled=True)
    with t.span("outer"):
        with t.span("inner"):
            pass
    inner, outer = t.events()
    i0, i1 = inner["ts"], inner["ts"] + inner["dur"]
    o0, o1 = outer["ts"], outer["ts"] + outer["dur"]
    assert o0 <= i0 and i1 <= o1


def test_ring_buffer_caps_and_counts_drops():
    t = Tracer(enabled=True, max_events=8)
    for i in range(20):
        t.instant(f"e{i}")
    assert len(t) == 8
    assert t.dropped == 12
    names = [e["name"] for e in t.events()]
    assert names == [f"e{i}" for i in range(12, 20)]   # newest survive
    assert t.to_chrome_trace()["metadata"]["dropped_events"] == 12


def test_events_drain_empties_buffer():
    t = Tracer(enabled=True)
    t.instant("a")
    assert len(t.events(drain=True)) == 1
    assert len(t) == 0 and t.events() == []


def test_disabled_tracer_records_nothing_and_allocates_nothing():
    t = Tracer(enabled=False)
    spans = [t.span("x", args={"big": list(range(100))}) for _ in range(5)]
    assert all(s is NULL_SPAN for s in spans)   # one shared singleton
    with t.span("y"):
        pass
    t.instant("z")
    assert len(t) == 0 and t.events() == []


def test_configure_rearms_in_place_keeping_newest():
    t = Tracer(enabled=True, max_events=16)
    for i in range(10):
        t.instant(f"e{i}")
    t.configure(True, max_events=4)
    assert t.max_events == 4
    assert [e["name"] for e in t.events()] == ["e6", "e7", "e8", "e9"]


def test_write_produces_loadable_file(tmpdir):
    t = Tracer(enabled=True)
    with t.span("s"):
        pass
    path = t.write(str(tmpdir.join("trace.json")))
    with open(path) as f:
        doc = json.load(f)
    assert doc["traceEvents"][0]["name"] == "s"


# -- registry ---------------------------------------------------------------

def test_prom_name_sanitization():
    assert prom_name("Train/Samples/train_loss") == "Train_Samples_train_loss"
    assert prom_name("Serving/ttft_s") == "Serving_ttft_s"
    assert prom_name("7weird metric!") == "_7weird_metric_"


def test_registry_prometheus_round_trip():
    r = MetricsRegistry()
    r.counter("Train/steps", help="optimizer steps").inc()
    r.counter("Train/steps").inc(2)
    r.gauge("Serving/active").set(3)
    h = r.histogram("Serving/ttft_s", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    text = r.render_prometheus()
    assert "# HELP Train_steps optimizer steps" in text
    assert "# TYPE Train_steps counter" in text
    assert "Train_steps 3.0" in text
    assert "Serving_active 3.0" in text
    assert 'Serving_ttft_s_bucket{le="0.1"} 1' in text
    assert 'Serving_ttft_s_bucket{le="1.0"} 2' in text
    assert 'Serving_ttft_s_bucket{le="+Inf"} 3' in text
    assert "Serving_ttft_s_sum 2.55" in text
    assert "Serving_ttft_s_count 3" in text


def test_registry_type_mismatch_raises():
    r = MetricsRegistry()
    r.counter("x")
    with pytest.raises(ValueError, match="already registered as counter"):
        r.gauge("x")


def test_counter_rejects_decrease():
    with pytest.raises(ValueError):
        MetricsRegistry().counter("c").inc(-1)


def test_pull_gauges_render_floats_dicts_and_skip_errors():
    r = MetricsRegistry()
    r.gauge_fn("Serving/occupancy", lambda: {"in_use": 2, "free": 6, "skip": "str"})
    r.gauge_fn("Supervisor/restarts", lambda: 1)
    r.gauge_fn("broken", lambda: 1 / 0)
    r.gauge_fn("absent", lambda: None)
    text = r.render_prometheus()
    assert "Serving_occupancy_in_use 2.0" in text
    assert "Serving_occupancy_free 6.0" in text
    assert "Supervisor_restarts 1.0" in text
    assert "broken" not in text and "absent" not in text and "skip" not in text


def test_monitor_bridge_buffers_then_flushes():
    r = MetricsRegistry()
    b = MonitorBridge(r, auto_flush_every=100)
    b.record("Train/Samples/train_loss", np.float32(2.5), 1)
    b.record("Serving/ttft_s", 0.2, 1)
    assert r.as_dict() == {}            # deferred: nothing applied yet
    b.flush()
    d = r.as_dict()
    assert d["Train/Samples/train_loss"] == 2.5
    assert d["Serving/ttft_s"]["count"] == 1          # histogram-routed
    assert d["Train/Samples/train_loss/samples_total"] == 1.0


def test_monitor_bridge_auto_flush_and_rank_gating():
    r = MetricsRegistry()
    b = MonitorBridge(r, auto_flush_every=3)
    for i in range(3):
        b.record("Train/x", i, i)
    assert r.as_dict()["Train/x"] == 2.0              # hit the bound

    r2 = MetricsRegistry()
    b2 = MonitorBridge(r2, rank=1)
    b2.record("Train/x", 1.0, 0)
    b2.close()
    assert r2.as_dict() == {}           # non-zero ranks record nothing


# -- HTTP endpoint ----------------------------------------------------------

def test_endpoint_serves_all_routes_over_a_real_socket():
    tracer = Tracer(enabled=True)
    with tracer.span("serving/decode_step", cat="serving"):
        pass
    reg = MetricsRegistry()
    reg.gauge("Serving/active").set(1)
    srv = TelemetryServer(registry=reg, tracer=tracer).start()
    try:
        status, body, headers = _get(srv.url + "/metrics")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain; version=0.0.4")
        assert "Serving_active 1.0" in body

        srv.add_health_provider("loop", lambda: {"healthy": True, "steps": 7})
        status, body, _ = _get(srv.url + "/healthz")
        assert status == 200
        doc = json.loads(body)
        assert doc["status"] == "ok" and doc["loop"]["steps"] == 7

        srv.add_snapshot_provider("pool", lambda: {"in_use": 0})
        srv.add_snapshot_provider("broken", lambda: 1 / 0)
        status, body, _ = _get(srv.url + "/snapshot")
        doc = json.loads(body)
        assert status == 200 and doc["pool"] == {"in_use": 0}
        assert "error" in doc["broken"]   # one broken provider, inline

        status, body, _ = _get(srv.url + "/trace?drain=0")
        assert status == 200
        assert json.loads(body)["traceEvents"][0]["name"] == "serving/decode_step"
        _get(srv.url + "/trace")          # default drains
        status, body, _ = _get(srv.url + "/trace?drain=0")
        assert json.loads(body)["traceEvents"] == []

        with pytest.raises(urllib.error.HTTPError) as e:
            _get(srv.url + "/nope")
        assert e.value.code == 404
    finally:
        srv.stop()


def test_endpoint_unhealthy_provider_returns_503():
    srv = TelemetryServer().start()
    srv.add_health_provider("worker", lambda: False)
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(srv.url + "/healthz")
        assert e.value.code == 503
        assert json.loads(e.value.read())["status"] == "unhealthy"
    finally:
        srv.stop()


# -- config block -----------------------------------------------------------

def test_telemetry_config_defaults_and_validation():
    c = DeepSpeedTelemetryConfig({})
    assert not c.configured and not c.enabled and c.http_port is None

    c = DeepSpeedTelemetryConfig({"telemetry": {
        "enabled": True, "trace_max_events": 128, "http_port": 0,
        "trace_file": "/tmp/t.json"}})
    assert c.configured and c.enabled
    assert c.trace_max_events == 128 and c.http_port == 0

    for bad in ({"enabled": "yes"}, {"trace_max_events": 0},
                {"trace_max_events": True}, {"http_port": 70000},
                {"http_port": True}, {"trace_file": 7}):
        with pytest.raises(Exception):
            DeepSpeedTelemetryConfig({"telemetry": bad})


def test_ds_config_carries_telemetry_block():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    cfg = DeepSpeedConfig({"train_batch_size": 8,
                           "telemetry": {"enabled": True}})
    assert cfg.telemetry_config.enabled and cfg.telemetry_config.configured
    assert not DeepSpeedConfig({"train_batch_size": 8}).telemetry_config.configured


def test_absent_block_does_not_disarm_an_armed_process():
    telemetry.configure(True)
    telemetry.configure_from_config(DeepSpeedTelemetryConfig({}))
    assert telemetry.get_tracer().enabled
    telemetry.configure_from_config(
        DeepSpeedTelemetryConfig({"telemetry": {"enabled": False}}))
    assert not telemetry.get_tracer().enabled


# -- CompileSentinel recompile instants -------------------------------------

def test_compile_sentinel_emits_recompile_instant():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.profiling import CompileSentinel

    telemetry.configure(True)
    fn = jax.jit(lambda x: x + 1)
    sent = CompileSentinel(fn, budget=4, name="toy")
    fn(jnp.zeros((2,)))
    sent.check()
    fn(jnp.zeros((3,)))      # shape change: one recompile
    sent.check()
    sent.check()             # no NEW compile: no second instant
    evts = [e for e in telemetry.get_tracer().events()
            if e["name"] == "jax/recompile"]
    assert len(evts) == 2
    assert evts[-1]["args"] == {"name": "toy", "compiles": 2, "budget": 4}


# -- WorkerSupervisor attachment --------------------------------------------

def test_supervisor_restart_instants_and_health():
    from deepspeed_tpu.launcher.supervisor import WorkerSupervisor

    telemetry.configure(True)
    sup = WorkerSupervisor([sys.executable, "-c", "import sys; sys.exit(3)"],
                           max_restarts=1, backoff_s=0.0)
    rc = sup.run()
    assert rc == 3 and sup.restarts == 1
    names = [e["name"] for e in telemetry.get_tracer().events()]
    assert names.count("worker/exit") == 2
    assert names.count("worker/restart") == 1
    assert telemetry.get_registry().as_dict()["Supervisor/restarts_total"] == 1.0
    assert sup._snapshot()["exit_history"] == [
        {"class": "crash", "returncode": 3}] * 2
    assert sup._worker_health()["healthy"] is False   # child exited


def test_supervisor_serves_healthz_while_child_runs():
    from deepspeed_tpu.launcher.supervisor import WorkerSupervisor

    sup = WorkerSupervisor(
        [sys.executable, "-c", "import time; time.sleep(30)"],
        http_port=0, term_grace_s=1.0)
    sup._spawn()
    srv = sup._start_telemetry_server()
    try:
        status, body, _ = _get(srv.url + "/healthz")
        doc = json.loads(body)
        assert status == 200 and doc["worker"]["healthy"] is True
        status, body, _ = _get(srv.url + "/snapshot")
        assert json.loads(body)["supervisor"]["child_alive"] is True
        status, body, _ = _get(srv.url + "/metrics")
        assert "Supervisor_restarts 0.0" in body
    finally:
        srv.stop()
        sup._stop_child()


# -- CsvMonitor crash-safety satellite --------------------------------------

def test_csv_monitor_bounded_auto_flush(tmpdir):
    from deepspeed_tpu.monitor.csv_monitor import CsvMonitor

    m = CsvMonitor(str(tmpdir), "job", auto_flush_every=3)
    for i in range(3):
        m.record("Train/x", float(i), i)
    path = tmpdir.join("job", "Train_x.csv")
    assert path.check()          # hit the bound: flushed without flush()
    assert len(path.read().splitlines()) == 4   # header + 3 rows
    m.close()


@pytest.mark.slow
def test_csv_monitor_flushes_on_interpreter_exit(tmpdir):
    import subprocess

    code = (
        "from deepspeed_tpu.monitor.csv_monitor import CsvMonitor\n"
        f"m = CsvMonitor({str(tmpdir)!r}, 'job')\n"
        "m.record('Train/x', 1.0, 0)\n"
        # NO flush()/close(): the atexit hook must write the row
    )
    subprocess.run([sys.executable, "-c", code], check=True)
    assert tmpdir.join("job", "Train_x.csv").check()


# -- engines under telemetry ------------------------------------------------

def _serving_pair():
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2

    cfg = GPT2Config(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=32,
                     hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    _, params = init_gpt2(cfg, batch_size=2, seq_len=4, seed=0)
    return cfg, params


@pytest.mark.slow
def test_serving_spans_carry_request_ids_and_metrics_export(tmpdir):
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    cfg, params = _serving_pair()
    trace_file = str(tmpdir.join("serving_trace.json"))
    eng = ServingEngine(
        params, cfg,
        ServingConfig(max_slots=3, max_queue=8, max_seq_len=32,
                      prompt_buckets=(4, 8)),
        telemetry_config=DeepSpeedTelemetryConfig({"telemetry": {
            "enabled": True, "http_port": 0, "trace_file": trace_file}}))
    try:
        rng = np.random.RandomState(0)
        futs = [eng.submit(rng.randint(0, 64, (4,)).tolist(), max_new_tokens=4)
                for _ in range(2)]
        eng.drain(max_steps=50)
        for f in futs:
            f.result(timeout=1)

        events = telemetry.get_tracer().events()
        by_name = {}
        for e in events:
            by_name.setdefault(e["name"], []).append(e)
        assert "serving/admission" in by_name
        assert "serving/prefill_batch" in by_name
        assert "serving/decode_step" in by_name
        assert "serving/retire" in by_name
        prefill_ids = by_name["serving/prefill_batch"][0]["args"]["request_ids"]
        decode_ids = by_name["serving/decode_step"][0]["args"]["request_ids"]
        assert prefill_ids and decode_ids
        retire_ids = {e["args"]["request_id"] for e in by_name["serving/retire"]}
        assert len(retire_ids) == 2

        # serving snapshot gauges are live on /metrics via export_to
        status, body, _ = _get(eng.telemetry_server.url + "/metrics")
        assert status == 200
        assert "Serving_Snapshot_requests_completed 2.0" in body
        status, body, _ = _get(eng.telemetry_server.url + "/snapshot")
        doc = json.loads(body)
        assert doc["serving"]["requests_completed"] == 2
        assert "in_use" in doc["kv_pool"]
    finally:
        eng.close()
    with open(trace_file) as f:          # close() wrote the trace
        doc = json.load(f)
    assert any(e["name"] == "serving/decode_step" for e in doc["traceEvents"])


@pytest.mark.slow
def test_steady_state_decode_transfer_free_with_tracing_armed():
    """The zero-hot-path-cost claim with telemetry ON: span bookkeeping is
    perf_counter + tuple append, so the armed decode loop must still pass
    the transfer guard."""
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.profiling import transfer_free

    cfg, params = _serving_pair()
    eng = ServingEngine(
        params, cfg,
        ServingConfig(max_slots=3, max_queue=8, max_seq_len=32,
                      prompt_buckets=(4, 8)),
        telemetry_config=DeepSpeedTelemetryConfig(
            {"telemetry": {"enabled": True}}))
    try:
        rng = np.random.RandomState(1)
        futs = [eng.submit(rng.randint(0, 64, (3,)).tolist(), max_new_tokens=8)
                for _ in range(2)]
        eng.step()             # admission
        eng.step()             # flush lane churn upload
        assert eng.tracer.enabled
        with transfer_free():
            for _ in range(4):
                stats = eng.step()
                assert stats["decoded"] == 2
        eng.drain(max_steps=100)
        for f in futs:
            f.result(timeout=1)
    finally:
        eng.close()
    assert any(e["name"] == "serving/decode_step"
               for e in telemetry.get_tracer().events())


@pytest.mark.slow
def test_train_engine_spans_and_checkpoint_instant(tmpdir):
    from tests.unit.simple_model import make_simple_engine, random_dataloader

    engine = make_simple_engine(tmpdir, {
        "train_batch_size": 8,
        "steps_per_print": 100,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "telemetry": {"enabled": True},
    }, hidden_dim=8)
    loader = random_dataloader(engine, total_samples=16, hidden_dim=8)
    it = iter(loader)
    for _ in range(2):
        engine.train_batch(data_iter=it)
    engine.save_checkpoint(str(tmpdir.join("ckpt")))

    names = [e["name"] for e in telemetry.get_tracer().events()]
    for expected in ("train/batch_fetch", "train/fwd_bwd_opt_step",
                     "train/loss_sync", "train/checkpoint_save",
                     "checkpoint/commit"):
        assert expected in names, (expected, sorted(set(names)))

    # the monitor fan-out includes the registry bridge: flushed training
    # scalars appear on the shared registry under their slash tags
    engine.monitor.flush()
    d = telemetry.get_registry().as_dict()
    assert "Train/Samples/train_loss" in d
    assert "Train/Samples/lr" in d


@pytest.mark.slow
def test_disabled_telemetry_records_nothing_through_engines(tmpdir):
    from tests.unit.simple_model import make_simple_engine, random_dataloader

    engine = make_simple_engine(tmpdir, {
        "train_batch_size": 8,
        "steps_per_print": 100,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    }, hidden_dim=8)
    assert engine._tracer.enabled is False
    loader = random_dataloader(engine, total_samples=8, hidden_dim=8)
    engine.train_batch(data_iter=iter(loader))
    assert len(telemetry.get_tracer()) == 0
