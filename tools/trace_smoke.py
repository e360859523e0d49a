"""End-to-end telemetry smoke: train + serve + lifecycle in ONE trace.

Runs a short training loop and a serving burst on the CPU backend with the
``telemetry`` config block enabled, exercises a real supervisor restart
(lifecycle instant events), then asserts the whole pipeline held together:

- the merged Chrome trace JSON is valid (required ``ph``/``ts``/``pid``/
  ``tid``/``name`` keys) and contains train-step spans, serving
  prefill/decode spans carrying request ids, and at least one lifecycle
  instant event;
- ``/metrics`` (scraped over a real socket from the serving engine's
  endpoint) serves Prometheus text with BOTH ``Train_*`` and ``Serving_*``
  families — one registry, one naming scheme.

Then the FLEET leg: two real supervised serving workers (subprocesses
under ``WorkerSupervisor``, fixed telemetry ports), one of which crashes
once before binding (exercising a real restart) and runs with a
``slow_decode`` fault arm (the deterministic straggler). A
``FleetCollector`` scrapes both, and the smoke asserts the merged trace
has both rank lanes + the restart instant, ``Fleet/straggler_rank``
fingers rank 1, and a deliberately-unmeetable TTFT SLO flips ``/alerts``
to 503.

Run it as ``make trace-smoke``; exits nonzero on any failed check. The
single-process trace lands in ``trace_smoke.json`` and the merged fleet
trace in ``trace_fleet_smoke.json`` (load either in Perfetto — see
docs/observability.md for how to read them).
"""

import argparse
import json
import os
import socket
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

# CPU backend (same contract as tests/): this smoke starts one process per
# rank, and only one process can hold a chip.
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

REQUIRED_KEYS = {"ph", "ts", "pid", "tid", "name"}

_failures = []


def check(ok, what):
    tag = "ok" if ok else "FAIL"
    print(f"[trace-smoke] {tag:4s} {what}")
    if not ok:
        _failures.append(what)
    return ok


def run_train_loop(steps=4):
    import jax.numpy as jnp

    import deepspeed_tpu

    def model(params, x, y):
        pred = x @ params["w"]
        return jnp.mean((pred - y) ** 2)

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        model_parameters={"w": jnp.ones((8, 4))},
        config_params={
            "train_batch_size": 4,
            "train_micro_batch_size_per_gpu": 4,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "steps_per_print": 100,
            "telemetry": {"enabled": True},
        },
    )
    rng = np.random.RandomState(0)

    def batches():
        while True:
            yield (rng.randn(4, 8).astype(np.float32),
                   rng.randn(4, 4).astype(np.float32))

    it = batches()
    for _ in range(steps):
        engine.train_batch(data_iter=it)
    engine.monitor.flush()   # push Train/* scalars through to the registry
    return engine


def run_serving_burst(n_requests=4):
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2
    from deepspeed_tpu.telemetry import DeepSpeedTelemetryConfig

    cfg = GPT2Config(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=32,
                     hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    _, params = init_gpt2(cfg, batch_size=2, seq_len=4, seed=0)
    eng = ServingEngine(
        params, cfg,
        ServingConfig(max_slots=3, max_queue=8, max_seq_len=32,
                      prompt_buckets=(4, 8)),
        telemetry_config=DeepSpeedTelemetryConfig(
            {"telemetry": {"enabled": True, "http_port": 0}}))
    rng = np.random.RandomState(7)
    futs = [eng.submit(rng.randint(0, 64, (4,)).tolist(), max_new_tokens=4)
            for _ in range(n_requests)]
    eng.drain(max_steps=100)
    for f in futs:
        f.result(timeout=5)
    return eng


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _get(url, timeout=5):
    """GET url; returns (status, body-bytes). 4xx/5xx are statuses, not
    exceptions — /alerts deliberately answers 503."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def worker_main(args):
    """Fleet-smoke worker subprocess: a tiny serving engine whose
    telemetry endpoint the collector scrapes. Rank comes from $RANK, the
    HTTP port from $DSTPU_TELEMETRY_PORT (both set by WorkerSupervisor)."""
    # crash-once leg: die BEFORE importing jax so the supervisor's restart
    # (and its worker/restart instant) happens fast and exactly once
    if args.crash_marker and not os.path.exists(args.crash_marker):
        with open(args.crash_marker, "w") as f:
            f.write(str(os.getpid()))
        sys.exit(7)

    from deepspeed_tpu.inference.serving import (ServingConfig, ServingEngine,
                                                 ServingFaultInjector)
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2
    from deepspeed_tpu.telemetry import DeepSpeedTelemetryConfig

    cfg = GPT2Config(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=32,
                     hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    _, params = init_gpt2(cfg, batch_size=2, seq_len=4, seed=0)
    injector = None
    if args.slow_decode > 0:
        # at_step=None -> every decode step: this rank IS the straggler
        injector = ServingFaultInjector()
        injector.arm_serving("slow_decode", seconds=args.slow_decode)
    eng = ServingEngine(
        params, cfg,
        ServingConfig(max_slots=3, max_queue=8, max_seq_len=32,
                      prompt_buckets=(4, 8)),
        injector=injector,
        telemetry_config=DeepSpeedTelemetryConfig(
            {"telemetry": {"enabled": True}}))
    rng = np.random.RandomState(int(os.environ.get("RANK", "0")))
    deadline = time.monotonic() + args.max_seconds
    while not os.path.exists(args.stop_file) and time.monotonic() < deadline:
        futs = [eng.submit(rng.randint(0, 64, (4,)).tolist(), max_new_tokens=4)
                for _ in range(2)]
        eng.drain(max_steps=200)
        for f in futs:
            f.result(timeout=30)
        time.sleep(0.02)
    eng.close()
    sys.exit(0)


def run_fleet_smoke(out_path):
    """Two supervised worker subprocesses + a FleetCollector: merged
    multi-rank trace, restart instant, straggler detection, SLO alert."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.launcher.supervisor import WorkerSupervisor
    from deepspeed_tpu.telemetry import FleetCollector, SloEngine

    tmpdir = tempfile.mkdtemp(prefix="dstpu_fleet_smoke_")
    stop_file = os.path.join(tmpdir, "stop")
    crash_marker = os.path.join(tmpdir, "crashed_once")
    ports = (_free_port(), _free_port())

    sups, threads, rcs = [], [], [None, None]
    for rank in (0, 1):
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["RANK"] = str(rank)
        env["JAX_PLATFORMS"] = "cpu"
        # workers run the script by path: make the package importable
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        cmd = [sys.executable, "-u", os.path.abspath(__file__), "--worker",
               "--stop_file", stop_file, "--max_seconds", "150"]
        if rank == 1:
            # rank 1 crashes once pre-bind (restart instant) and then runs
            # with a slow_decode arm: the deterministic straggler
            cmd += ["--crash_marker", crash_marker, "--slow_decode", "0.05"]
        sup = WorkerSupervisor(cmd, env=env, max_restarts=2, backoff_s=0.0,
                               worker_port=ports[rank])
        sups.append(sup)
        threads.append(threading.Thread(
            target=lambda i=rank, s=sup: rcs.__setitem__(i, s.run()),
            daemon=True))

    # the supervisors live in THIS process: arm the global tracer so their
    # worker/start + worker/restart instants land in the merged timeline
    telemetry.configure(True)
    telemetry.get_tracer().set_process_info(rank=-1, role="supervisor")

    slo = SloEngine(
        # unmeetable on purpose: any completed request breaches instantly
        [{"metric": "Serving/ttft_p95_s", "max": 1e-9, "for_s": 0.0}],
        policy="warn", tracer=telemetry.get_tracer(),
        registry=telemetry.get_registry())
    coll = FleetCollector(timeout_s=5.0, slo=slo)
    for rank in (0, 1):
        coll.add_endpoint(rank, f"http://127.0.0.1:{ports[rank]}", role="serve")
    coll.attach_local(telemetry.get_tracer(), telemetry.get_registry())
    for sup in sups:
        sup.export_gauges(telemetry.get_registry())
    server = coll.serve(port=0, scrape_on_request=False)

    for t in threads:
        t.start()

    # poll until both ranks answer and the straggler is flagged
    deadline = time.monotonic() + 180
    both_up = straggler = False
    while time.monotonic() < deadline:
        coll.scrape()
        fm = coll.fleet_metrics()
        both_up = (fm.get("Fleet/rank0/up") == 1.0
                   and fm.get("Fleet/rank1/up") == 1.0)
        straggler = both_up and fm.get("Fleet/straggler_rank") == 1.0
        if straggler:
            break
        time.sleep(0.5)
    check(both_up, "fleet: both worker /metrics endpoints scraped")
    check(straggler,
          "fleet: slow_decode straggler flagged (Fleet/straggler_rank == 1)")
    check(sups[1].restarts >= 1, "fleet: rank 1 crashed once and was restarted")

    # collector's own endpoints over a real socket
    status, body = _get(server.url + "/fleet/metrics")
    text = body.decode("utf-8")
    check(status == 200 and "Fleet_straggler_rank" in text
          and "Fleet_rank0_up" in text,
          "fleet: /fleet/metrics serves rank-labelled + rollup families")
    status, body = _get(server.url + "/fleet/snapshot")
    snap = json.loads(body)
    check(status == 200 and set(map(int, snap.get("ranks", {}))) >= {0, 1},
          "fleet: /fleet/snapshot covers both ranks")
    status, body = _get(server.url + "/alerts")
    doc = json.loads(body)
    check(status == 503 and doc.get("firing"),
          f"fleet: TTFT SLO breach flips /alerts to 503 (got {status})")

    # clean shutdown: stop-file protocol, then join the supervisors
    with open(stop_file, "w") as f:
        f.write("stop")
    for t in threads:
        t.join(timeout=120)
    check(all(not t.is_alive() for t in threads), "fleet: supervisors exited")
    check(rcs[0] == 0 and rcs[1] == 0,
          f"fleet: both workers exited clean (rcs={rcs})")

    # final scrape drains the supervisor-side tracer (restart instants)
    coll.scrape()
    merged = coll.merged_trace()
    events = merged["traceEvents"]
    check(all(REQUIRED_KEYS <= set(e) for e in events),
          "fleet: every merged event has ph/ts/pid/tid/name")
    pids = {e["pid"] for e in events}
    check({0, 1} <= pids,
          f"fleet: merged trace has both rank lanes (pids={sorted(pids)})")
    meta_pids = {e["pid"] for e in events
                 if e["ph"] == "M" and e["name"] == "process_name"}
    check({0, 1} <= meta_pids,
          "fleet: per-rank process_name metadata names the lanes")
    check(any(e["name"] == "serving/decode_step" for e in events),
          "fleet: decode spans from the workers merged in")
    check(any(e["ph"] == "i" and e["name"] == "worker/restart"
              for e in events),
          "fleet: supervisor restart instant in the merged timeline")
    check(any(e["ph"] == "i" and e["name"] == "fleet/straggler"
              for e in events),
          "fleet: straggler instant in the merged timeline")
    check(any(e["ph"] == "i" and e["name"] == "slo/alert" for e in events),
          "fleet: SLO alert instant in the merged timeline")

    path = coll.write_merged_trace(out_path)
    with open(path) as f:
        json.load(f)          # artifact round-trips as valid JSON
    coll.stop()     # also shuts the /fleet/* server down
    print(f"[trace-smoke] fleet trace written to {path}")


def run_supervised_restart():
    """A real worker crash + restart through WorkerSupervisor — the
    lifecycle instant events the trace must carry."""
    from deepspeed_tpu.launcher.supervisor import WorkerSupervisor

    sup = WorkerSupervisor([sys.executable, "-c", "import sys; sys.exit(7)"],
                           max_restarts=1, backoff_s=0.0)
    sup.run()
    return sup


def main():
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--out", default="trace_smoke.json",
                        help="merged Chrome trace output path")
    parser.add_argument("--fleet-out", default="trace_fleet_smoke.json",
                        help="merged multi-rank fleet trace output path")
    parser.add_argument("--worker", action="store_true",
                        help="internal: run as a fleet-smoke worker process")
    parser.add_argument("--stop_file", default=None,
                        help="worker mode: exit cleanly once this file exists")
    parser.add_argument("--crash_marker", default=None,
                        help="worker mode: crash once, creating this marker")
    parser.add_argument("--slow_decode", type=float, default=0.0,
                        help="worker mode: slow_decode fault arm seconds")
    parser.add_argument("--max_seconds", type=float, default=150.0,
                        help="worker mode: hard wall-clock exit deadline")
    args = parser.parse_args()

    if args.worker:
        if not args.stop_file:
            parser.error("--worker needs --stop_file")
        worker_main(args)

    from deepspeed_tpu import telemetry

    run_train_loop()
    eng = run_serving_burst()
    sup = run_supervised_restart()
    check(sup.restarts == 1, "supervisor performed one restart")

    # one registry: /metrics must expose BOTH families over a real socket
    url = eng.telemetry_server.url
    with urllib.request.urlopen(url + "/metrics", timeout=5) as resp:
        metrics = resp.read().decode("utf-8")
        ctype = resp.headers["Content-Type"]
    check(ctype.startswith("text/plain; version=0.0.4"),
          f"/metrics content type is Prometheus text ({ctype})")
    check("Train_Samples_train_loss" in metrics, "/metrics has Train_* family")
    check(any(line.startswith("Serving_") for line in metrics.splitlines()),
          "/metrics has Serving_* family")
    with urllib.request.urlopen(url + "/healthz", timeout=5) as resp:
        check(json.loads(resp.read())["status"] == "ok", "/healthz reports ok")

    # one tracer: write + re-load the merged trace, then validate it
    path = telemetry.get_tracer().write(args.out)
    eng.close()
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    check(len(events) > 0, f"trace has events ({len(events)})")
    check(all(REQUIRED_KEYS <= set(e) for e in events),
          "every event has ph/ts/pid/tid/name")

    names = {e["name"] for e in events}
    check("train/batch_fetch" in names, "train batch-fetch spans present")
    check("train/fwd_bwd_opt_step" in names, "train step spans present")
    prefill = [e for e in events if e["name"] == "serving/prefill_batch"]
    decode = [e for e in events if e["name"] == "serving/decode_step"]
    check(bool(prefill) and prefill[0].get("args", {}).get("request_ids"),
          "serving prefill spans carry request ids")
    check(bool(decode) and decode[0].get("args", {}).get("request_ids"),
          "serving decode spans carry request ids")
    instants = [e for e in events if e["ph"] == "i"]
    check(any(e["name"] == "worker/restart" for e in instants),
          "lifecycle instant events present (worker/restart)")

    run_fleet_smoke(args.fleet_out)

    if _failures:
        print(f"[trace-smoke] {len(_failures)} check(s) FAILED")
        return 1
    print(f"[trace-smoke] all checks passed — trace written to {path} "
          f"(load it in Perfetto / chrome://tracing)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
