"""One run of a serving cell.

The load generator is this process's main thread; the engine's loop is the
program's own thread. Offered load is fixed by the traffic file: an open loop
sends on its schedule whatever the system does, a closed loop's clients each
send their next request when the previous one completes. Tokens are timed in
``stream_cb`` on a monotonic clock. After the window the plain reference
reads a sample of the finished requests once, prompt and served tokens
together, and ``correct`` compares how far each served token's logit lies
below the reference's best.
"""

import gc
import os
import queue
import sys
import time

import numpy as np

from benchmarks.harness import check as check_mod
from benchmarks.harness import device as device_mod
from benchmarks.harness import runtime, stats, tracing
from benchmarks.harness import traffic as traffic_mod
from benchmarks.refs import weights as weights_mod


class Flight:
    """One request in flight: what was asked and what came back."""

    __slots__ = ("request", "prompt", "due", "submitted", "times", "future",
                 "error")

    def __init__(self, request, prompt, due):
        self.request = request
        self.prompt = prompt
        self.due = due              # monotonic instant it was due
        self.submitted = None
        self.times = []             # monotonic instant of each token
        self.future = None
        self.error = None

    @property
    def finished(self):
        return len(self.times) >= self.request.output_len


def _bucket(length, buckets):
    return next(b for b in buckets if length <= b)


def warm_up(program, cfg, requests, seed):
    """Compile (or load from the cache) every program this cell's traffic
    uses and no others: one short request in each prompt bucket the
    schedule touches, which also runs the decode and install programs."""
    buckets = cfg["serving"]["prompt_buckets"]
    used = sorted({_bucket(r.prompt_len, buckets) for r in requests})
    rng = np.random.default_rng(seed % (2 ** 32))
    room = int(cfg["serving"]["max_seq_len"]) - 2    # two tokens are decoded
    futures = [program.submit(
        rng.integers(0, cfg["vocab_size"], min(b, room)).astype(np.int32),
        2, None) for b in used]
    for f in futures:
        f.result(timeout=1100.0)
    return used


class Generator:
    """Drives one schedule against the program and keeps every flight."""

    def __init__(self, program, cfg, traffic, seed, seconds):
        self.program = program
        self.cfg = cfg
        self.traffic = traffic
        self.seed = seed
        self.warm_s = float(traffic["warm_seconds"])
        self.seconds = float(seconds)
        self.flights = []
        self.refused = 0
        self.done = queue.Queue()
        horizon = self.warm_s + self.seconds
        self.schedule = traffic_mod.serve_schedule(traffic, horizon)

    def all_requests(self):
        if self.traffic["loop"] == "open":
            return self.schedule
        return [r for client in self.schedule for r in client]

    def _send(self, request, due):
        prompt = traffic_mod.prompt_ids(self.seed, request,
                                        self.cfg["vocab_size"])
        flight = Flight(request, prompt, due)
        self.flights.append(flight)
        want = request.output_len

        def on_token(_rid, _tok, flight=flight, want=want):
            flight.times.append(time.monotonic())
            if len(flight.times) == want:
                self.done.put(flight)

        flight.submitted = time.monotonic()
        try:
            flight.future = self.program.submit(prompt, want, on_token)
        except Exception as e:  # refused (queue full) or failed: it counts
            flight.error = e
            self.refused += 1
        return flight

    def run(self, marks):
        """``marks`` is a list of (seconds after schedule start, callable),
        called from this thread at those instants. Returns the instant the
        schedule started."""
        t0 = time.monotonic()
        end = self.warm_s + self.seconds
        marks = sorted(marks, key=lambda m: m[0])
        if self.traffic["loop"] == "open":
            events = sorted(
                [(r.due_s, 1, r) for r in self.schedule if r.due_s < end]
                + [(m[0], 0, m[1]) for m in marks], key=lambda e: e[:2])
            for at, kind, what in events:
                delay = t0 + at - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                if kind == 0:
                    what()
                else:
                    self._send(what, t0 + at)
        else:
            nxt = [0] * len(self.schedule)
            for c, client in enumerate(self.schedule):
                self._send(client[0], time.monotonic())
                nxt[c] = 1
            marks = list(marks)
            while True:
                now = time.monotonic() - t0
                while marks and marks[0][0] <= now:
                    marks.pop(0)[1]()
                if now >= end:
                    break
                until = min(end, marks[0][0] if marks else end)
                try:
                    flight = self.done.get(timeout=max(0.0, until - now))
                except queue.Empty:
                    continue
                c = flight.request.client
                if nxt[c] < len(self.schedule[c]):
                    self._send(self.schedule[c][nxt[c]], time.monotonic())
                    nxt[c] += 1
        delay = t0 + end - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        return t0


def _is_count(v):
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _counted(before, after):
    """What the program's counters counted between two snapshots."""
    return {k: v - before.get(k, 0) for k, v in after.items()
            if _is_count(v) and _is_count(before.get(k, 0))}


def pick_sample(flights, window, traffic, seed):
    """A sample, drawn from the seed, of the requests finished inside the
    window, with the longest in it."""
    ws, we = window
    done = [f for f in flights if f.finished and f.error is None
            and ws <= f.times[f.request.output_len - 1] < we]
    if not done:
        return []
    n = int(traffic["check_requests"])
    longest = max(done, key=lambda f: (f.request.output_len,
                                       f.request.prompt_len))
    rest = [f for f in done if f is not longest]
    rng = np.random.default_rng(seed % (2 ** 32))
    rng.shuffle(rest)
    return [longest] + rest[:n - 1]


def reference_gaps(cell, sample, seed, precision="f32"):
    """Widest gap by which a served token's logit lies below the
    reference's best over the sample (and the same for the tokens a
    ``precision`` forward pass would put first). One padded batch, so one
    compiled reference program per cell."""
    import jax
    import jax.numpy as jnp

    cfg, traffic = cell.config, cell.traffic
    ref = runtime.load_reference(cfg)
    n = int(traffic["check_requests"])
    T = int(cfg["serving"]["max_seq_len"])
    P = int(traffic["output_tokens"]["max"])
    ids = np.zeros((n, T), np.int32)
    positions = np.zeros((n, P), np.int32)
    tokens = np.zeros((n, P), np.int32)
    valid = np.zeros((n, P), bool)
    for i, f in enumerate(sample):
        served = f.future.tokens[:f.request.output_len]
        plen, olen = f.request.prompt_len, len(served)
        ids[i, :plen] = f.prompt
        ids[i, plen:plen + olen] = served
        positions[i, :olen] = plen - 1 + np.arange(olen)
        tokens[i, :olen] = served
        valid[i, :olen] = True
    dtype = jnp.dtype(cfg["serving"]["param_dtype"])
    weights = weights_mod.make_weights(ref.weight_shapes(cfg), seed, dtype)
    fn = jax.jit(ref.served_token_gaps,
                 static_argnames=("n_heads", "precision"))
    gap, control = fn(weights, jnp.asarray(ids), jnp.asarray(positions),
                      jnp.asarray(tokens), jnp.asarray(valid),
                      n_heads=cfg["num_attention_heads"], precision=precision)
    return (float(jnp.max(gap)), float(jnp.max(control)), int(valid.sum()))


def run(cell, seed, seconds, trace, t_start, devices, out=sys.stderr,
        precision="f32"):
    import jax.numpy as jnp

    runtime.CompileCounter.install()
    cfg, traffic = cell.config, cell.traffic
    ref = runtime.load_reference(cfg)
    adapter = runtime.load_adapter(cfg)
    dtype = jnp.dtype(cfg["serving"]["param_dtype"])
    program = adapter.Program(cfg, weights_mod.make_weights(
        ref.weight_shapes(cfg), seed, dtype))
    gen = Generator(program, cfg, traffic, seed, seconds)
    program.start()
    state = {}
    try:
        used = warm_up(program, cfg, gen.all_requests(), seed)
        print(f"[setup] warmed prompt buckets {used}", file=out, flush=True)
        capture = tracing.Capture(os.path.join(cell.root, ".bench_trace")) \
            if trace else None

        def window_start():
            gc.collect()
            gc.freeze()
            state["compiled"] = runtime.CompileCounter.read()
            state["counters"] = program.counters()
            state["setup_s"] = time.perf_counter() - t_start
            state["ws"] = time.monotonic()

        marks = [(gen.warm_s, window_start)]
        if capture is not None:
            marks.append((gen.warm_s + max(
                0.0, seconds - float(traffic["trace_seconds"])),
                capture.start))
        t0 = gen.run(marks)
        ws = t0 + gen.warm_s
        we = ws + seconds
        counters_after = program.counters()
        compiled = runtime.CompileCounter.read() - state["compiled"]
        if capture is not None:
            capture.stop()
    finally:
        program.stop()
    gc.unfreeze()
    peak = device_mod.memory_peak_bytes(devices)

    flights = gen.flights
    wrong = 0
    for f in flights:
        if f.error is not None or f.future is None:
            continue
        if f.future.done():
            try:
                toks = f.future.result(timeout=0)
            except Exception:
                wrong += 1
                continue
            if (len(toks) != f.request.output_len or not all(
                    0 <= t < cfg["vocab_size"] for t in toks)):
                wrong += 1
    failed = gen.refused + wrong
    tokens_in = sum(1 for f in flights for t in f.times if ws <= t < we)
    gaps_ms = [1e3 * g for g in stats.token_gaps(
        [f.times for f in flights], ws, we)]
    ttft_ms = [1e3 * (f.times[0] - f.due) for f in flights
               if f.times and ws <= f.times[0] < we]
    lag_ms = [1e3 * (f.submitted - f.due) for f in flights
              if ws <= f.due < we]
    finished_in = sum(1 for f in flights if f.finished
                      and ws <= f.times[f.request.output_len - 1] < we)
    print(f"[window] set-up {state['setup_s']:.2f}s; peak {peak} bytes; "
          f"{tokens_in} tokens, {finished_in} requests finished, "
          f"{len(gaps_ms)} gaps, {compiled} programs compiled inside it; "
          f"{len(flights)} sent, {gen.refused} refused, {wrong} wrong",
          file=out, flush=True)

    sample = pick_sample(flights, (ws, we), traffic, seed)
    program.close()
    del program
    gc.collect()
    t_ref = time.perf_counter()
    cmp = check_mod.Comparison()
    limits = cell.limits
    if sample:
        gap, control_gap, n_tok = reference_gaps(cell, sample, seed, precision)
        cmp.add("served_logit_gap_max", gap, limits["served_logit_gap"],
                f"{n_tok} served tokens of {len(sample)} requests")
        state["control_gap"] = control_gap
    else:
        cmp.add("served_logit_gap_max", None, limits["served_logit_gap"],
                "no request finished inside the window")
    cmp.add("failed_requests", failed, 0)
    cmp.add("compiled_in_window", compiled, 0)
    cmp.print(out)
    print(f"[check] reference took {time.perf_counter() - t_ref:.1f}s",
          file=out, flush=True)

    device = device_mod.device_info(devices)
    device["memory_peak_bytes"] = peak
    values = {
        "serve_tokens_per_s": tokens_in / seconds,
        "serve_itl_tail_ms": stats.tail_mean(gaps_ms, 0.05),
        "setup_s": state["setup_s"],
    }
    extra = {"control_gap": state.get("control_gap")}
    mid = ws + seconds / 2.0
    first_half = [1e3 * (f.times[0] - f.due) for f in flights
                  if f.times and ws <= f.due < mid]
    second_half = [1e3 * (f.times[0] - f.due) for f in flights
                   if f.times and mid <= f.due < we]
    diag = {
        "ttft_mean_ms_first_half": float(np.mean(first_half))
        if first_half else None,
        "ttft_mean_ms_second_half": float(np.mean(second_half))
        if second_half else None,
        "in_flight_at_window_start": sum(
            1 for f in flights if f.due < ws
            and (not f.finished or f.times[f.request.output_len - 1] >= ws)),
        "in_flight_at_window_end": sum(
            1 for f in flights if f.due < we
            and (not f.finished or f.times[f.request.output_len - 1] >= we)),
        "finished_in_window": finished_in,
        "itl_p50_ms": stats.percentile(gaps_ms, 50),
        "itl_tail_ms": stats.tail_mean(gaps_ms, 0.05),
    }
    if not trace:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end()
                   if values.get(m["name"]) is not None}
        line = runtime.result_line(correct=cmp.correct,
                                   attempted=len(flights), failed=failed,
                                   metrics=metrics, device=device)
        line["check"] = dict(cmp.as_dict(), **extra)
        line["diag"] = diag
        return line

    summary = tracing.TraceSummary(capture.events)
    counters = _counted(state["counters"], counters_after)
    live_kv = [f.request.prompt_len + f.request.output_len / 2.0
               for f in flights if f.times and f.times[0] < we
               and (not f.finished or f.times[-1] >= ws)]
    host = {"gaps_ms": gaps_ms, "ttft_ms": ttft_ms, "lag_ms": lag_ms,
            "tokens_in_window": tokens_in, "window_s": seconds,
            "max_slots": cfg["serving"]["max_slots"],
            "mean_live_kv_tokens_per_lane":
                float(np.mean(live_kv)) if live_kv else 0.0}
    run_data = runtime.RunData(cell, host, counters, summary, device["kind"])
    device["busy_s"] = summary.busy_s
    device["window_s"] = summary.window_s
    line = runtime.result_line(
        correct=cmp.correct, attempted=len(flights), failed=failed,
        metrics=runtime.read_per_layer(cell, run_data), device=device,
        breakdown={"device_ops": summary.top_ops(10),
                   "idle_gaps": summary.idle_gaps(10)})
    line["check"] = dict(cmp.as_dict(), **extra)
    return line
