"""The serving loop's own account of its time (``ServingMetrics``), the one
span system on the profiler's clock, and the names inside the programs.

A scripted clock stands in for ``time`` in the engine and the scheduler: it
moves only when the test (or a wrapped phase of the loop) moves it, and it
counts how often it is read. So every counter has one exact expected value,
and the price of the accounting (clock reads per iteration) is pinned.
"""

import glob
import threading
import types

import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
from deepspeed_tpu.inference.serving import engine as engine_mod
from deepspeed_tpu.inference.serving.families import gpt2 as gpt2_mod
from deepspeed_tpu.inference.serving.families import (
    kimi_linear as kimi_mod,
    slot_state as slot_state_mod,
)
from deepspeed_tpu.inference.serving import metrics as metrics_mod
from deepspeed_tpu.inference.serving import scheduler as scheduler_mod
from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2
from deepspeed_tpu.telemetry import trace as trace_mod
from deepspeed_tpu.telemetry.trace import NULL_SPAN

# clock reads of the loop before it kept these accounts (PR 23's engine):
# top of step, t0, step_s and the emit stamp in a decode iteration; t0,
# prefill_s and the install stamp more in one that also admits a batch
PARENT_READS_DECODE = 4
PARENT_READS_ADMIT_AND_DECODE = 7
# PR 25's accounts: the end of the iteration, and the admission's end
ACCOUNT_READS_DECODE = 1
ACCOUNT_READS_ADMIT_AND_DECODE = 2
# the loop's account of its waits for the device (PR 37): ``launched`` reads
# the clock once and ``read_back`` twice, so three reads a decode call, three
# a prefill call and two more for the settle behind a batch's installs
WAIT_READS_DECODE = 3
WAIT_READS_ADMIT_AND_DECODE = 3 + 3 + 2

PREFILL_S, DECODE_S, EMIT_S = 0.2, 0.05, 0.001


class Clock:
    """``time`` for the modules under test: scripted, and counting."""

    def __init__(self):
        self.now = 1000.0
        self.reads = 0

    def monotonic(self):
        self.reads += 1
        return self.now

    def advance(self, seconds):
        self.now += seconds

    def sleep(self, seconds):
        self.advance(seconds)


@pytest.fixture(scope="module")
def model():
    cfg = GPT2Config(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    _, params = init_gpt2(cfg, batch_size=2, seq_len=4, seed=0)
    yield cfg, params
    jax.clear_caches()


@pytest.fixture(autouse=True)
def _disarm():
    yield
    telemetry.configure(False)
    telemetry.get_tracer().clear()
    telemetry.get_tracer().set_annotation_factory(None)


@pytest.fixture
def clock(monkeypatch):
    """The scripted clock, with the device phases made to take time: a
    prefill 0.2 s, a decode step 0.05 s, and 1 ms of the caller's
    ``stream_cb`` per token."""
    c = Clock()
    monkeypatch.setattr(engine_mod, "time", c)
    monkeypatch.setattr(gpt2_mod, "time", c)
    monkeypatch.setattr(scheduler_mod, "time", c)
    prefill, decode = gpt2_mod._prefill_batch_jit, gpt2_mod._decode_step_jit

    def slow_prefill(*a, **k):
        c.advance(PREFILL_S)
        return prefill(*a, **k)

    def slow_decode(*a, **k):
        c.advance(DECODE_S)
        return decode(*a, **k)

    monkeypatch.setattr(gpt2_mod, "_prefill_batch_jit", slow_prefill)
    monkeypatch.setattr(gpt2_mod, "_decode_step_jit", slow_decode)
    return c


def _engine(model, **overrides):
    cfg, params = model
    kw = dict(max_slots=3, max_queue=8, max_seq_len=32, prompt_buckets=(4, 8))
    kw.update(overrides)
    return ServingEngine(params, cfg, ServingConfig(**kw))


def _step(eng, clock):
    """One iteration; returns (activity, clock reads it made)."""
    before = clock.reads
    stats = eng.step()
    return stats, clock.reads - before


def test_counters_on_a_scripted_clock(model, clock):
    eng = _engine(model)
    emitted = []

    def on_token(rid, _tok):
        clock.advance(EMIT_S)
        emitted.append(rid)

    # A waits 2.5 s in the queue, then is admitted and decoded alone
    a = eng.submit([1, 2, 3], max_new_tokens=6, stream_cb=on_token)
    clock.advance(2.5)
    stats, reads_admit = _step(eng, clock)
    assert stats["admitted"] == 1 and stats["decoded"] == 1
    m = eng.metrics
    assert m.queue_waits == 1
    assert m.queue_wait_s == pytest.approx(2.5)
    assert m.prefill_positions_run == 3 * 4        # all rows x bucket 4
    assert m.prefill_tokens == 3
    assert reads_admit == (PARENT_READS_ADMIT_AND_DECODE
                           + ACCOUNT_READS_ADMIT_AND_DECODE
                           + WAIT_READS_ADMIT_AND_DECODE)

    stats, reads_decode = _step(eng, clock)
    assert stats == {"admitted": 0, "decoded": 1, "retired": 0,
                     "prefill_chunks": 0}
    assert reads_decode == (PARENT_READS_DECODE + ACCOUNT_READS_DECODE
                            + WAIT_READS_DECODE)
    assert m.stalled_gaps == 0 and m.token_gaps == 2

    # B arrives while A decodes: its prefill (bucket 8) stalls A's next gap
    b = eng.submit([5, 6, 7, 8, 9], max_new_tokens=3, stream_cb=on_token)
    clock.advance(0.5)
    stats, _ = _step(eng, clock)
    assert stats["admitted"] == 1 and stats["decoded"] == 2
    assert m.queue_wait_s == pytest.approx(3.0) and m.queue_waits == 2
    assert m.prefill_positions_run == 3 * 4 + 3 * 8
    # A's gap over the prefill is the one stalled gap; B's first gap (its
    # own prefill lies before its first token) is not
    assert m.stalled_gaps == 1
    # A's stamps: emit stamp of the last step, then this step's: B's wait
    # in the queue, B's prefill, the decode step; the 1 ms callbacks of
    # A's last token and of B's first token lie between them too
    assert m.stalled_gap_s == pytest.approx(
        0.5 + PREFILL_S + DECODE_S + 2 * EMIT_S)

    eng.drain(max_steps=50)
    assert a.result(timeout=1) and b.result(timeout=1)
    assert len(emitted) == 9
    assert m.token_gaps == len(emitted) - 2        # tokens - requests
    assert m.stalled_gaps == 1
    assert m.prefill_calls == 2
    assert m.prefill_time_s == pytest.approx(2 * PREFILL_S)
    assert m.decode_time_s == pytest.approx(m.decode_steps * DECODE_S)
    # behind each decode step's read-back: the callbacks of its tokens
    assert m.decode_host_s == pytest.approx((len(emitted) - 2) * EMIT_S)
    # an admission beyond its prefill: the first token's callback
    assert m.admit_time_s == pytest.approx(2 * (PREFILL_S + EMIT_S))
    assert m.loop_busy_s == pytest.approx(
        m.decode_time_s + m.prefill_time_s + len(emitted) * EMIT_S)
    assert m.loop_busy_s >= m.decode_time_s + m.prefill_time_s

    # an idle iteration counts nothing and reads the clock once
    busy = m.loop_busy_s
    stats, reads_idle = _step(eng, clock)
    assert not any(stats.values()) and reads_idle == 1
    assert m.loop_busy_s == busy

    snap = m.snapshot()
    for key in ("loop_busy_s", "decode_host_s", "admit_time_s",
                "queue_wait_s", "queue_waits", "token_gaps", "token_gap_s",
                "stalled_gaps", "stalled_gap_s", "prefill_positions_run",
                "decode_time_s", "prefill_time_s"):
        assert isinstance(snap[key], (int, float)), key
        assert snap[key] == getattr(m, key)


def test_chunked_prefill_counts_its_chunks(model, clock):
    """Rows x chunk per chunk; every chunk stalls the lanes that decode."""
    eng = _engine(model, prefill_chunk_tokens=4, prompt_buckets=(4, 8, 16))
    a = eng.submit([1, 2, 3], max_new_tokens=8)
    eng.step()
    b = eng.submit(list(range(1, 11)), max_new_tokens=2)   # three chunks
    clock.advance(1.0)
    m = eng.metrics
    eng.step()                  # reserves the chunk lane, decodes A
    for _ in range(3):
        eng.step()              # one chunk each, and A's decode step
    assert m.prefill_calls == 2 and m.queue_waits == 2
    # until its first chunk was dispatched, one decode step later
    assert m.queue_wait_s == pytest.approx(1.0 + DECODE_S)
    assert m.prefill_positions_run == 3 * 4 + 3 * 4      # 1 row x 4, thrice
    assert m.prefill_tokens == 3 + 10
    assert m.stalled_gaps == 3                           # A, behind each
    assert m.admit_time_s >= m.prefill_time_s
    eng.drain(max_steps=50)
    assert a.result(timeout=1) and b.result(timeout=1)
    assert m.loop_busy_s >= m.decode_time_s + m.prefill_time_s


# -- the loop's waits for the device (``launched`` / ``read_back``) ---------

DISPATCH_S = {"decode": 0.002, "prefill": 0.003}
READ_S = {"decode": 0.02, "prefill": 0.2}
SETTLE_S = 0.004


@pytest.fixture
def waits(monkeypatch):
    """The scripted clock with the loop's waits scripted apart: calling a
    program takes ``DISPATCH_S`` of its kind (the device is asynchronous: a
    call returns when the program is queued), a blocking read takes what
    ``script.read_s`` holds for it (``READ_S`` of the kind of the program
    dispatched last unless a test says otherwise) and the settle
    ``SETTLE_S``. ``script.reads`` lists the reads made, in order."""
    c = Clock()
    for mod in (engine_mod, gpt2_mod, scheduler_mod, slot_state_mod,
                kimi_mod):
        monkeypatch.setattr(mod, "time", c)
    script = types.SimpleNamespace(clock=c, read_s=[], reads=[], kind=None)

    def program(fn, kind):
        def call(*a, **k):
            c.advance(DISPATCH_S[kind])
            script.kind = kind
            return fn(*a, **k)
        return call

    def device_get(tree):
        seconds = script.read_s.pop(0) if script.read_s \
            else READ_S[script.kind]
        script.reads.append(seconds)
        c.advance(seconds)
        return jax.device_get(tree)

    def block_until_ready(tree):
        c.advance(SETTLE_S)
        return jax.block_until_ready(tree)

    # the engine's ``jax``: the two calls that wait, scripted
    monkeypatch.setattr(engine_mod, "jax", types.SimpleNamespace(
        device_get=device_get, block_until_ready=block_until_ready,
        profiler=jax.profiler))
    monkeypatch.setattr(gpt2_mod, "_prefill_batch_jit",
                        program(gpt2_mod._prefill_batch_jit, "prefill"))
    monkeypatch.setattr(gpt2_mod, "_decode_step_jit",
                        program(gpt2_mod._decode_step_jit, "decode"))
    family = kimi_mod.KimiLinearFamily
    monkeypatch.setattr(family, "prefill_program", staticmethod(
        program(family.prefill_program, "prefill")))
    monkeypatch.setattr(family, "decode_program", staticmethod(
        program(family.decode_program, "decode")))
    return script


def _bucket(seconds):
    """The key suffix of the bucket a plain read of ``seconds`` is in."""
    for edge in metrics_mod.READ_EDGES_US:
        if seconds * 1e6 <= edge:
            return str(edge)
    return "inf"


def _behind_prefill(m):
    """``(reads, seconds)`` of the decode reads that stood behind a prefill
    program, as an operator derives them: the totals less the buckets."""
    snap = m.snapshot()
    return (snap["decode_reads"] - sum(snap[f"decode_reads_le_us_{b}"]
                                       for b in metrics_mod.READ_BUCKETS),
            snap["decode_read_wait_s"] - sum(snap[f"decode_read_s_le_us_{b}"]
                                             for b in metrics_mod.READ_BUCKETS))


def test_a_synchronous_steps_waits_on_a_scripted_clock(model, waits):
    """GPT-2's step: every read is of the program dispatched last, so each
    opens a dry spell that the next launch of either kind closes."""
    eng = _engine(model)
    clock, m = waits.clock, eng.metrics

    def on_token(_rid, _tok):
        clock.advance(EMIT_S)

    a = eng.submit([1, 2, 3], max_new_tokens=6, stream_cb=on_token)
    eng.step()                      # admits A and decodes its second token
    # the first tokens' read, then the settle behind the lane installs
    assert m.prefill_reads == 2
    assert m.prefill_read_wait_s == pytest.approx(READ_S["prefill"] + SETTLE_S)
    # first-token read to the decode launch: A's callback and the settle
    assert m.dry_spells_after_prefill == 1
    assert m.dry_after_prefill_s == pytest.approx(EMIT_S + SETTLE_S)
    assert m.decode_dispatch_s == pytest.approx(DISPATCH_S["decode"])
    assert m.decode_reads == 1 and _behind_prefill(m)[0] == 0
    assert m.dry_after_decode_s == 0.0  # the decode read's spell is open

    clock.advance(0.5)              # the caller pauses: the device is dry
    eng.step()
    # decode read to the next launch: one callback and the caller's pause
    assert m.dry_after_decode_s == pytest.approx(EMIT_S + 0.5)

    waits.read_s.append(0.150)      # one read comes back late
    eng.step()
    eng.drain(max_steps=20)
    assert a.result(timeout=1)
    steps = m.decode_steps
    assert steps == 5 and m.decode_reads == steps
    assert m.decode_dispatch_s == pytest.approx(steps * DISPATCH_S["decode"])
    assert m.decode_read_wait_s == pytest.approx(
        (steps - 1) * READ_S["decode"] + 0.150)
    # dispatch and read are the whole call here: the family's tail (a copy,
    # a tolist) takes no scripted time
    assert m.decode_dispatch_s + m.decode_read_wait_s <= m.decode_time_s \
        + 1e-9
    assert m.decode_time_s == pytest.approx(
        m.decode_dispatch_s + m.decode_read_wait_s)
    assert m.prefill_time_s == pytest.approx(
        DISPATCH_S["prefill"] + READ_S["prefill"])
    snap = m.snapshot()
    assert _bucket(READ_S["decode"]) == "32000" and _bucket(0.150) == "256000"
    assert snap["decode_reads_le_us_32000"] == steps - 1
    assert snap["decode_read_s_le_us_32000"] == pytest.approx(
        (steps - 1) * READ_S["decode"])
    assert snap["decode_reads_le_us_256000"] == 1
    assert snap["decode_read_s_le_us_256000"] == pytest.approx(0.150)
    assert sum(snap[f"decode_reads_le_us_{b}"]
               for b in metrics_mod.READ_BUCKETS) == steps
    # the last spell ends where the loop runs out of work, not at the next
    # request: an idle loop's device is dry for want of requests
    eng.step()
    dry = m.dry_after_decode_s
    assert dry == pytest.approx(0.5 + steps * EMIT_S)
    clock.advance(30.0)
    eng.step()
    b = eng.submit([4, 5], max_new_tokens=2)
    eng.drain(max_steps=10)
    assert b.result(timeout=1)
    assert m.dry_after_decode_s == pytest.approx(dry)
    for key in ("decode_dispatch_s", "decode_reads", "decode_read_wait_s",
                "prefill_reads", "prefill_read_wait_s", "dry_after_decode_s",
                "dry_after_prefill_s", "dry_spells_after_prefill"):
        assert m.snapshot()[key] == getattr(m, key), key


def test_a_chunk_that_is_not_read_back_stands_before_the_next_decode_read(
        model, waits):
    """GPT-2's chunked prefill reads only its last chunk back: the decode
    read behind a mid chunk holds that chunk's device time, and stays out
    of the buckets."""
    eng = _engine(model, prefill_chunk_tokens=4, prompt_buckets=(4, 8, 16))
    m = eng.metrics
    a = eng.submit([1, 2, 3], max_new_tokens=8)
    eng.step()
    b = eng.submit(list(range(1, 11)), max_new_tokens=2)   # three chunks
    eng.step()                  # reserves the chunk lane, decodes A
    assert _behind_prefill(m)[0] == 0
    waits.read_s.extend([0.07, 0.07])
    eng.step()                  # chunk 1, not read; A's read stands behind it
    eng.step()                  # chunk 2, likewise
    assert _behind_prefill(m) == (2, pytest.approx(0.14))
    eng.step()                  # chunk 3 is read back: nothing stands before
    assert _behind_prefill(m)[0] == 2
    eng.drain(max_steps=50)
    assert a.result(timeout=1) and b.result(timeout=1)
    snap = m.snapshot()
    assert sum(snap[f"decode_reads_le_us_{e}"]
               for e in metrics_mod.READ_BUCKETS) == m.decode_reads - 2
    assert snap["decode_reads_le_us_128000"] == 0


def test_the_prefix_caches_copy_is_no_prefill_read(model, waits):
    """With the prefix cache on, an inserted prompt's K/V is copied to the
    host through the helper (its wait, its span), but it is no program's
    output: the prefill reads' mean stays what the loop stood behind a
    prefill program, whatever the cache's hit rate."""
    eng = _engine(model, prefix_cache_mb=4.0)
    m = eng.metrics
    waits.read_s.extend([READ_S["prefill"], 0.05])   # first tokens, the copy
    a = eng.submit([1, 2, 3], max_new_tokens=2)
    eng.step()
    assert waits.reads[:2] == [READ_S["prefill"], 0.05]
    assert eng.prefix_cache.stats()["entries"] == 1
    # the first tokens' read and the settle, as with the cache off
    assert m.prefill_reads == 2
    assert m.prefill_read_wait_s == pytest.approx(READ_S["prefill"] + SETTLE_S)
    # the copy falls in the spell the first tokens' read began
    assert m.dry_spells_after_prefill == 1
    assert m.dry_after_prefill_s == pytest.approx(0.05 + SETTLE_S)
    eng.drain(max_steps=10)
    assert a.result(timeout=1)


def test_a_step_in_flights_waits_on_a_scripted_clock(waits):
    """A family that keeps a decode step in flight (Kimi-Linear, tiny): the
    first call reads nothing, a read is of the step BEFORE the one just
    dispatched, a prefill call that ends no prompt is not read back and its
    device time is inside the read of the step dispatched behind it, and a
    first-token read opens a dry spell that the next launch closes."""
    from tests.unit import test_kimi_linear as tiny

    _, params, mcfg = tiny.make()
    eng = tiny.engine(params, mcfg)
    clock, m = waits.clock, eng.metrics
    chunk = tiny.CHUNK

    def on_token(_rid, _tok):
        clock.advance(EMIT_S)

    a = eng.submit(list(range(1, 11)), max_new_tokens=12, stream_cb=on_token)
    eng.step()                      # admits A: a slot, no program yet
    assert m.dry_spells_after_prefill == m.prefill_reads == 0
    eng.step()                      # A's one chunk ends its prompt; step 1
    assert m.prefill_reads == 1
    assert m.prefill_read_wait_s == pytest.approx(READ_S["prefill"])
    # the first token's read to the launch of step 1: A's callback
    assert m.dry_spells_after_prefill == 1
    assert m.dry_after_prefill_s == pytest.approx(EMIT_S)
    # step 1 is dispatched and nothing is read: its dispatch ends the call
    assert m.decode_steps == 1 and m.decode_reads == 0
    assert m.decode_dispatch_s == pytest.approx(DISPATCH_S["decode"])
    assert m.decode_time_s == pytest.approx(DISPATCH_S["decode"])

    # B's prompt takes three chunks: two are not read back
    b = eng.submit(list(range(1, 2 * chunk + 9)), max_new_tokens=3)
    eng.step()                      # admits B; step 2, reads step 1
    assert m.decode_reads == 1
    eng.step()                      # chunk 1 (no read); step 3, reads step 2
    assert m.prefill_reads == 1 and m.prefill_chunks == 2
    # step 2 was queued before the chunk: its read is a plain one
    assert m.decode_reads == 2 and _behind_prefill(m)[0] == 0
    waits.read_s.append(0.26)
    eng.step()                      # chunk 2 (no read); step 4, reads step 3
    # step 3 was queued behind chunk 1, which nothing had read back
    assert _behind_prefill(m) == (1, pytest.approx(0.26))
    eng.step()                      # chunk 3 ends B's prompt: read back
    assert m.prefill_reads == 2
    # everything queued before that read has run: step 4's read is plain
    assert m.decode_reads == 4 and _behind_prefill(m)[0] == 1
    # no read but a first token's left the device dry: a step was in flight
    assert m.dry_spells_after_prefill == 2
    assert m.dry_after_decode_s == 0.0
    eng.drain(max_steps=40)
    assert a.result(timeout=1) and b.result(timeout=1)
    snap = m.snapshot()
    plain = m.decode_reads - 1
    assert snap["decode_reads_le_us_32000"] == plain
    assert snap["decode_reads_le_us_512000"] == 0      # the 0.26 s stayed out
    assert m.decode_read_wait_s == pytest.approx(
        plain * READ_S["decode"] + 0.26)
    assert m.decode_dispatch_s == pytest.approx(
        m.decode_steps * DISPATCH_S["decode"])
    # the call beyond its dispatch and its read: the family's host tail
    assert m.decode_dispatch_s + m.decode_read_wait_s <= m.decode_time_s \
        + 1e-9


def test_accounting_grows_no_container(model, clock):
    """Nothing the counters keep grows with the tokens served."""
    eng = _engine(model)

    def sizes():
        return {k: len(v) for k, v in vars(eng.metrics).items()
                if hasattr(v, "__len__")}

    eng.submit([1, 2, 3], max_new_tokens=4)
    eng.drain(max_steps=50)
    before = sizes()
    eng.submit([3, 2, 1], max_new_tokens=24)
    eng.drain(max_steps=50)
    after = sizes()
    after["_ttft_window"] -= 1      # one sample per REQUEST, bounded
    assert after == before
    for key in ("loop_busy_s", "decode_host_s", "admit_time_s",
                "queue_wait_s", "token_gap_s", "stalled_gap_s",
                "decode_dispatch_s", "decode_read_wait_s",
                "prefill_read_wait_s", "dry_after_decode_s",
                "dry_after_prefill_s"):
        assert type(getattr(eng.metrics, key)) is float, key
    # the reads' buckets are a fixed list, filled and not lengthened
    n = len(metrics_mod.READ_BUCKETS)
    assert before["_plain_reads"] == before["_plain_read_s"] == n == 13
    assert sum(eng.metrics._plain_reads) == eng.metrics.decode_reads > 20


def test_disarmed_tracer_allocates_no_span(model, monkeypatch):
    made = []
    real_init = trace_mod._Span.__init__

    def counting_init(self, *a, **k):
        made.append(self)
        real_init(self, *a, **k)

    monkeypatch.setattr(trace_mod._Span, "__init__", counting_init)
    eng = _engine(model)
    tracer = telemetry.get_tracer()
    annotated = []
    tracer.set_annotation_factory(
        lambda name, **kw: annotated.append(name) or NULL_SPAN)
    assert not tracer.enabled
    iterations = 0
    while iterations < 100:
        eng.submit([1, 2, 3, 4], max_new_tokens=20)
        eng.submit([4, 3], max_new_tokens=20)
        iterations += eng.drain(max_steps=100)
    assert not made and not annotated and len(tracer) == 0
    assert tracer.span("serving/decode_step", cat="serving") is NULL_SPAN

    telemetry.configure(True)
    eng.submit([1, 2, 3], max_new_tokens=2)
    eng.drain(max_steps=10)
    names = {e["name"] for e in tracer.events()}
    assert {"serving/admission", "serving/prefill_batch", "serving/install",
            "serving/upload_lanes", "serving/decode_step",
            "serving/read_back", "serving/emit"} <= names
    # the same spans went to the annotation factory, scalars only
    assert {"serving/install", "serving/emit"} <= set(annotated)
    assert len(made) == len(annotated)


def test_every_request_ends_in_one_of_two_instants(model, clock):
    """``serving/retire`` for a request that ran to its end,
    ``serving/retire_timeout`` with the phase its deadline passed in for the
    others: queued (never admitted) and decoding."""
    telemetry.configure(True)
    eng = _engine(model, max_slots=1)
    done = eng.submit([1, 2, 3], max_new_tokens=2)
    waiting = eng.submit([4, 5], max_new_tokens=2, timeout_s=0.01)
    eng.drain(max_steps=10)
    slow = eng.submit([6, 7], max_new_tokens=8, timeout_s=1.0)
    eng.step()
    clock.advance(2.0)
    eng.drain(max_steps=10)
    assert done.result(timeout=1)
    for fut in (waiting, slow):
        with pytest.raises(scheduler_mod.RequestTimeoutError):
            fut.result(timeout=1)
    ends = [(e["name"], e["args"]) for e in telemetry.get_tracer().events()
            if e["name"].startswith("serving/retire")]
    assert [n for n, _ in ends].count("serving/retire") == 1
    timeouts = {a["phase"]: a for n, a in ends
                if n == "serving/retire_timeout"}
    assert sorted(timeouts) == ["decoding", "queued"]
    assert timeouts["queued"]["tokens"] == 0
    assert timeouts["decoding"]["tokens"] >= 1
    assert eng.metrics.requests_timed_out == 2


def test_a_corrupt_spill_entry_leaves_an_instant(model):
    """The spill tier's listener: counted, and one ``serving/spill_corrupt``
    instant with the running total when the tracer is armed."""
    eng = _engine(model)
    eng._on_spill_event("spill_corrupt")        # disarmed: counted only
    assert len(telemetry.get_tracer()) == 0
    telemetry.configure(True)
    eng._on_spill_event("spill_corrupt")
    ev, = [e for e in telemetry.get_tracer().events()
           if e["name"] == "serving/spill_corrupt"]
    assert ev["ph"] == "i" and ev["args"] == {"total": 2}
    assert eng.metrics.snapshot()["spill_corrupt_total"] == 2


def test_span_hands_scalars_to_the_annotation():
    seen = []

    class Annotation:
        def __init__(self, name, **kwargs):
            seen.append(("init", name, kwargs))

        def __enter__(self):
            seen.append("enter")

        def __exit__(self, *exc):
            seen.append("exit")

    tracer = trace_mod.Tracer(enabled=True)
    tracer.set_annotation_factory(Annotation)
    with tracer.span("serving/prefill_batch", cat="serving",
                     args={"request_ids": [1, 2], "bucket": 8, "group": 2}):
        assert seen[-1] == "enter"
    assert seen == [("init", "serving/prefill_batch",
                     {"bucket": 8, "group": 2}), "enter", "exit"]
    ev = tracer.events()[0]
    assert ev["args"]["request_ids"] == [1, 2]     # lists stay in the ring
    tracer.set_annotation_factory(None)
    with tracer.span("x"):
        pass
    assert len(seen) == 3


def test_telemetry_trace_stays_stdlib_only():
    src = open(trace_mod.__file__).read()
    assert "import jax" not in src and "import numpy" not in src
    assert "TraceAnnotation" not in src.replace(
        "jax.profiler.TraceAnnotation", "")


def test_one_span_system_in_the_program():
    """``TraceAnnotation`` is named only where an engine hands the class to
    the tracer: every annotation is made by ``Tracer.span``."""
    import os
    import re

    root = os.path.dirname(os.path.dirname(engine_mod.__file__))
    root = os.path.dirname(root)                      # deepspeed_tpu/
    uses = []
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        for n, line in enumerate(open(path), 1):
            code = line.split("#", 1)[0]
            if re.search(r"TraceAnnotation\b", code) and '"""' not in code:
                uses.append((os.path.relpath(path, root), code.strip()))
    assert uses, "no engine installs the annotation factory"
    for path, code in uses:
        assert code.endswith(
            "set_annotation_factory(jax.profiler.TraceAnnotation)") \
            or path == os.path.join("telemetry", "trace.py"), (path, code)


def _lowered_text(jitted, *args, **kwargs):
    return jitted.lower(*args, **kwargs).as_text(debug_info=True)


def test_decode_and_install_programs_carry_their_scopes(model):
    eng = _engine(model)
    eng.submit([1, 2, 3], max_new_tokens=2)
    eng.step()
    text = _lowered_text(
        gpt2_mod._decode_step_jit, eng.params, eng.pool.k, eng.pool.v,
        eng.lanes.dev_page_tables, eng.lanes.dev_tokens,
        eng.lanes.dev_positions, eng.lanes.dev_active,
        n_heads=eng.family.n_heads)
    for scope in ("kv_gather", "attend", "kv_scatter", "sample"):
        assert f"/{scope}/" in text, scope

    from deepspeed_tpu.inference.serving import kv_pool

    fam = eng.family
    shape = (fam.n_layers, 1, fam.n_heads, eng.max_seq_len, fam.head_dim)
    new = jnp.zeros(shape, eng.pool.compute_dtype)
    dest = jnp.zeros((eng.pool.page_tables.shape[1],), jnp.int32)
    text = _lowered_text(kv_pool._install_pages_jit, eng.pool.k, eng.pool.v,
                         new, new, dest, eng.pool.page_tokens)
    assert "/install_pages/" in text
    eng.drain(max_steps=10)


@pytest.mark.parametrize("program,kwargs", [
    ("_decode_step_quant_jit", {"qmode": "bf16"}),
    ("_decode_step_window_jit", {"qmode": None, "page_tokens": None}),
])
def test_sibling_decode_programs_carry_the_same_scopes(model, program, kwargs):
    eng = _engine(model)
    eng.submit([1, 2, 3], max_new_tokens=2)
    eng.step()
    if "page_tokens" in kwargs:
        kwargs = dict(kwargs, page_tokens=eng.pool.page_tokens)
    text = _lowered_text(
        getattr(gpt2_mod, program), eng.params, eng.pool.k, eng.pool.v,
        None, None, eng.lanes.dev_page_tables, eng.lanes.dev_tokens,
        eng.lanes.dev_positions, eng.lanes.dev_active,
        n_heads=eng.family.n_heads, **kwargs)
    for scope in ("kv_gather", "attend", "kv_scatter", "sample"):
        assert f"{scope}/" in text, scope
    eng.drain(max_steps=10)


def test_fused_train_step_carries_its_scopes(tmpdir):
    from tests.unit.simple_model import make_simple_engine

    engine = make_simple_engine(tmpdir, {
        "train_batch_size": 8, "steps_per_print": 100,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}}, hidden_dim=8)
    engine._ensure_opt_state()
    fused = engine._get_train_step(engine._module_needs_rng(), 2)
    x = jnp.zeros((1, 8, 8), jnp.float32)
    text = _lowered_text(
        getattr(fused, "_fn", fused), engine.params, engine.opt_state,
        engine.scaler_state, jax.random.PRNGKey(0), jnp.float32(1.0),
        jnp.float32(1e-3), x, x)
    assert "jvp(loss)" in text and "transpose(jvp(loss))" in text
    assert "grad_accumulate/" in text               # inside the scan
    assert "/optimizer_update/" in text
    # the spans that timed nothing are gone with their loop
    import inspect

    assert "train/grad_reduce" not in inspect.getsource(type(engine))


def _run_with_limit(fn, seconds):
    """``fn()`` on a thread, given up on after ``seconds``: a profiler
    that hangs fails this test and not the run's time limit."""
    box = {}

    def target():
        try:
            box["value"] = fn()
        except BaseException as e:      # handed to the test's own thread
            box["error"] = e

    t = threading.Thread(target=target, daemon=True)
    t.start()
    t.join(seconds)
    assert not t.is_alive(), f"still running after {seconds}s"
    if "error" in box:
        raise box["error"]
    return box["value"]


def test_armed_spans_land_in_the_profiler_trace(model, tmpdir):
    """Under a ``jax.profiler`` session the armed spans are events of the
    same ``.xplane.pb`` as the programs, on its clock."""
    from jax.profiler import ProfileData

    telemetry.configure(True)
    eng = _engine(model)
    eng.submit([1, 2, 3], max_new_tokens=3)
    eng.drain(max_steps=20)                  # compiled before the session

    def traced():
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(tmpdir), profiler_options=options)
        try:
            # the test's own mark on the profiler's clock, inside the session
            with jax.profiler.TraceAnnotation("session_probe"):
                eng.submit([4, 5, 6, 7], max_new_tokens=4)
                eng.drain(max_steps=20)
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(str(tmpdir.join(
            "plugins", "profile", "*", "*.xplane.pb")))
        assert len(paths) == 1
        return ProfileData.from_file(paths[0])

    data = _run_with_limit(traced, 120.0)
    spans, programs = {}, 0
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.split("#")[0]
                span = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if name.startswith("serving/") or name == "session_probe":
                    spans.setdefault(name, []).append((span, dict(ev.stats)))
                elif "decode_step_jit" in name:
                    programs += 1
    assert programs, "the trace holds no execution of the decode program"
    assert len(spans.get("serving/decode_step", [])) == 3
    assert len(spans.get("serving/emit", [])) == 3
    assert len(spans.get("serving/install", [])) == 1
    ((lo, hi), _), = spans.pop("session_probe")
    for name, found in spans.items():
        for (start, end), _stats in found:
            assert lo <= start <= end <= hi, (name, start, end, lo, hi)
    # a decode step's read-back ends before its emit phase begins
    for ((_, d_end), _), ((e_start, _), _) in zip(
            sorted(spans["serving/decode_step"]),
            sorted(spans["serving/emit"])):
        assert d_end <= e_start
    # scalar arguments ride along; request-id lists do not
    _, stats = spans["serving/decode_step"][0]
    assert stats.get("active") == 1 and "request_ids" not in stats
    # each decode step's blocking read lies inside the step's span, and the
    # prefill's two (first tokens, settle) outside every decode step
    steps = sorted(span for span, _ in spans["serving/decode_step"])
    reads = {"decode": [], "prefill": []}
    for span, stats in spans["serving/read_back"]:
        reads[stats["kind"]].append(span)
    assert len(reads["decode"]) == 3 and len(reads["prefill"]) == 2
    for (start, end), (r_start, r_end) in zip(steps, sorted(reads["decode"])):
        assert start <= r_start <= r_end <= end
