"""Disaggregated prefill/decode serving: fault-tolerant KV-page handoff.

Two tiers, like test_router.py. The FAST tier proves the protocol and
policy machinery without real processes: the length-prefixed crc32
frame codec (oversize refused before the payload is read, truncation
and corruption named), the KV pool's page-state guards (double free,
install-over-live-lane, idempotent re-install under one handoff key)
and a bitwise raw export/install roundtrip in fp32 AND int8, the
HandoffReceiver claim/install/ack state machine with an injected clock
driving both orphan-reaper TTLs, the HandoffSender's bounded
retry/backoff against a scripted decode-side stub (frame error, budget
exhaustion, duplicate ack, timeout, injected wire corruption), the
router's role-aware routing (missing ``role`` in a health snapshot is
``mixed``; a decode-only fleet raises a structured WrongRoleError; a
``wrong_role`` rejection teaches the router the replica's real role;
losing the decode pool degrades to interleaved mixed mode with an
edge-triggered instant), the two-loop role-pool autoscaler, and an
in-process two-engine (then two-replica-over-sockets) handoff held
bitwise against the one-shot ``generate()`` oracle.

The SLOW tier spawns REAL prefill/decode replica processes and runs
the disagg chaos arms — kill the prefill worker mid-transfer, kill the
decode worker right after it acked — asserting every affected request
completes exactly once bitwise and no KV page leaks (pool occupancy
and pending handoff claims return to zero on every survivor).
"""

import socket
import struct
import threading
import time
import zlib

import numpy as np
import pytest

import jax

from deepspeed_tpu.inference import generate
from deepspeed_tpu.inference.serving import (
    FleetConfig,
    HandoffConfig,
    HandoffFrameError,
    HandoffReceiver,
    HandoffRetryError,
    HandoffSender,
    HandoffSizeError,
    KVCachePool,
    PageStateError,
    PoolExhaustedError,
    ReplicaEndpoint,
    ReplicaServer,
    RolesConfig,
    Router,
    ServingConfig,
    ServingEngine,
    ServingFaultInjector,
    WrongRoleError,
)
from deepspeed_tpu.inference.serving.autoscaler import (
    ProcessReplicaSpawner,
    RolePoolAutoscaler,
)
from deepspeed_tpu.inference.serving.chaos import DisaggChaosHarness
from deepspeed_tpu.inference.serving.config import AutoscaleConfig
from deepspeed_tpu.inference.serving.handoff import (
    read_frame,
    write_frame,
)
from deepspeed_tpu.inference.serving.router import read_line, send_line
from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2
from tests.unit.test_router import (  # noqa: F401  (stubs: fixture re-export)
    FAST_CFG,
    StubReplica,
    make_router,
    stub_tokens,
    stubs,
)


def _crc(payload):
    return zlib.crc32(payload) & 0xFFFFFFFF


def _pair():
    a, b = socket.socketpair()
    a.settimeout(5.0)
    b.settimeout(5.0)
    return a, b


# ---------------------------------------------------------------------------
# fast tier: the binary frame codec
# ---------------------------------------------------------------------------

def test_frame_roundtrip_bitwise():
    a, b = _pair()
    try:
        payload = bytes(range(256)) * 7
        write_frame(a, payload)
        assert read_frame(b.makefile("rb")) == payload
    finally:
        a.close()
        b.close()


def test_frame_oversize_refused_on_send():
    a, b = _pair()
    try:
        with pytest.raises(HandoffSizeError):
            write_frame(a, b"x" * 100, max_bytes=64)
    finally:
        a.close()
        b.close()


def test_frame_oversize_refused_before_payload_read():
    # a hostile/corrupt header claiming 1 GiB must be refused from the
    # header alone — no payload follows, and read_frame must not block
    # trying to consume one
    a, b = _pair()
    try:
        a.sendall(struct.pack(">II", 1 << 30, 0))
        a.close()
        with pytest.raises(HandoffSizeError):
            read_frame(b.makefile("rb"), max_bytes=1 << 20)
    finally:
        b.close()


def test_frame_truncated_payload_named():
    a, b = _pair()
    try:
        payload = b"hello world"
        a.sendall(struct.pack(">II", len(payload) + 5, _crc(payload)))
        a.sendall(payload)
        a.close()                       # EOF before the promised bytes
        with pytest.raises(HandoffFrameError, match="truncated|EOF|short"):
            read_frame(b.makefile("rb"))
    finally:
        b.close()


def test_frame_crc_mismatch_named():
    a, b = _pair()
    try:
        payload = b"page bytes here"
        corrupt = bytes([payload[0] ^ 0xFF]) + payload[1:]
        a.sendall(struct.pack(">II", len(payload), _crc(payload)) + corrupt)
        a.close()
        with pytest.raises(HandoffFrameError, match="crc"):
            read_frame(b.makefile("rb"))
    finally:
        b.close()


# ---------------------------------------------------------------------------
# fast tier: KV pool page-state guards + raw export/install (satellite)
# ---------------------------------------------------------------------------

def _pool(dt="fp32"):
    # multi-page lanes: 16-token lanes in 4-token pages
    return KVCachePool(n_layers=1, max_slots=2, n_heads=1, max_seq_len=16,
                       head_dim=4, kv_cache_dtype=dt, page_tokens=4)


def _filled_slot(pool, n_tokens=8, position=6, seed=3):
    rng = np.random.RandomState(seed)
    slot = pool.allocate(n_tokens)
    k = rng.randn(1, 1, 1, 16, 4).astype(np.float32)
    v = rng.randn(1, 1, 1, 16, 4).astype(np.float32)
    pool.install(k, v, slot, position)
    return slot


def test_pool_double_free_is_named_page_state_error():
    pool = _pool()
    slot = pool.allocate(4)
    pool.free(slot)
    with pytest.raises(PageStateError, match="double free"):
        pool.free(slot)
    # PageStateError must stay a ValueError: pre-existing callers catch
    # the broad class
    assert issubclass(PageStateError, ValueError)


@pytest.mark.parametrize("dt", ["fp32", "int8"])
def test_pool_export_install_raw_roundtrip_bitwise(dt):
    src, dst = _pool(dt), _pool(dt)
    slot = _filled_slot(src, n_tokens=8, position=6)
    meta, frames = src.export_lane(slot)
    assert meta["pages"] == 2
    assert meta["position"] == 6
    assert meta["kv_cache_dtype"] == dt
    assert len(frames) == meta["pages"] + (1 if dt == "int8" else 0)
    tgt = dst.allocate(8)
    assert dst.install_raw(tgt, meta, frames, handoff_key="hk") is True
    meta2, frames2 = dst.export_lane(tgt)
    # the installed lane re-exports bit-identically: bytes, position,
    # scales and all
    assert frames2 == frames
    assert meta2 == meta
    assert dst.handoff_slot("hk") == tgt


def test_pool_install_raw_idempotent_under_same_key():
    src, dst = _pool(), _pool()
    slot = _filled_slot(src)
    meta, frames = src.export_lane(slot)
    tgt = dst.allocate(8)
    assert dst.install_raw(tgt, meta, frames, handoff_key="hk") is True
    # a re-sent handoff under the live key is a no-op, never a second
    # install
    assert dst.install_raw(tgt, meta, frames, handoff_key="hk") is False
    # ... while a DIFFERENT key aimed at the live lane is a bug, loudly
    with pytest.raises(PageStateError, match="already holds"):
        dst.install_raw(tgt, meta, frames, handoff_key="other")
    # freeing the lane retires the key: the slot is reusable
    dst.free(tgt)
    assert dst.handoff_slot("hk") is None
    tgt2 = dst.allocate(8)
    assert dst.install_raw(tgt2, meta, frames, handoff_key="hk") is True


def test_pool_install_raw_refuses_dtype_and_page_mismatch():
    src = _pool("fp32")
    slot = _filled_slot(src)
    meta, frames = src.export_lane(slot)
    wrong_dt = _pool("int8")
    tgt = wrong_dt.allocate(8)
    with pytest.raises(PageStateError, match="dtype"):
        wrong_dt.install_raw(tgt, meta, frames)
    small = _pool("fp32")
    tiny = small.allocate(4)            # one page < the export's two
    with pytest.raises(PageStateError, match="pages"):
        small.install_raw(tiny, meta, frames)


def test_pool_install_raw_into_free_slot_refused():
    src, dst = _pool(), _pool()
    meta, frames = src.export_lane(_filled_slot(src))
    tgt = dst.allocate(8)
    dst.free(tgt)
    with pytest.raises(PageStateError, match="not allocated"):
        dst.install_raw(tgt, meta, frames, handoff_key="hk")


# ---------------------------------------------------------------------------
# fast tier: HandoffReceiver state machine (claim -> transfer -> ack)
# ---------------------------------------------------------------------------

class _FakePool:
    """Slot bookkeeping without device state, counting every call."""

    def __init__(self, slots=4):
        self._free = list(range(slots))
        self.alloc_calls = 0
        self.installed = {}             # slot -> key
        self.freed = []

    def allocate(self, n_tokens):
        self.alloc_calls += 1
        if not self._free:
            raise PoolExhaustedError("no free slots")
        return self._free.pop(0)

    def install(self, slot, meta, frames, key):
        if key in self.installed.values():
            return False                # idempotent duplicate
        self.installed[slot] = key
        return True

    def free(self, slot):
        self.freed.append(slot)
        self.installed.pop(slot, None)
        self._free.append(slot)


def _receiver(pool, clock=None, **cfg):
    kw = dict(enabled=True, retries=3, backoff_s=0.001, backoff_max_s=0.002,
              attempt_timeout_s=5.0, claim_ttl_s=1.0, resume_ttl_s=3.0)
    kw.update(cfg)
    return HandoffReceiver(HandoffConfig(**kw), allocate_fn=pool.allocate,
                           install_fn=pool.install, free_fn=pool.free,
                           clock=clock or time.monotonic)


def _frame_bytes(frames):
    return b"".join(struct.pack(">II", len(p), _crc(p)) + p for p in frames)


def _drive(rcv, key, meta, frames, raw=None):
    """Feed one handoff op into the receiver over a socketpair; returns
    the reply docs in order."""
    a, b = _pair()
    replies = []
    try:
        a.sendall(_frame_bytes(frames) if raw is None else raw)
        a.shutdown(socket.SHUT_WR)
        rcv.handle(b, b.makefile("rb"),
                   {"op": "handoff", "key": key, "meta": meta,
                    "frames": len(frames)},
                   lambda _conn, doc: replies.append(doc))
    finally:
        a.close()
        b.close()
    return replies


META = {"pages": 2, "position": 6, "reserve_tokens": 12}
FRAMES = [b"k-page-0v-page-0", b"k-page-1v-page-1"]


def test_receiver_claim_transfer_ack():
    pool = _FakePool()
    rcv = _receiver(pool)
    replies = _drive(rcv, "hk", META, FRAMES)
    assert replies[0] == {"claimed": True, "key": "hk", "slot": 0}
    assert replies[1] == {"acked": True, "key": "hk", "pages": 2,
                          "dup": False}
    assert pool.installed == {0: "hk"}
    assert rcv.pending() == 1           # installed, awaiting resume
    assert rcv.take("hk") == (0, META)
    assert rcv.pending() == 0
    assert rcv.take("hk") is None       # gone once taken


def test_receiver_duplicate_resend_acks_without_second_install():
    pool = _FakePool()
    rcv = _receiver(pool)
    _drive(rcv, "hk", META, FRAMES)
    replies = _drive(rcv, "hk", META, FRAMES)
    # the dup short-circuits before the allocator: exactly-once install
    assert replies == [{"acked": True, "key": "hk", "dup": True}]
    assert pool.alloc_calls == 1
    assert rcv.counters["dup_acks"] == 1


def test_receiver_frame_error_keeps_claim_and_retry_reuses_slot():
    pool = _FakePool()
    rcv = _receiver(pool)
    bad = bytes([FRAMES[0][0] ^ 0xFF]) + FRAMES[0][1:]
    raw = (struct.pack(">II", len(FRAMES[0]), _crc(FRAMES[0])) + bad
           + _frame_bytes(FRAMES[1:]))
    replies = _drive(rcv, "hk", META, FRAMES, raw=raw)
    assert replies[0]["claimed"]
    assert replies[1]["etype"] == "HandoffFrameError"
    # the torn transfer's claim survives for the sender's retry ...
    assert rcv.pending() == 1
    assert pool.freed == []
    # ... which lands on the SAME slot without a second allocation
    replies = _drive(rcv, "hk", META, FRAMES)
    assert replies[0] == {"claimed": True, "key": "hk", "slot": 0}
    assert replies[1]["acked"] and not replies[1]["dup"]
    assert pool.alloc_calls == 1
    assert rcv.counters["frame_errors"] == 1


def test_receiver_rejects_on_pool_exhaustion():
    pool = _FakePool(slots=0)
    rcv = _receiver(pool)
    replies = _drive(rcv, "hk", META, FRAMES)
    assert replies == [{"rejected": "pool_exhausted",
                        "detail": "no free slots"}]
    assert rcv.counters["rejected"] == 1


def test_receiver_reaps_orphans_on_both_ttls():
    t = [0.0]
    pool = _FakePool()
    rcv = _receiver(pool, clock=lambda: t[0], claim_ttl_s=1.0,
                    resume_ttl_s=3.0)
    # orphaned CLAIM: the prefill worker died mid-transfer (frame error
    # path leaves the claim in "claimed")
    raw = struct.pack(">II", len(FRAMES[0]), _crc(FRAMES[0]) ^ 1) + FRAMES[0]
    _drive(rcv, "dead-sender", META, [FRAMES[0]], raw=raw)
    assert rcv.pending() == 1
    t[0] = 0.5
    assert rcv.reap() == 0              # inside claim_ttl_s: kept
    t[0] = 1.5
    assert rcv.reap() == 1              # past it: freed
    assert pool.freed == [0]
    assert rcv.counters["reaped_claimed"] == 1
    # orphaned INSTALL: the router never resumed (it re-routed or died)
    _drive(rcv, "no-resume", META, FRAMES)
    t[0] = 3.0
    assert rcv.reap() == 0              # inside resume_ttl_s: kept
    t[0] = 5.0
    assert rcv.reap() == 1
    assert rcv.counters["reaped_installed"] == 1
    assert rcv.pending() == 0


def test_receiver_restore_undoes_a_failed_take():
    pool = _FakePool()
    rcv = _receiver(pool)
    _drive(rcv, "hk", META, FRAMES)
    slot, meta = rcv.take("hk")
    rcv.restore("hk", slot, meta)       # resume failed before handover
    assert rcv.pending() == 1
    assert rcv.take("hk") == (slot, meta)


# ---------------------------------------------------------------------------
# fast tier: HandoffSender bounded retry against a scripted stub
# ---------------------------------------------------------------------------

class _HandoffStub:
    """Scripted decode-side endpoint: one behavior per connection.

    "ok"          claim, read+verify frames, ack
    "dup"         immediate duplicate ack
    "reject"      refuse the claim
    "frame_error" claim, read frames, report a frame error
    "hang"        claim, then never reply (forces the attempt timeout)
    "eof"         close without replying
    """

    def __init__(self, script=()):
        self.script = list(script)
        self.received = []              # (key, meta, frames) of acked sends
        self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind(("127.0.0.1", 0))
        self._lsock.listen(8)
        self.host, self.port = self._lsock.getsockname()[:2]
        self._closing = threading.Event()
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while not self._closing.is_set():
            try:
                conn, _ = self._lsock.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn):
        try:
            with conn:
                op = read_line(conn.makefile("rb"))
                if op is None:
                    return
                beh = self.script.pop(0) if self.script else "ok"
                if beh == "eof":
                    return
                if beh == "dup":
                    send_line(conn, {"acked": True, "key": op["key"],
                                     "dup": True})
                    return
                if beh == "reject":
                    send_line(conn, {"rejected": "pool_exhausted"})
                    return
                send_line(conn, {"claimed": True, "key": op["key"],
                                 "slot": 0})
                stream = conn.makefile("rb")
                try:
                    frames = [read_frame(stream)
                              for _ in range(int(op["frames"]))]
                except (HandoffFrameError, HandoffSizeError) as e:
                    send_line(conn, {"error": str(e),
                                     "etype": type(e).__name__})
                    return
                if beh == "frame_error":
                    send_line(conn, {"error": "scripted",
                                     "etype": "HandoffFrameError"})
                    return
                if beh == "hang":
                    time.sleep(10.0)
                    return
                self.received.append((op["key"], op["meta"], frames))
                send_line(conn, {"acked": True, "key": op["key"],
                                 "dup": False})
        except (OSError, ValueError):
            pass

    def close(self):
        self._closing.set()
        try:
            self._lsock.close()
        except OSError:
            pass


def _sender(**cfg):
    kw = dict(enabled=True, retries=3, backoff_s=0.001, backoff_max_s=0.002,
              attempt_timeout_s=5.0)
    kw.update(cfg)
    return HandoffSender(config=HandoffConfig(**kw))


def test_sender_retries_through_a_frame_error():
    stub = _HandoffStub(["frame_error", "ok"])
    try:
        snd = _sender()
        ack = snd.send(stub.host, stub.port, "hk", META, FRAMES)
        assert ack["acked"] and not ack.get("dup")
        assert stub.received == [("hk", META, FRAMES)]
        assert snd.counters["attempts"] == 2
        assert snd.counters["retries"] == 1
        assert snd.counters["frame_errors"] == 1
    finally:
        stub.close()


def test_sender_exhausts_bounded_budget():
    stub = _HandoffStub(["frame_error"] * 5)
    try:
        snd = _sender(retries=2)
        with pytest.raises(HandoffRetryError) as ei:
            snd.send(stub.host, stub.port, "hk", META, FRAMES)
        assert ei.value.attempts == 2
        assert "refused a frame" in ei.value.last_error
        assert snd.counters["failed"] == 1
        assert snd.counters["attempts"] == 2    # bounded, not forever
    finally:
        stub.close()


def test_sender_duplicate_ack_short_circuits():
    stub = _HandoffStub(["dup"])
    try:
        snd = _sender()
        ack = snd.send(stub.host, stub.port, "hk", META, FRAMES)
        assert ack["dup"]
        assert snd.counters["dup_acked"] == 1
        assert stub.received == []      # nothing re-installed
    finally:
        stub.close()


def test_sender_times_out_a_hung_receiver():
    stub = _HandoffStub(["hang"])
    try:
        snd = _sender(retries=1, attempt_timeout_s=0.2)
        with pytest.raises(HandoffRetryError) as ei:
            snd.send(stub.host, stub.port, "hk", META, FRAMES)
        assert "exceeded" in ei.value.last_error
    finally:
        stub.close()


def test_sender_refuses_oversize_frame():
    stub = _HandoffStub(["ok", "ok"])
    try:
        snd = _sender(retries=2, max_frame_bytes=64)
        with pytest.raises(HandoffRetryError) as ei:
            snd.send(stub.host, stub.port, "hk", META, [b"x" * 100])
        assert "exceeds the 64-byte cap" in ei.value.last_error
        assert stub.received == []
    finally:
        stub.close()


def test_sender_injected_corruption_caught_by_crc_then_retried():
    # the chaos arm flips a payload byte AFTER the crc was computed; the
    # receiver's crc check must refuse the frame and the retry must land
    # the ORIGINAL bytes
    stub = _HandoffStub(["ok", "ok"])
    try:
        injector = ServingFaultInjector().arm_serving(
            "handoff_corrupt_frame", times=1)
        snd = HandoffSender(config=HandoffConfig(enabled=True, retries=3,
                                                 backoff_s=0.001,
                                                 backoff_max_s=0.002),
                            injector=injector)
        ack = snd.send(stub.host, stub.port, "hk", META, FRAMES)
        assert ack["acked"]
        assert snd.counters["frame_errors"] == 1
        assert snd.counters["retries"] == 1
        assert stub.received == [("hk", META, FRAMES)]      # bitwise
    finally:
        stub.close()


# ---------------------------------------------------------------------------
# fast tier: role-aware routing (satellite regressions)
# ---------------------------------------------------------------------------

class RoleStub(StubReplica):
    """StubReplica that advertises a role (optionally hiding it, like a
    pre-roles replica would) and enforces the decode-side submit
    rejection the real replica server applies."""

    def __init__(self, role="mixed", advertise_role=True, **kw):
        self.role = role
        self.advertise_role = advertise_role
        super().__init__(**kw)

    def _serve(self, conn):
        try:
            with conn:
                op = read_line(conn.makefile("rb"))
                if op is None:
                    return
                if op["op"] == "health":
                    doc = {"healthy": True, "draining": self.draining,
                           "queue_depth": self.queue_depth,
                           "active_requests": 0}
                    if self.advertise_role:
                        doc["role"] = self.role
                    send_line(conn, doc)
                    return
                if op["op"] == "degrade":
                    send_line(conn, {"rung": int(op.get("rung", 0))})
                    return
                if (self.role == "decode" and not op.get("force")
                        and not op.get("handoff_key")):
                    send_line(conn, {"rejected": "wrong_role",
                                     "role": self.role})
                    return
                with self.lock:
                    self.submits.append((op["key"], int(op.get("from", 0))))
                toks = self.token_fn(op["prompt"], self.n_tokens)
                for i in range(int(op.get("from", 0)), len(toks)):
                    send_line(conn, {"t": toks[i], "i": i})
                send_line(conn, {"done": True, "n": len(toks)})
        except (OSError, ValueError):
            pass


def _free_port():
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_endpoint_rejects_unknown_role():
    with pytest.raises(ValueError, match="role"):
        ReplicaEndpoint("r0", "127.0.0.1", 1, role="bogus")


def test_health_snapshot_missing_role_defaults_to_mixed(stubs):
    # backward compat: a pre-roles replica whose health doc has no
    # "role" key keeps routing exactly as before
    s = stubs()                         # plain StubReplica: no role key
    r = make_router([s])
    try:
        got = r.submit([1, 2, 3], max_new_tokens=6).result(timeout=5)
        assert got == stub_tokens([1, 2, 3], 6)
        ep = r.endpoints()[0]
        assert ep.role == "mixed"
    finally:
        r.close()


def test_decode_only_fleet_raises_structured_wrong_role_error():
    d = RoleStub(role="decode")
    ep = ReplicaEndpoint("d0", "127.0.0.1", d.port, role="decode")
    r = Router([ep], FleetConfig(enabled=True, **FAST_CFG))
    try:
        fut = r.submit([1, 2, 3], max_new_tokens=4)
        with pytest.raises(WrongRoleError) as ei:
            fut.result(timeout=5)
        assert ei.value.request_kind == "submit"
        assert ei.value.roles == {"d0": "decode"}
        assert d.submits == []          # never reached the replica
    finally:
        r.close()
        d.close()


def test_wrong_role_rejection_teaches_router_the_role():
    # a decode replica the router believes is mixed (stale/absent role in
    # its health doc) rejects the submit with its real role; the router
    # adopts it and re-routes — the request still completes exactly once
    hidden = RoleStub(role="decode", advertise_role=False)
    mixed = RoleStub(role="mixed", queue_depth=5)   # less attractive pick
    eps = [ReplicaEndpoint("hidden", "127.0.0.1", hidden.port),
           ReplicaEndpoint("mixed", "127.0.0.1", mixed.port)]
    # affinity off: least-loaded picks the (queue_depth 0) hidden decode
    # replica first, deterministically
    r = Router(eps, FleetConfig(enabled=True,
                                **{**FAST_CFG, "affinity_prefix_tokens": 0}))
    try:
        got = r.submit([4, 5], max_new_tokens=6).result(timeout=5)
        assert got == stub_tokens([4, 5], 6)
        assert hidden.submits == []     # the decode side served nothing
        assert len(mixed.submits) == 1
        by_name = {ep.name: ep for ep in r.endpoints()}
        assert by_name["hidden"].role == "decode"   # learned from the
    finally:                                        # rejection doc
        r.close()
        hidden.close()
        mixed.close()


def test_handoff_degrades_to_mixed_mode_edge_triggered():
    # phase 1: the only decode endpoint is dead -> requests fall back to
    # interleaved mixed mode, and the degraded instant fires ONCE
    worker = RoleStub(role="mixed")
    dead = ReplicaEndpoint("d-dead", "127.0.0.1", _free_port(),
                           role="decode")
    r = Router([ReplicaEndpoint("m0", "127.0.0.1", worker.port), dead],
               FleetConfig(enabled=True, **FAST_CFG))
    try:
        for prompt in ([1, 2], [3, 4]):
            got = r.submit(prompt, max_new_tokens=6).result(timeout=5)
            assert got == stub_tokens(prompt, 6)
        c = r.counters()
        assert c["handoff_degraded"] == 1       # edge, not per-request
        assert c["handoff_routed"] == 0
        # phase 2: a decode worker comes back -> the handoff path is
        # attempted again and the degraded state clears ...
        alive = RoleStub(role="decode")
        r.remove_endpoint("d-dead")
        r.add_endpoint(ReplicaEndpoint("d0", "127.0.0.1", alive.port,
                                       role="decode"))
        got = r.submit([5, 6], max_new_tokens=6).result(timeout=5)
        assert got == stub_tokens([5, 6], 6)
        assert r.counters()["handoff_routed"] == 1
        # ... so losing it again re-fires the edge exactly once more
        r.remove_endpoint("d0")
        alive.close()
        r.add_endpoint(ReplicaEndpoint("d-dead2", "127.0.0.1",
                                       _free_port(), role="decode"))
        for prompt in ([7, 8], [9, 1]):
            got = r.submit(prompt, max_new_tokens=6).result(timeout=5)
            assert got == stub_tokens(prompt, 6)
        assert r.counters()["handoff_degraded"] == 2
    finally:
        r.close()
        worker.close()


# ---------------------------------------------------------------------------
# fast tier: two role pools, two SLO signals, one autoscaler
# ---------------------------------------------------------------------------

class _RoleHandle:
    def __init__(self, name, role, port):
        self.name = name
        self.role = role
        self.host = "127.0.0.1"
        self.port = port
        self._alive = True

    def alive(self):
        return self._alive

    def endpoint(self):
        return ReplicaEndpoint(self.name, self.host, self.port,
                               role=self.role)


class _RoleSpawner:
    def __init__(self):
        self.roles = []                 # role of every spawn, in order
        self._seq = 0

    def spawn(self, name=None, generation=None, role=None):
        self._seq += 1
        self.roles.append(role)
        return _RoleHandle(name or f"{role}-{self._seq}", role or "mixed",
                           9000 + self._seq)

    def drain(self, handle, wait_s=0.0):
        handle._alive = False
        return True

    def kill(self, handle):
        handle._alive = False


def test_role_pool_autoscaler_scales_pools_on_their_own_signals():
    t = [0.0]
    ttft_firing = [False]
    decode_firing = [False]
    sp = _RoleSpawner()
    hp = _RoleHandle("p0", "prefill", 8001)
    hd = _RoleHandle("d0", "decode", 8002)
    router = Router([hp.endpoint(), hd.endpoint()],
                    FleetConfig(enabled=True, **FAST_CFG))

    def pool_sizes():
        sizes = {"prefill": 0, "decode": 0, "mixed": 0}
        for ep in router.endpoints():
            sizes[ep.role] += 1
        return sizes

    try:
        auto = RolePoolAutoscaler(
            router, sp,
            roles_config=RolesConfig(enabled=True, prefill_replicas=1,
                                     decode_replicas=1,
                                     max_prefill_replicas=3,
                                     max_decode_replicas=3),
            autoscale_config=AutoscaleConfig(enabled=True, warm_spares=0,
                                             up_after_s=1.0,
                                             down_after_s=1000.0,
                                             cooldown_s=0.0),
            ttft_alerts=lambda: ttft_firing[0],
            decode_alerts=lambda: decode_firing[0],
            prefill_replicas=[hp], decode_replicas=[hd],
            clock=lambda: t[0])
        assert auto.step() == {"prefill": None, "decode": None}
        # TTFT over budget grows ONLY the prefill pool
        ttft_firing[0] = True
        auto.step()                     # pressure window opens
        t[0] = 1.0
        assert auto.step()["prefill"] == "up"
        assert sp.roles == ["prefill"]
        assert pool_sizes() == {"prefill": 2, "decode": 1, "mixed": 0}
        # decode tok/s under floor grows ONLY the decode pool
        ttft_firing[0] = False
        decode_firing[0] = True
        t[0] = 1.1
        auto.step()
        t[0] = 2.2
        assert auto.step()["decode"] == "up"
        assert sp.roles == ["prefill", "decode"]
        assert pool_sizes() == {"prefill": 2, "decode": 2, "mixed": 0}
        # only the decode loop owns the fleet-wide degrade rung
        assert auto.prefill.ladder.rung == 0
        stats = auto.stats()
        assert stats["prefill_scale_ups"] == 1.0
        assert stats["decode_scale_ups"] == 1.0
    finally:
        router.close()


# ---------------------------------------------------------------------------
# fast tier: real engines, in-process — the bitwise handoff contract
# ---------------------------------------------------------------------------

def _tiny_config():
    return GPT2Config(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)


@pytest.fixture(autouse=True, scope="module")
def _drop_jit_caches():
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def model():
    cfg = _tiny_config()
    _, params = init_gpt2(cfg, batch_size=2, seq_len=4, seed=0)
    return cfg, params


def _serving(dt="fp32"):
    return ServingConfig(max_slots=3, max_queue=8, max_seq_len=32,
                         prompt_buckets=(4, 8), kv_cache_dtype=dt)


def _await_export(req, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while getattr(req, "export_payload", None) is None:
        if time.monotonic() > deadline:
            raise AssertionError("prefill never exported its KV pages")
        time.sleep(0.005)
    return req.export_payload


def _await_idle(eng, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while eng.occupancy()["in_use"] != 0:
        if time.monotonic() > deadline:
            raise AssertionError(
                f"KV pages leaked: occupancy {eng.occupancy()}")
        time.sleep(0.005)


@pytest.mark.parametrize("dt", ["fp32", "int8"])
def test_engine_handoff_roundtrip_bitwise(model, dt):
    cfg, params = model
    src = ServingEngine(params, cfg, _serving(dt))
    dst = ServingEngine(params, cfg, _serving(dt))
    src.start()
    dst.start()
    try:
        prompt = [5, 9, 2, 7]
        # the oracle a MIXED-mode admission would produce (for fp32 that
        # also equals one-shot generate(); int8 quantizes, so the
        # contract is vs the same engine class, not the fp32 generate)
        oracle = list(dst.submit(prompt, max_new_tokens=6).result(
            timeout=120))
        if dt == "fp32":
            ref = np.asarray(generate(params, cfg, np.array([prompt]),
                                      max_new_tokens=6))[0].tolist()
            assert oracle == ref
        _await_idle(dst)
        req = src.submit_handoff(prompt, reserve_new_tokens=6)
        first = list(req.future.result(timeout=120))
        assert first == oracle[:1]      # prefill emits exactly token 0
        meta, frames = _await_export(req)
        meta = dict(meta, reserve_tokens=min(len(prompt) + 6, 32))
        slot = dst.handoff_claim(meta["reserve_tokens"])
        assert dst.handoff_install(slot, meta, frames,
                                   handoff_key="hk") is True
        # idempotent re-install under the same key: exactly-once
        assert dst.handoff_install(slot, meta, frames,
                                   handoff_key="hk") is False
        req2 = dst.resume_handoff(slot, prompt, first[0], max_new_tokens=6)
        got = list(req2.future.result(timeout=120))
        assert got == oracle            # bitwise: resume continued
        _await_idle(src)                # exactly where prefill left off
        _await_idle(dst)
        m = dst.metrics.snapshot()
        assert m["handoff_installs"] == 1
        assert m["handoff_dup_installs"] == 1
        assert m["handoff_resumes"] == 1
    finally:
        src.close()
        dst.close()


def test_disagg_socket_end_to_end_bitwise(model):
    """The tentpole, over real sockets: a router drives prefill on one
    replica, ships the KV pages to a decode replica, and the resumed
    stream is bitwise ``generate()`` with zero pages left behind."""
    cfg, params = model
    pre_eng = ServingEngine(params, cfg, _serving())
    dec_eng = ServingEngine(params, cfg, _serving())
    pre = ReplicaServer(pre_eng, role="prefill").start()
    dec = ReplicaServer(dec_eng, role="decode").start()
    r = Router(
        [ReplicaEndpoint("pre", pre.host, pre.port, role="prefill"),
         ReplicaEndpoint("dec", dec.host, dec.port, role="decode")],
        FleetConfig(enabled=True,
                    **{**FAST_CFG, "attempt_timeout_s": 120.0}))
    try:
        prompt = [5, 9, 2, 7]
        oracle = np.asarray(generate(params, cfg, np.array([prompt]),
                                     max_new_tokens=6))[0].tolist()
        streamed = []
        got = r.submit(prompt, max_new_tokens=6,
                       stream_cb=lambda k, t: streamed.append(t)
                       ).result(timeout=120)
        assert list(got) == oracle
        # streamed exactly once, in order, across the two hops
        assert streamed == oracle
        c = r.counters()
        assert c["handoff_routed"] == 1
        assert c["handoff_completed"] == 1
        assert c["handoff_failed"] == 0
        assert c["handoff_degraded"] == 0
        _await_idle(pre_eng)
        _await_idle(dec_eng)
        assert pre._handoff_receiver.pending() == 0
        assert dec._handoff_receiver.pending() == 0
        # a plain submit aimed straight at the decode replica is refused
        # with a structured error naming its role
        with socket.create_connection((dec.host, dec.port),
                                      timeout=5.0) as sock:
            send_line(sock, {"op": "submit", "v": 1, "key": "direct",
                             "prompt": prompt, "max_new_tokens": 2})
            reply = read_line(sock.makefile("rb"))
        assert reply == {"rejected": "wrong_role", "role": "decode"}
    finally:
        r.close()
        pre.close()
        dec.close()


# ---------------------------------------------------------------------------
# bench gate: the disagg artifact kind and its refusals
# ---------------------------------------------------------------------------

def _disagg_artifact():
    import json
    import os

    from tools import bench_gate

    path = os.path.join(bench_gate.REPO_ROOT, "DISAGG_BENCH_CPU.json")
    with open(path) as f:
        return path, json.load(f)


def test_bench_gate_detects_disagg_before_chaos(tmp_path):
    """The disagg artifact embeds the chaos mini-leg's ``chaos_episodes``
    rollup; the TTFT marker must still win kind detection."""
    from tools import bench_gate

    path, doc = _disagg_artifact()
    assert "chaos_episodes" in doc     # the hazard this test pins
    kind, _ = bench_gate.load_artifact(path)
    assert kind == "disagg"
    assert bench_gate.main(["--check-schema", path]) == 0
    assert bench_gate.main(["compare", path, path]) == 0


@pytest.mark.parametrize("key,bad", [
    ("dropped_total", 1),
    ("duplicated_total", 2),
    ("bitwise_mismatch_total", 1),
    ("leaked_pages_total", 3),
    ("chaos_pages_clean", False),
    ("chaos_bitwise_ok", False),
    ("ttft_improvement", 0.97),
    ("handoffs_completed", 0),
    ("complete", False),
])
def test_bench_gate_refuses_broken_disagg_baselines(tmp_path, key, bad):
    import json

    from tools import bench_gate

    _, doc = _disagg_artifact()
    doc[key] = bad
    broken = tmp_path / "broken.json"
    broken.write_text(json.dumps(doc))
    assert bench_gate.main(["--check-schema", str(broken)]) == 1


# ---------------------------------------------------------------------------
# slow tier: real processes under the disagg chaos arms
# ---------------------------------------------------------------------------

def _disagg_replica_config(tmp_path):
    import json

    from tests.unit.test_router import MODEL

    spec = {"model": MODEL, "seed": 0, "chaos": True, "ds_config": {
        "train_batch_size": 1,
        "serving": {"max_slots": 4, "max_queue": 16, "max_seq_len": 128},
        "fleet": {"handoff": {
            "attempt_timeout_s": 60.0, "retries": 3, "backoff_s": 0.02,
            "backoff_max_s": 0.2,
            # short TTLs so the zero-orphan invariant is observable
            # within the episode window
            "claim_ttl_s": 2.0, "resume_ttl_s": 4.0}}}}
    path = tmp_path / "replica.json"
    path.write_text(json.dumps(spec))
    return str(path)


def _replica_env():
    import os

    return dict(os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=1")


@pytest.mark.slow
@pytest.mark.faults
@pytest.mark.parametrize("kind", ["kill_prefill_mid_handoff",
                                  "kill_decode_post_ack"])
def test_disagg_chaos_kill_loses_nothing(tmp_path, kind):
    """The acceptance criterion: kill the prefill worker mid-transfer /
    the decode worker right after its ack — every affected request still
    completes exactly once, bitwise ``generate()``, and no replica is
    left holding orphaned KV pages."""
    from tests.unit.test_router import _reference

    cache = {}

    def reference(prompt, n):
        key = (tuple(prompt), n)
        if key not in cache:
            cache[key] = _reference([list(prompt)], n)[0]
        return cache[key]

    spawner = ProcessReplicaSpawner(_disagg_replica_config(tmp_path),
                                    env=_replica_env())
    router = None
    try:
        replicas = [spawner.spawn("p0", role="prefill"),
                    spawner.spawn("p1", role="prefill"),
                    spawner.spawn("d0", role="decode")]
        router = Router([h.endpoint() for h in replicas],
                        FleetConfig(enabled=True, retry_budget=4,
                                    retry_backoff_s=0.05,
                                    attempt_timeout_s=300.0,
                                    health_ttl_s=0.1,
                                    affinity_prefix_tokens=0))
        # pre-warm the compile caches through a full handoff route
        # before any clock starts
        warm = [2, 3, 5, 7]
        out = router.submit(warm, max_new_tokens=6).result(timeout=600)
        assert list(out) == reference(warm, 6)
        assert router.counters()["handoff_completed"] >= 1
        harness = DisaggChaosHarness(
            router, spawner, reference, replicas, seed=11,
            max_new_tokens=6, request_timeout_s=300.0,
            recovery_timeout_s=300.0, vocab=100)
        record = harness.run_episode(kind=kind)
        assert record["bitwise_mismatch"] == 0
        assert record["stuck"] == 0
        assert record["recovered"]
        assert record["pages_clean"]
        report = harness.report()
        assert report["invariant_pages_clean"]
        assert report["disagg_episodes"] == 1
    finally:
        if router is not None:
            router.close()
        spawner.stop_all()
