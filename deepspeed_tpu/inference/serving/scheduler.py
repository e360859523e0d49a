"""Continuous-batching scheduler: admission queue, bucketing, retirement.

Pure host-side policy — no jax. The scheduler decides *which* requests
run; the engine (engine.py) owns *how* (prefill/decode programs and the
KV pool). Keeping the policy import-light makes it unit-testable without
a device and reusable by any future engine variant.

Three decisions live here:

- **admission**: a bounded FIFO queue with named backpressure
  (``QueueFullError``) — under overload the caller learns immediately
  instead of the queue growing without bound; requests join the batch
  whenever a KV slot frees (join-at-free-slot), not at epoch boundaries.
- **bucketing**: prompt lengths are rounded up to a fixed ladder of
  bucket lengths, so the number of distinct prefill programs XLA ever
  compiles is bounded by ``len(buckets)`` no matter what lengths traffic
  brings (the recompile pin in tests/unit/test_serving.py).
- **retirement**: a sequence leaves its slot on EOS, on reaching its
  ``max_new_tokens``, or on blowing its per-request deadline
  (``RequestTimeoutError`` delivered through the request's future).
"""

import itertools
import threading
import time
from collections import deque


class QueueFullError(RuntimeError):
    """Admission queue is at capacity — backpressure signal to callers.

    Deliberately raised from ``submit()`` (not parked/blocked): a serving
    front-end under overload must shed or retry with its own policy."""


class EngineDrainingError(RuntimeError):
    """The engine is draining for a planned restart and admits nothing
    new; in-flight requests keep running to completion. A router should
    take the replica out of rotation and re-route, not retry here."""


class RequestTimeoutError(TimeoutError):
    """A request exceeded its deadline (queued or mid-decode) and was
    retired; delivered via the request's future."""

    def __init__(self, request_id, timeout_s, phase, tokens_done=0):
        self.request_id = request_id
        self.timeout_s = timeout_s
        self.phase = phase          # "queued" | "prefill" | "decoding"
        self.tokens_done = tokens_done
        super().__init__(
            f"request {request_id} exceeded its {timeout_s}s deadline "
            f"while {phase} ({tokens_done} token(s) generated)")


def default_buckets(max_prompt_len, smallest=8):
    """Power-of-two ladder up to (and including a cover of)
    ``max_prompt_len`` — log2 many prefill programs bound the compile
    count for arbitrary traffic."""
    buckets = []
    b = smallest
    while b < max_prompt_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_prompt_len)
    return tuple(buckets)


def bucket_for(length, buckets):
    """Smallest bucket >= length (buckets are validated ascending)."""
    for b in buckets:
        if length <= b:
            return b
    raise ValueError(
        f"prompt length {length} exceeds the largest bucket {buckets[-1]}")


class ServingFuture:
    """Result handle returned by ``submit()``.

    ``tokens`` is the streaming view (tokens emitted so far);
    ``result()`` blocks until retirement and returns the full token list
    or raises the retirement error (e.g. ``RequestTimeoutError``)."""

    def __init__(self, request_id):
        self.request_id = request_id
        self._tokens = []
        self._event = threading.Event()
        self._exc = None

    @property
    def tokens(self):
        return list(self._tokens)

    def done(self):
        return self._event.is_set()

    def result(self, timeout=None):
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not finished after {timeout}s "
                "(serving loop not running?)")
        if self._exc is not None:
            raise self._exc
        return list(self._tokens)

    # engine-side hooks
    def _append(self, token):
        self._tokens.append(token)

    def _finish(self, exc=None):
        self._exc = exc
        self._event.set()


class Request:
    """One generation request plus its in-flight state."""

    def __init__(self, request_id, prompt, max_new_tokens, eos_token_id=None,
                 timeout_s=None, stream_cb=None, submitted_at=None):
        self.id = request_id
        self.prompt = prompt                    # list[int]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.timeout_s = timeout_s              # None = no deadline
        self.stream_cb = stream_cb
        self.future = ServingFuture(request_id)
        # submitted_at (monotonic) backdates a request that already waited
        # elsewhere — a PoolExhaustedError requeue or a router re-route
        # must NOT reset the deadline clock or the TTFT percentiles.
        self.submit_time = (time.monotonic() if submitted_at is None
                            else float(submitted_at))
        self.first_token_time = None            # TTFT endpoint
        self.slot = None
        self.emitted = 0
        # the engine's stamp at this request's latest token, and how many
        # prefills the loop had run by then (token gaps, stalled or not)
        self.last_emit_time = None
        self.last_emit_prefill_seq = 0
        self.prefix_entry = None                # held prefix-cache ref
        self.attn_impl = "dense"                # set by engine at admission

    def deadline_exceeded(self, now):
        return (self.timeout_s is not None
                and now - self.submit_time > self.timeout_s)


class ContinuousBatchingScheduler:
    """Bounded admission queue + bucketing + retirement policy."""

    def __init__(self, max_queue, buckets, default_max_new_tokens=64,
                 request_timeout_s=0.0):
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        buckets = tuple(int(b) for b in buckets)
        if not buckets or any(b < 1 for b in buckets):
            raise ValueError(f"buckets must be positive ints, got {buckets}")
        if list(buckets) != sorted(set(buckets)):
            raise ValueError(
                f"buckets must be strictly ascending, got {buckets}")
        self.max_queue = int(max_queue)
        self.buckets = buckets
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.request_timeout_s = float(request_timeout_s)
        self._queue = deque()
        self._lock = threading.Lock()
        self._ids = itertools.count()
        # retirement counters (metrics reads these)
        self.completed = 0
        self.timed_out = 0
        # admission backpressure: requeue_front() calls (pool/chunk-lane
        # filled between pop and placement; pool-exhaustion requeues only
        # happen AFTER the engine attempted memory-pressure relief)
        self.requeues = 0

    def queue_depth(self):
        with self._lock:
            return len(self._queue)

    def submit(self, prompt, max_new_tokens=None, eos_token_id=None,
               timeout_s=None, stream_cb=None, submitted_at=None):
        """Enqueue a request; QueueFullError when at capacity.

        ``submitted_at`` (monotonic seconds) backdates the enqueue
        timestamp for a request that already waited somewhere else —
        e.g. one bounced off ``PoolExhaustedError`` backpressure or
        re-routed from a dead replica — so its deadline and TTFT clock
        keep running instead of silently resetting on retry."""
        if max_new_tokens is None:
            max_new_tokens = self.default_max_new_tokens
        if timeout_s is None and self.request_timeout_s > 0:
            timeout_s = self.request_timeout_s
        req = Request(next(self._ids), list(prompt), max_new_tokens,
                      eos_token_id=eos_token_id, timeout_s=timeout_s,
                      stream_cb=stream_cb, submitted_at=submitted_at)
        with self._lock:
            if len(self._queue) >= self.max_queue:
                raise QueueFullError(
                    f"admission queue is full ({self.max_queue} waiting); "
                    f"request rejected — retry with backpressure")
            self._queue.append(req)
        return req

    def enqueue(self, req):
        """Enqueue an ``adopt()``-minted request whose flags were set
        before it became visible to the serving loop (submit() races:
        the loop may admit between the append and any attribute write)."""
        with self._lock:
            if len(self._queue) >= self.max_queue:
                raise QueueFullError(
                    f"admission queue is full ({self.max_queue} waiting); "
                    f"request rejected — retry with backpressure")
            self._queue.append(req)
        return req

    def adopt(self, prompt, max_new_tokens=None, eos_token_id=None,
              timeout_s=None, stream_cb=None, submitted_at=None):
        """Mint a Request WITHOUT enqueueing it — for requests that
        bypass admission because their KV state already exists (a
        disaggregated handoff resume installs prefill-produced pages
        directly, so there is no prefill to queue for). The caller is
        responsible for activating the request on a pool slot."""
        if max_new_tokens is None:
            max_new_tokens = self.default_max_new_tokens
        if timeout_s is None and self.request_timeout_s > 0:
            timeout_s = self.request_timeout_s
        return Request(next(self._ids), list(prompt), max_new_tokens,
                       eos_token_id=eos_token_id, timeout_s=timeout_s,
                       stream_cb=stream_cb, submitted_at=submitted_at)

    def pop_expired(self, now):
        """Remove and return queued requests whose deadline passed while
        waiting (they must not waste a prefill)."""
        expired = []
        with self._lock:
            keep = deque()
            for req in self._queue:
                (expired if req.deadline_exceeded(now) else keep).append(req)
            self._queue = keep
        return expired

    def pop_next(self):
        """Next request to admit (FIFO), or None."""
        with self._lock:
            return self._queue.popleft() if self._queue else None

    def pop_matching(self, pred, max_n):
        """Pop up to ``max_n`` queued requests satisfying ``pred``,
        preserving FIFO order among them; non-matching requests keep
        their queue positions. The engine's batched-per-bucket prefill
        admission uses this to group same-bucket prompts into one
        prefill call."""
        if max_n < 1:
            return []
        taken = []
        with self._lock:
            keep = deque()
            for req in self._queue:
                if len(taken) < max_n and pred(req):
                    taken.append(req)
                else:
                    keep.append(req)
            self._queue = keep
        return taken

    def requeue_front(self, req):
        """Put an admitted-but-unplaced request back at the head (e.g. the
        pool filled between pop and placement)."""
        with self._lock:
            self._queue.appendleft(req)
            self.requeues += 1

    # -- retirement policy ---------------------------------------------
    def should_retire(self, req, token, stuck=False):
        """Retirement verdict after ``token`` was emitted for ``req``:
        'eos', 'length', or None (keep decoding). ``stuck`` (fault
        injection) suppresses both natural retirements so only the
        deadline can reap the request."""
        if stuck:
            return None
        if req.eos_token_id is not None and token == req.eos_token_id:
            return "eos"
        if req.emitted >= req.max_new_tokens:
            return "length"
        return None
