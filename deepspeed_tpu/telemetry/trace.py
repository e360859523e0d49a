"""Chrome-trace span/event tracer: bounded ring buffer, deferred rendering.

Design constraints (they are the whole point):

- **Hot-path work is timestamps only.** Opening/closing a span records two
  ``time.perf_counter()`` floats and appends ONE tuple to a
  ``collections.deque`` — no string formatting, no dict churn, no JSON, no
  host syncs, so jaxlint JL002 and ``transfer_free()`` stay green when the
  training/serving hot loops are traced. All Chrome-trace-event rendering
  is deferred to :meth:`Tracer.events` / :meth:`Tracer.to_chrome_trace`,
  which run off the hot path (test asserts, ``/trace`` scrapes, shutdown).
- **Bounded.** The ring buffer is ``deque(maxlen=max_events)``: a
  long-running server drops the oldest spans instead of growing without
  limit. Dropped-event count is tracked so a truncated trace says so.
- **Provably free when disabled.** ``span()`` on a disabled tracer returns
  a single module-level no-op object (``NULL_SPAN``) — no per-call
  allocation — and ``instant()`` returns before touching the clock. Hot
  loops additionally guard on ``tracer.enabled`` (one attribute read) so
  even argument construction is skipped.

- **One span system, two sinks.** A process that imports JAX installs an
  annotation factory (:meth:`Tracer.set_annotation_factory`, given
  ``jax.profiler.TraceAnnotation`` by the engines): an armed span then also
  opens one such annotation, so under a profiler session the host span
  lands in the profiler's own trace, on its clock, beside the device's
  programs. Scalar span arguments go along as the annotation's keyword
  arguments; lists (request ids) stay with the ring buffer. Without a
  factory, or with no session open, nothing changes.

The emitted JSON is the Chrome trace event format (load in Perfetto or
``chrome://tracing``): complete events ``ph="X"`` with ``ts``/``dur`` in
microseconds, instant events ``ph="i"``, one ``pid`` per process and the
recording thread's ident as ``tid``.

This module is stdlib-only on purpose: the launcher supervisor (itself
stdlib-only) serves traces too, and must not drag jax into its process.
"""

import json
import os
import threading
import time
from collections import deque

PH_COMPLETE = "X"
PH_INSTANT = "i"
PH_METADATA = "M"

_DEFAULT_MAX_EVENTS = 65536

# span arguments cheap enough to ride on a profiler annotation
_SCALARS = (int, float, str, bool)


class _NullSpan:
    """Shared no-op context manager returned by disabled tracers."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


NULL_SPAN = _NullSpan()


class _Span:
    """Live span: ``__enter__`` stamps t0, ``__exit__`` stamps t1 and
    appends one tuple. Everything else happens at render time."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_t0", "_annotation")

    def __init__(self, tracer, name, cat, args):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._t0 = 0.0
        self._annotation = None

    def __enter__(self):
        factory = self._tracer._annotation_factory
        if factory is not None:
            scalars = {k: v for k, v in self._args.items()
                       if isinstance(v, _SCALARS)} if self._args else {}
            self._annotation = factory(self._name, **scalars)
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        self._tracer._append(
            (PH_COMPLETE, self._name, self._cat, self._t0, t1 - self._t0,
             threading.get_ident(), self._args))
        return False


class Tracer:
    """Span/instant recorder over a bounded ring buffer."""

    def __init__(self, enabled=False, max_events=_DEFAULT_MAX_EVENTS):
        self.enabled = bool(enabled)
        self._events = deque(maxlen=int(max_events))
        self._dropped = 0
        # Two clocks sampled back-to-back: ts values are rendered relative
        # to the perf_counter epoch (monotonic, sub-us), while epoch_unix
        # pins that epoch to wall-clock time so a fleet collector can
        # rebase traces from different processes onto one timeline.
        self._epoch = time.perf_counter()
        self.epoch_unix = time.time()
        self._process_info = None
        self._annotation_factory = None
        self._lock = threading.Lock()   # drain/render only; appends rely on GIL

    # -- configuration --------------------------------------------------
    def configure(self, enabled, max_events=None):
        """Re-arm (or disarm) the tracer in place. Shrinking ``max_events``
        keeps the newest events. Used by ``telemetry.configure_from_config``
        so engines constructed later see the same global tracer."""
        if max_events is not None and int(max_events) != self._events.maxlen:
            with self._lock:
                self._events = deque(self._events, maxlen=int(max_events))
        self.enabled = bool(enabled)
        return self

    @property
    def max_events(self):
        return self._events.maxlen

    def set_annotation_factory(self, factory):
        """``factory(name, **scalar_args)`` returns a context manager that
        every armed span opens beside its ring-buffer record (``None``
        takes it out again). The engines pass
        ``jax.profiler.TraceAnnotation``; this module never imports it."""
        self._annotation_factory = factory
        return self

    def set_process_info(self, rank=None, role=None, label=None,
                         sort_index=None):
        """Stamp process identity onto the trace. Rendered as Chrome ``M``
        (metadata) records — ``process_name``/``process_sort_index`` — so a
        single-process trace opens in Perfetto with a named lane and a
        multi-rank merge needs no guesswork. ``None`` fields leave any
        previously-set value alone; repeated calls merge."""
        info = dict(self._process_info or {})
        if rank is not None:
            info["rank"] = int(rank)
        if role is not None:
            info["role"] = str(role)
        if label is not None:
            info["label"] = str(label)
        if sort_index is not None:
            info["sort_index"] = int(sort_index)
        self._process_info = info or None
        return self

    @property
    def process_info(self):
        return dict(self._process_info) if self._process_info else None

    # -- hot path -------------------------------------------------------
    def span(self, name, cat="train", args=None):
        """Context manager timing a region. ``args`` must be a dict of
        JSON-serializable host values (request ids, counts) or None."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, cat, args)

    def instant(self, name, cat="lifecycle", args=None):
        """Point-in-time event (lifecycle transitions: rollback,
        preemption, restart, elastic resume, recompile)."""
        if not self.enabled:
            return
        self._append((PH_INSTANT, name, cat, time.perf_counter(), 0.0,
                      threading.get_ident(), args))

    def _append(self, rec):
        if len(self._events) == self._events.maxlen:
            self._dropped += 1
        self._events.append(rec)

    # -- cold path ------------------------------------------------------
    def __len__(self):
        return len(self._events)

    @property
    def dropped(self):
        return self._dropped

    def clear(self):
        with self._lock:
            self._events.clear()
            self._dropped = 0

    def events(self, drain=False):
        """Render the buffered records as Chrome trace event dicts
        (oldest first). ``drain=True`` empties the ring buffer."""
        with self._lock:
            if drain:
                recs = []
                while True:
                    try:
                        recs.append(self._events.popleft())
                    except IndexError:
                        break
            else:
                recs = list(self._events)
        pid = os.getpid()
        out = self._metadata_events(pid)
        for ph, name, cat, t0, dur, tid, args in recs:
            ev = {
                "ph": ph,
                "name": name,
                "cat": cat,
                "ts": (t0 - self._epoch) * 1e6,
                "pid": pid,
                "tid": tid,
            }
            if ph == PH_COMPLETE:
                ev["dur"] = dur * 1e6
            else:
                ev["s"] = "t"       # instant scope: thread
            if args:
                ev["args"] = dict(args)
            out.append(ev)
        return out

    def _metadata_events(self, pid):
        """Chrome ``M`` records for process identity (empty when unset).
        Synthesized at render time so they survive ``drain=True`` and ring
        overflow; ``ts``/``tid`` are zero by Chrome convention but present
        so every emitted event carries the same required keys."""
        info = self._process_info
        if not info:
            return []
        rank = info.get("rank")
        role = info.get("role")
        label = info.get("label")
        if label is None:
            parts = ([str(role)] if role is not None else []) \
                + ([f"rank{rank}"] if rank is not None else [])
            label = " ".join(parts) or f"pid{pid}"
        sort_index = info.get(
            "sort_index", rank if isinstance(rank, int) and rank >= 0 else 0)
        name_args = {"name": label, "os_pid": pid}
        if rank is not None:
            name_args["rank"] = rank
        if role is not None:
            name_args["role"] = role
        return [
            {"ph": PH_METADATA, "name": "process_name", "cat": "__metadata",
             "ts": 0, "pid": pid, "tid": 0, "args": name_args},
            {"ph": PH_METADATA, "name": "process_sort_index",
             "cat": "__metadata", "ts": 0, "pid": pid, "tid": 0,
             "args": {"sort_index": sort_index}},
        ]

    def to_chrome_trace(self, drain=False):
        """The full JSON-object trace form Perfetto/chrome://tracing load."""
        meta = {"epoch_unix": self.epoch_unix}
        if self._process_info:
            meta.update(self._process_info)
        if self._dropped:
            meta["dropped_events"] = self._dropped
        return {"traceEvents": self.events(drain=drain),
                "displayTimeUnit": "ms",
                "metadata": meta}

    def write(self, path, drain=False):
        doc = self.to_chrome_trace(drain=drain)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path
