"""op_builder-style availability registry for the Pallas kernel tier.

Mirrors ``ops/op_builder.py``'s availability contract, upgraded from
*import* probing to *execution* probing: a kernel is available only if
a tiny instance of its Pallas implementation actually runs on this
backend (native on TPU, interpret mode elsewhere) and matches its XLA
twin. What a failed probe means depends on the backend:

- on a TPU it is an ERROR: ``resolve()`` raises ``KernelProbeError``
  with the compiler's message whenever Pallas was asked for (requested
  ``None`` or ``"pallas"``). The chip is what the kernels exist for, so
  a kernel that does not compile there must never be reported as
  running. ``requested="xla"`` stays the explicit way to get the twin.
- anywhere else (CPU development and CI, where the kernels run in
  interpret mode) it degrades to the composed-XLA twin with ONE
  edge-triggered ``jax/kernel_fallback`` telemetry instant per kernel.

The resolved selection is handed to callers as a plain string
("pallas" / "xla") that they thread into their jitted programs as a
STATIC argument — selection is part of every jit cache key, so a
changed selection can never serve a stale compiled program.
"""

import threading

import numpy as np

from deepspeed_tpu import telemetry

KERNEL_IMPL_CHOICES = ("pallas", "xla")


class KernelProbeError(RuntimeError):
    """A kernel's execution probe failed. Off-TPU it is carried in the
    registry's snapshot as the fallback reason; on a TPU backend
    ``resolve()`` raises it."""


def _on_tpu():
    import jax
    return jax.default_backend() == "tpu"


class _KernelSpec:
    __slots__ = ("name", "probe_fn", "doc")

    def __init__(self, name, probe_fn, doc=""):
        self.name = name
        self.probe_fn = probe_fn
        self.doc = doc


class KernelRegistry:
    """Availability + selection + telemetry for the kernel tier.

    ``probe(name)`` runs (once, cached) the kernel's tiny execution
    probe; ``resolve(name)`` turns a config request (None = probe
    result) into the ("pallas"|"xla", interpret) static pair;
    ``record_call(name, impl)`` feeds the ``Kernels/<name>/calls``
    counters the serving ``/snapshot`` and SLO rules read."""

    def __init__(self):
        self._specs = {}
        self._probe = {}           # name -> (ok, error-string-or-None)
        self._fallback_emitted = set()
        self._calls = {}           # name -> {"pallas": n, "xla": n}
        self._lock = threading.Lock()

    # -- registration ---------------------------------------------------
    def register(self, name, probe_fn, doc=""):
        """Register a kernel: ``probe_fn(interpret)`` must execute a tiny
        Pallas instance and raise on any failure (its return value is
        ignored — raising IS the unavailability signal)."""
        with self._lock:
            self._specs[name] = _KernelSpec(name, probe_fn, doc)
            self._probe.pop(name, None)
        return self

    def names(self):
        return tuple(sorted(self._specs))

    # -- probing --------------------------------------------------------
    @staticmethod
    def interpret_default():
        """Interpret mode everywhere but a real TPU backend: the same
        kernel body runs under CPU CI (eager, slow, bit-checkable) and
        compiles natively on TPU."""
        return not _on_tpu()

    def probe(self, name, interpret=None):
        """(ok, error) for ``name``, cached after the first execution.
        An unknown kernel is reported as unavailable; ``resolve()``
        decides whether that degrades or raises."""
        with self._lock:
            if name in self._probe:
                return self._probe[name]
        spec = self._specs.get(name)
        if spec is None:
            result = (False, f"unknown kernel {name!r}")
        else:
            try:
                spec.probe_fn(self.interpret_default()
                              if interpret is None else bool(interpret))
                result = (True, None)
            except Exception as e:  # noqa: BLE001 — any failure = unavailable
                result = (False, f"{type(e).__name__}: {e}")
        with self._lock:
            self._probe[name] = result
        return result

    def available(self, name):
        return self.probe(name)[0]

    # -- selection ------------------------------------------------------
    def resolve(self, name, requested=None, interpret=None):
        """The (impl, interpret) static pair a call site should thread
        into its jitted programs. ``requested`` is the config's
        ``attention_kernel`` value (None = default to the probe result);
        ``interpret`` the config's ``kernel_interpret`` (None = auto).
        On a TPU backend interpret mode is never selected and a failed
        probe raises ``KernelProbeError`` unless "xla" was requested;
        elsewhere a failed probe degrades to "xla" and emits the
        edge-triggered fallback instant."""
        if requested is not None and requested not in KERNEL_IMPL_CHOICES:
            raise ValueError(
                f"kernel impl must be one of {KERNEL_IMPL_CHOICES} or None "
                f"(= probe result), got {requested!r}")
        on_tpu = _on_tpu()
        if interpret and on_tpu:
            raise ValueError(
                "kernel_interpret=True on a TPU backend: the interpreter is "
                "for CPU runs only; the chip compiles the kernels natively")
        interp = (not on_tpu) if interpret is None else bool(interpret)
        if requested == "xla":
            return "xla", interp
        ok, err = self.probe(name)
        if ok:
            return "pallas", interp
        if on_tpu:
            raise KernelProbeError(
                f"kernel {name!r} failed its probe on the TPU backend and "
                f"attention_kernel={requested!r} asks for Pallas (set "
                f"attention_kernel='xla' to run the XLA twin on purpose): "
                f"{err}")
        self._emit_fallback(name, err)
        return "xla", interp

    def _emit_fallback(self, name, error):
        """ONE instant per failed kernel (edge-triggered), plus a
        registry counter so an SLO rule like
        {"metric": "Kernels/fallbacks_total", "max": 0} can alert on
        any (non-TPU) fleet member running the XLA twin."""
        with self._lock:
            if name in self._fallback_emitted:
                return
            self._fallback_emitted.add(name)
        telemetry.instant("jax/kernel_fallback", cat="lifecycle",
                          args={"kernel": name, "error": error})
        telemetry.get_registry().counter(
            "Kernels/fallbacks_total",
            help="kernels degraded from Pallas to the XLA fallback").inc()

    # -- telemetry ------------------------------------------------------
    def record_call(self, name, impl="pallas"):
        """Count one dispatch of ``name`` (host-side, at the call sites
        that invoke the kernel-bearing jitted programs)."""
        with self._lock:
            per = self._calls.setdefault(name, {"pallas": 0, "xla": 0})
            per[impl] = per.get(impl, 0) + 1
        telemetry.get_registry().counter(
            f"Kernels/{name}/calls",
            help="kernel-tier program dispatches").inc()

    def snapshot(self):
        """The serving ``/snapshot``'s ``kernels`` section: selection,
        availability, probe error, and call counts per kernel."""
        out = {}
        for name in self.names():
            probed = self._probe.get(name)
            ok, err = probed if probed is not None else (None, None)
            with self._lock:
                calls = dict(self._calls.get(name,
                                             {"pallas": 0, "xla": 0}))
            out[name] = {
                "available": ok,
                "probed": probed is not None,
                "selected": (None if ok is None
                             else ("pallas" if ok else "xla")),
                "interpret": self.interpret_default(),
                "probe_error": err,
                "calls": calls,
            }
        return out

    def export_gauges(self, registry=None):
        """Selected-backend gauges (1.0 = Pallas selected, 0.0 = XLA
        fallback) per kernel, as pull gauges on the shared metrics
        registry — rendered at /metrics scrape next to the counters."""
        reg = registry or telemetry.get_registry()

        def pull():
            vals = {}
            for name, snap in self.snapshot().items():
                sel = snap["selected"]
                if sel is not None:
                    vals[f"{name}/selected_pallas"] = float(sel == "pallas")
                    vals[f"{name}/interpret"] = float(bool(snap["interpret"]))
            return vals

        reg.gauge_fn("Kernels", pull,
                     help="kernel-tier backend selection (1 = Pallas)")

    # -- test hooks -----------------------------------------------------
    def force_probe_result(self, name, ok, error=None):
        """Test hook: pin a probe outcome (e.g. simulate a broken Pallas
        install) without monkeypatching jax internals."""
        with self._lock:
            self._probe[name] = (bool(ok),
                                 None if ok else (error or "forced"))
            if ok:
                self._fallback_emitted.discard(name)

    def reset(self):
        with self._lock:
            self._probe.clear()
            self._fallback_emitted.clear()
            self._calls.clear()


_registry = None
_registry_lock = threading.Lock()


def get_registry():
    """The process-global kernel registry, with the built-in kernels
    registered on first touch."""
    global _registry
    with _registry_lock:
        if _registry is None:
            _registry = KernelRegistry()
            _register_builtin(_registry)
        return _registry


def reset_registry():
    """Drop cached probe results/counters (tests)."""
    global _registry
    with _registry_lock:
        if _registry is not None:
            _registry.reset()


def record_call(name, impl="pallas"):
    get_registry().record_call(name, impl)


def registry_snapshot():
    return get_registry().snapshot()


def _register_builtin(reg):
    # imported lazily: the kernel modules import this one
    from deepspeed_tpu.kernels import decode_attention, sparse_attention

    reg.register("decode_attention", decode_attention.probe,
                 doc="fused paged decode attention (QK, mask, online "
                     "softmax, V-gather across the page table; int8 "
                     "pages consumed directly)")
    reg.register("sparse_attention", sparse_attention.probe,
                 doc="banded sink+window block-sparse attention "
                     "(the sparse_xla seam's band)")


def assert_probe_parity(name, got, want, interpret):
    """Probe-side parity check (numpy — probes run outside any trace).
    Under the interpreter both sides run the same literal math, so the
    bound is rounding-order noise; natively Mosaic and XLA:TPU order
    their f32 accumulations differently, so the chip gets an
    f32-accumulation bound (a wrong mask or page shows up at 1e-1)."""
    tol = 1e-5 if interpret else 1e-3
    got, want = np.asarray(got), np.asarray(want)
    if not np.allclose(got, want, rtol=tol, atol=tol):
        raise KernelProbeError(
            f"{name} probe mismatch vs its XLA twin: max abs err "
            f"{float(np.max(np.abs(got - want))):.3g} (tolerance {tol:g})")
