"""From a profiler trace to numbers. ``capture`` wraps ``jax.profiler`` and
loads the ``.xplane.pb`` it writes into plain event tuples; everything after
that (``reduce_events`` and the helpers the metric readers call) works on
those tuples alone, so it is checked on a small recorded trace
(``tests/benchmark/data``) with no profiler and no chip.

An event is ``(device, line, name, start_s, dur_s)``: ``device`` the ordinal
of the chip's plane (``/device:TPU:<n>``), ``line`` one of ``modules`` (one
event per execution of a jitted program, named like ``jit__decode_step_jit``)
and ``ops`` (one per HLO operation, named by the operation and its result
shape, like ``copy.31_bf16_36_65_20_128_64_``).
"""

import glob
import os
import re
import shutil

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_LINES = {"XLA Modules": "modules", "XLA Ops": "ops"}
_HLO = re.compile(r"^%?([\w.\-]+) = \(?(\w+)\[([\d,]*)\]")
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")
# operations that only enclose others on the operations line (a scan's
# ``while`` spans its whole body): never counted as work of their own
CONTAINER = re.compile(r"^(while|conditional|call)[._]")


def op_label(hlo_text):
    """``%copy.31 = bf16[36,65,20,128,64]{...} copy(...)`` ->
    ``copy.31_bf16_36_65_20_128_64_``; text that is no HLO line is kept up to
    its first space."""
    m = _HLO.match(hlo_text)
    if not m:
        return hlo_text.split(" ")[0].lstrip("%")[:120]
    name, dtype, dims = m.groups()
    return f"{name}_{dtype}_{dims.replace(',', '_')}_"


def program_label(module_name):
    """``jit__decode_step_jit(1467310...)`` -> ``jit__decode_step_jit``."""
    return module_name.split("(")[0]


def load_events(xplane_path):
    """Device events of one ``.xplane.pb`` as plain tuples."""
    from jax.profiler import ProfileData

    events = []
    data = ProfileData.from_file(xplane_path)
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = int(m.group(1))
        for line in plane.lines:
            kind = _LINES.get(line.name)
            if kind is None:
                continue
            label = program_label if kind == "modules" else op_label
            for ev in line.events:
                events.append((dev, kind, label(ev.name),
                               ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return events


class Capture:
    """``with Capture(dir) as c:`` traces the block; afterwards ``c.events``
    holds the device events and the trace directory is removed."""

    def __init__(self, trace_dir):
        self.trace_dir = trace_dir
        self.events = []

    def start(self):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host overhead would distort
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    def stop(self):
        import jax

        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(self.trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        for p in paths:
            self.events.extend(load_events(p))
        shutil.rmtree(self.trace_dir, ignore_errors=True)


def _union(intervals):
    """Total length and merged list of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


class TraceSummary:
    """What the metric readers ask of a trace."""

    def __init__(self, events):
        self.events = events
        self.devices = sorted({e[0] for e in events})
        ops = [e for e in events if e[1] == "ops"]
        if ops:
            self.start = min(e[3] for e in ops)
            self.end = max(e[3] + e[4] for e in ops)
        else:
            self.start = self.end = 0.0
        self.window_s = self.end - self.start
        busy = []
        for d in self.devices:
            total, _ = _union([(e[3], e[3] + e[4]) for e in ops if e[0] == d])
            busy.append(total)
        self.busy_s = sum(busy) / len(busy) if busy else 0.0

    @property
    def idle_share(self):
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else None

    def op_time(self, pattern):
        """Mean over devices of the summed durations of operations whose
        label matches ``pattern`` (a compiled regex, ``search``)."""
        if not self.devices:
            return 0.0
        return sum(e[4] for e in self.events
                   if e[1] == "ops" and pattern.search(e[2])) / len(self.devices)

    def program_durations(self, name):
        """Device durations of each execution of the jitted program
        ``name`` on the first device."""
        if not self.devices:
            return []
        d0 = self.devices[0]
        return [e[4] for e in self.events
                if e[0] == d0 and e[1] == "modules" and e[2] == name]

    def program_time(self, names):
        """Mean over devices of the device time of the named programs."""
        if not self.devices:
            return 0.0
        return sum(e[4] for e in self.events
                   if e[1] == "modules" and e[2] in names) / len(self.devices)

    def top_ops(self, n=10):
        """The operations that took most device time (first device)."""
        if not self.devices:
            return []
        d0 = self.devices[0]
        total = {}
        for e in self.events:
            if e[0] == d0 and e[1] == "ops" and not CONTAINER.match(e[2]):
                total[e[2]] = total.get(e[2], 0.0) + e[4]
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in top]

    def idle_gaps(self, n=10):
        """The idle time of the first device by what ran on either side:
        gaps between consecutive program executions are named
        ``<before>-_<after>`` (``window_start`` / ``window_end`` at the
        edges), idle time inside a program ``within_<program>``."""
        if not self.devices:
            return []
        d0 = self.devices[0]
        mods = sorted((e[3], e[3] + e[4], e[2]) for e in self.events
                      if e[0] == d0 and e[1] == "modules")
        _, busy = _union([(e[3], e[3] + e[4]) for e in self.events
                          if e[0] == d0 and e[1] == "ops"])
        gaps = {}

        def add(name, dur):
            if dur > 0:
                gaps[name] = gaps.get(name, 0.0) + dur

        def idle_between(s, e):
            """Idle seconds inside [s, e]."""
            covered = sum(min(be, e) - max(bs, s) for bs, be in busy
                          if be > s and bs < e)
            return max(0.0, (e - s) - covered)

        prev_end, prev_name = self.start, "window_start"
        for s, e, name in mods:
            s, e = max(s, self.start), min(e, self.end)   # a cut-off program
            if s > prev_end:
                add(f"{prev_name}-_{name}", idle_between(prev_end, s))
            add(f"within_{name}", idle_between(max(s, prev_end), e))
            if e > prev_end:
                prev_end, prev_name = e, name
        if self.end > prev_end:
            add(f"{prev_name}-_window_end", idle_between(prev_end, self.end))
        top = sorted(gaps.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v] for k, v in top]

    def exposed_collective_s(self):
        """Mean over devices of the time in collective operations on the
        operations line. That line is what the core executes in order, so
        while a collective (or the ``-done`` half of an asynchronous one)
        holds it no other operation runs there: the time is exposed."""
        return self.op_time(COLLECTIVE)
