"""Everything the harness knows about a cell comes from data: the entry of
``BENCHMARK.json``, the configuration's file, the traffic mix's file and one
reader file per per-layer metric, all found by name. Adding a cell, a
configuration, a traffic mix or a metric adds files and entries and edits
nothing that is there."""

import importlib.util
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    pass


class Cell:
    """One entry of ``workloads`` with its configuration and traffic files
    loaded."""

    def __init__(self, root, bench, workload):
        self.root = root
        self.bench = bench
        self.name = workload["name"]
        self.chips = int(workload["chips"])
        self.workload = workload
        cfg_entry = next((c for c in bench["configs"]
                          if c["name"] == workload["config"]), None)
        if cfg_entry is None:
            raise SpecError(f"cell {self.name}: no configuration "
                            f"{workload['config']!r} in BENCHMARK.json")
        self.config_entry = cfg_entry
        self.config = _load_json(os.path.join(root, cfg_entry["file"]))
        self.traffic_name = workload["traffic"]
        self.bench_dir = os.path.join(root, bench["paths"][0])
        self.traffic = _load_json(os.path.join(
            self.bench_dir, "traffic", self.traffic_name + ".json"))

    @property
    def limits(self):
        """The limits of ``correct``. They live in the configuration's file
        alone, which only a benchmark PR may change: ``check.limits``, with
        what ``check.limits_at_chips`` states for this cell's number of
        chips on top (a larger global batch averages more rounding noise
        away, so the control reads lower there too)."""
        check = self.config["check"]
        return dict(check["limits"],
                    **check.get("limits_at_chips", {}).get(str(self.chips), {}))

    def reports(self, metric):
        """Whether this cell reports ``metric`` (an entry of ``end_to_end``
        or ``per_layer``): it does unless the entry lists other cells."""
        cells = metric.get("workloads")
        return cells is None or self.name in cells

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self.reports(m)]

    def per_layer(self):
        return [m for m in self.bench["per_layer"] if self.reports(m)]


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root):
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(root, workload_name):
    bench = load_benchmark(root)
    for w in bench["workloads"]:
        if w["name"] == workload_name:
            return Cell(root, bench, w)
    raise SpecError(f"no workload {workload_name!r} in BENCHMARK.json; "
                    f"known: {[w['name'] for w in bench['workloads']]}")


def load_module(path, name):
    """Import one file by path (readers, model adapters and references are
    found by the name in a data file, not by an import list)."""
    if not os.path.exists(path):
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(bench_dir, metric_name):
    """The reader of one per-layer metric: ``metrics/<name>.py`` with a
    ``read(run)`` function that returns a number, or None when it finds
    nothing to read."""
    mod = load_module(os.path.join(bench_dir, "metrics", metric_name + ".py"),
                      "benchmark_metric_" + metric_name.replace(".", "_")
                      .replace("-", "_"))
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"metrics/{metric_name}.py defines no read(run)")
    return mod


def validate(bench):
    """The contract's rules on names, units and references between entries;
    returns a list of complaints (empty when the file is sound)."""
    bad = []
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for entry in bench.get(group, []):
            n = entry.get("name", "")
            if not NAME_RE.match(n):
                bad.append(f"{group}: bad name {n!r}")
            if n in seen:
                bad.append(f"{group}: duplicate name {n!r}")
            seen.add(n)
        if group in ("end_to_end", "per_layer"):
            if names & seen:
                bad.append(f"metric names repeated: {sorted(names & seen)}")
            names |= seen
    cfgs = {c["name"] for c in bench.get("configs", [])}
    cells = {w["name"] for w in bench.get("workloads", [])}
    e2e = {m["name"] for m in bench.get("end_to_end", [])}
    pairs = set()
    for w in bench.get("workloads", []):
        if w["config"] not in cfgs:
            bad.append(f"cell {w['name']}: unknown config {w['config']!r}")
        if not NAME_RE.match(w["traffic"]):
            bad.append(f"cell {w['name']}: bad traffic name")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"cell {w['name']}: pair repeated")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            bad.append(f"cell {w['name']}: why has {len(w['why'])} chars")
    for m in bench.get("end_to_end", []) + bench.get("per_layer", []):
        if not UNIT_RE.match(m.get("unit", "")):
            bad.append(f"metric {m['name']}: bad unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: better {m.get('better')!r}")
        if m.get("source") not in SOURCES:
            bad.append(f"metric {m['name']}: source {m.get('source')!r}")
        for c in m.get("workloads", []):
            if c not in cells:
                bad.append(f"metric {m['name']}: unknown cell {c!r}")
    for m in bench.get("end_to_end", []):
        if not 0 < m.get("bound", 0) <= 0.1:
            bad.append(f"metric {m['name']}: bound {m.get('bound')!r}")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"metric {m['name']}: end-to-end source {m['source']}")
    for m in bench.get("per_layer", []):
        if m.get("moves") not in e2e:
            bad.append(f"metric {m['name']}: moves {m.get('moves')!r}")
        if not 1 <= len(m.get("layer", "")) <= 200:
            bad.append(f"metric {m['name']}: layer")
    if "setup_s" not in e2e:
        bad.append("no setup_s among the end-to-end metrics")
    return bad
