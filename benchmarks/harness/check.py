"""How ``correct`` is decided: a list of numbers, each beside its limit.

A run is correct when every number is finite and at or under its limit (an
exact comparison has the limit 0) and nothing failed. Every run prints each
number compared beside its limit, so a reader of a run's output sees how far
from the edge it was."""

import math


class Comparison:
    def __init__(self):
        self.rows = []

    def add(self, name, value, limit, note=""):
        """``value`` must not exceed ``limit``. A missing or non-finite value
        fails."""
        ok = (value is not None and isinstance(value, (int, float))
              and math.isfinite(value) and value <= limit)
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": bool(ok), "note": note})
        return ok

    @property
    def correct(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def print(self, out):
        for r in self.rows:
            v = "none" if r["value"] is None else f"{r['value']:.6g}"
            print(f"[check] {r['name']}: {v} (limit {r['limit']:.6g}) "
                  f"{'ok' if r['ok'] else 'FAIL'} {r['note']}".rstrip(),
                  file=out, flush=True)

    def as_dict(self):
        return {r["name"]: r["value"] for r in self.rows}
