"""The comparisons that decide `correct`, and the line each prints."""
from __future__ import annotations

import numpy as np


def rel_l2(got, want) -> float:
    """||got - want|| / ||want|| in float64."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


class Checks:
    """Collects (name, value, limit) and prints each beside its limit."""

    def __init__(self):
        self.rows = []

    def add(self, name: str, value: float, limit: float):
        """`value` must be finite and <= `limit` (an exact comparison has
        the limit 0)."""
        ok = bool(np.isfinite(value)) and value <= limit
        self.rows.append((name, float(value), float(limit), ok))
        print("check %-40s %.6g  limit %.6g  %s"
              % (name, value, limit, "ok" if ok else "FAILED"), flush=True)
        return ok

    @property
    def ok(self) -> bool:
        return bool(self.rows) and all(r[3] for r in self.rows)
