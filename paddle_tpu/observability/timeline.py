"""Step timeline: two bounded rings, one of per-step and one of
per-acquisition ("compile") events.

Where metrics.py answers "how many / how fast on average", the timeline
answers "what happened around step N": each Executor.run / run_loop /
ParallelExecutor.run dispatch appends one step event carrying wall time,
optional block-until-ready device time, feed/fetch byte volumes, and the
program fingerprint; every executable ACQUIRED (loaded from the AOT disk
tier, lowered and compiled, or traced inside a first call through
``jax.jit``: ``observability.observe_acquire``, the one writer) appends
a compile event with the executable's name, the path it came by, when
it began, its wall time and the parts of it, and (when available) XLA
cost-analysis FLOPs/bytes estimates, straight from the compiled
executable. Per-request serving latency is
NOT a timeline event; it lives in the registry's
``paddle_tpu_predict_latency_ms`` histogram.

Each ring is a ``collections.deque(maxlen=...)``: recording is an O(1)
append and memory is bounded no matter how long the process serves. The
compile events have a ring of their own (the ``TraceRecorder`` has two
for the same reason) because a trainer appends a step event a dispatch:
a thousand steps later the few dozen acquisitions that preceded them
would be gone from a shared ring, and they are what says where a
start-up's seconds went.
Recording is on by default (an append costs ~1 µs); the DEVICE-time fence
is opt-in (``set_device_time(True)``) because a block-until-ready per step
would serialize the async dispatch pipeline the executor is built around.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Dict, List, Optional

from . import tracing

__all__ = ["StepTimeline", "TIMELINE", "get_timeline", "hlo_cost_stats"]

_DEFAULT_CAP = 1024
# the compile ring: an acquisition is rare beside a step (dozens in a
# start-up, then one a new shape)
_COMPILE_CAP = 1024


def hlo_cost_stats(compiled) -> Optional[Dict[str, float]]:
    """FLOPs / bytes-accessed estimates from a ``jax.stages.Compiled``
    (the compiler's own; no trace, no runtime). Returns None when the
    backend exposes no cost analysis."""
    try:
        cost = compiled.cost_analysis()
        # some jax versions return a list with one dict per computation
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        if not isinstance(cost, dict):
            return None
        out = {}
        if "flops" in cost:
            out["flops"] = float(cost["flops"])
        if "bytes accessed" in cost:
            out["bytes_accessed"] = float(cost["bytes accessed"])
        return out or None
    except Exception:  # pragma: no cover - backend-dependent
        return None


class StepTimeline:
    RINGS = ("step", "compile")

    def __init__(self, capacity: Optional[int] = None,
                 compile_capacity: int = _COMPILE_CAP):
        if capacity is None:
            try:
                capacity = int(os.environ.get("PADDLE_TPU_TIMELINE_CAP",
                                              _DEFAULT_CAP))
            except ValueError:
                capacity = _DEFAULT_CAP
        self._lock = threading.Lock()
        self._rings = {
            "step": collections.deque(maxlen=max(1, capacity)),
            "compile": collections.deque(maxlen=max(1, compile_capacity))}
        self._recorded = dict.fromkeys(self.RINGS, 0)
        self._seq = 0          # total events ever recorded, both rings
        self._device_time = False
        self._hlo_cost = False

    # -- switches --------------------------------------------------------
    def set_device_time(self, on: bool):
        """Fence (block-until-ready) each step so events carry true device
        time. Serializes async dispatch — debugging/measurement only."""
        self._device_time = bool(on)

    def device_time_enabled(self) -> bool:
        return self._device_time

    def set_hlo_cost(self, on: bool):
        """Attach XLA cost-analysis estimates to Executor compile events
        (free on the AOT path; on the lazy-jit fallback it pays an extra
        explicit lower+compile, which also splits trace from XLA time).
        Predictor compiles always carry them."""
        self._hlo_cost = bool(on)

    def hlo_cost_enabled(self) -> bool:
        return self._hlo_cost

    # -- recording -------------------------------------------------------
    def _append(self, ev: Dict):
        with self._lock:
            ev["seq"] = self._seq
            self._seq += 1
            self._recorded[ev["type"]] += 1
            self._rings[ev["type"]].append(ev)

    def record_step(self, kind: str, wall_ms: float, *, steps: int = 1,
                    program: Optional[str] = None,
                    device_ms: Optional[float] = None,
                    feed_bytes: int = 0, fetch_bytes: int = 0):
        ev = {"type": "step", "ts": time.time(), "kind": kind,
              "wall_ms": round(wall_ms, 4), "steps": steps,
              "feed_bytes": int(feed_bytes), "fetch_bytes": int(fetch_bytes)}
        if program is not None:
            ev["program"] = program
        if device_ms is not None:
            ev["device_ms"] = round(device_ms, 4)
        self._append(ev)
        # mirror into the distributed-tracing flight recorder (rate-
        # sampled like request traces, through the one entry point for
        # spans that belong to no request) so a trainer's steps land on
        # the same trace_dump waterfall/clock as the serving spans and
        # loop iterations; free when PADDLE_TPU_TRACE_SAMPLE is 0
        if tracing.sampled():
            tracing.record_process_span("train.step", dur_ms=wall_ms,
                                        kind=kind, steps=steps)

    def record_compile(self, kind: str, program: Optional[str] = None, *,
                       ts: Optional[float] = None, cache: str = "miss",
                       **fields):
        """One acquisition's event; ``observability.observe_acquire`` is
        its one caller and documents the fields. ``ts`` is the START
        (now, where the caller gives none); a field that is None is left
        out, and one named ``*_ms`` is rounded."""
        ev = {"type": "compile", "ts": time.time() if ts is None else ts,
              "kind": kind, "cache": cache}
        if program is not None:
            ev["program"] = program
        for name, val in fields.items():
            if val is not None:
                ev[name] = round(val, 4) if name.endswith("_ms") else val
        self._append(ev)

    # -- reading ---------------------------------------------------------
    def _merged(self, type: Optional[str] = None) -> List[Dict]:
        # caller holds the lock. One ring is in the order recorded; both
        # are merged by `ts` (a compile event's is its start, and it is
        # appended when the acquisition ends)
        if type is not None:
            return [dict(e) for e in self._rings.get(type, ())]
        evs = [dict(e) for ring in self.RINGS for e in self._rings[ring]]
        evs.sort(key=lambda e: (e["ts"], e["seq"]))
        return evs

    def snapshot(self) -> Dict:
        """JSON-able view: the events of both rings oldest-first plus
        ring accounting (`dropped` = events that aged out; the totals at
        the top level with the step ring's `capacity`, each ring's own
        under `rings`)."""
        with self._lock:
            events = self._merged()
            rings = {}
            for ring in self.RINGS:
                dq, n = self._rings[ring], self._recorded[ring]
                rings[ring] = {"capacity": dq.maxlen, "recorded": n,
                               "dropped": n - len(dq)}
            return {"capacity": self._rings["step"].maxlen,
                    "recorded": self._seq,
                    "dropped": self._seq - len(events),
                    "rings": rings,
                    "events": events}

    def events(self, type: Optional[str] = None) -> List[Dict]:
        """The events of one ring (``"step"`` | ``"compile"``) in the
        order recorded, or of both merged by ``ts``."""
        with self._lock:
            return self._merged(type)

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.snapshot(), indent=indent, sort_keys=True)

    def reset(self):
        with self._lock:
            for ring in self.RINGS:
                self._rings[ring].clear()
                self._recorded[ring] = 0
            self._seq = 0


TIMELINE = StepTimeline()


def get_timeline() -> StepTimeline:
    return TIMELINE
