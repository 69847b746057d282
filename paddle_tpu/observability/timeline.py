"""Step timeline: a bounded ring buffer of per-step and per-compile events.

Where metrics.py answers "how many / how fast on average", the timeline
answers "what happened around step N": each Executor.run / run_loop /
ParallelExecutor.run dispatch appends one step event carrying wall time,
optional block-until-ready device time, feed/fetch byte volumes, and the
program fingerprint; every compile (executor AND Predictor) appends a
compile event with trace/XLA-compile timings and (when available) XLA
cost-analysis FLOPs/bytes estimates — the same numbers
tools/hlo_stats.py extracts from an xprof capture, obtained here
straight from the compiled executable. Per-request serving latency is
NOT a timeline event; it lives in the registry's
``paddle_tpu_predict_latency_ms`` histogram.

The buffer is a ``collections.deque(maxlen=...)``: recording is an O(1)
append and memory is bounded no matter how long the process serves.
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


def hlo_cost_stats(compiled) -> Optional[Dict[str, float]]:
    """FLOPs / bytes-accessed estimates from a ``jax.stages.Compiled``
    (the numbers tools/hlo_stats.py derives from a trace, minus the
    runtime). Returns None when the backend exposes no cost analysis."""
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
    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            try:
                capacity = int(os.environ.get("PADDLE_TPU_TIMELINE_CAP",
                                              _DEFAULT_CAP))
            except ValueError:
                capacity = _DEFAULT_CAP
        self._lock = threading.Lock()
        self._events = collections.deque(maxlen=max(1, capacity))
        self._seq = 0          # total events ever recorded
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
        """Make Executor compiles pay an extra explicit lower+compile to
        split trace/lowering time and attach XLA cost-analysis estimates
        (Predictor compiles get them for free — they are AOT already)."""
        self._hlo_cost = bool(on)

    def hlo_cost_enabled(self) -> bool:
        return self._hlo_cost

    # -- recording -------------------------------------------------------
    def _append(self, ev: Dict):
        with self._lock:
            ev["seq"] = self._seq
            self._seq += 1
            self._events.append(ev)

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
                       wall_ms: Optional[float] = None,
                       trace_ms: Optional[float] = None,
                       xla_ms: Optional[float] = None,
                       cache: str = "miss",
                       flops: Optional[float] = None,
                       bytes_accessed: Optional[float] = None,
                       **described):
        """``trace_ms`` is jax trace + StableHLO lowering (``fn.lower()``);
        ``xla_ms`` is the XLA backend compile (``lowered.compile()``) —
        usually the dominant term, and the one to blame for a slow first
        step. ``described`` is what the caller read off the executable
        (a decode step's ``cache_fed`` and ``cache_aliased``)."""
        ev = {"type": "compile", "ts": time.time(), "kind": kind,
              "cache": cache}
        if program is not None:
            ev["program"] = program
        for name, val in (("wall_ms", wall_ms), ("trace_ms", trace_ms),
                          ("xla_ms", xla_ms)):
            if val is not None:
                ev[name] = round(val, 4)
        if flops is not None:
            ev["flops"] = flops
        if bytes_accessed is not None:
            ev["bytes_accessed"] = bytes_accessed
        ev.update(described)
        self._append(ev)

    # -- reading ---------------------------------------------------------
    def snapshot(self) -> Dict:
        """JSON-able view: events oldest-first plus ring-buffer accounting
        (`dropped` = events that aged out of the buffer)."""
        with self._lock:
            events = [dict(e) for e in self._events]
            return {"capacity": self._events.maxlen,
                    "recorded": self._seq,
                    "dropped": self._seq - len(events),
                    "events": events}

    def events(self, type: Optional[str] = None) -> List[Dict]:
        with self._lock:
            evs = [dict(e) for e in self._events]
        if type is not None:
            evs = [e for e in evs if e["type"] == type]
        return evs

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.snapshot(), indent=indent, sort_keys=True)

    def reset(self):
        with self._lock:
            self._events.clear()
            self._seq = 0


TIMELINE = StepTimeline()


def get_timeline() -> StepTimeline:
    return TIMELINE
