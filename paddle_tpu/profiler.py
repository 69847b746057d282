"""Profiler: wall-clock stats for compile/run events + XLA trace capture.

Reference: python/paddle/fluid/profiler.py (start/stop_profiler, profiler
context manager, reset_profiler, cuda_profiler). The reference times every
op kernel launch; here a whole Program executes as ONE fused XLA
computation, so the meaningful events are per-program compiles and step
executions (plus compile-cache hits/misses), and deep per-op timelines come
from the XLA trace viewer via ``jax.profiler`` (`tpu_trace`).

This module is now a thin compatibility shim over
``paddle_tpu.observability``: events recorded while profiling is on live in
the registry's ``paddle_tpu_profiler_event_ms`` summary (exact
count/sum/min/max per event — the reference report's columns), and
``reset_profiler`` performs the registry-wide reset. The always-on metrics
(compile cache, step latency, serving) record regardless of the
start/stop window; this window only gates the legacy event table.

Each event also lands as a span in the distributed-tracing flight
recorder (``observability.tracing.record_process_span``, the one way in
for spans that belong to no request) — so a legacy ``with
profiler.profiler():`` window gets a timeline in
``tools/trace_dump.py`` (text waterfall / Chrome trace JSON) for free,
on the same clock as the serving spans. The start/stop window IS the
opt-in; the spans cost nothing while profiling is off.
"""
from __future__ import annotations

import contextlib
import warnings
from typing import Optional

from . import observability as _obs
from .observability import tracing as _tracing

__all__ = [
    "cuda_profiler", "reset_profiler", "start_profiler", "stop_profiler",
    "profiler", "tpu_trace",
]

_enabled = False
_cache_stats = {"hits": 0, "misses": 0}


def is_profiling() -> bool:
    return _enabled


# -- hooks called by the executors --------------------------------------


def record_event(name: str, seconds: float):
    if _enabled:
        _obs.PROFILER_EVENT_MS.observe(seconds * 1e3, event=name)
        _tracing.record_process_span("profiler." + name,
                                     dur_ms=seconds * 1e3)


def record_cache(hit: bool):
    if _enabled:
        _cache_stats["hits" if hit else "misses"] += 1


def cache_stats():
    """Compile-cache stats within the profiling window (SURVEY aux:
    tracing / compile-cache stats). The always-on equivalents are the
    ``paddle_tpu_compile_cache_*_total`` registry counters."""
    return dict(_cache_stats)


# -- reference API -------------------------------------------------------


def reset_profiler():
    """Clear the event table — and, since the table lives in the
    observability registry now, the whole registry and step timeline with
    it (one reset clears everything, as the reference's global reset)."""
    _obs.reset_all()
    _cache_stats["hits"] = 0
    _cache_stats["misses"] = 0


def start_profiler(state="All"):
    """reference profiler.py:start_profiler. `state` ('CPU'/'GPU'/'All') is
    accepted for compatibility; there is one device timeline on TPU."""
    global _enabled
    if state not in ("CPU", "GPU", "All"):
        raise ValueError("The state must be 'CPU' or 'GPU' or 'All'.")
    _enabled = True


def _event_rows():
    """(name, calls, total_s, avg_s, min_s, max_s) per recorded event."""
    rows = []
    for labels, v in _obs.PROFILER_EVENT_MS.samples():
        calls, total_ms, min_ms, max_ms = v
        rows.append((labels.get("event", "?"), calls, total_ms / 1e3,
                     total_ms / 1e3 / max(calls, 1), min_ms / 1e3,
                     max_ms / 1e3))
    return rows


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    """Stop and emit the event table (reference profiler.py:stop_profiler).
    sorted_key in {None, 'calls', 'total', 'max', 'min', 'ave'} — each
    sorts descending by that column (min/max are tracked per event)."""
    global _enabled
    _enabled = False
    rows = _event_rows()
    if sorted_key == "calls":
        rows.sort(key=lambda r: -r[1])
    elif sorted_key == "total":
        rows.sort(key=lambda r: -r[2])
    elif sorted_key == "ave":
        rows.sort(key=lambda r: -r[3])
    elif sorted_key == "min":
        rows.sort(key=lambda r: -r[4])
    elif sorted_key == "max":
        rows.sort(key=lambda r: -r[5])
    lines = ["%-50s %8s %12s %12s %12s %12s"
             % ("Event", "Calls", "Total(ms)", "Min(ms)", "Max(ms)",
                "Avg(ms)")]
    for name, calls, total, avg, mn, mx in rows:
        lines.append("%-50s %8d %12.3f %12.3f %12.3f %12.3f"
                     % (name[:50], calls, total * 1e3, mn * 1e3, mx * 1e3,
                        avg * 1e3))
    lines.append("compile cache: %(hits)d hits / %(misses)d misses"
                 % _cache_stats)
    report = "\n".join(lines)
    print(report)
    if profile_path:
        try:
            with open(profile_path, "w") as f:
                f.write(report + "\n")
        except OSError as e:
            warnings.warn("could not write profile to %s: %s" % (profile_path, e))
    return report


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile"):
    """reference profiler.py:profiler context manager."""
    start_profiler(state)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(output_file, output_mode=None, config=None):
    """CUDA-only in the reference; a warning no-op on TPU (use tpu_trace)."""
    warnings.warn("cuda_profiler is a no-op on TPU; use "
                  "profiler.tpu_trace(log_dir) for an XLA trace")
    yield


@contextlib.contextmanager
def tpu_trace(log_dir: str, host_tracer_level: Optional[int] = None):
    """Capture a jax.profiler trace viewable in TensorBoard/Perfetto —
    the TPU equivalent of the reference's per-kernel timeline."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
