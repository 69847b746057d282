"""Exposition: Prometheus text format + JSON snapshots of the registry.

``to_prometheus()`` renders the registry in the Prometheus text exposition
format (version 0.0.4) — the payload ``PredictorServer``'s ``/metrics``
endpoint serves and a scrape job ingests directly. ``to_json()`` bundles
the same data with the step timeline for humans and dashboards.
``counters_state``/``delta_state`` give cheap before/after diffs so a
caller (a benchmark run, a test) can attach "what this block of work
cost" without resetting anyone else's metrics.
"""
from __future__ import annotations

import json
import math
from typing import Dict, Optional

from .metrics import (Counter, Gauge, Histogram, MetricRegistry, Summary,
                      REGISTRY, process_labels)
from .timeline import TIMELINE, StepTimeline

__all__ = [
    "to_prometheus", "to_json", "dumps_json",
    "counters_state", "delta_state", "merge_json_snapshots",
]


def _escape(value: str) -> str:
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _labels_str(labels: Dict[str, str], extra: Optional[Dict] = None) -> str:
    items = dict(labels)
    if extra:
        items.update(extra)
    if not items:
        return ""
    body = ",".join('%s="%s"' % (k, _escape(v))
                    for k, v in sorted(items.items()))
    return "{%s}" % body


def _fmt(v: float) -> str:
    if isinstance(v, float) and math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    f = float(v)
    return repr(int(f)) if f == int(f) else repr(f)


def to_prometheus(registry: Optional[MetricRegistry] = None) -> str:
    """Prometheus text exposition of every registered metric. Metrics with
    no series yet still emit HELP/TYPE plus (for unlabeled counters and
    gauges) an explicit 0 sample, so scrape dashboards see the full
    catalogue from the first scrape."""
    registry = registry or REGISTRY
    proc = process_labels()  # replica identity, when set (fleet workers)
    out = []
    for m in registry.collect():
        samples = [(dict(proc, **labels), v) for labels, v in m.samples()]
        kind = "summary" if isinstance(m, Summary) else m.kind
        out.append("# HELP %s %s" % (m.name, _escape(m.help or m.name)))
        out.append("# TYPE %s %s" % (m.name, kind))
        if isinstance(m, (Counter, Gauge)):
            if not samples:
                out.append("%s%s 0" % (m.name, _labels_str(proc)))
            for labels, value in samples:
                out.append("%s%s %s" % (m.name, _labels_str(labels),
                                        _fmt(value)))
        elif isinstance(m, Histogram):
            for labels, v in samples:
                cum = 0
                for ub, n in zip(m.buckets, v[:len(m.buckets)]):
                    cum += n
                    out.append("%s_bucket%s %d" % (
                        m.name, _labels_str(labels, {"le": _fmt(ub)}), cum))
                cum += v[len(m.buckets)]  # overflow
                out.append("%s_bucket%s %d" % (
                    m.name, _labels_str(labels, {"le": "+Inf"}), cum))
                out.append("%s_sum%s %s" % (m.name, _labels_str(labels),
                                            _fmt(v[-2])))
                out.append("%s_count%s %d" % (m.name, _labels_str(labels),
                                              v[-1]))
        elif isinstance(m, Summary):
            for labels, v in samples:
                ls = _labels_str(labels)
                out.append("%s_count%s %d" % (m.name, ls, v[0]))
                out.append("%s_sum%s %s" % (m.name, ls, _fmt(v[1])))
                out.append("%s_min%s %s" % (m.name, ls, _fmt(v[2])))
                out.append("%s_max%s %s" % (m.name, ls, _fmt(v[3])))
    return "\n".join(out) + "\n"


def to_json(registry: Optional[MetricRegistry] = None,
            timeline: Optional[StepTimeline] = None,
            include_timeline: bool = True) -> Dict:
    """JSON-able snapshot: {"metrics": {name: {kind, help, series}},
    "timeline": <timeline snapshot>}."""
    registry = registry or REGISTRY
    proc = process_labels()
    metrics = {}
    for m in registry.collect():
        series = []
        for labels, v in m.samples():
            labels = dict(proc, **labels)
            if isinstance(m, Histogram):
                series.append({"labels": labels,
                               "buckets": dict(zip(
                                   [_fmt(b) for b in m.buckets] + ["+Inf"],
                                   v[:len(m.buckets) + 1])),
                               "sum": v[-2], "count": v[-1]})
            elif isinstance(m, Summary):
                series.append({"labels": labels, "count": v[0], "sum": v[1],
                               "min": v[2], "max": v[3]})
            else:
                series.append({"labels": labels, "value": v})
        metrics[m.name] = {"kind": m.kind, "help": m.help, "series": series}
    out = {"metrics": metrics}
    if proc:
        out["replica"] = proc.get("replica")
    if include_timeline:
        out["timeline"] = (timeline or TIMELINE).snapshot()
    return out


def dumps_json(registry: Optional[MetricRegistry] = None,
               timeline: Optional[StepTimeline] = None,
               indent: Optional[int] = None,
               include_timeline: bool = True) -> str:
    return json.dumps(to_json(registry, timeline, include_timeline),
                      indent=indent, sort_keys=True)


def merge_json_snapshots(snapshots) -> Dict:
    """Aggregate several ``to_json()`` snapshots (one per fleet worker /
    per dump file) into one: series whose label sets match are SUMMED
    (counters, gauges, histogram buckets, summary count/sum; summary
    min/max take the min/max), distinct label sets stay distinct — so
    dumps whose series carry a ``replica`` label merge collision-free
    while the per-metric totals a dashboard wants come from summing the
    label dimension away downstream, exactly the Prometheus model.
    Timelines are per-process and are NOT merged (dropped); the output
    records the source replicas under ``"replicas"``."""
    merged: Dict = {"metrics": {}, "replicas": []}
    out_metrics = merged["metrics"]
    for snap in snapshots:
        rep = snap.get("replica")
        if rep is not None:
            merged["replicas"].append(rep)
        for name, m in (snap.get("metrics") or {}).items():
            om = out_metrics.setdefault(
                name, {"kind": m.get("kind"), "help": m.get("help"),
                       "series": []})
            index = {tuple(sorted((s.get("labels") or {}).items())): s
                     for s in om["series"]}
            for s in m.get("series") or ():
                key = tuple(sorted((s.get("labels") or {}).items()))
                dst = index.get(key)
                if dst is None:
                    import copy

                    dst = copy.deepcopy(s)
                    om["series"].append(dst)
                    index[key] = dst
                    continue
                if "buckets" in s:  # histogram
                    for ub, n in (s.get("buckets") or {}).items():
                        dst["buckets"][ub] = dst["buckets"].get(ub, 0) + n
                    dst["sum"] += s.get("sum", 0)
                    dst["count"] += s.get("count", 0)
                elif "min" in s:  # summary
                    dst["count"] += s.get("count", 0)
                    dst["sum"] += s.get("sum", 0)
                    dst["min"] = min(dst["min"], s.get("min", dst["min"]))
                    dst["max"] = max(dst["max"], s.get("max", dst["max"]))
                else:  # counter / gauge
                    dst["value"] = dst.get("value", 0) + s.get("value", 0)
    return merged


def counters_state(registry: Optional[MetricRegistry] = None) -> Dict[str, float]:
    """Flat {"name{a=b}": value} state of counters plus histogram/summary
    sums and counts — the before-image for delta_state()."""
    registry = registry or REGISTRY
    state: Dict[str, float] = {}
    for m in registry.collect():
        for labels, v in m.samples():
            key = m.name + _labels_str(labels)
            if isinstance(m, Counter):
                state[key] = float(v)
            elif isinstance(m, (Histogram, Summary)):
                if isinstance(m, Summary):
                    count, total = v[0], v[1]
                else:
                    count, total = v[-1], v[-2]
                state[key + "#count"] = float(count)
                state[key + "#sum"] = float(total)
    return state


def delta_state(before: Dict[str, float],
                registry: Optional[MetricRegistry] = None) -> Dict[str, float]:
    """What moved since ``before`` (a counters_state snapshot): only
    positive deltas are returned (a mid-phase registry reset would
    otherwise surface as a wall of negative counters)."""
    after = counters_state(registry)
    out = {}
    for key, val in after.items():
        d = val - before.get(key, 0.0)
        if d > 0:
            out[key] = d
    return out
