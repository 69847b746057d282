"""Render a fleet trace snapshot: text waterfall / JSON / Chrome trace.

Input is the merged span list ``Router.fleet_trace()`` returns — the
same document ``GET /trace.json`` serves and the loadgen verdict's
``trace_phases`` is derived from. A single-process recorder snapshot
(``observability.tracing.snapshot()``) is accepted too and merged on
the fly. Three output modes:

* default — a per-request text waterfall: one block per trace_id, one
  line per span with its offset from trace start, duration, origin
  replica, and a proportional bar. The fastest way to answer "where
  did this request's 40 ms go?" at a terminal.
* ``--json`` — a structured ``trace_dump/1`` document (schema-pinned by
  tests/test_trace_dump_smoke.py): spans grouped per trace with start
  time and total extent, plus the fleet ring accounting.
* ``--chrome`` — Chrome trace-event JSON (the ``traceEvents`` array
  format): load it in Perfetto / chrome://tracing and every replica is
  a process row, every trace a thread row, every span a slice. A
  process-scoped record with phases (``tracing.phase``: one
  ``decode.loop.iter`` per loop iteration) renders its phases as child
  slices, here and in the waterfall.

Stays OFF the jax import path entirely (the metrics_dump --merge
trick): rendering is pure dict arithmetic and the observability
subtree is jax-free, so a trace sidecar pays ~ms, not a framework
import. ``--demo`` synthesizes a two-process request trace through the
real ``merge_snapshots`` path — a fixture for the smoke test and a
format preview that needs no fleet.

Usage:
    curl -s localhost:8000/trace.json | python tools/trace_dump.py
    python tools/trace_dump.py --input fleet_trace.json --chrome > t.json
    python tools/trace_dump.py --demo --json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import types
from typing import Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCHEMA = "trace_dump/1"

# span keys that are structure, not user attrs (everything else prints
# in the waterfall's attr column)
_CORE_KEYS = frozenset(("trace_id", "name", "ts", "dur_ms", "seq",
                        "replica", "phases", "depth"))


def _import_tracing():
    """paddle_tpu.observability.tracing without the parent package's
    jax-importing __init__ (bare namespace stub with the right
    __path__ — the metrics_dump --merge idiom)."""
    if "paddle_tpu" not in sys.modules:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        stub = types.ModuleType("paddle_tpu")
        stub.__path__ = [os.path.join(root, "paddle_tpu")]
        sys.modules["paddle_tpu"] = stub
    from paddle_tpu.observability import tracing
    return tracing


def demo_snapshot() -> Dict:
    """A deterministic two-process fleet trace (router + one worker,
    one served request + one shed request) through the REAL
    merge_snapshots path — the smoke-test fixture."""
    tracing = _import_tracing()
    base = 1700000000.0
    tid = "deadbeef4ee75ace"
    shed = "deadbeef00005hed"
    router = {
        "capacity": 4096, "recorded": 6, "dropped": 0, "replica": "",
        "spans": [
            {"trace_id": tid, "name": "client.submit", "ts": base,
             "dur_ms": 0.0, "seq": 0, "rid": 1, "klass": "interactive"},
            {"trace_id": tid, "name": "router.queue", "ts": base,
             "dur_ms": 1.8, "seq": 1, "rid": 1, "klass": "interactive"},
            {"trace_id": tid, "name": "router.dispatch",
             "ts": base + 0.0018, "dur_ms": 0.0, "seq": 2, "rid": 1,
             "replica": "w0"},
            {"trace_id": tid, "name": "router.reply",
             "ts": base + 0.0018, "dur_ms": 6.4, "seq": 3, "rid": 1,
             "error": False},
            {"trace_id": shed, "name": "client.submit",
             "ts": base + 0.001, "dur_ms": 0.0, "seq": 4, "rid": 2,
             "klass": "batch"},
            {"trace_id": shed, "name": "router.shed",
             "ts": base + 0.001, "dur_ms": 3.1, "seq": 5, "rid": 2,
             "reason": "expired", "dominant_phase": "queue"},
        ]}
    worker = {
        "capacity": 4096, "recorded": 4, "dropped": 0, "replica": "w0",
        "spans": [
            {"trace_id": tid, "name": "worker.recv", "ts": base + 0.0021,
             "dur_ms": 0.0, "seq": 0, "rid": 7},
            {"trace_id": tid, "name": "server.stack", "ts": base + 0.0034,
             "dur_ms": 0.9, "seq": 1, "rid": 7, "rows": 4, "bucket": 4},
            {"trace_id": tid, "name": "server.device", "ts": base + 0.0043,
             "dur_ms": 3.2, "seq": 2, "rid": 7},
            {"trace_id": tid, "name": "worker.reply", "ts": base + 0.0021,
             "dur_ms": 5.9, "seq": 3, "rid": 7},
        ]}
    return tracing.merge_snapshots([router, worker])


def load_snapshot(path: str) -> Dict:
    """Load a fleet_trace() document — or a single recorder snapshot,
    normalized through merge_snapshots so both shapes render."""
    if path == "-":
        snap = json.load(sys.stdin)
    else:
        with open(path) as f:
            snap = json.load(f)
    if "spans" not in snap:
        raise SystemExit("trace_dump: %s carries no 'spans' list "
                         "(expected a /trace.json or tracing.snapshot() "
                         "document)" % path)
    if "replicas" not in snap:  # single-process recorder snapshot
        snap = _import_tracing().merge_snapshots([snap])
    return snap


def group_traces(merged: Dict) -> List[Dict]:
    """Per-trace_id groups, each ts-sorted with start/extent computed —
    the unit both the waterfall and the JSON doc render."""
    by_tid: Dict[str, List[Dict]] = {}
    for s in merged.get("spans", ()):
        by_tid.setdefault(s["trace_id"], []).append(s)
    traces = []
    for tid, spans in by_tid.items():
        spans = sorted(spans, key=lambda s: (s["ts"], s.get("seq", 0)))
        t0 = min(s["ts"] for s in spans)
        t1 = max(s["ts"] + float(s.get("dur_ms", 0.0)) / 1e3
                 for s in spans)
        traces.append({"trace_id": tid, "start_ts": t0,
                       "total_ms": round((t1 - t0) * 1e3, 4),
                       "spans": spans})
    traces.sort(key=lambda t: t["start_ts"])
    return traces


def phase_slices(span: Dict) -> List[Dict]:
    """Child slices of one process-scoped record (``tracing.phase``: a
    ``decode.loop.iter`` carries ``phases``, each with its summed
    ``ms`` and the offset ``end_ms`` at which it last ended): one
    pseudo-span per phase, placed so that it ends where the phase
    did, ``depth`` levels under the record. A phase that ran ``n`` > 1
    times shows as one slice of its summed duration."""
    depth = {span["name"]: 0}
    out = []
    # parents close after their children, so walk the list backwards
    for ph in reversed(span.get("phases") or ()):
        d = depth.get(ph["parent"], 0) + 1
        depth[ph["name"]] = d
        out.append({
            "trace_id": span["trace_id"], "name": ph["name"],
            "ts": span["ts"] + (ph["end_ms"] - ph["ms"]) / 1e3,
            "dur_ms": ph["ms"], "seq": span.get("seq", 0),
            "replica": span.get("replica", ""), "depth": d,
            "self_ms": ph["self_ms"], "n": ph["n"]})
    out.sort(key=lambda s: (s["ts"], s["depth"]))
    return out


def with_phase_slices(spans: List[Dict]) -> List[Dict]:
    out = []
    for s in spans:
        out.append(s)
        out.extend(phase_slices(s))
    return out


def _attr_str(span: Dict) -> str:
    attrs = {k: v for k, v in span.items() if k not in _CORE_KEYS}
    if not attrs:
        return ""
    return " ".join("%s=%s" % (k, attrs[k]) for k in sorted(attrs))


def render_text(merged: Dict, width: int = 32) -> str:
    traces = group_traces(merged)
    lines = ["fleet trace: %d span(s), %d trace(s), replicas=%s, "
             "recorded=%d dropped=%d"
             % (len(merged.get("spans", ())), len(traces),
                ",".join(r or "router"
                         for r in merged.get("replicas", [])) or "-",
                merged.get("recorded", 0), merged.get("dropped", 0))]
    for tr in traces:
        extent = max(tr["total_ms"], 1e-9)
        lines.append("")
        lines.append("trace %s  (%d spans, %.3f ms)"
                     % (tr["trace_id"], len(tr["spans"]),
                        tr["total_ms"]))
        for s in with_phase_slices(tr["spans"]):
            off_ms = (s["ts"] - tr["start_ts"]) * 1e3
            dur = float(s.get("dur_ms", 0.0))
            lo = int(round(off_ms / extent * width))
            lo = min(lo, width - 1)
            if dur > 0:
                n = max(1, int(round(dur / extent * width)))
                bar = " " * lo + "#" * min(n, width - lo)
            else:
                bar = " " * lo + "|"
            lines.append(
                "  +%9.3fms %-16s %-8s %9.3fms  [%-*s] %s"
                % (off_ms, "  " * s.get("depth", 0) + s["name"],
                   s.get("replica", "") or "router",
                   dur, width, bar, _attr_str(s)))
    return "\n".join(lines)


def to_doc(merged: Dict) -> Dict:
    """The trace_dump/1 JSON document (schema pinned in CI)."""
    traces = group_traces(merged)
    return {"schema": SCHEMA,
            "replicas": merged.get("replicas", []),
            "recorded": merged.get("recorded", 0),
            "dropped": merged.get("dropped", 0),
            "span_count": len(merged.get("spans", ())),
            "trace_count": len(traces),
            "traces": traces}


def to_chrome(merged: Dict) -> Dict:
    """Chrome trace-event JSON: replica -> process row, trace_id ->
    thread row, span -> "X" slice (instants become zero-width slices —
    Perfetto renders them as ticks). ts/dur are microseconds."""
    events = []
    pids: Dict[str, int] = {}
    tids: Dict[str, int] = {}
    for s in with_phase_slices(merged.get("spans", ())):
        replica = s.get("replica", "") or "router"
        if replica not in pids:
            pids[replica] = len(pids) + 1
            events.append({"ph": "M", "name": "process_name",
                           "pid": pids[replica], "tid": 0,
                           "args": {"name": replica}})
        tkey = s["trace_id"]
        if tkey not in tids:
            tids[tkey] = len(tids) + 1
        events.append({
            "ph": "X", "name": s["name"], "cat": "paddle_tpu",
            "pid": pids[replica], "tid": tids[tkey],
            "ts": round(s["ts"] * 1e6, 1),
            "dur": round(float(s.get("dur_ms", 0.0)) * 1e3, 1),
            "args": {k: v for k, v in s.items() if k not in _CORE_KEYS}})
    for tkey, tnum in tids.items():
        for pid in set(e["pid"] for e in events if e["ph"] == "X"
                       and e["tid"] == tnum):
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": tnum,
                           "args": {"name": "trace %s" % tkey}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--input", default="-", metavar="FILE",
                    help="fleet /trace.json (or a single recorder "
                    "snapshot); '-' = stdin (default)")
    ap.add_argument("--demo", action="store_true",
                    help="render a synthesized two-process demo trace "
                    "instead of reading input")
    ap.add_argument("--json", action="store_true",
                    help="emit the structured trace_dump/1 document")
    ap.add_argument("--chrome", action="store_true",
                    help="emit Chrome trace-event JSON (Perfetto / "
                    "chrome://tracing)")
    args = ap.parse_args()

    merged = demo_snapshot() if args.demo else load_snapshot(args.input)
    if args.chrome:
        print(json.dumps(to_chrome(merged), sort_keys=True))
    elif args.json:
        print(json.dumps(to_doc(merged), indent=2, sort_keys=True))
    else:
        print(render_text(merged))


if __name__ == "__main__":
    main()
