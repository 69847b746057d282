"""The program's own spans out of the profiler's trace, and the idle
of the device laid under them.

`paddle_tpu.observability.tracing.phase` enters a
`jax.profiler.TraceAnnotation("ptpu." + name, **counts)` while a traced
phase is open, so under the benchmark's profiler session the phases of
`DecodeServer._loop` (`ptpu.decode.loop.*`) land on the xplane's host
plane, on the clock of the device events. `trace_reduce.load_xplane`
keeps only the benchmark's own `bench.*` annotations, so this file opens
the same xplane once more (memoised) for what it leaves out: the
`ptpu.*` host events with their counts, the device planes' module-line
events (one per executed program, named after the jitted function:
`jit_ptpu_prefill_b1_s512(...)`), and the operation events.

The arithmetic works on plain tuples and is `trace_reduce`'s (`union`,
`subtract`, `gaps`), so it is tested on synthetic traces. A program
without such spans (the parent of the PR that added them) gives empty
lists, and every reader built on this returns None.

    spans = {"host":    [(name, start_ns, dur_ns, {count: value}, thread)],
             "modules": {plane: [(name, start_ns, dur_ns)]},
             "ops":     {plane: [(name, start_ns, dur_ns, text)]}}
"""
from __future__ import annotations

import functools
import json
import os

from . import trace_reduce
from .trace_reduce import gaps, subtract, total, union

PREFIX = "ptpu."
LOOP = PREFIX + "decode.loop."
ITER = LOOP + "iter"
ADMIT = LOOP + "admit"
PARK = LOOP + "park"
DISPATCH = LOOP + "dispatch"
ADMISSION = (ADMIT, LOOP + "prefill", LOOP + "first_token", LOOP + "scatter")
MODULE_LINES = ("XLA Modules",)


@functools.lru_cache(maxsize=2)
def load_xplane(path: str, device_plane=trace_reduce.is_device_plane,
                op_lines=trace_reduce.OP_LINES,
                module_lines=MODULE_LINES) -> dict:
    from jax.profiler import ProfileData

    host, modules, ops = [], {}, {}
    for plane in ProfileData.from_file(path).planes:
        if device_plane(plane.name):
            for ln in plane.lines:
                if ln.name in module_lines:
                    modules.setdefault(plane.name, []).extend(
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in ln.events)
                elif any(ln.name.startswith(p) for p in op_lines):
                    ops.setdefault(plane.name, []).extend(
                        (trace_reduce.short_name(e.name), float(e.start_ns),
                         float(e.duration_ns), e.name) for e in ln.events)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(PREFIX):
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns), dict(e.stats),
                                     ln.name))
    host.sort(key=lambda h: (h[1], -h[2]))
    return {"host": host, "modules": modules, "ops": ops}


def of_run(run: dict):
    """The spans of a traced run, or None where there is no xplane."""
    path = (run.get("trace") or {}).get("path")
    if not path or not os.path.exists(path):
        return None
    return load_xplane(path)


# -- innermost phase at every instant ----------------------------------------

def innermost(host, prefix: str = LOOP):
    """Disjoint [(name, group, start, end)] covering the union of the
    `prefix` phases of the loop's thread: at every instant the innermost
    open phase, and its `group`: "admit" anywhere
    under `decode.loop.admit`, "park" under `decode.loop.park`, "step"
    for the rest of an iteration (the per-token round trip)."""
    out, stack = [], []  # stack of (name, group, end)
    t = None

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][2] <= limit:
            name, group, end = stack.pop()
            if end > t:
                out.append((name, group, t, end))
                t = end

    loop = [h for h in host if h[0].startswith(prefix)]
    # one thread's phases nest; two servers' would not: keep the busiest
    threads = [h[4] for h in loop]
    keep = max(set(threads), key=threads.count) if threads else None
    spans = sorted(((h[0], h[1], h[1] + h[2]) for h in loop
                    if h[4] == keep), key=lambda s: (s[1], -s[2]))
    for name, s, e in spans:
        close_until(s)
        if stack:
            e = min(e, stack[-1][2])  # a child never outlives its parent
            if s > t:
                out.append((stack[-1][0], stack[-1][1], t, s))
            group = stack[-1][1]
        else:
            group = "step"
        if name in ADMISSION:  # a child whose `admit` the session cut
            group = "admit"
        elif name == PARK:
            group = "park"
        t = s if t is None else max(t, s)
        if e > s:
            stack.append((name, group, e))
    close_until(float("inf"))
    return out


def first_device(ops_by_plane: dict):
    """Operation events of the first chip: idle is attributed there."""
    planes = sorted(p for p, evs in ops_by_plane.items() if evs)
    return ops_by_plane[planes[0]] if planes else []


def idle_by_phase(ops, host) -> dict:
    """Each gap between device operations, inside the traced sub-window
    (first operation's start to the last one's end), split BY OVERLAP
    over the innermost program phases it lies under; what no phase
    covers is unattributed. Nanoseconds:
    {"window", "idle", "unattributed", "by_phase": {name: ns},
     "by_group": {"step" | "admit" | "park": ns}}; None without ops."""
    busy = union((s, s + d) for _, s, d, *_ in ops)
    if not busy:
        return None
    idle = gaps(busy)
    segs = innermost(host)

    def under(idx):
        acc = {}
        for key in sorted(set(sg[idx] for sg in segs)):
            cover = union(sg[2:] for sg in segs if sg[idx] == key)
            acc[key] = total(idle) - total(subtract(idle, cover))
        return acc

    covered = union(sg[2:] for sg in segs)
    return {"window": busy[-1][1] - busy[0][0], "idle": total(idle),
            "unattributed": total(subtract(idle, covered)),
            "by_phase": under(0), "by_group": under(1)}


def edge_phases(host, records):
    """The iterations the profiler's session cut. An annotation that was
    open when the session started or stopped is not in the xplane, so
    up to one iteration at each edge of the sub-window (with an
    admission, some 80 ms) would lie under no phase. The flight
    recorder has them: its `decode.loop.iter` records are the same
    iterations on the wall clock. The complete iterations of the xplane
    are matched to their records by their durations, which gives the
    offset between the clocks, and the records just before and after
    them come back as host events (the phases that ran once; a record
    keeps a sum for the others). [] where nothing matches."""
    x = [h for h in host if h[0] == ITER]
    recs = sorted((r for r in records or ()
                   if PREFIX + r["name"] == ITER), key=lambda r: r["seq"])
    k = min(len(x), 8)
    if k < 3 or len(recs) < len(x):
        return []

    def miss(j):
        return sum(abs(recs[j + i]["dur_ms"] - x[i][2] / 1e6)
                   for i in range(k)) / k

    j = min(range(len(recs) - len(x) + 1), key=miss)
    if miss(j) > 0.1:
        return []
    shifts = sorted(x[i][1] - recs[j + i]["ts"] * 1e9 for i in range(len(x)))
    shift = shifts[len(shifts) // 2]
    out = []
    for r in recs[max(j - 1, 0):j] + recs[j + len(x):j + len(x) + 1]:
        t0 = r["ts"] * 1e9 + shift
        out.append((ITER, t0, r["dur_ms"] * 1e6, {}, x[0][4]))
        out.extend((PREFIX + p["name"], t0 + (p["end_ms"] - p["ms"]) * 1e6,
                    p["ms"] * 1e6, {}, x[0][4])
                   for p in r.get("phases") or () if p["n"] == 1)
    return out


_TABLES = {}  # xplane path -> its table: three readers, one reduction


def idle_table(run: dict):
    """`idle_by_phase` of a run's first chip, with what `PERF.md` wants
    beside it: per decode step (a `dispatch` phase that carries
    `active`) the idle milliseconds under each phase, and each phase's
    own mean duration. Written to `<out>/<cell>/idle_by_phase.json` and
    printed, once a run. None where the program has no phases."""
    from . import harness

    spans = of_run(run)
    if not spans:
        return None
    path = run["trace"]["path"]
    if path not in _TABLES:
        _TABLES[path] = _idle_table(
            spans, run.get("spans"),
            os.path.join(harness.OUT_ROOT, run["cell"]["name"]))
    return _TABLES[path]


def _idle_table(spans, records, out_dir: str):
    ops = first_device(spans["ops"])
    if not ops or not any(h[0].startswith(LOOP) for h in spans["host"]):
        return None
    edges = edge_phases(spans["host"], records)
    idle = idle_by_phase(ops, spans["host"] + edges)
    lo, hi = min(o[1] for o in ops), max(o[1] + o[2] for o in ops)
    inside = [h for h in spans["host"] if lo <= h[1] <= hi]
    steps = sum(1 for h in inside
                if h[0] == DISPATCH and "active" in h[3]) or 1
    mean = {}
    for h in inside:
        m = mean.setdefault(h[0][len(PREFIX):], [0.0, 0])
        m[0] += h[2]
        m[1] += 1

    def pct(ns):
        return 100.0 * ns / idle["window"]

    table = {
        "window_ms": idle["window"] / 1e6, "decode_steps": steps,
        "edge_iterations_recovered": sum(1 for e in edges if e[0] == ITER),
        "idle_pct": pct(idle["idle"]),
        "unattributed_pct": pct(idle["unattributed"]),
        "group_pct": {k: pct(v) for k, v in idle["by_group"].items()},
        "phases": {k[len(PREFIX):]: {"idle_pct": pct(v),
                                     "idle_ms_per_step": v / 1e6 / steps}
                   for k, v in idle["by_phase"].items()},
        "phase_mean_ms": {k: v[0] / v[1] / 1e6
                          for k, v in sorted(mean.items())},
        "phase_count": {k: v[1] for k, v in sorted(mean.items())},
    }
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "idle_by_phase.json"), "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    print("idle_by_phase " + json.dumps(table, sort_keys=True), flush=True)
    return table


def group_idle_pct(run: dict, group: str):
    """Reader body of the idle metrics: device idle under one group of
    phases ("step", "admit") or under none ("unattributed"), as a
    percentage of the traced sub-window."""
    table = idle_table(run)
    if table is None:
        return None
    if group == "unattributed":
        return table["unattributed_pct"]
    return table["group_pct"].get(group, 0.0)


# -- device time by program --------------------------------------------------

def module_intervals(modules, pattern: str):
    """Merged intervals of the module-line events whose name has
    `pattern` (one event per executed program)."""
    return union((s, s + d) for n, s, d in modules if pattern in n)


def step_of(host, t: float):
    """Counts of the decode step whose program started at `t` on the
    device: the latest `dispatch` phase that carries `active` and began
    before `t` (the loop waits for each step, so the next dispatch
    begins after this program has ended). None before the first."""
    best = None
    for name, s, _, counts, _ in host:
        if s > t:
            break
        if name == DISPATCH and "active" in counts:
            best = counts
    return best
