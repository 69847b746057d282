"""From a profiler trace to numbers: device busy/idle, per-operation
sums, collectives and their exposed part, and the longest idle gaps
named by what the host was doing.

The arithmetic works on plain tuples, so it is tested on synthetic
traces; `load_xplane` is the only part that touches the profiler's file.

A trace is {"devices": {plane: [(name, start_ns, dur_ns, info)]},
            "host": [(name, start_ns, dur_ns)]}
where `name` is the operation's short name and `info` the whole text
the profiler gives the event (on a TPU the HLO instruction, in which a
Mosaic kernel shows as `custom_call_target="tpu_custom_call"`) followed
by the event's own statistics; a reader finds a kernel there by pattern.
"""
from __future__ import annotations

import glob
import os
import re

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)")
# lines of a device plane that hold one event per executed operation;
# the others ("XLA Modules", "Steps", ...) span whole programs and would
# make the device look busy all the time
OP_LINES = ("XLA Ops",)
HOST_PREFIX = "bench."
# operations that only contain others (their bodies have events of their
# own): part of the busy union, left out of per-operation sums
CONTAINER = re.compile(r"^(while|conditional|call)([.\s]|$)")


def short_name(text: str) -> str:
    """`%fusion.3 = bf16[...] fusion(...)` -> `fusion.3`."""
    return text.split(" = ", 1)[0].lstrip("%")[:80]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def load_xplane(logdir: str, device_plane=is_device_plane,
                op_lines=OP_LINES) -> dict:
    """Read the newest `.xplane.pb` under `logdir`."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % logdir)
    pd = ProfileData.from_file(paths[-1])
    devices, host, lines_seen = {}, [], {}
    for plane in pd.planes:
        names = [ln.name for ln in plane.lines]
        lines_seen[plane.name] = names
        if device_plane(plane.name):
            evs = []
            for ln in plane.lines:
                if not any(ln.name.startswith(p) for p in op_lines):
                    continue
                for e in ln.events:
                    info = e.name + " " + " ".join(
                        "%s=%s" % (k, v) for k, v in e.stats
                        if isinstance(v, str))
                    evs.append((short_name(e.name), float(e.start_ns),
                                float(e.duration_ns), info))
            devices[plane.name] = evs
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
    return {"devices": devices, "host": host, "lines": lines_seen,
            "path": paths[-1]}


# -- interval arithmetic ----------------------------------------------------

def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(merged) -> float:
    return float(sum(e - s for s, e in merged))


def subtract(a, b):
    """Parts of merged `a` not covered by merged `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(merged):
    return [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)]


def _host_name_at(host, t):
    """Innermost benchmark annotation covering instant `t`."""
    best = None
    for name, s, d in host:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "unattributed"


# -- the reduction ----------------------------------------------------------

def reduce_trace(trace: dict, top: int = 10) -> dict:
    """Numbers of one traced window, in seconds.

    window_s: first device operation's start to the last one's end, the
    widest over the devices; busy_s: union of operation intervals,
    averaged over devices; per device op sums; collective time and the
    part of it during which no other operation runs on that device."""
    devs = {p: evs for p, evs in trace["devices"].items() if evs}
    if not devs:
        return {"devices": 0}
    busy, windows, coll, exposed = [], [], [], []
    op_sum = {}
    first_gaps = None
    for plane in sorted(devs):
        evs = devs[plane]
        iv_all = union((s, s + d) for _, s, d, _ in evs)
        iv_coll = union((s, s + d) for n, s, d, _ in evs
                        if COLLECTIVE.match(n))
        iv_comp = union((s, s + d) for n, s, d, _ in evs
                        if not COLLECTIVE.match(n))
        busy.append(total(iv_all))
        windows.append(iv_all[-1][1] - iv_all[0][0])
        coll.append(total(iv_coll))
        exposed.append(total(subtract(iv_coll, iv_comp)))
        for n, _, d, _ in evs:
            if not CONTAINER.match(n):
                op_sum[n] = op_sum.get(n, 0.0) + d
        if first_gaps is None:
            gs = sorted(gaps(iv_all), key=lambda g: g[0] - g[1])[:50]
            first_gaps = [(_host_name_at(trace["host"], (s + e) / 2), e - s)
                          for s, e in gs]
    n = len(devs)
    gap_by = {}
    for name, d in first_gaps:
        gap_by[name] = gap_by.get(name, 0.0) + d
    ns = 1e-9
    return {
        "devices": n,
        "window_s": max(windows) * ns,
        "busy_s": sum(busy) / n * ns,
        "collective_s": sum(coll) / n * ns,
        "collective_exposed_s": sum(exposed) / n * ns,
        # summed over devices then averaged, so that a 4-chip cell's
        # entries compare with its per-chip busy time
        "device_ops": [[k, v / n * ns] for k, v in sorted(
            op_sum.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * ns] for k, v in sorted(
            gap_by.items(), key=lambda kv: -kv[1])[:top]],
    }


def sample_events(trace: dict, top: int = 40):
    """[name, total seconds, count, one event's statistics] of the
    operations of the first device that took most time: for a person to
    read when a reader's pattern finds nothing."""
    devs = {p: evs for p, evs in trace["devices"].items() if evs}
    if not devs:
        return []
    agg = {}
    for n, _, d, info in devs[sorted(devs)[0]]:
        a = agg.setdefault(n, [0.0, 0, info])
        a[0] += d
        a[1] += 1
    return [[n, a[0] * 1e-9, a[1], a[2][:1200]] for n, a in sorted(
        agg.items(), key=lambda kv: -kv[1][0])[:top]]


def idle_pct(run: dict):
    """Reader body of the `device_idle_pct.*` metrics: the share of the
    traced sub-window in which no operation ran, averaged over chips."""
    tn = run.get("trace_numbers") or {}
    if not tn.get("devices"):
        return None
    return 100.0 * (1.0 - tn["busy_s"] / tn["window_s"])


def kernel_seconds(trace: dict, pattern: str):
    """(seconds summed over the events whose name or text match
    `pattern`, averaged over devices; number of events on one device)."""
    rx = re.compile(pattern)
    devs = {p: evs for p, evs in trace["devices"].items() if evs}
    if not devs:
        return 0.0, 0
    tot, cnt = 0.0, 0
    for evs in devs.values():
        for n, _, d, info in evs:
            if rx.search(n) or rx.search(info):
                tot += d
                cnt += 1
    return tot / len(devs) * 1e-9, cnt // len(devs)
