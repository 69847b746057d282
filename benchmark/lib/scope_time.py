"""A traced run's device time laid under the Fluid ops that made it.

The program's tracer runs every Fluid op's kernel under a scope
`fl.<type>:<anchor>` (`paddle_tpu/framework/trace.py`), and
`paddle_tpu.observability.scopes.maps()` gives, for every executable
the run acquired, the table from an HLO instruction's name to its
scopes, to the scopes of the instructions inside its fusion, and to the
entry parameters (the weights) it reads. A device event carries the
instruction's name and lies inside one `XLA Modules` event, whose name
is the module's: this file joins the two, program by program, on the
first chip.

    table = {"programs": [{"program", "calls", "device_s", "s_per_call",
                           "op_s", "mapped", "scoped", "found_pct",
                           "classes": [[class, seconds]], "unnamed_s"}],
             "largest": [{"name", "program", "seconds", "scope",
                          "members", "reads"}],
             "unnamed": [[kind of instruction, program, seconds, events]],
             "busy_s", "mapped_s", "named_s", "maps_s", "reader_s"}

A CLASS is an event's innermost scope with the digits of its anchor
starred (`fl.mul:lm.l*.ffn.fc*.w`; a `ptpu.*` scope as it is; ` bwd`
after it under a transposition); an event with no scope of its own goes
under its fusion's first member's, and one with neither (the wait for a
weight's prefetch, `slice-done`; the compiler's `ragged-dot`) under that
of the nearest scoped instruction its result goes to (the map's
`users`). `CONTAINER` events (`while`, `call`)
are left out of every sum, as `trace_reduce` leaves them out: their
bodies have events of their own.

The arithmetic works on plain tuples and dicts, so it is tested on
synthetic ones. A program without `observability.scopes` (the parent of
the PR that added it) has no maps: `of_run` answers None and so does
every reader built on it.
"""
from __future__ import annotations

import bisect
import json
import os
import re
import time

from . import program_spans
from .trace_reduce import CONTAINER, total, union

DENSE = ("fl.mul:", "fl.matmul:", "fl.fc:")
CLASSES_SHOWN = 12
LARGEST_SHOWN = 10
UNNAMED_SHOWN = 6
UNNAMED = "unnamed"
_CALL_ID = re.compile(r"\(\d+\)$")
_DIGITS = re.compile(r"\d+")
_SERIAL = re.compile(r"[.\d]+$")
_KERNEL = re.compile(r"(ptpu\.[A-Za-z_]+)")


def program_of(module_event: str) -> str:
    """`jit_ptpu_decode_b32_s16384(1)` -> `jit_ptpu_decode_b32_s16384`."""
    return _CALL_ID.sub("", module_event)


def starred(leaf: str) -> str:
    """`fl.mul:lm.l13.ffn.w1` -> `fl.mul:lm.l*.ffn.w*`."""
    kind, sep, anchor = leaf.partition(":")
    return kind + sep + _DIGITS.sub("*", anchor)


def leaf_of(entry):
    """An event's innermost scope: its own, else its fusion's first
    member's, else that of the nearest scoped instruction its result
    goes to (an instruction the compiler made without one: a weight's
    prefetch, the expansion of a ragged dot); None where the map has
    none of the three."""
    if entry is None:
        return None
    if entry["scope"]:
        return entry["scope"][-1]
    for key in ("members", "users"):
        if entry.get(key):
            return entry[key][0]
    return None


def class_of(name: str, entry) -> str:
    leaf = leaf_of(entry)
    if leaf is None:
        # a Mosaic call is named after its own scope, map or no map
        kernel = _KERNEL.search(name)
        return kernel.group(1).rstrip("_") if kernel else UNNAMED
    return starred(leaf) + (" bwd" if entry["pass"] == "bwd" else "")


def weight_of(leaf: str) -> str:
    """The parameter a dense leaf's anchor names."""
    return "state['%s']" % leaf.partition(":")[2]


def is_dense(entry, params) -> bool:
    """A product against a weight, outside any `ptpu.*` scope: the
    event's own innermost scope is `fl.mul|matmul|fc` with an anchor
    that is a parameter, or a member of its fusion is and the event
    reads that parameter (an instruction under such a scope may be a
    reshape of the product, fused into the next operation)."""
    if entry is None:
        return False
    own = entry["scope"][-1] if entry["scope"] else ""
    if own.startswith(DENSE) and weight_of(own) in params:
        return True
    return any(m.startswith(DENSE) and weight_of(m) in entry["reads"]
               for m in entry["members"])


def dense_weights(entry) -> set:
    """The weights a dense event's products are against and it reads:
    the anchors of its own and its members' `fl.mul|matmul|fc` scopes.
    Not everything it reads: a fusion may also take eight rows of a
    table, and those are not the table's bytes."""
    own = entry["scope"][-1:] if entry["scope"] else []
    return {weight_of(leaf) for leaf in own + entry["members"]
            if leaf.startswith(DENSE)} & set(entry["reads"])


def is_dense_wait(entry) -> bool:
    """The compiler's prefetch of a weight FOR a dense product: no scope
    of its own, its result goes to one, it reads that product's weight
    (`slice-done`: the wait for a slice of it to arrive)."""
    return (entry is not None and not entry["scope"]
            and not entry["members"]
            and any(u.startswith(DENSE) and weight_of(u) in entry["reads"]
                    for u in entry.get("users", ())))


def by_program(ops, modules):
    """{program: [calls, module ns, [op events inside]]}: an operation
    belongs to the module-line event its start lies in."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = {}
    for name, s, d in modules:
        row = out.setdefault(program_of(name), [0, 0.0, []])
        row[0] += 1
        row[1] += d
    for ev in ops:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < modules[i][1] + modules[i][2]:
            out[program_of(modules[i][0])][2].append(ev)
    return out


def map_for(program: str, names, maps):
    """The map of the module `program` that holds most of the
    instruction `names` (the newest of equals: a training cell's check
    step and its timed step are both `jit_stepfn`), and the share of
    the names it holds; (None, 0.0) where no executable has that
    module's name."""
    best, share = None, -1.0
    for m in maps.values():
        if m["module"] != program:
            continue
        found = sum(1 for n in names if n in m["ops"]) / max(len(names), 1)
        if found >= share:
            best, share = m, found
    return best, max(share, 0.0)


def join(ops, modules, maps) -> list:
    """[(program, calls, module ns, its operation events without the
    containers, its map or None, share of their names the map holds)]"""
    out = []
    for program, (calls, mod_ns, evs) in by_program(ops, modules).items():
        evs = [e for e in evs if not CONTAINER.match(e[0])]
        m, found = map_for(program, {e[0] for e in evs}, maps)
        out.append((program, calls, mod_ns, evs, m, found))
    return out


def reduce_events(ops, joined) -> dict:
    """The table (module doc) of one device's events; nanoseconds in,
    seconds out."""
    ns = 1e-9
    programs, largest, bare = [], {}, {}
    mapped_s = named_s = 0.0
    for program, calls, mod_ns, evs, m, found in joined:
        scoped = bool(m and m["scoped"])
        classes, op_ns = {}, 0.0
        for name, _, d, *_ in evs:
            entry = m["ops"].get(name) if scoped else None
            cls = class_of(name, entry)
            classes[cls] = classes.get(cls, 0.0) + d
            op_ns += d
            big = largest.setdefault((name, program), [0.0, entry])
            big[0] += d
            if scoped and cls == UNNAMED:
                kind = bare.setdefault((_SERIAL.sub("", name), program),
                                       [0.0, 0])
                kind[0] += d
                kind[1] += 1
        unnamed = classes.pop(UNNAMED, 0.0)
        if scoped:
            mapped_s += op_ns * ns
            named_s += (op_ns - unnamed) * ns
        programs.append({
            "program": program, "calls": calls, "device_s": mod_ns * ns,
            "s_per_call": mod_ns * ns / calls, "op_s": op_ns * ns,
            "mapped": m is not None, "scoped": scoped,
            "found_pct": 100.0 * found,
            "classes": [[k, v * ns] for k, v in sorted(
                classes.items(), key=lambda kv: -kv[1])],
            "unnamed_s": unnamed * ns})
    programs.sort(key=lambda p: -p["device_s"])
    top = sorted(largest.items(), key=lambda kv: -kv[1][0])[:LARGEST_SHOWN]
    return {
        "programs": programs,
        "largest": [{"name": name, "program": program, "seconds": d * ns,
                     "scope": (e or {}).get("scope", []),
                     "members": (e or {}).get("members", []),
                     "reads": (e or {}).get("reads", [])}
                    for (name, program), (d, e) in top],
        "unnamed": [[kind, program, d * ns, n] for (kind, program), (d, n)
                    in sorted(bare.items(), key=lambda kv: -kv[1][0])[
                        :UNNAMED_SHOWN]],
        "busy_s": total(union((e[1], e[1] + e[2]) for e in ops)) * ns,
        "mapped_s": mapped_s, "named_s": named_s}


def select_s(joined, prefix: str, want):
    """Seconds of the events `want(entry, map)` takes inside the scoped
    programs named `prefix`*; None where there is no such program."""
    acc = None
    for program, _, _, evs, m, _ in joined:
        if program.startswith(prefix) and m and m["scoped"]:
            acc = (acc or 0.0) + 1e-9 * sum(
                e[2] for e in evs if want(m["ops"].get(e[0]), m))
    return acc


def dense_roofline(joined, prefix: str, bytes_per_s: float):
    """{"bytes", "seconds", "pct"} of the dense events inside the scoped
    programs named `prefix`* that stream a weight from HBM THEMSELVES:
    the bytes of the DISTINCT weights their products are against
    (`dense_weights`) and that they read where the parameter lies (the
    map's `reads` less its `copied`: a weight that a prefetch or a
    `copy` moved first is read from HBM by THAT operation, beside other
    events or in one of its own, and its reader reads what the copy
    left, which the TPU compiler may have rounded to bfloat16 and put
    in on-chip memory: left out on both sides), each as often as the
    event that streams it most ran (once a step; a weight that two
    events of a step stream counts once), over the peak, against those
    events' time. Those bytes must pass from HBM inside that time, so
    the share cannot pass 100. None where no such event ran."""
    need_bytes = seconds = 0.0
    for program, _, _, evs, m, _ in joined:
        if not (program.startswith(prefix) and m and m["scoped"]):
            continue
        streams, ran = {}, {}  # event -> its weights; weight -> {event: n}
        for name, _, d, *_ in evs:
            if name not in streams:
                entry = m["ops"].get(name)
                streams[name] = (
                    dense_weights(entry) - set(entry.get("copied", ()))
                    if is_dense(entry, m["params"]) else ())
            for w in streams[name]:
                by_event = ran.setdefault(w, {})
                by_event[name] = by_event.get(name, 0) + 1
            if streams[name]:
                seconds += d * 1e-9
        need_bytes += sum(m["params"].get(w, 0) * max(by_event.values())
                          for w, by_event in ran.items())
    if not seconds:
        return None
    return {"bytes": need_bytes, "seconds": seconds,
            "pct": 100.0 * need_bytes / bytes_per_s / seconds}


# -- of a run -----------------------------------------------------------------

_TABLES = {}  # xplane path -> (table, joined): one reduction a run


def of_run(run: dict):
    """(table, `join`'s list) of a traced run's first chip, the table
    printed and written to `<out>/<cell>/scope_time.json` once a
    run; None where there is no trace, no program event, or the program
    has no `observability.scopes`."""
    spans = program_spans.of_run(run)
    if not spans:
        return None
    path = run["trace"]["path"]
    if path not in _TABLES:
        _TABLES[path] = _of_spans(spans, run["cell"]["name"])
    return _TABLES[path]


def _of_spans(spans, cell: str):
    from . import harness

    try:
        from paddle_tpu.observability import scopes
    except ImportError:  # the parent of the PR that added the scopes
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    if not ops or not modules:
        return None
    t0 = time.perf_counter()
    maps = scopes.maps()
    t1 = time.perf_counter()
    joined = join(ops, modules, maps)
    table = reduce_events(ops, joined)
    table["maps_s"] = t1 - t0
    table["reader_s"] = time.perf_counter() - t1
    table["executables"] = len(maps)
    out_dir = os.path.join(harness.OUT_ROOT, cell)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "scope_time.json"), "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)
    for line in lines(table):
        print("scope_time: " + line, flush=True)
    return table, joined


def lines(table) -> list:
    """The table as the run's log prints it."""
    busy = table["busy_s"] or 1.0
    out = ["%d executables mapped in %.3f s, read in %.3f s; busy %.6f s, "
           "%.6f s of it inside scoped programs, %.6f s of that named"
           % (table.get("executables", 0), table.get("maps_s", 0.0),
              table.get("reader_s", 0.0), table["busy_s"],
              table["mapped_s"], table["named_s"])]
    for p in table["programs"]:
        out.append("program %-34s calls=%-5d device_s=%.6f s_per_call=%.6f "
                   "op_s=%.6f (%.1f%% of busy) %s found=%.0f%%"
                   % (p["program"], p["calls"], p["device_s"],
                      p["s_per_call"], p["op_s"], 100.0 * p["op_s"] / busy,
                      "scoped" if p["scoped"] else
                      "unscoped" if p["mapped"] else "no-map",
                      p["found_pct"]))
        if not p["scoped"]:
            continue
        for cls, s in p["classes"][:CLASSES_SHOWN]:
            out.append("  %-60s %.6f s %5.1f%% of busy"
                       % (cls, s, 100.0 * s / busy))
        rest = sum(s for _, s in p["classes"][CLASSES_SHOWN:])
        if rest:
            out.append("  %-60s %.6f s %5.1f%% of busy"
                       % ("(%d smaller classes)"
                          % (len(p["classes"]) - CLASSES_SHOWN), rest,
                          100.0 * rest / busy))
        out.append("  %-60s %.6f s %5.1f%% of busy"
                   % (UNNAMED, p["unnamed_s"], 100.0 * p["unnamed_s"] / busy))
    for b in table["largest"]:
        out.append("largest %s program=%s seconds=%.6f scope=%s members=%s "
                   "reads=%s" % (b["name"], b["program"], b["seconds"],
                                 "/".join(b["scope"]) or "-",
                                 ",".join(b["members"]) or "-",
                                 ",".join(b["reads"][:6]) or "-"))
    for kind, program, seconds, n in table["unnamed"]:
        out.append("unnamed %s program=%s seconds=%.6f events=%d"
                   % (kind, program, seconds, n))
    return out


# -- reader bodies of the metrics ---------------------------------------------

def named_pct(run):
    """`scope_named_pct.*`: of the operation time inside programs that
    have a scoped map, the share in events whose instruction, or a
    member of its fusion, carries a scope (a Mosaic call's own name
    counts). None where no program of the trace has one."""
    found = of_run(run)
    if not found or not found[0]["mapped_s"]:
        return None
    return 100.0 * found[0]["named_s"] / found[0]["mapped_s"]


def share_of_busy(run, prefix: str, want):
    """Share of the busy time of the first chip in the events `want`
    takes inside the programs named `prefix`*; None where no program of
    that name has a scoped map."""
    found = of_run(run)
    if not found:
        return None
    table, joined = found
    seconds = select_s(joined, prefix, want)
    return None if seconds is None else 100.0 * seconds / table["busy_s"]
