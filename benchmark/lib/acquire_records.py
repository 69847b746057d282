"""The program's own records of the executables a run acquired before its
window opened: what the `setup_*` per-layer metrics read.

`paddle_tpu.observability.observe_acquire` writes one record for every
executable loaded from the AOT disk tier (`path` "warm"), lowered and
compiled ("cold") or traced inside a first call through `jax.jit`
("lazy"), into a ring of the step timeline that the steps cannot evict:
`name`, `kind`, `path`, `ts` (the start, `time.time()`), `wall_ms`, the
parts of it (`build_ms`, `load_ms`, `trace_ms`, `xla_ms`, `store_ms`,
`describe_ms`), `blob_bytes`, and the `phase` it began under. The
readers run in the run's own process, after it, and read that ring.

Counted are the records that began before the window
(`run["window_wall"][0]`, which the serving runners give); where the
runner gives no such key (`run_train.py`) every record counts: there the
harness refuses a run that built or loaded anything inside the window,
and the traced steps reuse the window's executables. A program whose
records carry no `path` (the parent of the PR that added it) has nothing
to read, and every reader answers None.
"""
from __future__ import annotations

import functools

PARTS = ("build_ms", "load_ms", "trace_ms", "xla_ms", "store_ms",
         "describe_ms")


def split(records, t_window=None):
    """-> (the records counted, how many were left out), or None where
    no record carries a `path`. Counted: those with a `path` that began
    before `t_window` (all of them where it is None)."""
    records = list(records or ())
    if not any("path" in r for r in records):
        return None
    kept = [r for r in records if "path" in r
            and (t_window is None or r["ts"] < t_window)]
    return kept, len(records) - len(kept)


def summary(kept) -> dict:
    """The three numbers the metrics report."""
    return {
        "acquire_s": sum(r.get("wall_ms", 0.0) for r in kept) / 1e3,
        "load_s": sum(r.get("load_ms", 0.0) for r in kept
                      if r["path"] == "warm") / 1e3,
        "executables": len(kept)}


def table(kept) -> list:
    """One line a record, slowest first: name, path, wall_ms, the parts
    it has, blob_bytes, phase."""
    lines = []
    for r in sorted(kept, key=lambda r: -r.get("wall_ms", 0.0)):
        parts = " ".join("%s=%.1f" % (p[:-3], r[p]) for p in PARTS
                         if p in r)
        lines.append("%-32s %-4s wall_ms=%-10.1f %s blob_bytes=%s phase=%s"
                     % (r.get("name", r.get("kind", "?")), r["path"],
                        r.get("wall_ms", 0.0), parts,
                        r.get("blob_bytes", "-"), r.get("phase", "-")))
    return lines


@functools.lru_cache(maxsize=2)
def _of_process(t_window, setup_s=None):
    """Read the process's ring once a run, print the table once."""
    try:
        from paddle_tpu import observability as obs
        records = obs.TIMELINE.events("compile")
    except Exception:  # a program without the timeline reads as nothing
        return None
    found = split(records, t_window)
    if found is None:
        return None
    kept, left_out = found
    out = summary(kept)
    print("acquire_records: %d executables before the window, %.3f s "
          "(%.3f s of it loading from the disk tier) of a setup_s of %s; "
          "%d record(s) left out"
          % (out["executables"], out["acquire_s"], out["load_s"],
             "?" if setup_s is None else "%.3f" % setup_s, left_out),
          flush=True)
    for line in table(kept):
        print("acquire_records: " + line, flush=True)
    return out


def of_run(run: dict):
    """{"acquire_s", "load_s", "executables"} of the run's own process,
    or None (module doc)."""
    window = run.get("window_wall")
    return _of_process(window[0] if window else None,
                       (run.get("end_to_end") or {}).get("setup_s"))
