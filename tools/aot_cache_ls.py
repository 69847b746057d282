"""List / inspect / GC the persistent AOT executable cache.

Enumerates `runtime/aot_cache.py` entries (training Executor dir by
default; point --dir at a model's `__aot_cache__/` for serving caches):
key, size, age, and the sidecar's key fields (kind, program fingerprint,
transpile/quant tier [raw|O1|O2|int8 — one model's raw, optimized, and
quantized executables coexist and this column tells them apart], feed
signature, jax/jaxlib/backend environment). `--gc` applies the same
mtime-LRU the executor runs after every store, against `--max-bytes` (or
`PADDLE_TPU_AOT_CACHE_MAX_BYTES` / the 1 GiB default); `--rm KEY` drops
one entry. tests/test_aot_cache_ls_smoke.py pins the `--json` schema in
tier-1, so a field rename fails CI before it breaks a cleanup cron.

Usage:
    python tools/aot_cache_ls.py [--dir D] [--json]
    python tools/aot_cache_ls.py --gc [--max-bytes N]
    python tools/aot_cache_ls.py --rm KEY
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCHEMA = "aot_cache_ls/1"

_ENV_FIELDS = ("format", "jax", "jaxlib", "backend", "device_kind",
               "x64", "xla_flags", "trace_env")


def _jsonable(v):
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return repr(v)


def _env_dict(env):
    """aot_cache.env_fingerprint tuple -> named dict (sidecars written by
    a future format keep extra positions under 'extra')."""
    if not isinstance(env, (list, tuple)):
        return {"raw": _jsonable(env)}
    out = dict(zip(_ENV_FIELDS, (_jsonable(x) for x in env)))
    if len(env) > len(_ENV_FIELDS):
        out["extra"] = _jsonable(env[len(_ENV_FIELDS):])
    return out


def snapshot(cache, now=None):
    """The --json payload (also what the smoke test pins)."""
    now = time.time() if now is None else now
    entries = []
    for e in cache.entries():
        meta = e["meta"] or {}
        entries.append({
            "key": e["key"],
            "bytes": e["bytes"],
            "mtime": e["mtime"],
            "age_s": max(0.0, now - e["mtime"]),
            "kind": meta.get("kind"),
            # transpile/quant tier (Engine.meta): raw | O1 | O2 | int8 —
            # what distinguishes one model's coexisting raw, optimized,
            # and quantized executables; pre-tier sidecars show None
            "tier": meta.get("tier"),
            "program": meta.get("program"),
            "feed_sig": _jsonable(meta.get("feed_sig")),
            "fetch_names": _jsonable(meta.get("fetch_names")),
            "env": _env_dict(meta.get("env")) if "env" in meta else None,
            "created": meta.get("created"),
            "meta_v": meta.get("v"),
        })
    return {
        "schema": SCHEMA,
        "dir": cache.dir,
        "enabled": cache.enabled,
        "max_bytes": cache.max_bytes,
        "total_bytes": cache.total_bytes(),
        "entries": entries,
    }


def _fmt_age(s):
    for unit, div in (("d", 86400.0), ("h", 3600.0), ("m", 60.0)):
        if s >= div:
            return "%.1f%s" % (s / div, unit)
    return "%.0fs" % s


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=None,
                    help="cache directory (default: the AOT tier of "
                         "runtime/aot_cache.py's one cache rule)")
    ap.add_argument("--json", action="store_true",
                    help="print the pinned-schema JSON snapshot")
    ap.add_argument("--gc", action="store_true",
                    help="apply the mtime-LRU GC against --max-bytes")
    ap.add_argument("--max-bytes", type=int, default=None,
                    help="GC bound (default: PADDLE_TPU_AOT_CACHE_MAX_BYTES"
                         " or 1 GiB; 0 = unbounded)")
    ap.add_argument("--rm", metavar="KEY", default=None,
                    help="remove one entry (blob + sidecar) by key")
    args = ap.parse_args()

    from paddle_tpu.runtime import aot_cache

    cache = aot_cache.AotDiskCache(cache_dir=args.dir,
                                   max_bytes=args.max_bytes)
    out = snapshot(cache)
    if args.rm:
        removed = []
        for p in (cache.blob_path(args.rm), cache.meta_path(args.rm)):
            try:
                os.unlink(p)
                removed.append(p)
            except OSError:
                pass
        out["removed"] = removed
        out["entries"] = [e for e in out["entries"] if e["key"] != args.rm]
        out["total_bytes"] = cache.total_bytes()
    if args.gc:
        out["evicted"] = cache.gc(args.max_bytes)
        out["total_bytes"] = cache.total_bytes()
        out["entries"] = [e for e in out["entries"]
                          if e["key"] not in out["evicted"]]

    if args.json:
        json.dump(out, sys.stdout, indent=2)
        sys.stdout.write("\n")
        return

    print("cache dir: %s  (enabled=%s, bound=%s)"
          % (out["dir"], out["enabled"],
             "unbounded" if out["max_bytes"] <= 0 else out["max_bytes"]))
    fmt = "%-26s %10s %8s %-8s %-5s %-9s %-10s %s"
    print(fmt % ("KEY", "BYTES", "AGE", "KIND", "TIER", "PROGRAM", "JAX",
                 "BACKEND"))
    for e in out["entries"]:
        env = e["env"] or {}
        print(fmt % (e["key"], e["bytes"], _fmt_age(e["age_s"]),
                     e["kind"] or "?", e["tier"] or "?",
                     e["program"] or "?",
                     env.get("jax", "?"), env.get("backend", "?")))
    print("%d entries, %d bytes total" % (len(out["entries"]),
                                          out["total_bytes"]))
    if args.rm:
        print("removed: %s" % (out["removed"] or "nothing"))
    if args.gc:
        print("gc evicted: %s" % (out["evicted"] or "nothing"))


if __name__ == "__main__":
    main()
