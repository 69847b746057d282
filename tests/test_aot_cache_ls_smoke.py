"""Tier-1 smoke for tools/aot_cache_ls.py: builds a real cache entry
through the Executor, then pins the tool's --json schema (the
metrics_dump pattern — a field rename fails CI before it breaks a
cleanup cron) and exercises --gc / --rm end to end. The tool logic is
imported in-process (snapshot()); one subprocess run checks the CLI."""
from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers, optimizer
from paddle_tpu.runtime import aot_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOL = os.path.join(_REPO, "tools", "aot_cache_ls.py")

_spec = importlib.util.spec_from_file_location("aot_cache_ls", _TOOL)
aot_cache_ls = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(aot_cache_ls)


def _populate(cache_dir):
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[6])
            y = layers.data(name="y", shape=[1])
            loss = layers.mean(layers.square(layers.fc(x, 9) - y))
            optimizer.SGD(0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe._disk = aot_cache.AotDiskCache(cache_dir=cache_dir)
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((2, 6), np.float32),
                            "y": np.ones((2, 1), np.float32)},
                fetch_list=[loss])
    return exe._disk


# the --json payload is the acceptance surface: renaming any of these is
# a deliberate, test-updating change
_TOP_FIELDS = ("schema", "dir", "enabled", "max_bytes", "total_bytes",
               "entries")
_ENTRY_FIELDS = ("key", "bytes", "mtime", "age_s", "kind", "tier",
                 "program", "feed_sig", "fetch_names", "env", "created",
                 "meta_v")


def test_snapshot_schema(tmp_path):
    cache = _populate(str(tmp_path / "cache"))
    snap = aot_cache_ls.snapshot(cache)
    for f in _TOP_FIELDS:
        assert f in snap, f
    assert snap["schema"] == "aot_cache_ls/1"
    assert snap["entries"], "executor runs produced no cache entries"
    assert snap["total_bytes"] > 0
    json.dumps(snap)  # every value must be JSON-serializable
    for e in snap["entries"]:
        for f in _ENTRY_FIELDS:
            assert f in e, f
    kinds = {e["kind"] for e in snap["entries"]}
    assert "step" in kinds  # startup + main step entries
    step = next(e for e in snap["entries"] if e["kind"] == "step"
                and e["feed_sig"])
    assert step["env"]["backend"] == "cpu"
    assert ["x", [2, 6], "float32"] in step["feed_sig"]
    # unoptimized executor programs carry the raw tier marker
    assert step["tier"] == "raw"


def test_gc_and_rm_via_snapshot(tmp_path):
    cache = _populate(str(tmp_path / "cache"))
    entries = cache.entries()
    assert len(entries) >= 2
    # --rm semantics: removing one key drops blob + sidecar
    victim = entries[0]["key"]
    os.unlink(cache.blob_path(victim))
    os.unlink(cache.meta_path(victim))
    assert victim not in {e["key"] for e in cache.entries()}
    # --gc semantics: a 1-byte bound evicts everything
    evicted = cache.gc(max_bytes=1)
    assert evicted and not cache.entries()


def test_cli_json(tmp_path):
    cache = _populate(str(tmp_path / "cache"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, _TOOL, "--dir", cache.dir, "--json"],
        capture_output=True, text=True, timeout=600, env=env, cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    snap = json.loads(proc.stdout)
    assert snap["schema"] == "aot_cache_ls/1"
    assert {e["key"] for e in snap["entries"]} == {
        e["key"] for e in cache.entries()}
