"""Persistent AOT executable cache (runtime/aot_cache.py): the failure
contract from the acceptance criteria — corruption, version mismatch,
read-only dirs, the kill switch — must all degrade to an in-memory
compile with a counter incremented, NEVER a crash; plus warm-start reuse
(fresh executor + rebuilt program loads from disk, no re-trace), LRU GC,
and in-place donation on the deserialized-executable path."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import layers, optimizer
from paddle_tpu import observability as obs
from paddle_tpu.runtime import aot_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(width=9):
    """Deterministic tiny training program (same content -> same
    fingerprint -> same cache key across rebuilds)."""
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[6])
            y = layers.data(name="y", shape=[1])
            loss = layers.mean(layers.square(layers.fc(x, width) - y))
            optimizer.SGD(0.1).minimize(loss)
    return main, startup, scope, loss


_FEED = {"x": np.linspace(0, 1, 12).reshape(2, 6).astype(np.float32),
         "y": np.ones((2, 1), np.float32)}


def _run_once(cache_dir, width=9, loop=False):
    """Fresh executor + freshly-built program against `cache_dir`.
    Returns the fetched loss."""
    main, startup, scope, loss = _build(width)
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe._disk = aot_cache.AotDiskCache(cache_dir=cache_dir)
        exe.run(startup)
        if loop:
            return float(exe.run_loop(main, feed=_FEED, fetch_list=[loss],
                                      steps=2)[0])
        return float(exe.run(main, feed=_FEED, fetch_list=[loss])[0])


def _blobs(cache_dir):
    try:
        return sorted(n for n in os.listdir(cache_dir)
                      if n.endswith(aot_cache.BLOB_SUFFIX))
    except OSError:
        return []


# -- warm start ----------------------------------------------------------

def test_fresh_executor_loads_training_executable_from_disk(tmp_path):
    d = str(tmp_path / "cache")
    warm0 = obs.AOT_COMPILE_MS.stats(path="warm", kind="run")["count"]
    v_cold = _run_once(d)
    assert len(_blobs(d)) == 2  # startup program + training step
    assert obs.AOT_COMPILE_MS.stats(path="warm", kind="run")["count"] == warm0

    cold0 = obs.AOT_COMPILE_MS.stats(path="cold", kind="run")["count"]
    v_warm = _run_once(d)
    # both compiles (startup + step) came from disk: zero cold compiles,
    # two warm loads — and the numerics are identical
    assert obs.AOT_COMPILE_MS.stats(path="cold", kind="run")["count"] == cold0
    assert (obs.AOT_COMPILE_MS.stats(path="warm", kind="run")["count"]
            - warm0 == 2)
    assert v_warm == v_cold


def test_loop_executable_cached_and_reused(tmp_path):
    d = str(tmp_path / "cache")
    v1 = _run_once(d, loop=True)
    n1 = len(_blobs(d))  # startup + loop window
    cold0 = obs.AOT_COMPILE_MS.stats(path="cold", kind="loop")["count"]
    v2 = _run_once(d, loop=True)
    assert len(_blobs(d)) == n1
    assert (obs.AOT_COMPILE_MS.stats(path="cold", kind="loop")["count"]
            == cold0)
    assert v2 == v1


# -- failure modes (never a crash) ---------------------------------------

def test_corrupted_blob_quarantined_and_recompiled(tmp_path):
    d = str(tmp_path / "cache")
    v1 = _run_once(d)
    for n in _blobs(d):
        with open(os.path.join(d, n), "wb") as f:
            f.write(b"not an executable")
    corrupt0 = obs.AOT_CACHE_CORRUPT.value(reason="blob")
    v2 = _run_once(d)  # falls back to a fresh compile
    assert v2 == v1
    assert obs.AOT_CACHE_CORRUPT.value(reason="blob") - corrupt0 == 2
    # bad blobs moved aside for postmortem, then rewritten by the fresh
    # compile's store
    quarantined = [n for n in os.listdir(d)
                   if n.endswith(aot_cache.QUARANTINE_SUFFIX)]
    assert len(quarantined) == 2
    assert len(_blobs(d)) == 2


def test_truncated_blob_also_recovers(tmp_path):
    d = str(tmp_path / "cache")
    v1 = _run_once(d)
    for n in _blobs(d):
        p = os.path.join(d, n)
        with open(p, "r+b") as f:
            f.truncate(os.path.getsize(p) // 2)
    assert _run_once(d) == v1


def test_env_mismatch_is_a_miss_not_a_load(tmp_path, monkeypatch):
    d = str(tmp_path / "cache")
    _run_once(d)
    n1 = len(_blobs(d))
    # a trace-time lever changes the key: the existing entries are
    # unreachable (miss -> fresh compile + new entries), NOT loaded
    monkeypatch.setenv("PADDLE_TPU_NO_PALLAS", "1")
    warm0 = obs.AOT_COMPILE_MS.stats(path="warm", kind="run")["count"]
    miss0 = obs.CACHE_MISSES.total()
    _run_once(d)
    assert (obs.AOT_COMPILE_MS.stats(path="warm", kind="run")["count"]
            == warm0)
    assert obs.CACHE_MISSES.total() > miss0
    assert len(_blobs(d)) == n1 + 2


def test_jax_version_is_in_the_key(tmp_path, monkeypatch):
    d = str(tmp_path / "cache")
    _run_once(d)
    n1 = len(_blobs(d))
    real = aot_cache.env_fingerprint()
    monkeypatch.setattr(
        aot_cache, "env_fingerprint",
        lambda: ("fmt1", "99.99.99") + tuple(real[2:]))
    warm0 = obs.AOT_COMPILE_MS.stats(path="warm", kind="run")["count"]
    _run_once(d)  # "newer jax": old entries must not load
    assert (obs.AOT_COMPILE_MS.stats(path="warm", kind="run")["count"]
            == warm0)
    assert len(_blobs(d)) == n1 + 2


def test_unwritable_cache_dir_degrades_to_compile_only(tmp_path):
    # a FILE where the cache dir should be: makedirs/open fail on every
    # store. (chmod is unreliable here — the suite may run as root.)
    blocker = tmp_path / "blocked"
    blocker.write_text("in the way")
    err0 = obs.AOT_CACHE_ERRORS.value(op="store")
    v = _run_once(str(blocker))
    assert np.isfinite(v)
    assert obs.AOT_CACHE_ERRORS.value(op="store") - err0 >= 2
    assert blocker.read_text() == "in the way"  # nothing clobbered it


def test_kill_switch_disables_disk_tier(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_AOT_CACHE", "0")
    d = str(tmp_path / "cache")
    v = _run_once(d)
    assert np.isfinite(v)
    assert not os.path.exists(d)  # nothing written anywhere


def test_bad_max_bytes_env_falls_back(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_AOT_CACHE_MAX_BYTES", "a lot")
    with pytest.warns(UserWarning, match="PADDLE_TPU_AOT_CACHE_MAX_BYTES"):
        assert aot_cache.max_bytes_from_env() == aot_cache.DEFAULT_MAX_BYTES


# -- GC ------------------------------------------------------------------

def test_gc_evicts_oldest_past_max_bytes(tmp_path):
    d = str(tmp_path / "cache")
    cache = aot_cache.AotDiskCache(cache_dir=d)
    os.makedirs(d)
    for i, key in enumerate(["aa", "bb", "cc", "dd"]):
        with open(cache.blob_path(key), "wb") as f:
            f.write(b"x" * 100)
        cache.write_meta(key, {"kind": "step"})
        mtime = 1_000_000 + i * 1000
        for p in (cache.blob_path(key), cache.meta_path(key)):
            os.utime(p, (mtime, mtime))
    evict0 = obs.AOT_CACHE_EVICTIONS.total()
    # keep roughly two entries' worth: the two OLDEST pairs must go
    evicted = cache.gc(max_bytes=2 * 100 + 120)
    assert evicted == ["aa", "bb"]
    assert _blobs(d) == [n + aot_cache.BLOB_SUFFIX for n in ("cc", "dd")]
    assert obs.AOT_CACHE_EVICTIONS.total() - evict0 == 2
    assert cache.total_bytes() <= 2 * 100 + 120
    # use refreshes recency: touching cc makes dd the eviction victim
    os.utime(cache.blob_path("cc"), None)
    assert cache.gc(max_bytes=150) == ["dd"]
    assert _blobs(d) == ["cc" + aot_cache.BLOB_SUFFIX]


def test_store_applies_the_bound(tmp_path):
    d = str(tmp_path / "cache")
    # every executor store GCs: with a tiny bound the directory can hold
    # at most the newest entry, and execution still works
    main, startup, scope, loss = _build()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe._disk = aot_cache.AotDiskCache(cache_dir=d, max_bytes=1)
        exe.run(startup)
        v = float(exe.run(main, feed=_FEED, fetch_list=[loss])[0])
    assert np.isfinite(v)
    assert _blobs(d) == []  # both entries evicted straight away


# -- donation ------------------------------------------------------------

def test_donation_still_in_place_on_the_aot_path(tmp_path):
    d = str(tmp_path / "cache")
    _run_once(d)  # prime: the next executor runs DESERIALIZED executables
    main, startup, scope, loss = _build()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe._disk = aot_cache.AotDiskCache(cache_dir=d)
        exe.run(startup)
        warm0 = obs.AOT_COMPILE_MS.stats(path="warm", kind="run")["count"]
        exe.run(main, feed=_FEED, fetch_list=[loss])
        assert (obs.AOT_COMPILE_MS.stats(path="warm", kind="run")["count"]
                > warm0), "expected the disk-cached executable"
        # grab the live param buffers, run again: the deserialized
        # executable must DONATE them (in-place update at the XLA buffer
        # level), not copy
        params = [scope.find_var(p.name)
                  for p in main.global_block().all_parameters()]
        params = [p for p in params if isinstance(p, jax.Array)]
        assert params, "no device-resident parameters to check"
        exe.run(main, feed=_FEED, fetch_list=[loss])
        assert all(p.is_deleted() for p in params), \
            "AOT executable did not donate the state buffers"


# -- cross-process reuse (the acceptance-criteria subprocess test) -------

def test_second_process_reuses_training_executable(tmp_path):
    """A warm SECOND process must pay zero cold compiles: startup, step,
    and fused-loop executables all deserialize from the first process's
    cache (no re-trace — tracing only happens inside cold lower())."""
    d = str(tmp_path / "cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_AOT_CACHE_DIR=d, PADDLE_TPU_AOT_CACHE="1")

    def child():
        proc = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tests", "_aot_child.py")],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=_REPO)
        assert proc.returncode == 0, proc.stderr[-3000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    first = child()
    assert first["cold_compiles"] >= 3  # startup + step + loop
    assert first["warm_loads"] == 0
    second = child()
    assert second["cold_compiles"] == 0, "warm process re-compiled"
    assert second["warm_loads"] >= 3
    assert second["first_loss"] == first["first_loss"]
    assert second["ttfs_s"] < first["ttfs_s"]


# -- shared layout -------------------------------------------------------

def test_predictor_and_executor_share_the_store(tmp_path):
    """One module, one file layout: a Predictor's __aot_cache__ is
    enumerable by the same AotDiskCache/ls code path the training cache
    uses, with kind=predict sidecars."""
    from paddle_tpu.inference import Predictor

    mp, sp = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(mp, sp):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[4])
            out = layers.fc(x, 3, act="softmax")
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(sp)
        fluid.io.save_inference_model(str(tmp_path), ["x"], [out], exe,
                                      main_program=mp, scope=scope)
    p = Predictor(str(tmp_path))
    p.run({"x": np.ones((2, 4), np.float32)})
    cache = aot_cache.AotDiskCache(
        cache_dir=os.path.join(str(tmp_path), "__aot_cache__"))
    entries = cache.entries()
    assert entries and entries[0]["meta"]["kind"] == "predict"
    assert entries[0]["meta"]["feed_sig"] == (("x", (2, 4), "float32"),)


# -- multi-process safety (the fleet-spawn story) -------------------------

def test_concurrent_cold_compile_same_key(tmp_path):
    """TWO processes cold-compile the SAME key against one cache dir at
    once — the fleet-startup race (N replicas spawned into an empty
    cache). Writes are tmp+rename atomic and idempotent (identical
    blobs, last rename wins), so both must exit clean and the surviving
    blob must be a VALID executable: a third process pays zero cold
    compiles."""
    import threading

    d = str(tmp_path / "cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_AOT_CACHE_DIR=d, PADDLE_TPU_AOT_CACHE="1")
    cmd = [sys.executable, os.path.join(_REPO, "tests", "_aot_child.py")]

    procs = [subprocess.Popen(cmd, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True, env=env,
                              cwd=_REPO)
             for _ in range(2)]
    outs = []

    def reap(p):
        out, err = p.communicate(timeout=600)
        outs.append((p.returncode, out, err))

    threads = [threading.Thread(target=reap, args=(p,)) for p in procs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    results = []
    for rc, out, err in outs:
        assert rc == 0, err[-3000:]
        results.append(json.loads(out.strip().splitlines()[-1]))
    # both children actually raced cold (neither found a finished warm
    # cache): at least one compiled everything; losses agree either way
    assert max(r["cold_compiles"] for r in results) >= 3
    assert results[0]["first_loss"] == results[1]["first_loss"]
    assert not [n for n in os.listdir(d) if ".tmp." in n], "torn tmp left"
    assert not [n for n in os.listdir(d)
                if n.endswith(aot_cache.QUARANTINE_SUFFIX)]
    # the blob both wrote is loadable: a third process is fully warm
    third = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=600, env=env, cwd=_REPO)
    assert third.returncode == 0, third.stderr[-3000:]
    rec = json.loads(third.stdout.strip().splitlines()[-1])
    assert rec["cold_compiles"] == 0, "racing writers corrupted the blob"
    assert rec["warm_loads"] >= 3
    assert rec["first_loss"] == results[0]["first_loss"]


def test_corrupt_sidecar_with_valid_blob_repairs(tmp_path):
    """A torn/garbage .sig next to a VALID blob must not cost the blob:
    preload skips it (counted reason=sidecar), the predict call still
    disk-loads the executable (zero re-compiles), and the sidecar is
    REWRITTEN so the next process's preload works again."""
    from paddle_tpu.inference import Predictor

    mp, sp = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(mp, sp):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[4])
            out = layers.fc(x, 3, act="softmax")
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(sp)
        fluid.io.save_inference_model(str(tmp_path), ["x"], [out], exe,
                                      main_program=mp, scope=scope)
    feed = {"x": np.ones((2, 4), np.float32)}
    p = Predictor(str(tmp_path))
    want, = p.run(feed)
    cache = aot_cache.AotDiskCache(
        cache_dir=os.path.join(str(tmp_path), "__aot_cache__"))
    (entry,) = cache.entries()
    sig_path = cache.meta_path(entry["key"])
    with open(sig_path, "wb") as f:
        f.write(b"\x80garbage not a pickle")

    corrupt0 = obs.AOT_CACHE_CORRUPT.value(reason="sidecar")
    p2 = Predictor(str(tmp_path))  # preload scans the corrupt sidecar
    assert p2._compiled == {}, "corrupt sidecar should not preload"
    got, = p2.run(feed)
    np.testing.assert_allclose(got, want)
    assert p2.traces == 0, "valid blob was recompiled over a bad sidecar"
    assert obs.AOT_CACHE_CORRUPT.value(reason="sidecar") > corrupt0
    # repaired: readable again, and the next process preloads normally
    meta = cache.read_meta(entry["key"])
    assert meta is not None and meta["kind"] == "predict"
    p3 = Predictor(str(tmp_path))
    assert len(p3._compiled) == 1
    assert p3.traces == 0
