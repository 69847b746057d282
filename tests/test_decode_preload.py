"""`DecodePredictor.preload`: the prefill executables a predictor's disk
directory holds are loaded when its first server starts, on the caller's
thread, through `Engine.acquire` (a `path == "warm"` record each, begun
under the phase `decode.preload`); an admission then finds its shape in
memory; nothing is ever compiled there; what cannot be used is skipped
without an exception; a second server of the predictor pays nothing."""
from __future__ import annotations

import os
import pickle
import shutil
import threading

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.observability import tracing
from paddle_tpu.runtime import aot_cache
from paddle_tpu.serving.decode import DecodePredictor, DecodeServer
# the tiny LM exported for decode serving (37 tokens, max_len 64)
from test_acquire_records import decode_dir  # noqa: F401

SLOTS = 4
# one prompt a bucket: 16, 32 and (with the reply's room) the slab's 64
PROMPTS = [np.arange(1, 6, dtype=np.int64),
           np.arange(1, 21, dtype=np.int64),
           np.arange(1, 41, dtype=np.int64)]
PREFILLS = {"ptpu_prefill_b1_s16", "ptpu_prefill_b%d_s16" % SLOTS,
            "ptpu_prefill_b1_s32", "ptpu_prefill_b1_s64"}
ZERO = dict.fromkeys(("found", "loaded", "resident", "stale", "unreadable"),
                     0)


@pytest.fixture(autouse=True)
def trace_isolation():
    tracing.reset()
    tracing.set_sample_rate(0.0)
    yield
    tracing.set_sample_rate(0.0)
    tracing.reset()


def _serve(pred, prompts=PROMPTS, **kw):
    """One server of `pred`, the prompts through it one at a time (each
    its own admission), stopped: -> the tokens."""
    srv = DecodeServer(pred, slots=SLOTS, max_new_tokens=4, **kw)
    srv.start()
    try:
        return [np.asarray(srv.submit((p,)).result(timeout=300)[0])
                for p in prompts]
    finally:
        srv.stop()


@pytest.fixture(scope="module")
def served(decode_dir, tmp_path_factory):
    """A disk directory as a first server's admissions leave it (every
    executable compiled cold, on the loop thread or in `start`), and
    the tokens that server answered."""
    cache = str(tmp_path_factory.mktemp("preload_aot"))
    pred = DecodePredictor(decode_dir, cache_dir=cache)
    seen = len(obs.TIMELINE.events("compile"))
    tokens = _serve(pred)
    recs = obs.TIMELINE.events("compile")[seen:]
    assert {r["path"] for r in recs} == {"cold"}
    assert {r["name"] for r in recs if r["kind"] == "prefill"} == PREFILLS
    assert pred.preload() == ZERO   # the directory was empty at `start`
    return cache, tokens


def _copy(served, tmp_path):
    cache = str(tmp_path / "aot")
    shutil.copytree(served[0], cache)
    return cache


def _records(seen):
    return obs.TIMELINE.events("compile")[seen:]


def _count(inst, **want):
    return sum(v for labels, v in inst.samples()
               if all(labels.get(k) == x for k, x in want.items()))


def _key_of(cache, name):
    """The disk key of the prefill sidecar that names `(batch, seq)`."""
    batch, seq = (int(x[1:]) for x in name.split("_")[2:])
    for key, meta in aot_cache.AotDiskCache(cache).sidecars_by_recency():
        if meta["kind"] == "prefill" and any(
                n == "tokens" and tuple(shp) == (batch, seq)
                for n, shp, _ in meta["feed_sig"]):
            return key
    raise AssertionError("no sidecar for %s" % name)


# -- the path the benchmark's warm run takes -----------------------------------

def test_a_new_predictors_start_preloads_and_admissions_hit_memory(
        decode_dir, served, tmp_path):
    cache, want_tokens = _copy(served, tmp_path), served[1]
    tracing.set_sample_rate(1.0)
    pred = DecodePredictor(decode_dir, cache_dir=cache)
    seen = len(obs.TIMELINE.events("compile"))
    loaded_before = _count(obs.DECODE_PRELOAD, result="loaded")
    srv = DecodeServer(pred, slots=SLOTS, max_new_tokens=4)
    srv.start()
    try:
        recs = _records(seen)
        # one warm record a shape, each begun under the preload's phase;
        # the server's own step after it, under none
        assert all(r["path"] == "warm" and r["load_ms"] > 0
                   and r["blob_bytes"] > 0 for r in recs), recs
        pre = [r for r in recs if r["kind"] == "prefill"]
        assert sorted(r["name"] for r in pre) == sorted(PREFILLS)
        assert {r["phase"] for r in pre} == {"decode.preload"}
        (step,) = [r for r in recs if r["kind"] == "decode"]
        assert "phase" not in step and len(recs) == len(PREFILLS) + 1
        assert pred.traces == 0
        assert pred.preload() == dict(ZERO, found=4, loaded=4)
        assert _count(obs.DECODE_PRELOAD,
                      result="loaded") - loaded_before == 4
        # the admissions: no record, a memory hit each, the same tokens
        seen = len(obs.TIMELINE.events("compile"))
        hits = _count(obs.CACHE_HITS, kind="prefill", tier="memory")
        got = [np.asarray(srv.submit((p,)).result(timeout=300)[0])
               for p in PROMPTS]
    finally:
        srv.stop()
    assert _records(seen) == []
    assert _count(obs.CACHE_HITS, kind="prefill",
                  tier="memory") - hits == len(PROMPTS)
    for g, w in zip(got, want_tokens):
        np.testing.assert_array_equal(g, w)
    # the preload is one span of the process ring with its counts, each
    # load an `acquire` / `acquire.load` pair inside it
    (span,) = [s for s in tracing.get_recorder().spans()
               if s["name"] == "decode.preload"]
    assert (span["found"], span["loaded"], span["skipped"]) == (4, 4, 0)
    phases = {(p["parent"], p["name"]): p["n"] for p in span["phases"]}
    assert phases[("decode.preload", "acquire")] == 4
    assert phases[("acquire", "acquire.load")] == 4
    # a second server of the same predictor: not one record more
    seen = len(obs.TIMELINE.events("compile"))
    assert [t.tolist() for t in _serve(pred)] == [
        t.tolist() for t in want_tokens]
    assert _records(seen) == []
    assert pred.preload() == dict(ZERO, found=4, loaded=4)
    assert pred.traces == 0


def test_preload_runs_on_the_thread_that_calls_start(decode_dir, served,
                                                     tmp_path, monkeypatch):
    pred = DecodePredictor(decode_dir, cache_dir=_copy(served, tmp_path))
    threads = []
    real = aot_cache.AotDiskCache.load

    def load(self, key):
        threads.append(threading.current_thread().name)
        return real(self, key)

    monkeypatch.setattr(aot_cache.AotDiskCache, "load", load)
    _serve(pred)
    assert len(threads) == len(PREFILLS) + 1
    assert set(threads) == {threading.current_thread().name}


def test_servers_started_at_once_from_many_threads_pay_one_walk(
        decode_dir, served, tmp_path):
    pred = DecodePredictor(decode_dir, cache_dir=_copy(served, tmp_path))
    seen = len(obs.TIMELINE.events("compile"))
    got, gate = [], threading.Barrier(6)

    def start():
        gate.wait(timeout=60)
        got.append(pred.preload())

    threads = [threading.Thread(target=start) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads)
    assert got == [dict(ZERO, found=4, loaded=4)] * 6
    assert sorted(r["name"] for r in _records(seen)) == sorted(PREFILLS)


def test_what_was_in_memory_already_is_left_alone(decode_dir, served,
                                                  tmp_path):
    pred = DecodePredictor(decode_dir, cache_dir=_copy(served, tmp_path))
    pred.acquire("prefill", 1, 32)   # as a check's rollout would have
    seen = len(obs.TIMELINE.events("compile"))
    assert pred.preload() == dict(ZERO, found=4, loaded=3, resident=1)
    assert sorted(r["name"] for r in _records(seen)) == sorted(
        PREFILLS - {"ptpu_prefill_b1_s32"})


# -- what cannot be used is skipped: no exception, no compile ------------------

def _other_program(cache, key):
    """The pair under a key it does not hash to: as another program's,
    environment's or jax's executable of the same shape lies there."""
    disk = aot_cache.AotDiskCache(cache)
    other = "0" * len(key)
    os.replace(disk.blob_path(key), disk.blob_path(other))
    os.replace(disk.meta_path(key), disk.meta_path(other))


def _truncated_blob(cache, key):
    path = aot_cache.AotDiskCache(cache).blob_path(key)
    with open(path, "rb") as f:
        payload = f.read()
    with open(path, "wb") as f:
        f.write(payload[:len(payload) // 2])


def _rewrite_meta(change):
    def damage(cache, key):
        disk = aot_cache.AotDiskCache(cache)
        meta = disk.read_meta(key)
        change(meta)
        disk.write_meta(key, meta)
    return damage


def _garbage_sidecar(cache, key):
    with open(aot_cache.AotDiskCache(cache).meta_path(key), "wb") as f:
        pickle.dump({"kind": "prefill", "feed_sig": 7,
                     "env": aot_cache.env_fingerprint()}, f)


@pytest.mark.parametrize("damage,counts,lazy_path", [
    (_other_program, dict(found=4, loaded=3, stale=1), "cold"),
    (_truncated_blob, dict(found=4, loaded=3, unreadable=1), "cold"),
    (_rewrite_meta(lambda m: m.pop("feed_sig")),
     dict(found=3, loaded=3), "warm"),
    (_rewrite_meta(lambda m: m.update(env=("another", "jax"))),
     dict(found=3, loaded=3), "warm"),
    (_rewrite_meta(lambda m: m.update(kind="predict")),
     dict(found=3, loaded=3), "warm"),
    (_garbage_sidecar, dict(found=3, loaded=3), "warm"),
], ids=["other_program", "truncated_blob", "no_feed_sig", "other_env",
        "other_kind", "garbage_sidecar"])
def test_a_sidecar_that_cannot_be_used_is_skipped(decode_dir, served,
                                                  tmp_path, damage, counts,
                                                  lazy_path):
    cache = _copy(served, tmp_path)
    name = "ptpu_prefill_b1_s32"
    damage(cache, _key_of(cache, name))
    pred = DecodePredictor(decode_dir, cache_dir=cache)
    seen = len(obs.TIMELINE.events("compile"))
    compiles = _count(obs.COMPILE_TOTAL)
    before = {r: _count(obs.DECODE_PRELOAD, result=r)
              for r in ("loaded", "stale", "unreadable")}
    assert pred.preload() == dict(ZERO, **counts)
    recs = _records(seen)
    assert sorted(r["name"] for r in recs) == sorted(PREFILLS - {name})
    assert {r["path"] for r in recs} == {"warm"}
    assert pred.traces == 0 and _count(obs.COMPILE_TOTAL) == compiles
    assert {r: _count(obs.DECODE_PRELOAD, result=r) - before[r]
            for r in before} == {
        r: counts.get(r, 0) for r in before}
    # the shape is acquired at its first use, as it always was
    seen = len(obs.TIMELINE.events("compile"))
    pred.acquire("prefill", 1, 32)
    (rec,) = _records(seen)
    assert (rec["name"], rec["path"]) == (name, lazy_path)
    tokens = _serve(pred)
    for g, w in zip(tokens, served[1]):
        np.testing.assert_array_equal(g, w)


# -- where there is nothing to preload -----------------------------------------

@pytest.mark.parametrize("case", ["empty_directory", "tier_off",
                                  "prewarm_false"])
def test_nothing_is_preloaded_and_nothing_compiled(decode_dir, served,
                                                   tmp_path, case):
    tracing.set_sample_rate(1.0)
    if case == "empty_directory":
        pred = DecodePredictor(decode_dir, cache_dir=str(tmp_path / "new"))
    elif case == "tier_off":
        pred = DecodePredictor(decode_dir, aot_cache=False,
                               cache_dir=_copy(served, tmp_path))
    else:
        pred = DecodePredictor(decode_dir,
                               cache_dir=_copy(served, tmp_path))
    seen = len(obs.TIMELINE.events("compile"))
    if case == "prewarm_false":
        srv = DecodeServer(pred, slots=SLOTS, max_new_tokens=4,
                           prewarm=False)
        srv.start()
        try:
            assert pred._preloaded is None and _records(seen) == []
            srv.submit((PROMPTS[1],)).result(timeout=300)
        finally:
            srv.stop()
        # loaded at its first use, on the loop thread, as it always was
        recs = _records(seen)
        assert sorted(r["name"] for r in recs) == [
            "ptpu_decode_b%d_s64" % SLOTS, "ptpu_prefill_b1_s32"]
        assert [r["phase"] for r in recs if r["kind"] == "prefill"] == [
            "decode.loop.admit"]
        assert pred._preloaded is None
    else:
        assert pred.preload() == ZERO
        assert _records(seen) == [] and pred.traces == 0
    assert not [s for s in tracing.get_recorder().spans()
                if s["name"] == "decode.preload"]


def test_engine_acquire_without_lower_takes_the_disk_tier_or_nothing(
        decode_dir, tmp_path):
    pred = DecodePredictor(decode_dir, cache_dir=str(tmp_path))
    ck = pred._signature("prefill", 1, 16)
    keyed = pred._keyed(ck)
    seen = len(obs.TIMELINE.events("compile"))
    assert keyed.engine.acquire("prefill", keyed.key, None) == (
        None, "absent", None)
    assert pred._acquire_keyed(ck, keyed, compile=False) is None
    assert _records(seen) == [] and pred.traces == 0
    assert ck not in pred._compiled


# -- counts a phase learns while it runs ---------------------------------------

@pytest.mark.parametrize("rate", [0.0, 1.0])
def test_phase_note_adds_counts_to_the_record(rate):
    tracing.set_sample_rate(rate)
    with tracing.phase("outer", found=3) as ph:
        with tracing.phase("inner") as inner:
            inner.note(loaded=2)
        ph.note(skipped=1)
    spans = [s for s in tracing.get_recorder().spans()
             if s["name"] == "outer"]
    if rate:
        (span,) = spans
        assert (span["found"], span["loaded"], span["skipped"]) == (3, 2, 1)
    else:
        assert spans == []
