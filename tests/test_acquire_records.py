"""One record per executable acquisition (`observability.observe_acquire`,
fed by `Engine.acquire`, `ShardedPredictor` and a lazy first call through
`observe_run`): a name, the path it came by, when it began, its wall time
and the parts of it, the `tracing.phase` it began under; a ring of its
own on the timeline; the same span in the flight recorder; and the
registry's compile instruments moved exactly as they always were (the
expected deltas below were read off the parent commit, scenario by
scenario)."""
from __future__ import annotations

import contextlib
import json
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, optimizer
from paddle_tpu import observability as obs
from paddle_tpu.observability import tracing
from paddle_tpu.runtime import aot_cache

PARTS = ("build_ms", "load_ms", "trace_ms", "xla_ms", "store_ms",
         "describe_ms")
# the parts a path must have, and those it must not
HAS = {"warm": {"load_ms", "blob_bytes"},
       "cold": {"trace_ms", "xla_ms", "store_ms", "blob_bytes"},
       "lazy": set()}
HAS_NOT = {"warm": {"trace_ms", "xla_ms", "store_ms"},
           "cold": {"load_ms"},
           "lazy": {"load_ms", "store_ms", "blob_bytes", "build_ms"}}


@pytest.fixture(autouse=True)
def trace_isolation():
    tracing.reset()
    tracing.set_sample_rate(0.0)
    yield
    tracing.set_sample_rate(0.0)
    tracing.reset()


def _registry():
    """The compile instruments, by kind (and tier, path): counts."""
    out = {}
    for name, inst in (("compile_total", obs.COMPILE_TOTAL),
                       ("hits", obs.CACHE_HITS),
                       ("misses", obs.CACHE_MISSES)):
        for labels, value in inst.samples():
            key = (name,) + tuple(sorted(
                (k, v) for k, v in labels.items() if k != "program"))
            out[key] = out.get(key, 0) + int(value)
    for name, hist in (("aot_ms", obs.AOT_COMPILE_MS),
                       ("latency", obs.COMPILE_LATENCY_MS)):
        for labels, _ in hist.samples():
            out[(name,) + tuple(sorted(labels.items()))] = int(
                hist.stats(**labels)["count"])
    return out


def _moved(before):
    now = _registry()
    return {k: now[k] - before.get(k, 0) for k in now
            if now[k] != before.get(k, 0)}


def _expected(kind, path, memory_tier=True, compile_counted=True):
    """What one acquisition of `kind` by `path` followed by one memory
    hit moves (the parent's deltas)."""
    want = {}
    if memory_tier:
        want[("misses", ("kind", kind), ("tier", "memory"))] = 1
    want[("hits", ("kind", kind), ("tier", "memory"))] = 1
    if path != "lazy":
        want[("aot_ms", ("kind", kind), ("path", path))] = 1
        tier = ("hits" if path == "warm" else "misses")
        want[(tier, ("kind", kind), ("tier", "disk"))] = 1
    if compile_counted:
        want[("compile_total", ("kind", kind))] = 1
        want[("latency", ("kind", kind))] = 1
    return want


def _check(rec, path, kind, name):
    t_now = time.time()
    assert (rec["type"], rec["kind"], rec["path"]) == ("compile", kind, path)
    assert rec["cache"] == ("aot-load" if path == "warm" else "miss")
    assert rec["name"] == name, rec
    assert HAS[path] <= set(rec), rec
    assert not HAS_NOT[path] & set(rec), rec
    assert all(rec[p] >= 0 for p in PARTS if p in rec)
    assert sum(rec.get(p, 0.0) for p in PARTS) <= rec["wall_ms"] + 0.01, rec
    # `ts` is the start, on time.time()'s clock
    assert rec["ts"] + rec["wall_ms"] / 1e3 <= t_now + 0.01
    if path == "warm":
        assert rec["load_ms"] > 0 and rec["blob_bytes"] > 0
    json.dumps(rec)


def _new_records(seen, kind):
    return [e for e in obs.TIMELINE.events("compile")[seen:]
            if e["kind"] == kind]


# -- the tiny programs -------------------------------------------------------

def _build():
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[6])
            y = layers.data(name="y", shape=[1])
            loss = layers.mean(layers.square(layers.fc(x, 5) - y))
            optimizer.SGD(0.1).minimize(loss)
    return main, startup, scope, loss


_FEED = {"x": np.linspace(0, 1, 48).reshape(8, 6).astype(np.float32),
         "y": np.ones((8, 1), np.float32)}


def _executor(cache_dir, loop=False, under=None):
    """A fresh Executor and a freshly built program on `cache_dir` (None:
    the disk tier off, the lazy path): two dispatches, the second a
    memory hit. -> (the main program's new compile records, what moved,
    the program's fingerprint)."""
    main, startup, scope, loss = _build()
    kind = "loop" if loop else "run"
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe._disk = aot_cache.AotDiskCache(cache_dir=cache_dir,
                                           enabled=cache_dir is not None)
        exe.run(startup)
        seen, before = len(obs.TIMELINE.events("compile")), _registry()
        for _ in range(2):
            with (tracing.phase(under) if under
                  else contextlib.nullcontext()):
                if loop:
                    exe.run_loop(main, feed=_FEED, fetch_list=[loss],
                                 steps=2)
                else:
                    exe.run(main, feed=_FEED, fetch_list=[loss])
    return _new_records(seen, kind), _moved(before), obs.program_fp(main)


@pytest.fixture(scope="module")
def predict_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("acq_predict_model"))
    mp, sp = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(mp, sp):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[4])
            out = layers.fc(x, 3, act="softmax")
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(sp)
        fluid.io.save_inference_model(d, ["x"], [out], exe,
                                      main_program=mp, scope=scope)
    return d


DV, DL, DH, DD, DI_, DML = 37, 2, 2, 16, 32, 64


@pytest.fixture(scope="module")
def decode_dir(tmp_path_factory):
    """test_tracing.py's tiny LM, exported for decode serving."""
    from paddle_tpu.models import transformer as T
    from paddle_tpu.serving.decode import DecodeConfig, save_decode_model

    d = str(tmp_path_factory.mktemp("acq_decode_model"))
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = startup.random_seed = 7
    with fluid.program_guard(prog, startup), fluid.unique_name.guard():
        ids = layers.data(name="ids", shape=[2, 16], dtype="int64",
                          append_batch_size=False)
        T.transformer_lm(ids, ids, DV, n_layer=DL, n_head=DH, d_model=DD,
                         d_inner=DI_, dropout_rate=0.0, max_len=DML,
                         fused_head=False)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        save_decode_model(d, DecodeConfig(
            vocab_size=DV, n_layer=DL, n_head=DH, d_model=DD, d_inner=DI_,
            max_len=DML), exe, scope=scope)
    return d


# -- cold, then warm from the same directory -----------------------------------

@pytest.mark.parametrize("loop", [False, True], ids=["run", "loop"])
def test_executor_cold_then_a_second_executor_warm(tmp_path, loop):
    kind = "loop" if loop else "run"
    for path in ("cold", "warm"):
        recs, moved, fp = _executor(str(tmp_path), loop=loop)
        (rec,) = recs   # the memory hit of the second dispatch wrote none
        _check(rec, path, kind, "%s/%s" % (kind, fp))
        assert rec["program"] == fp and rec["build_ms"] > 0
        assert "phase" not in rec
        # a first dispatch counts as a compilation whichever the path
        assert moved == _expected(kind, path), path


def test_predictor_cold_then_warm(predict_dir, tmp_path):
    from paddle_tpu.inference import Predictor

    feed = {"x": np.ones((2, 4), np.float32)}
    for path in ("cold", "warm"):
        pred = Predictor(predict_dir, cache_dir=str(tmp_path),
                         preload=False)
        seen, before = len(obs.TIMELINE.events("compile")), _registry()
        pred.run(feed)
        pred.run(feed)
        (rec,) = _new_records(seen, "predict")
        _check(rec, path, "predict",
               "predict/" + obs.program_fp(pred._program))
        # the predictor's cold record carries XLA's estimates, as before
        assert ("flops" in rec) == (path == "cold")
        assert ("describe_ms" in rec) == (path == "cold")
        assert _moved(before) == _expected(
            "predict", path, compile_counted=path == "cold"), path


@pytest.mark.parametrize("kind,batch,seq,name", [
    ("prefill", 1, 16, "ptpu_prefill_b1_s16"),
    ("decode", 4, 32, "ptpu_decode_b4_s32")], ids=["prefill", "decode"])
def test_decode_predictor_cold_then_warm(decode_dir, tmp_path, kind, batch,
                                         seq, name):
    from paddle_tpu.serving.decode import DecodePredictor

    for path in ("cold", "warm"):
        pred = DecodePredictor(decode_dir, cache_dir=str(tmp_path))
        seen, before = len(obs.TIMELINE.events("compile")), _registry()
        pred.acquire(kind, batch, seq)
        pred.acquire(kind, batch, seq)
        (rec,) = _new_records(seen, kind)
        _check(rec, path, kind, name)
        # what `describe` read off the executable rides along, timed
        assert {"cache_fed", "cache_aliased", "describe_ms"} <= set(rec)
        assert rec["build_ms"] > 0   # the program is built before the key
        assert _moved(before) == _expected(
            kind, path, memory_tier=False,
            compile_counted=path == "cold"), path


# -- the lazy path ---------------------------------------------------------------

def test_lazy_executor_first_call_is_the_acquisition():
    recs, moved, fp = _executor(None)
    (rec,) = recs
    _check(rec, "lazy", "run", "run/" + fp)
    assert moved == _expected("run", "lazy")


def test_lazy_parallel_executor_first_call_is_the_acquisition():
    from paddle_tpu.parallel import ParallelExecutor

    main, startup, scope, loss = _build()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                              scope=scope)
        seen, before = len(obs.TIMELINE.events("compile")), _registry()
        pe.run([loss], feed=_FEED)
        pe.run([loss], feed=_FEED)
    (rec,) = _new_records(seen, "parallel")
    _check(rec, "lazy", "parallel", "parallel/" + obs.program_fp(main))
    moved = _moved(before)
    assert moved[("compile_total", ("kind", "parallel"))] == 1
    assert moved[("latency", ("kind", "parallel"))] == 1
    assert not any(k[0] == "aot_ms" for k in moved)


# -- the phase an acquisition began under, and its own span ------------------------

def test_phase_is_named_when_one_is_open_and_absent_otherwise(tmp_path):
    tracing.set_sample_rate(1.0)
    (under,), _, _ = _executor(str(tmp_path / "a"), under="warm.up")
    assert under["phase"] == "warm.up"
    (lazy,), _, _ = _executor(None, under="warm.up")
    assert lazy["phase"] == "warm.up" and lazy["path"] == "lazy"
    (bare,), _, _ = _executor(str(tmp_path / "b"))
    assert "phase" not in bare   # its own "acquire" span is not a parent
    tracing.set_sample_rate(0.0)
    (off,), _, _ = _executor(str(tmp_path / "c"), under="warm.up")
    assert "phase" not in off    # at rate 0 no phase is ever open


def test_acquisition_is_a_span_of_the_process_ring_at_rate_1(tmp_path):
    ring = tracing.get_recorder()
    _executor(str(tmp_path))                       # rate 0, cold
    assert ring.snapshot()["rings"]["process"]["recorded"] == 0
    tracing.set_sample_rate(1.0)
    (rec,), _, fp = _executor(str(tmp_path))       # warm
    spans = [s for s in ring.spans() if s["name"] == "acquire"
             and s["executable"] == "run"]
    assert spans, ring.spans()
    span = spans[-1]
    assert span["blob_bytes"] == rec["blob_bytes"]
    assert [(p["parent"], p["name"]) for p in span["phases"]] == [
        ("acquire", "acquire.load")]
    (_cold,), _, _ = _executor(str(tmp_path / "fresh"))
    cold = [s for s in ring.spans() if s["name"] == "acquire"][-1]
    assert [p["name"] for p in cold["phases"]] == [
        "acquire.trace", "acquire.xla", "acquire.store"]
    # opened under a phase, the acquisition is a child of that record
    _executor(str(tmp_path / "fresh2"), under="warm.up")
    outer = [s for s in ring.spans() if s["name"] == "warm.up"]
    assert any(("warm.up", "acquire") in {
        (p["parent"], p["name"]) for p in s["phases"]} for s in outer)


# -- the ring of its own -------------------------------------------------------------

def test_compile_records_outlive_a_hundred_thousand_steps():
    tl = obs.StepTimeline()
    for i in range(30):
        tl.record_compile("run", "p%02d" % i, name="exe%02d" % i,
                          path="cold", wall_ms=float(i))
    for i in range(100_000):
        tl.record_step("run", 1.0)
    compiles = tl.events("compile")
    assert [e["name"] for e in compiles] == ["exe%02d" % i
                                             for i in range(30)]
    assert len(tl.events("step")) == 1024
    snap = tl.snapshot()
    assert snap["capacity"] == 1024 and snap["recorded"] == 100_030
    assert snap["dropped"] == 100_030 - 1024 - 30
    assert snap["rings"]["compile"] == {"capacity": 1024, "recorded": 30,
                                        "dropped": 0}
    assert snap["rings"]["step"]["dropped"] == 100_000 - 1024


def test_both_rings_read_merged_by_ts_and_each_is_bounded():
    tl = obs.StepTimeline(capacity=4, compile_capacity=2)
    t0 = time.time()
    # an acquisition is appended when it ENDS and stamped where it began
    tl.record_step("run", 1.0)
    tl.record_compile("run", "p", name="early", path="warm", ts=t0 - 60.0,
                      wall_ms=5.0, load_ms=4.0, trace_ms=None)
    tl.record_step("run", 2.0)
    merged = tl.events()
    assert [e["type"] for e in merged] == ["compile", "step", "step"]
    assert [e["seq"] for e in merged] == [1, 0, 2]
    assert "trace_ms" not in merged[0]   # a part that is None is left out
    assert tl.snapshot()["events"] == merged
    assert tl.events("neither") == []
    for i in range(3):
        tl.record_compile("run", "p", name="late%d" % i, path="cold")
    assert [e["name"] for e in tl.events("compile")] == ["late1", "late2"]
    rings = tl.snapshot()["rings"]
    assert rings["compile"] == {"capacity": 2, "recorded": 4, "dropped": 2}
    assert rings["step"] == {"capacity": 4, "recorded": 2, "dropped": 0}
    tl.reset()
    assert tl.snapshot()["recorded"] == 0 and tl.events() == []


def test_observe_acquire_feeds_only_what_its_caller_names():
    """A compile outside the AOT tier (`ShardedPredictor`'s): a record
    and a counted compilation, nothing of the tier's."""
    seen, before = len(obs.TIMELINE.events("compile")), _registry()
    obs.observe_acquire("predict_sharded", "cold", 12.0, program="abcd1234",
                        compile_ms=11.0, build_ms=1.0, trace_ms=4.0,
                        xla_ms=7.0)
    (rec,) = _new_records(seen, "predict_sharded")
    assert rec["name"] == "predict_sharded/abcd1234"
    assert (rec["path"], rec["cache"], rec["wall_ms"]) == ("cold", "miss",
                                                           12.0)
    assert _moved(before) == {
        ("compile_total", ("kind", "predict_sharded")): 1,
        ("latency", ("kind", "predict_sharded")): 1}
