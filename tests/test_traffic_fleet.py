"""Traffic-shaped fleet tests (ISSUE 13): shed-vs-timeout semantics and
priority dispatch against a deliberately SLOW replica (a
``serving.request`` fault-DELAY barrier makes queueing deterministic
instead of racing the scheduler), and the acceptance trace — a scripted
sequence driven through a live Router + Autoscaler covering scale-up,
burst, replica SIGKILL, and drain-shrink with zero dropped/misversioned
requests, every shed request receiving an explicit structured reject.
The full-scale chaos + latency-vs-offered-load curve variant runs under
``slow``."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu import observability as obs
from paddle_tpu.inference import Predictor
from paddle_tpu.serving import Autoscaler, RejectedError, Router

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from tools.loadgen import run_trace  # noqa: E402


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    """Saved 4->8->6 softmax MLP + (feed rows, direct-predictor rows);
    the direct Predictor primes the shared AOT cache so every fleet
    worker below warm-starts."""
    model_dir = str(tmp_path_factory.mktemp("traffic_model"))
    mp, sp = fluid.Program(), fluid.Program()
    mp.random_seed = sp.random_seed = 11
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(mp, sp):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[4])
            h = layers.fc(x, 8, act="relu")
            out = layers.fc(h, 6, act="softmax")
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(sp)
        fluid.io.save_inference_model(model_dir, ["x"], [out], exe,
                                      main_program=mp, scope=scope)
    feed = np.linspace(-1, 1, 5 * 4).reshape(5, 4).astype(np.float32)
    want, = Predictor(model_dir).run({"x": feed})
    return model_dir, feed, np.asarray(want)


@pytest.fixture(scope="module")
def slow_fleet(model):
    """One replica that takes >=150ms per request (fault-DELAY at the
    worker's ``serving.request`` barrier) behind a 2-deep in-flight
    window: submissions beyond the window QUEUE in the router, which is
    exactly the regime shedding and priority dispatch exist for."""
    model_dir, _feed, _want = model
    router = Router(
        model_dir, replicas=1, max_batch=4, max_outstanding=2,
        jax_platform="cpu", start_timeout=300,
        worker_env={"PADDLE_TPU_FAULT_DELAY": "serving.request:0.15"})
    router.start()
    yield router
    router.stop()


def _wait(cond, timeout: float) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.1)
    return bool(cond())


# -- shed-vs-timeout semantics (ISSUE satellite) ---------------------------

def test_deadline_expiry_in_queue_is_reject_not_hang(slow_fleet, model):
    """A client whose deadline expires while QUEUED must receive the
    structured reject — promptly, from the dispatch sweep — and
    ``fleet_shed_total{class}`` must tick once per reject. Sheds are
    answers, not failures: the router failure counter must not move."""
    router = slow_fleet
    _model_dir, feed, _want = model
    # two unbounded requests first: establishes the service-time EWMA
    for f in [router.submit((feed[0],)) for _ in range(2)]:
        f.result(timeout=120)
    shed0 = obs.FLEET_SHED.value(**{"class": "interactive"})
    fail0 = obs.PREDICT_FAILURES.value(path="router")
    futs = [router.submit((feed[i % 5],), slo="interactive",
                          deadline_ms=600) for i in range(12)]
    t0 = time.perf_counter()
    oks, rejects = 0, []
    for f in futs:
        try:
            f.result(timeout=60)
            oks += 1
        except RejectedError as e:
            rejects.append(e)
    elapsed = time.perf_counter() - t0
    # every future answered (nothing raised TimeoutError above), and the
    # tail was answered by REJECTS long before 12 x 150ms could drain
    assert oks >= 1, "the in-window head of the queue should serve"
    assert rejects, "the queued tail should shed against a 600ms deadline"
    assert elapsed < 30.0
    assert (obs.FLEET_SHED.value(**{"class": "interactive"}) - shed0
            == len(rejects))
    assert obs.PREDICT_FAILURES.value(path="router") == fail0
    for e in rejects:
        assert e.slo == "interactive"
        assert e.reason in ("expired", "hopeless")
        assert e.queue_depth is not None
        assert e.deadline_remaining_ms is not None
    # the exposition line dashboards key on (also pinned fleet-wide in
    # test_metrics_dump's merge round)
    text = obs.export.to_prometheus()
    assert any(ln.startswith(
        'paddle_tpu_fleet_shed_total{class="interactive"}')
        for ln in text.splitlines())


def test_priority_classes_dispatch_urgent_first(slow_fleet, model):
    """With the replica busy, later-submitted interactive (priority 0)
    requests must overtake earlier batch (priority 2) requests in the
    dispatch queue."""
    router = slow_fleet
    _model_dir, feed, _want = model
    order: list = []
    lock = threading.Lock()

    def tagged(tag):
        def _cb(_f):
            with lock:
                order.append(tag)
        return _cb

    # occupy the 2-deep window so everything below queues in the router
    fillers = [router.submit((feed[0],)) for _ in range(2)]
    batch = []
    for i in range(5):
        f = router.submit((feed[i % 5],), slo="batch")
        f.add_done_callback(tagged("b%d" % i))
        batch.append(f)
    urgent = []
    for i in range(5):
        f = router.submit((feed[i % 5],), slo="interactive")
        f.add_done_callback(tagged("i%d" % i))
        urgent.append(f)
    for f in fillers + batch + urgent:
        f.result(timeout=120)
    pos = {tag: i for i, tag in enumerate(order)}
    mean_i = sum(pos["i%d" % i] for i in range(5)) / 5.0
    mean_b = sum(pos["b%d" % i] for i in range(5)) / 5.0
    assert mean_i < mean_b, (order, "interactive should complete first")


# -- the acceptance trace --------------------------------------------------

def test_scripted_trace_scale_up_burst_kill_drain_shrink(model):
    """The ISSUE acceptance: one scripted trace through (1) baseline,
    (2) a saturating burst the Autoscaler answers with scale-up, (3) a
    Poisson burst with a replica SIGKILLed mid-flight, (4) sustained
    pressure restoring the fleet, then (5) idle drain-shrink back to
    the floor — with zero dropped requests, zero misversioned
    responses, zero non-reject errors, and every shed an explicit
    reject."""
    model_dir, feed, _want = model
    classes = {
        "interactive": {"priority": 0, "deadline_ms": 400.0,
                        "weight": 0.75},
        "batch": {"priority": 2, "weight": 0.25},
    }
    from tools.loadgen import slo_classes_of

    router = Router(model_dir, replicas=1, max_batch=4,
                    max_outstanding=8, jax_platform="cpu",
                    start_timeout=300,
                    slo_classes=slo_classes_of({"classes": classes}))
    router.start()
    scaler = Autoscaler(router, min_replicas=1, max_replicas=2,
                        interval_s=0.2, up_ticks=1, down_ticks=4,
                        cooldown_s=0.5, high_util=0.6, low_util=0.1,
                        spawn_timeout=300)
    scaler.start()
    idx = [0]

    def next_sample():
        idx[0] = (idx[0] + 1) % 5
        return (feed[idx[0]],)

    def trace(name, phases):
        return {"name": name, "classes": classes, "phases": phases}

    killed: list = []

    def kill_one():
        with router._cond:
            ready = [w for w in router._workers if w.state == "ready"]
        if ready:
            ready[0].proc.kill()
            killed.append(ready[0].name)

    reports = []
    try:
        # 1) baseline on one replica
        reports.append(run_trace(router, trace(
            "baseline", [{"duration_s": 1.0, "rps": 15, "mode": "open"}]),
            next_sample))
        # 2) saturating burst (12 closed-loop clients > the 8-deep
        # window) -> the scaler must add the second replica
        reports.append(run_trace(router, trace(
            "burst-up", [{"duration_s": 3.0, "mode": "closed",
                          "clients": 12}]), next_sample))
        assert _wait(lambda: router.stats()["ready"] >= 2, 90), \
            (router.stats(), scaler.actions)
        assert any(d == "up" for _t, d in scaler.actions)
        # 3) Poisson burst with heavy-tail fan-out; SIGKILL a ready
        # replica mid-burst — crash requeue + (held) dispatch must
        # answer every request
        timer = threading.Timer(0.7, kill_one)
        timer.daemon = True
        timer.start()
        reports.append(run_trace(router, trace(
            "burst-kill", [{"duration_s": 2.5, "rps": 120, "mode": "open",
                            "fanout": {"dist": "pareto", "alpha": 1.5,
                                       "max": 8}}]), next_sample))
        timer.cancel()
        assert killed, "chaos kill never fired"
        # (the scaler thread reaps between its actions, and a scale-up it
        # is in the middle of is a worker spawn: see step 5's waits)
        assert _wait(lambda: router.stats()["dead"] == 0, 90), \
            "autoscaler should reap the crashed replica"
        # 4) sustained pressure: the fleet grows back to 2
        reports.append(run_trace(router, trace(
            "pressure", [{"duration_s": 3.0, "mode": "closed",
                          "clients": 12}]), next_sample))
        assert _wait(lambda: router.stats()["ready"] >= 2, 90), \
            (router.stats(), scaler.actions)
        # 5) idle: utilization collapses -> drain-shrink to the floor
        # (generous waits: worker spawn/stop under 2-core CPU contention
        # can stretch 10x, and the scaler thread serializes on them)
        assert _wait(lambda: any(d == "down" for _t, d in scaler.actions),
                     120), scaler.actions
        assert _wait(lambda: router.stats()["ready"] == 1, 60), \
            router.stats()
    finally:
        scaler.stop()
        router.stop()
    # -- the zero-drop / explicit-reject verdict over the WHOLE trace --
    for r in reports:
        assert r["dropped"] == 0, r
        assert r["errors"] == 0, r
        assert r["completed"] == r["offered"], r
        assert r["fleet"]["misversioned"] == 0, r
        assert r["sheds_all_rejected"], r
    served = sum(pc["ok"] for r in reports
                 for pc in r["per_class"].values())
    assert served > 0


# -- decode crash requeue with the PR-14 levers live -----------------------

def test_decode_crash_requeue_with_spec_and_prefix(tmp_path):
    """SIGKILL a decode replica mid-traffic with speculative rounds and
    the prefix store live: every in-flight sequence (mid-speculation,
    prefix-shared alike) re-prefills on a survivor — zero drops, zero
    misversioned, token-for-token correct output (the zero-drop
    contract of PR 8/13 extended to the PR-14 decode levers)."""
    from paddle_tpu import optimizer
    from paddle_tpu.models import transformer as T
    from paddle_tpu.serving.decode import (DecodeConfig, DecodePredictor,
                                           save_decode_model)

    V, L = 37, 2
    model_dir = str(tmp_path / "decode_model")
    prog, sp = fluid.Program(), fluid.Program()
    prog.random_seed = sp.random_seed = 7
    with fluid.program_guard(prog, sp):
        with fluid.unique_name.guard():
            ids = layers.data(name="ids", shape=[2, 16], dtype="int64",
                              append_batch_size=False)
            lbl = layers.data(name="lbl", shape=[2, 16], dtype="int64",
                              append_batch_size=False)
            loss, _ = T.transformer_lm(
                ids, lbl, V, n_layer=L, n_head=2, d_model=16, d_inner=32,
                dropout_rate=0.0, max_len=64, fused_head=False)
            optimizer.Adam(learning_rate=1e-3).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    r = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe.run(sp)
        x = r.randint(0, V, (2, 16)).astype(np.int64)
        exe.run(prog, feed={"ids": x, "lbl": x})
        save_decode_model(model_dir, DecodeConfig(
            vocab_size=V, n_layer=L, n_head=2, d_model=16, d_inner=32,
            max_len=64), exe, scope=scope)
    pred = DecodePredictor(model_dir)
    prompts = [r.randint(1, V, r.randint(3, 9)).astype(np.int64)
               for _ in range(6)]
    prompts += [prompts[0].copy()] * 2  # prefix sharers
    want = pred.generate(prompts, max_new_tokens=6)
    before_mis = obs.FLEET_MISVERSIONED.value()
    router = Router(model_dir, replicas=2, decode=True, decode_slots=2,
                    decode_max_seq=32, max_new_tokens=6,
                    decode_speculative=True, decode_spec_k=2,
                    decode_prefix_cache=True, jax_platform="cpu")
    router.start()
    opts = np.array([6], np.int64)
    futs = [router.submit((p, opts)) for p in prompts[:4]]
    time.sleep(0.2)  # let some sequences reach mid-speculation
    router._workers[0].proc.kill()  # hard SIGKILL, no drain
    futs += [router.submit((p, opts)) for p in prompts[4:]]
    got = [f.result(timeout=300)[0] for f in futs]
    router.stop()
    assert len(got) == len(prompts)  # zero drops
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert obs.FLEET_MISVERSIONED.value() == before_mis


# -- full-scale chaos + latency-vs-offered-load curve (slow) ---------------

@pytest.mark.slow
def test_full_chaos_latency_curve(model):
    """The curve shape: sweep offered load through the
    loadgen CLI (burst trace, autoscale 1:3, mid-burst SIGKILL at the
    heaviest level) and require the strict verdict at every level."""
    model_dir, _feed, _want = model
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "loadgen.py"),
         "--model-dir", model_dir, "--shape", "burst", "--rps", "30",
         "--burst-x", "5", "--duration", "6", "--replicas", "1",
         "--deadline-ms", "500", "--autoscale", "1:2",
         "--chaos-kill", "3", "--curve", "20,80", "--json",
         "--seed", "3"],
        capture_output=True, text=True, timeout=560, env=env, cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    assert len(lines) == 2
    for r in lines:
        assert r["schema"] == "loadgen/2"
        assert r["ok"] is True, r
        assert r["dropped"] == 0 and r["errors"] == 0
        assert r["sheds_all_rejected"] is True
    # the curve is monotone in offered load
    assert (lines[1]["offered_rps_target"]
            > lines[0]["offered_rps_target"])
