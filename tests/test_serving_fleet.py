"""Serving fleet tests: the shared Engine core, the multi-replica
Router (round-trip, balancing, backpressure, drain/restart with zero
drops, crash requeue), and the sharded (tp) predictor behind the same
front door. Workers are real subprocesses on the CPU backend over a
small MLP — the 2-replica round-trip is the tier-1 CI smoke from the
ISSUE checklist."""
from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu import observability as obs
from paddle_tpu.inference import Predictor
from paddle_tpu.serving import Router, ShardedPredictor


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    """Saved 4->8->6 softmax MLP + (feed rows, direct-predictor rows)."""
    model_dir = str(tmp_path_factory.mktemp("fleet_model"))
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
    # a direct Predictor primes the model's __aot_cache__ too, so every
    # fleet worker below warm-starts (the PR-5 shared-cache story)
    want, = Predictor(model_dir).run({"x": feed})
    return model_dir, feed, np.asarray(want)


@pytest.fixture(scope="module")
def fleet(model):
    """One 2-replica fleet shared by the read-only tests (spawning jax
    subprocesses is the dominant cost here)."""
    model_dir, _feed, _want = model
    router = Router(model_dir, replicas=2, max_batch=4,
                    jax_platform="cpu", start_timeout=300)
    router.start()
    yield router
    router.stop()


# -- the shared Engine core ----------------------------------------------

def test_engine_is_the_one_core(model):
    """Executor and Predictor both construct their compile/execute core
    through serving.engine.Engine: same feed plan, same key derivation
    (a predict key computed through either side's engine is identical)."""
    model_dir, feed, _want = model
    p = Predictor(model_dir)
    exe = fluid.Executor(fluid.CPUPlace())
    eng = exe._engine_for(p._program)
    # one feed-plan code path: identical plans from both engines
    assert eng.feed_plan(p.feed_names) == p._feed_plan
    assert p._engine.feed_plan() == p._feed_plan
    # one key-derivation code path: byte-identical keys
    feed_sig = (("x", (2, 4), "float32"),)
    assert (eng.key("predict", feed_sig, tuple(p.fetch_names))
            == p._key(feed_sig))
    # engines are per-program and cached per executor
    assert exe._engine_for(p._program) is eng
    # the executor run path goes through the same engine's feed_var memo
    got = eng.feed_var("x")
    assert got is not None and got.name == "x"


# -- 2-replica round trip (tier-1 CI smoke) -------------------------------

def test_two_replica_round_trip(fleet, model):
    _model_dir, feed, want = model
    assert [w["state"] for w in fleet.health()] == ["ready", "ready"]
    futs = [fleet.submit((feed[i % 5],)) for i in range(24)]
    for i, fut in enumerate(futs):
        row, = fut.result(timeout=120)
        np.testing.assert_allclose(row, want[i % 5], rtol=1e-4, atol=1e-5)
    # least-outstanding balancing actually spread the work
    dispatched = [w["dispatched"] for w in fleet.health()]
    assert sum(dispatched) >= 24 and min(dispatched) > 0, dispatched


def test_concurrent_clients_all_rows_correct(fleet, model):
    _model_dir, feed, want = model
    errs = []

    def client(cid):
        try:
            rs = np.random.RandomState(cid)
            for _ in range(20):
                i = rs.randint(0, 5)
                row = fleet.submit((feed[i],)).result(timeout=120)
                if not np.allclose(row[0], want[i], rtol=1e-4, atol=1e-5):
                    errs.append("client %d row %d diverged" % (cid, i))
        except Exception as e:  # pragma: no cover - failure reporting
            errs.append("client %d: %r" % (cid, e))

    threads = [threading.Thread(target=client, args=(c,)) for c in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)  # 20 results, a second each in practice
    assert not [t for t in threads if t.is_alive()], "a client never ended"
    assert not errs, errs


def test_fleet_metrics_merge_with_replica_labels(fleet, model):
    """Every worker's registry rides back over the control pipe labeled
    by replica; the merged snapshot keeps the series collision-free."""
    _model_dir, feed, _want = model

    def served():
        # the merge also carries THIS process's own registry, unlabeled:
        # whatever server an earlier test file ran in this worker
        merged = fleet.fleet_metrics()
        series = merged["metrics"].get(
            "paddle_tpu_predict_requests_total", {"series": []})["series"]
        return merged, {s["labels"]["replica"] for s in series
                        if s["labels"].get("path") == "server"
                        and "replica" in s["labels"]}

    # parallel traffic until least-outstanding has touched BOTH replicas
    # (a lone request legitimately lands on one, and so may a dozen where
    # one replica answers before the next request is routed)
    deadline = time.monotonic() + 120
    merged, by_replica = served()
    while len(by_replica) < 2 and time.monotonic() < deadline:
        for fut in [fleet.submit((feed[i % 5],)) for i in range(12)]:
            fut.result(timeout=120)
        merged, by_replica = served()
    assert sorted(merged["replicas"]) == ["replica0", "replica1"]
    assert by_replica == {"replica0", "replica1"}


def test_fleet_http_endpoints(fleet, model):
    import json
    import urllib.request

    _model_dir, feed, _want = model
    port = fleet.start_http(0)
    try:
        text = urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % port, timeout=30
        ).read().decode("utf-8")
        assert "paddle_tpu_fleet_dispatches_total" in text
        health = json.loads(urllib.request.urlopen(
            "http://127.0.0.1:%d/health.json" % port, timeout=30).read())
        assert [h["replica"] for h in health] == ["replica0", "replica1"]
        assert all(h["state"] == "ready" for h in health)
    finally:
        fleet.stop_http()


def test_backpressure_bounded_and_drains(model):
    """With a tiny per-replica window the dispatch loop must park (not
    drop, not crash) and everything still completes once capacity
    frees."""
    model_dir, feed, want = model
    router = Router(model_dir, replicas=1, max_batch=2,
                    max_outstanding=2, jax_platform="cpu",
                    start_timeout=300)
    router.start()
    try:
        futs = [router.submit((feed[i % 5],)) for i in range(30)]
        for i, fut in enumerate(futs):
            row, = fut.result(timeout=120)
            np.testing.assert_allclose(row, want[i % 5], rtol=1e-4,
                                       atol=1e-5)
    finally:
        router.stop()


# -- drain / restart under load (acceptance) ------------------------------

def test_drain_restart_zero_drops_under_load(model):
    """Recycle replica 0 while closed-loop clients hammer the fleet:
    every response must arrive, be correct, and carry the version its
    request was dispatched under (misversioned counter stays 0)."""
    model_dir, feed, want = model
    router = Router(model_dir, replicas=2, max_batch=4,
                    jax_platform="cpu", start_timeout=300)
    router.start()
    mis0 = obs.FLEET_MISVERSIONED.total()
    fail0 = obs.PREDICT_FAILURES.value(path="router")
    stop = threading.Event()
    errs, served = [], [0]

    def client(cid):
        try:
            rs = np.random.RandomState(cid)
            while not stop.is_set():
                i = rs.randint(0, 5)
                row = router.submit((feed[i],)).result(timeout=120)
                if not np.allclose(row[0], want[i], rtol=1e-4, atol=1e-5):
                    errs.append("client %d row %d diverged" % (cid, i))
                served[0] += 1
        except Exception as e:  # pragma: no cover - failure reporting
            errs.append("client %d: %r" % (cid, e))

    threads = [threading.Thread(target=client, args=(c,)) for c in range(3)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.5)  # load established
        router.drain_restart(0, timeout=300)
        time.sleep(0.5)  # keep serving through the recycled replica
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=120)
    router.stop()
    assert not errs, errs[:5]
    assert served[0] > 0
    assert obs.FLEET_MISVERSIONED.total() - mis0 == 0
    assert obs.PREDICT_FAILURES.value(path="router") - fail0 == 0
    states = [w["state"] for w in router.health()]
    assert states == ["stopped", "stopped"], states


def test_worker_crash_requeues_in_flight(model):
    """SIGKILL one replica with requests in flight: its outstanding
    frames are re-dispatched to the survivor (predict is idempotent) and
    every future still completes correctly."""
    model_dir, feed, want = model
    router = Router(model_dir, replicas=2, max_batch=4,
                    jax_platform="cpu", start_timeout=300)
    router.start()
    req0 = obs.FLEET_REQUEUED.total()
    try:
        futs = [router.submit((feed[i % 5],)) for i in range(40)]
        victim = router._workers[0]
        victim.proc.kill()  # hard SIGKILL, no drain
        for i, fut in enumerate(futs):
            row, = fut.result(timeout=120)
            np.testing.assert_allclose(row, want[i % 5], rtol=1e-4,
                                       atol=1e-5)
        # survivors keep serving new traffic too
        row, = router.submit((feed[0],)).result(timeout=120)
        np.testing.assert_allclose(row, want[0], rtol=1e-4, atol=1e-5)
        states = {w["state"] for w in router.health()}
        assert "dead" in states and "ready" in states
    finally:
        router.stop()
    # the kill either caught frames in flight (requeued > 0) or landed
    # between batches — both are legal; the invariant is zero losses,
    # asserted above. Record that the counter is at least consistent.
    assert obs.FLEET_REQUEUED.total() >= req0


def test_double_fault_replacement_killed_during_drain_restart(model):
    """Double fault (ISSUE 13 satellite): the REPLACEMENT worker is
    SIGKILLed during ``drain_restart`` — at the ``serving.worker_boot``
    fault barrier, before it ever reports ready. The Router must retry
    the spawn (phase 1: the retry boots clean and the restart succeeds)
    or, with every attempt exhausted, raise actionably while the
    survivor keeps serving (phase 2) — zero dropped, zero misversioned
    requests throughout either way."""
    model_dir, feed, want = model
    router = Router(model_dir, replicas=2, max_batch=4,
                    jax_platform="cpu", start_timeout=300,
                    spawn_retries=1)
    router.start()
    mis0 = obs.FLEET_MISVERSIONED.total()
    stop = threading.Event()
    errs, served = [], [0]

    def client(cid):
        try:
            rs = np.random.RandomState(cid)
            while not stop.is_set():
                i = rs.randint(0, 5)
                row = router.submit((feed[i],)).result(timeout=120)
                if not np.allclose(row[0], want[i], rtol=1e-4, atol=1e-5):
                    errs.append("client %d row %d diverged" % (cid, i))
                served[0] += 1
        except Exception as e:  # pragma: no cover - failure reporting
            errs.append("client %d: %r" % (cid, e))

    def unarm_after_first_replacement(orig_proc, unarmed):
        # the kill spec rides _opts["env"] (read at each _spawn), so
        # dropping it the moment attempt 1 exists makes attempt 2 boot
        # clean — attempt 1 itself already inherited the armed env and
        # dies inside its boot DELAY window, deterministically
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            w = router._workers[0]
            if w.proc is not None and w.proc is not orig_proc:
                router._opts["env"].pop("PADDLE_TPU_FAULT_KILL", None)
                router._opts["env"].pop("PADDLE_TPU_FAULT_DELAY", None)
                unarmed.set()
                return
            time.sleep(0.02)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(2)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.3)  # load established
        # -- phase 1: first replacement dies at boot, the retry serves --
        router._opts["env"]["PADDLE_TPU_FAULT_KILL"] = "serving.worker_boot"
        router._opts["env"]["PADDLE_TPU_FAULT_DELAY"] = \
            "serving.worker_boot:2.0"
        unarmed = threading.Event()
        orig = router._workers[0].proc
        watcher = threading.Thread(
            target=unarm_after_first_replacement, args=(orig, unarmed))
        watcher.start()
        router.drain_restart(0, timeout=300)
        watcher.join(timeout=120)
        assert unarmed.is_set(), "watcher never saw the first replacement"
        states = [w["state"] for w in router.health()]
        assert states == ["ready", "ready"], states
        # -- phase 2: kill EVERY attempt -> actionable raise, survivor
        # unharmed (no boot delay: dead attempts should fail fast) --
        router._opts["env"]["PADDLE_TPU_FAULT_KILL"] = "serving.worker_boot"
        with pytest.raises(RuntimeError) as ei:
            router.drain_restart(0, timeout=300)
        msg = str(ei.value)
        assert "could not be respawned" in msg
        assert "2 attempts" in msg
        assert "reap_dead" in msg  # the heal path, named for the operator
        # the reader thread marks the dead replacement on EOF — poll
        deadline = time.monotonic() + 30
        while (time.monotonic() < deadline
               and [w["state"] for w in router.health()]
               != ["dead", "ready"]):
            time.sleep(0.05)
        states = [w["state"] for w in router.health()]
        assert states == ["dead", "ready"], states
        time.sleep(0.3)  # survivor keeps serving through the outage
        # -- heal: reap the dead replacement, grow back to 2 ---------------
        router._opts["env"].pop("PADDLE_TPU_FAULT_KILL", None)
        assert router.reap_dead() == ["replica0"]
        router.add_replica(timeout=300)
        assert [w["state"] for w in router.health()] == ["ready", "ready"]
        time.sleep(0.3)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=120)
        router.stop()
    assert not errs, errs[:5]
    assert served[0] > 0
    assert obs.FLEET_MISVERSIONED.total() - mis0 == 0


# -- sharded (tp) serving -------------------------------------------------

@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >= 2 (virtual) devices")
def test_sharded_predictor_parity_tp2(model):
    """ShardedPredictor over a 2-way mp mesh produces the single-device
    predictor's logits exactly (same program, GSPMD-partitioned), with
    the infer_tp_plan column/row alternation on the fc weights."""
    model_dir, feed, want = model
    sp = ShardedPredictor(model_dir, shard=2)
    got, = sp.run({"x": feed})
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    specs = {n: sp._state[n].sharding.spec for n in sp._state_names}
    from jax.sharding import PartitionSpec as P

    assert specs["fc_0.w_0"] == P(None, "mp")  # column-parallel
    assert specs["fc_1.w_0"] == P("mp", None)  # row-parallel
    assert sp.warm(4) is True  # bucket pre-warm works for the server


def test_router_serves_sharded_model_tp2(model):
    """Acceptance: a tp=2 model serves THROUGH the router (worker gets 2
    virtual CPU devices) with logits parity vs the single-device
    predictor."""
    model_dir, feed, want = model
    router = Router(
        model_dir, replicas=1, shard=2, max_batch=4,
        jax_platform="cpu",
        worker_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
        start_timeout=300)
    router.start()
    try:
        assert router.health()[0]["shard"] == 2
        futs = [router.submit((feed[i % 5],)) for i in range(10)]
        for i, fut in enumerate(futs):
            row, = fut.result(timeout=120)
            np.testing.assert_allclose(row, want[i % 5], rtol=1e-5,
                                       atol=1e-6)
    finally:
        router.stop()
