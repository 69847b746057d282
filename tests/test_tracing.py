"""Distributed request tracing (ISSUE 16): the wire trace header, the
bounded flight recorder, and the fleet round trip — one sampled request
submitted at the router front door must come back from
``Router.fleet_trace()`` as a single trace_id whose spans were recorded
by THREE processes (client/router, worker, server stages) in
near-monotonic waterfall order, and a SIGKILL mid-flight must not break
the trace (the requeued request re-dispatches with its header intact).

Off-by-default is load-bearing: at sample rate 0 the wire bytes are
byte-identical to the pre-trace form and the recorder never grows."""
from __future__ import annotations

import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu import observability as obs
from paddle_tpu.inference import Predictor
from paddle_tpu.observability import tracing
from paddle_tpu.serving import Router, wire


@pytest.fixture(autouse=True)
def trace_isolation():
    """Every test starts with an empty ring, no rid bindings, and
    sampling OFF — and cannot leak a nonzero rate into the suite."""
    tracing.reset()
    tracing.set_sample_rate(0.0)
    yield
    tracing.set_sample_rate(0.0)
    tracing.reset()


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    """Saved 4->8->6 softmax MLP + feed rows (the fleet-test fixture)."""
    model_dir = str(tmp_path_factory.mktemp("trace_model"))
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


# -- wire header ----------------------------------------------------------

def test_pack_read_trace_roundtrip():
    frame = b"\x01payload-bytes"
    tid = tracing.new_trace_id()
    wrapped = wire.pack_trace(frame, tid)
    got_tid, rest = wire.read_trace(wrapped)
    assert got_tid == tid
    assert bytes(rest) == frame
    # canonical nesting Q(T(frame)): SLO outermost, read in parse order
    q = wire.pack_slo(wire.pack_trace(frame, tid), 1, None, "standard")
    prio, deadline, klass, inner = wire.read_slo(q)
    assert (prio, klass) == (1, "standard")
    tid2, bare = wire.read_trace(inner)
    assert tid2 == tid and bytes(bare) == frame


def test_bare_frame_passes_through_untouched():
    # a pre-trace frame is valid byte for byte: no header, no copy
    frame = b"\x07bare"
    tid, rest = wire.read_trace(frame)
    assert tid is None and rest is frame


def test_trace_header_malformed_raises():
    tid = "ab12cd34ef56ab78"
    wrapped = wire.pack_trace(b"frame", tid)
    with pytest.raises(wire.WireError):
        wire.read_trace(wrapped[:3])  # truncated id
    with pytest.raises(wire.WireError):
        wire.read_trace(b"T\x00")     # zero-length id
    with pytest.raises(ValueError):
        wire.pack_trace(b"frame", "")
    with pytest.raises(ValueError):
        wire.pack_trace(b"frame", "x" * 256)


def test_off_by_default_wire_is_byte_identical():
    # sampling off: maybe_start mints nothing, so submit() never wraps —
    # the wire form is EXACTLY the pre-trace bytes (the acceptance
    # criterion that makes tracing free when unused)
    assert tracing.sample_rate() == 0.0
    assert tracing.maybe_start() is None
    assert not tracing.sampled()
    n0 = len(tracing.snapshot()["spans"])
    assert n0 == 0  # isolation fixture emptied the ring; nothing recorded


# -- the recorder ---------------------------------------------------------

def test_recorder_ring_is_bounded():
    rec = tracing.TraceRecorder(capacity=8)
    for i in range(20):
        rec.record("t1", "span%d" % i, dur_ms=1.0)
    snap = rec.snapshot()
    assert len(snap["spans"]) == 8
    assert snap["recorded"] == 20 and snap["dropped"] == 12
    # the survivors are the NEWEST spans (ring semantics)
    assert [s["name"] for s in snap["spans"]] == \
        ["span%d" % i for i in range(12, 20)]


def test_record_span_defaults_ts_to_span_start():
    rec = tracing.TraceRecorder(capacity=4)
    t0 = time.time()
    rec.record("t1", "phase", dur_ms=1000.0)
    s = rec.snapshot()["spans"][0]
    # ts = now - dur: the span STARTED about a second ago
    assert t0 - 1.2 <= s["ts"] <= t0 - 0.8 + 0.2


def test_merge_snapshots_stamps_replicas_and_sorts():
    a = {"recorded": 2, "dropped": 0, "replica": "",
         "spans": [{"trace_id": "t2", "name": "late", "ts": 5.0,
                    "dur_ms": 0, "seq": 0},
                   {"trace_id": "t1", "name": "first", "ts": 1.0,
                    "dur_ms": 0, "seq": 1}]}
    b = {"recorded": 1, "dropped": 3, "replica": "w0",
         "spans": [{"trace_id": "t1", "name": "second", "ts": 2.0,
                    "dur_ms": 0, "seq": 0}]}
    merged = tracing.merge_snapshots([a, b])
    assert merged["replicas"] == ["router", "w0"]
    assert merged["recorded"] == 3 and merged["dropped"] == 3
    names = [s["name"] for s in merged["spans"]]
    assert names == ["first", "second", "late"]  # (trace_id, ts) order
    assert merged["spans"][0]["replica"] == "router"
    assert merged["spans"][1]["replica"] == "w0"


def test_rid_binding_table():
    assert not tracing.bound()
    assert tracing.rid_trace(7) is None  # falsy fast path, no lock
    tracing.bind_rid(7, "tid7")
    assert tracing.bound()
    tracing.rid_span(7, "stage", dur_ms=2.0, rows=3)
    tracing.rid_span(8, "stage")  # unbound rid: silently nothing
    assert tracing.pop_rid(7) == "tid7"
    assert not tracing.bound()
    spans = tracing.snapshot()["spans"]
    assert [s["name"] for s in spans] == ["stage"]
    assert spans[0]["trace_id"] == "tid7" and spans[0]["rows"] == 3


# -- fleet round trip (the ISSUE-16 acceptance test) ----------------------

def test_fleet_round_trip_one_trace_across_processes(model):
    """client -> router queue -> dispatch -> worker recv -> stacking ->
    device -> reply, all under ONE trace_id, spans from the router
    process AND a worker subprocess, in near-monotonic ts order."""
    model_dir, feed, want = model
    router = Router(model_dir, replicas=2, max_batch=4,
                    jax_platform="cpu", start_timeout=300)
    tracing.set_sample_rate(1.0)
    try:
        router.start()
        futs = [router.submit((feed[i % 5],)) for i in range(6)]
        for i, fut in enumerate(futs):
            row, = fut.result(timeout=120)
            np.testing.assert_allclose(row, want[i % 5], rtol=1e-4,
                                       atol=1e-5)
        merged = router.fleet_trace()
    finally:
        tracing.set_sample_rate(0.0)
        router.stop()

    by_tid = {}
    for s in merged["spans"]:
        by_tid.setdefault(s["trace_id"], []).append(s)
    # every submit minted its own trace at rate 1.0
    request_traces = {tid: spans for tid, spans in by_tid.items()
                      if any(s["name"] == "client.submit" for s in spans)}
    assert len(request_traces) == 6, sorted(by_tid)

    waterfall = ["client.submit", "router.queue", "router.dispatch",
                 "worker.recv", "server.stack", "server.device",
                 "worker.reply", "router.reply"]
    full = 0
    for tid, spans in request_traces.items():
        names = [s["name"] for s in spans]
        assert names.count("client.submit") == 1
        assert names.count("router.reply") == 1
        if set(waterfall) <= set(names):
            full += 1
            # the router-side spans and the worker-side spans came from
            # different PROCESSES, merged over the control pipe
            replicas = {s["replica"] for s in spans}
            assert "router" in replicas
            assert replicas - {"router"}, replicas  # >=1 worker process
            # near-monotonic: each successive waterfall stage STARTS no
            # earlier than the one before it (shared machine clock;
            # 50 ms tolerance for clock granularity between processes)
            starts = {}
            for s in spans:
                if s["name"] not in starts:
                    starts[s["name"]] = s["ts"]
            order = [starts[n] for n in ("client.submit", "router.queue",
                                         "router.dispatch", "worker.recv",
                                         "server.device", "router.reply")]
            for a, b in zip(order, order[1:]):
                assert b >= a - 0.05, (tid, order)
    # every request that was served end to end carries the full
    # waterfall (all 6 were — each got a result above)
    assert full == 6, "only %d/6 traces carried the full waterfall" % full

    # completed requests folded into the per-phase histogram (the
    # router-side phases live in THIS process's registry; stack/device
    # fold in the worker processes and arrive via fleet_metrics)
    for phase in ("queue", "service", "total"):
        assert obs.REQUEST_PHASE_MS.stats(phase=phase)["count"] >= 6, phase


def test_crash_requeue_keeps_trace_alive(model):
    """SIGKILL a replica with traced requests in flight: requeued
    frames still carry their T header (req.raw is resent verbatim), so
    the re-dispatch lands under the SAME trace_id and every trace that
    recorded a requeue still completes with a router.reply."""
    model_dir, feed, want = model
    router = Router(model_dir, replicas=2, max_batch=4,
                    jax_platform="cpu", start_timeout=300)
    tracing.set_sample_rate(1.0)
    try:
        router.start()
        futs = [router.submit((feed[i % 5],)) for i in range(40)]
        router._workers[0].proc.kill()  # hard SIGKILL, no drain
        for i, fut in enumerate(futs):
            row, = fut.result(timeout=120)
            np.testing.assert_allclose(row, want[i % 5], rtol=1e-4,
                                       atol=1e-5)
        merged = router.fleet_trace()
    finally:
        tracing.set_sample_rate(0.0)
        router.stop()

    by_tid = {}
    for s in merged["spans"]:
        by_tid.setdefault(s["trace_id"], []).append(s)
    requeued = {tid: spans for tid, spans in by_tid.items()
                if any(s["name"] == "router.requeue" for s in spans)}
    # the kill either caught frames in flight (requeued traces exist)
    # or landed between batches — both legal (the fleet-test stance);
    # the invariant is zero losses, asserted via fut.result above. For
    # every trace the crash DID touch, the story must be complete:
    for tid, spans in requeued.items():
        names = [s["name"] for s in spans]
        # re-dispatched after the requeue... (second dispatch span)
        assert names.count("router.dispatch") >= 2, names
        # ...and answered (by the survivor; the victim's ring died
        # with it, so its worker-side spans are legitimately absent)
        assert "router.reply" in names, names
    # and every traced request completed, requeued or not
    replies = sum(1 for spans in by_tid.values()
                  for s in spans if s["name"] == "router.reply")
    assert replies == 40


# -- process-scoped phases (ISSUE 24) --------------------------------------
#
# `tracing.phase` is the one span primitive for work that belongs to no
# request: the outermost phase of a thread is one record of the
# recorder's process ring, the phases opened inside it add their self
# time to it, and each is a `jax.profiler.TraceAnnotation("ptpu.<name>")`
# on the profiler's clock. `DecodeServer._loop` opens one per boundary.

def _iters():
    return [s for s in tracing.get_recorder().spans()
            if s["name"] == "decode.loop.iter"]


def _phase(record, name, parent="decode.loop.iter"):
    return next(p for p in record["phases"]
                if p["name"] == name and p["parent"] == parent)


def test_phase_off_is_one_shared_noop_and_records_nothing():
    a = tracing.phase("decode.loop.iter")
    b = tracing.phase("decode.loop.dispatch", active=8, attended=4000)
    assert a is b and a.t0 is None and a.t1 is None
    with a as opened:
        with tracing.phase("decode.loop.fetch") as inner:
            assert inner is a
    assert opened is a
    snap = tracing.snapshot()
    assert snap["spans"] == [] and snap["recorded"] == 0


def test_nested_phases_give_one_record_with_self_times_and_parents():
    tracing.set_sample_rate(1.0)
    with tracing.phase("decode.loop.iter") as it:
        assert it.t0 is not None
        with tracing.phase("decode.loop.admit", admitted=2):
            time.sleep(0.004)
            with tracing.phase("decode.loop.prefill"):
                time.sleep(0.003)
            with tracing.phase("decode.loop.scatter"):
                time.sleep(0.002)
        for _ in range(2):  # one name twice in an iteration: summed
            with tracing.phase("decode.loop.dispatch", active=3,
                               attended=40) as ph:
                time.sleep(0.001)
            assert ph.t1 > ph.t0
    rec, = tracing.get_recorder().spans()
    assert rec["name"] == "decode.loop.iter"
    assert rec["trace_id"] == tracing.process_trace_id()
    # the counts of the phases inside land on the record
    assert (rec["active"], rec["attended"], rec["admitted"]) == (3, 40, 2)
    by = {p["name"]: p for p in rec["phases"]}
    assert by["decode.loop.prefill"]["parent"] == "decode.loop.admit"
    assert by["decode.loop.scatter"]["parent"] == "decode.loop.admit"
    assert by["decode.loop.admit"]["parent"] == "decode.loop.iter"
    assert by["decode.loop.dispatch"]["n"] == 2
    admit = by["decode.loop.admit"]
    assert admit["ms"] >= 9.0 and 3.5 <= admit["self_ms"] < admit["ms"] - 4.5
    selfs = sum(p["self_ms"] for p in rec["phases"])
    assert selfs <= rec["dur_ms"] + 1e-3
    assert selfs + rec["self_ms"] == pytest.approx(rec["dur_ms"], abs=0.01)
    assert all(p["end_ms"] <= rec["dur_ms"] + 1e-3 for p in rec["phases"])


def test_an_iteration_is_traced_whole_or_not_at_all(monkeypatch):
    tracing.set_sample_rate(0.5)
    draws = iter([0.9, 0.1])  # the first root loses the draw
    monkeypatch.setattr(tracing._rand, "random", lambda: next(draws))
    for _ in range(2):
        with tracing.phase("decode.loop.iter"):
            # no draw of its own: a lost root silences what is inside
            with tracing.phase("decode.loop.fetch"):
                pass
    rec, = tracing.get_recorder().spans()
    assert [p["name"] for p in rec["phases"]] == ["decode.loop.fetch"]


def test_iteration_records_never_evict_a_request_span():
    rec = tracing.TraceRecorder(capacity=16, process_capacity=4096)
    rec.record("t1", "client.submit", rid=1)
    for i in range(10_000):
        rec.record_process("decode.loop.iter", dur_ms=1.0, active=8)
    for i in range(20):
        rec.record("t2", "stage%d" % i)
    snap = rec.snapshot()
    assert snap["rings"]["process"] == {
        "capacity": 4096, "recorded": 10_000, "dropped": 10_000 - 4096}
    assert snap["rings"]["request"] == {
        "capacity": 16, "recorded": 21, "dropped": 5}
    assert snap["recorded"] == 10_021
    assert snap["dropped"] == 10_000 - 4096 + 5
    # both rings in one seq-ordered list; the submit span outlived ten
    # thousand iteration records and fell only to its own ring's spans
    seqs = [s["seq"] for s in snap["spans"]]
    assert seqs == sorted(seqs) and len(seqs) == 4096 + 16
    assert [s["name"] for s in rec.spans("t2")][0] == "stage4"
    # the default process ring holds a traced window's iterations
    assert tracing.snapshot()["rings"]["process"]["capacity"] == 32768


def test_train_steps_and_profiler_events_share_the_entry_point():
    import sys

    from paddle_tpu import profiler

    sys.path.insert(0, "tools")
    import trace_dump

    tracing.set_sample_rate(1.0)
    obs.TIMELINE.record_step("run", 12.5, steps=3)
    profiler.start_profiler()
    try:
        profiler.record_event("compile", 0.25)
    finally:
        profiler._enabled = False
    with tracing.phase("decode.loop.iter"):
        with tracing.phase("decode.loop.fetch"):
            time.sleep(0.001)
    assert not hasattr(profiler, "timed")
    snap = tracing.snapshot()
    assert [s["name"] for s in snap["spans"]] == [
        "train.step", "profiler.compile", "decode.loop.iter"]
    assert snap["rings"]["process"]["recorded"] == 3
    assert snap["rings"]["request"]["recorded"] == 0
    assert {s["trace_id"] for s in snap["spans"]} == {
        tracing.process_trace_id()}
    # one waterfall for all three; the record's phases are child slices
    merged = tracing.merge_snapshots([snap])
    text = trace_dump.render_text(merged)
    assert "train.step" in text and "profiler.compile" in text
    assert "  decode.loop.fetch" in text
    slices = [e for e in trace_dump.to_chrome(merged)["traceEvents"]
              if e.get("ph") == "X"]
    assert [e["name"] for e in slices][-2:] == ["decode.loop.iter",
                                                "decode.loop.fetch"]
    it, fetch = slices[-2:]
    assert it["ts"] <= fetch["ts"]
    assert fetch["ts"] + fetch["dur"] <= it["ts"] + it["dur"] + 1.0


# -- the decode loop's phases ----------------------------------------------

DV, DL, DH, DD, DI_, DML = 37, 2, 2, 16, 32, 64


@pytest.fixture(scope="module")
def decode_dir(tmp_path_factory):
    """A tiny LM exported for decode serving (random weights do)."""
    from paddle_tpu.models import transformer as T
    from paddle_tpu.serving.decode import DecodeConfig, save_decode_model

    d = str(tmp_path_factory.mktemp("trace_decode_model"))
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


def _serve(decode_dir, prompts, max_new, **kw):
    """Submit everything, then start: admission is one wave, so the
    loop's course is the same every time."""
    from paddle_tpu.serving.decode import DecodePredictor, DecodeServer

    srv = DecodeServer(DecodePredictor(decode_dir), slots=4, max_seq=32,
                       max_new_tokens=16, **kw)
    futs = [srv.submit((p, np.array([n], np.int64)))
            for p, n in zip(prompts, max_new)]
    srv.start()
    outs = [f.result(timeout=120) for f in futs]
    srv.stop()
    return srv, outs


def test_decode_server_gives_one_record_per_loop_iteration(decode_dir):
    r = np.random.RandomState(3)
    plens, max_new = [5, 9, 3], [4, 7, 2]
    prompts = [r.randint(1, DV, n).astype(np.int64) for n in plens]
    _serve(decode_dir, prompts[:1], max_new[:1])  # compile, untraced
    tracing.reset()
    before = obs.DECODE_STEP_MS.stats(stage="step")
    tracing.set_sample_rate(1.0)
    srv, outs = _serve(decode_dir, prompts, max_new)
    tracing.set_sample_rate(0.0)
    assert [len(o[0]) for o in outs] == max_new
    steps = [s for s in _iters() if "active" in s]
    counts = list(srv.step_active_counts)
    # one record per iteration that stepped, in order, with its counts
    assert [s["active"] for s in steps] == counts == [3, 2, 2, 1, 1, 1]
    # `attended`: the K/V rows the step's attention reads = each live
    # slot's length with the row this step appends. The admission's
    # first token is the prompt's own last logits, so step k of a
    # sequence attends len(prompt) + k rows.
    want = []
    for k in range(1, 7):
        want.append(sum(n + k for n, m in zip(plens, max_new) if k < m))
    assert [s["attended"] for s in steps] == want
    # `streamed`: the rows a layer's attention brings in. The lax path
    # of a CPU run reads the whole (4 slots x 32 rows) slab every step.
    assert [s["streamed"] for s in steps] == [4 * 32] * len(steps)
    first = steps[0]
    assert first["admitted"] == 3
    # an iteration dispatches its step BEFORE it reads the one before
    # it: the first after the park has a step to dispatch and none to
    # read, every later one holds ONE dispatch with its counts and ONE
    # fetch (of the step before), and the last reads the step still in
    # flight with nothing to put behind it
    for name in ("decode.loop.recv", "decode.loop.admit",
                 "decode.loop.feeds", "decode.loop.dispatch"):
        assert _phase(first, name)["n"] == 1
    assert not any(p["name"] in ("decode.loop.fetch", "decode.loop.retire")
                   for p in first["phases"])
    assert [s["in_flight"] for s in steps] == [0, 1, 1, 1, 1, 1]
    for s in steps[1:]:
        for name in ("decode.loop.feeds", "decode.loop.dispatch",
                     "decode.loop.fetch", "decode.loop.retire"):
            assert _phase(s, name)["n"] == 1
        assert (_phase(s, "decode.loop.dispatch")["end_ms"]
                <= _phase(s, "decode.loop.fetch")["end_ms"])
    fetches = [s for s in _iters()
               if any(p["name"] == "decode.loop.fetch" for p in s["phases"])]
    assert fetches[:-1] == steps[1:] and "active" not in fetches[-1]
    for name in ("decode.loop.prefill", "decode.loop.first_token",
                 "decode.loop.scatter"):
        assert _phase(first, name, "decode.loop.admit")["ms"] > 0
    # started with its requests queued, the idle server's first
    # iteration still parks on the channel (and returns at once)
    assert any(p["name"] == "decode.loop.park" for s in _iters()
               for p in s["phases"])
    # the histogram's "step" stage: one observation a step, from the
    # token before it reaching the host (the first: from its own
    # dispatch) to its own, so that their sum is the time the loop had
    # a step outstanding: here from the first dispatch to the last
    # fetch. The gap between two tokens read from the records (the
    # ends of consecutive `fetch` phases, as the benchmark's
    # token_gap_ms_p95.serve reads it) is the period of the steps
    after = obs.DECODE_STEP_MS.stats(stage="step")
    assert after["count"] - before["count"] == len(steps)
    hist_ms = after["sum"] - before["sum"]
    ends = [s["ts"] * 1e3 + _phase(s, "decode.loop.fetch")["end_ms"]
            for s in fetches]
    d0 = _phase(first, "decode.loop.dispatch")
    began = first["ts"] * 1e3 + d0["end_ms"] - d0["ms"]
    gaps = np.diff([began] + ends)
    assert len(gaps) == len(steps) and (gaps > 0).all()
    assert gaps.sum() == pytest.approx(hist_ms, rel=0.05, abs=0.5)
    # request spans and iteration records side by side, nothing dropped
    snap = tracing.snapshot()
    assert snap["dropped"] == 0
    assert snap["rings"]["request"]["recorded"] == 3 * 3  # submit/admit/retire


@pytest.mark.parametrize("rate", [1.0, 0.0], ids=["traced", "rate0"])
def test_prefill_instruments_run_from_dispatch_to_logits_on_the_host(
        decode_dir, rate):
    """`paddle_tpu_decode_step_ms{stage="prefill"}` and the
    `decode.admit` span's `prefill_ms` are ONE number: from the
    prefill's dispatch to its logits on the host, the `prefill` and
    `first_token` phases' sum (`pexe(...)` alone only dispatches; the
    host waits in `first_token`). At rate 0, where no phase reads a
    clock, the admission still observes it once."""
    r = np.random.RandomState(5)
    prompts = [r.randint(1, DV, n).astype(np.int64) for n in (6, 4)]
    _serve(decode_dir, prompts[:1], [2])  # compile, untraced
    tracing.reset()
    tracing.set_sample_rate(rate)
    before = obs.DECODE_STEP_MS.stats(stage="prefill")
    _serve(decode_dir, prompts, [3, 3])
    tracing.set_sample_rate(0.0)
    after = obs.DECODE_STEP_MS.stats(stage="prefill")
    assert after["count"] - before["count"] == 1      # one admission wave
    hist_ms = after["sum"] - before["sum"]
    assert hist_ms > 0
    if not rate:
        return
    (first,) = [s for s in _iters() if s.get("admitted")]
    span = sum(_phase(first, name, "decode.loop.admit")["ms"]
               for name in ("decode.loop.prefill", "decode.loop.first_token"))
    assert hist_ms == pytest.approx(span, rel=0.02, abs=0.05)
    admits = [s for s in tracing.get_recorder().spans()
              if s["name"] == "decode.admit"]
    assert len(admits) == 2 and all(
        s["prefill_ms"] == pytest.approx(hist_ms, abs=0.002) for s in admits)


@pytest.mark.parametrize("rows,lens,n_active,want", [
    # whole slabs: the lax paths and the per-head kernel
    (None, [5, 0, 70, 0], 2, {"attended": 77, "streamed": 4 * 256}),
    # the in-place kernel: every slot's length with the row this step
    # appends, rounded up to the block; a free slot costs one block
    (64, [5, 0, 70, 0], 2, {"attended": 77, "streamed": 64 * (1 + 1 + 2 + 1)}),
    (64, [63, 64, 127, 255], 4, {"attended": 513,
                                 "streamed": 64 * (1 + 2 + 2 + 4)}),
    (128, [0, 0, 0, 0], 0, {"attended": 0, "streamed": 4 * 128}),
    # a slab of 8 key/value heads under more query heads (256 x 8 x 128
    # float32 on a TPU: one 256-row block): the rounded-up sum, not
    # slots x seq; ONE key/value head reads whole slabs
    ({"kv_heads": 8}, [5, 0, 255, 256], 3,
     {"attended": 519, "streamed": 256 * (1 + 1 + 1 + 2)}),
    ({"kv_heads": 1}, [5, 0, 255, 256], 3,
     {"attended": 519, "streamed": 4 * 256}),
], ids=["whole-slab", "blocks", "boundaries", "idle", "grouped",
        "grouped-one-head"])
def test_step_counts_streamed_rows(monkeypatch, rows, lens, n_active, want):
    import types

    from paddle_tpu.ops import kv_cache as KV
    from paddle_tpu.serving.decode import DecodeServer

    if isinstance(rows, dict):  # what the server asks of a grouped slab
        monkeypatch.setattr(KV, "current_device",
                            lambda: types.SimpleNamespace(platform="tpu"))
        rows = KV.decode_stream_rows(KV.decode_view(
            256, 48, rows["kv_heads"], 128, "float32"))
    srv = DecodeServer.__new__(DecodeServer)
    srv.slots, srv.seq, srv._stream_rows = 4, 256, rows
    srv._state_bytes_per_slot = 0  # K/V rows alone: no fixed-size state
    got = srv._step_counts(np.array(lens, np.int32), n_active)
    assert got == dict(want, active=n_active, state_bytes=0)


def test_phases_land_on_the_profilers_host_plane(decode_dir, tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    r = np.random.RandomState(4)
    prompts = [r.randint(1, DV, n).astype(np.int64) for n in (6, 4)]
    _serve(decode_dir, prompts[:1], [2])  # compile outside the trace
    tracing.reset()
    tracing.set_sample_rate(1.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(decode_dir, prompts, [3, 3])
    finally:
        jax.profiler.stop_trace()
        tracing.set_sample_rate(0.0)
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("ptpu.decode.loop."):
                    events.setdefault(e.name[len("ptpu.decode.loop."):],
                                      []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats), line.name))
    for name in ("iter", "recv", "admit", "prefill", "first_token",
                 "scatter", "feeds", "dispatch", "fetch", "retire"):
        assert events.get(name), name

    def inside(child, parents):
        return any(p[0] <= child[0] and child[1] <= p[1]
                   and p[3] == child[3] for p in parents)

    # nested as the loop nests them, on the loop's one thread
    for name in ("recv", "admit", "feeds", "dispatch", "fetch", "retire"):
        assert all(inside(e, events["iter"]) for e in events[name]), name
    for name in ("prefill", "first_token", "scatter"):
        assert all(inside(e, events["admit"]) for e in events[name]), name
    steps = [s for s in _iters() if "active" in s]
    stats = [e[2] for e in sorted(events["dispatch"])]
    assert [(s["active"], s["attended"]) for s in stats] == [
        (s["active"], s["attended"]) for s in steps]
    assert dict(events["admit"][0][2]) == {"admitted": 2, "deferred": 0}


# -- stable names on the device side ----------------------------------------

def _lowered(fn, *args):
    import jax

    return jax.jit(fn).lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("case", ["train-fused", "train-split",
                                  "train-bhtd", "shard_map", "decode"])
def test_lowered_text_names_the_kernels(case, monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.ops import attention as A
    from paddle_tpu.ops import kv_cache as KV

    if case != "train-fused":  # a budget nothing fits: the split pair
        monkeypatch.setattr(A, "_FUSED_BWD_VMEM_BUDGET", 1)
    bwd = ([A.FLASH_BWD] if case == "train-fused"
           else [A.FLASH_BWD_DQ, A.FLASH_BWD_DKV])
    if case == "decode":
        q = jnp.ones((2, 1, 2, 128), jnp.float32)
        kv = jnp.ones((2, 128, 2, 128), jnp.float32)
        text = _lowered(
            lambda q, k, v, n: KV.pallas_decode_attention(
                q, k, v, n, interpret=True),
            q, kv, kv, jnp.array([5, 9], jnp.int32))
        assert KV.DECODE_ATTN == "ptpu.decode_attn"
        assert '/ptpu.decode_attn/' in text
        return
    if case == "train-bhtd":
        kern = lambda q, k, v: A.pallas_flash_attention(  # noqa: E731
            q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
        x = jnp.ones((2, 2, 256, 64), jnp.bfloat16)
    else:
        kern = lambda q, k, v: A.pallas_flash_attention_bthd(  # noqa: E731
            q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
        x = jnp.ones((2, 256, 2, 128), jnp.bfloat16)
    if case == "shard_map":
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
        spec = P("dp", None, "mp", None)
        inner = kern
        kern = lambda q, k, v: jax.shard_map(  # noqa: E731
            inner, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False)(q, k, v)

    def step(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(kern(q, k, v).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    text = _lowered(step, x, x, x)
    assert (A.FLASH_FWD, A.FLASH_BWD, A.FLASH_BWD_DQ, A.FLASH_BWD_DKV) == (
        "ptpu.flash_fwd", "ptpu.flash_bwd", "ptpu.flash_bwd_dq",
        "ptpu.flash_bwd_dkv")
    if case == "shard_map":
        # the body is a function of its own, its names relative to the
        # `shard_map` call: the innermost scope is the kernel's alone,
        # and the compiler names a Mosaic call after that
        assert 'loc("ptpu.flash_fwd/pallas_call"' in text
        assert all('loc("%s/pallas_call"' % b in text for b in bwd)
    else:
        # ... which autodiff wraps: the accepted readers' `^jvp_` and
        # `^transpose_jvp` still hold on the chip
        assert "jvp(ptpu.flash_fwd)/" in text
        assert all("transpose(jvp(%s))/" % b in text for b in bwd)


@pytest.mark.parametrize("kind,kw,fed", [
    ("decode", {}, 2 * DL), ("decode", {"kv_dtype": "int8"}, 4 * DL),
    ("verify", {"window": 3}, 2 * DL), ("prefill", {}, 0)],
    ids=["decode", "decode-int8", "verify", "prefill"])
def test_acquire_counts_the_cache_entries_fed_and_aliased(decode_dir, kind,
                                                          kw, fed):
    """Whether a donated cache entry comes back in a donated buffer is
    decided when the step is compiled, so it is counted there: beside
    `paddle_tpu_compile_total`, by `kind`, the entries the program is
    fed and those of them its `input_output_alias` hands back in place,
    and both on the timeline's compile record, cold and warm. On the CPU
    nothing is donated, so `aliased` is 0 here; on a chip it equals
    `fed` (tests/test_tpu_compile_serving.py reads the v5e program's map through
    the same `_aliased_outputs`). A draft step never donates and is not
    counted."""
    from paddle_tpu.serving.decode import DecodePredictor, _aliased_outputs

    def counts():
        return (obs.CACHE_ENTRIES_FED.value(kind=kind),
                obs.CACHE_ENTRIES_ALIASED.value(kind=kind))

    pred = DecodePredictor(decode_dir, draft_n_layer=1)
    for path, cache in [("cold", "miss"), ("warm", "aot-load")]:
        fed0, aliased0 = counts()
        seen = len(obs.TIMELINE.events("compile"))
        exe, _ = pred.acquire(kind, 4, 64, **kw)   # a shape of this test
        assert counts() == (fed0 + fed, aliased0), path
        (ev,) = [e for e in obs.TIMELINE.events("compile")[seen:]
                 if e["kind"] == kind]
        assert (ev["cache"], ev["cache_fed"], ev["cache_aliased"]) == (
            cache, fed, 0), path
        assert ("xla_ms" in ev) == (path == "cold")
        assert _aliased_outputs(exe) == set()
        pred = DecodePredictor(decode_dir, draft_n_layer=1)   # loads it
    before = counts(), obs.CACHE_ENTRIES_FED.value(kind="draft")
    pred.acquire("draft", 4, 64)
    assert (counts(), obs.CACHE_ENTRIES_FED.value(kind="draft")) == before
    assert {"paddle_tpu_decode_cache_entries_fed_total",
            "paddle_tpu_decode_cache_entries_aliased_total"} <= {
                m.name for m in obs.REGISTRY.collect()}


def test_decode_executables_carry_distinct_module_names(decode_dir):
    import re

    from paddle_tpu.serving.decode import (DecodePredictor,
                                           _executable_name)

    pred = DecodePredictor(decode_dir)
    names = {}
    for key in [("prefill", 1, 16, {}), ("prefill", 2, 32, {}),
                ("decode", 4, 32, {}), ("decode", 2, 32, {}),
                ("verify", 4, 32, {"window": 3}),
                ("draft", 4, 32, {})]:
        kind, batch, seq, kw = key
        exe, _ = pred.acquire(kind, batch, seq, **kw)
        names[key[:3]] = re.match(r"HloModule (\S+?),",
                                  exe.as_text()).group(1)
    assert names == {
        ("prefill", 1, 16): "jit_ptpu_prefill_b1_s16",
        ("prefill", 2, 32): "jit_ptpu_prefill_b2_s32",
        ("decode", 4, 32): "jit_ptpu_decode_b4_s32",
        ("decode", 2, 32): "jit_ptpu_decode_b2_s32",
        ("verify", 4, 32): "jit_ptpu_verify_b4_s32_w3",
        ("draft", 4, 32): "jit_ptpu_draft_b4_s32_l%d" % pred.draft_n_layer}
    assert _executable_name("decode", 8, 2048, "topk", "int8") == \
        "ptpu_decode_b8_s2048_topk_kv8"
    assert _executable_name("prefill", 1, 4096, ring=True) == \
        "ptpu_prefill_b1_s4096_ring"
