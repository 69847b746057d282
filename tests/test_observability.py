"""Observability subsystem: metric registry semantics, compile-cache
accounting through Executor.run / run_loop, the step timeline, Prometheus
exposition, the PredictorServer /metrics endpoint, and the legacy profiler
shim (ISSUE 1)."""
from __future__ import annotations

import json
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observability as obs, optimizer, profiler
from paddle_tpu.observability import export


def _tiny_program():
    x = layers.data(name="x", shape=[4])
    y = layers.data(name="y", shape=[1])
    h = layers.fc(x, 8, act="relu")
    loss = layers.mean(layers.square(layers.fc(h, 1) - y))
    optimizer.SGD(learning_rate=0.01).minimize(loss)
    return loss


def _feed(rows=2):
    return {"x": np.ones((rows, 4), np.float32),
            "y": np.zeros((rows, 1), np.float32)}


# -- registry primitives -------------------------------------------------

def test_counter_gauge_histogram_summary_basics():
    reg = obs.MetricRegistry()
    c = reg.counter("c_total", "a counter")
    c.inc()
    c.inc(2.5, kind="a")
    assert c.value() == 1.0 and c.value(kind="a") == 2.5
    assert c.total() == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("g", "a gauge")
    g.set(7, depth="q")
    g.inc(-2, depth="q")
    assert g.value(depth="q") == 5.0

    h = reg.histogram("h_ms", "a histogram", buckets=(1, 10, 100))
    for v in (0.5, 5, 50, 500):
        h.observe(v)
    s = h.stats()
    assert s["count"] == 4 and s["sum"] == 555.5

    m = reg.summary("s_ms", "a summary")
    for v in (3.0, 1.0, 2.0):
        m.observe(v, event="e")
    st = m.stats(event="e")
    assert (st["count"], st["min"], st["max"]) == (3, 1.0, 3.0)


def test_registry_registration_is_idempotent_but_kind_checked():
    reg = obs.MetricRegistry()
    c1 = reg.counter("same_name")
    assert reg.counter("same_name") is c1
    with pytest.raises(TypeError):
        reg.gauge("same_name")


def test_label_series_are_independent_and_order_insensitive():
    reg = obs.MetricRegistry()
    c = reg.counter("lbl_total")
    c.inc(a="1", b="2")
    c.inc(b="2", a="1")  # same series, different kwarg order
    c.inc(a="1", b="3")
    assert c.value(a="1", b="2") == 2.0
    assert c.value(a="1", b="3") == 1.0


# -- compile-cache accounting through the executor -----------------------

def test_run_then_identical_run_is_one_miss_one_hit():
    loss = _tiny_program()
    prog = fluid.default_main_program()
    fp = obs.program_fp(prog)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    miss0 = obs.CACHE_MISSES.value(kind="run", tier="memory", program=fp)
    hit0 = obs.CACHE_HITS.value(kind="run", tier="memory", program=fp)
    exe.run(prog, feed=_feed(), fetch_list=[loss])
    exe.run(prog, feed=_feed(), fetch_list=[loss])
    assert obs.CACHE_MISSES.value(
        kind="run", tier="memory", program=fp) - miss0 == 1
    assert obs.CACHE_HITS.value(
        kind="run", tier="memory", program=fp) - hit0 == 1


def test_run_loop_windows_do_not_double_count():
    loss = _tiny_program()
    prog = fluid.default_main_program()
    fp = obs.program_fp(prog)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    steps0 = obs.STEPS_TOTAL.value(kind="loop")
    disp0 = obs.STEP_LATENCY_MS.stats(kind="loop")["count"]
    miss0 = obs.CACHE_MISSES.value(kind="loop", tier="memory", program=fp)
    hit0 = obs.CACHE_HITS.value(kind="loop", tier="memory", program=fp)
    exe.run_loop(prog, feed=_feed(), fetch_list=[loss], steps=3)
    exe.run_loop(prog, feed=_feed(), fetch_list=[loss], steps=3)
    # 2 windows = 2 dispatches but 6 steps; the loop compiles ONCE
    assert obs.STEPS_TOTAL.value(kind="loop") - steps0 == 6
    assert obs.STEP_LATENCY_MS.stats(kind="loop")["count"] - disp0 == 2
    assert obs.CACHE_MISSES.value(
        kind="loop", tier="memory", program=fp) - miss0 == 1
    assert obs.CACHE_HITS.value(
        kind="loop", tier="memory", program=fp) - hit0 == 1


def test_feed_fetch_bytes_accounted():
    loss = _tiny_program()
    prog = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    before = obs.FEED_BYTES.value(kind="run")
    exe.run(prog, feed=_feed(rows=2), fetch_list=[loss])
    # x: 2x4 f32 + y: 2x1 f32 = 40 bytes
    assert obs.FEED_BYTES.value(kind="run") - before == 40


def test_reader_prefetch_lifecycle_and_depth_gauge():
    """run_loop over a py_reader: window 1 proves the window size, window
    2 stages the next window (staged event + depth gauge 1 on this
    executor's series), window 3 consumes it (used event)."""
    main_p, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            reader = layers.py_reader(capacity=16, shapes=[(-1, 2)],
                                      dtypes=["float32"], name="obs_pf_r")
            (x,) = layers.read_file(reader)
            loss = layers.mean(layers.fc(x, 1))
            optimizer.SGD(learning_rate=0.1).minimize(loss)
    rs = np.random.RandomState(7)
    batches = [rs.rand(4, 2).astype(np.float32) for _ in range(12)]
    reader.decorate_tensor_provider(lambda: iter([(b,) for b in batches]))

    staged0 = obs.READER_PREFETCH_EVENTS.value(event="staged")
    used0 = obs.READER_PREFETCH_EVENTS.value(event="used")
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        reader.start()
        exe.run_loop(main_p, fetch_list=[loss], steps=3)
        # first window: size unproven, nothing staged yet
        assert obs.READER_PREFETCH_EVENTS.value(event="staged") == staged0
        exe.run_loop(main_p, fetch_list=[loss], steps=3)
        assert obs.READER_PREFETCH_EVENTS.value(event="staged") - staged0 == 1
        assert obs.READER_PREFETCH_DEPTH.value(exe=exe._obs_exe) == 1
        exe.run_loop(main_p, fetch_list=[loss], steps=3)
        assert obs.READER_PREFETCH_EVENTS.value(event="used") - used0 == 1


def test_reset_clears_registry_and_timeline():
    loss = _tiny_program()
    prog = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(prog, feed=_feed(), fetch_list=[loss])
    assert obs.STEPS_TOTAL.total() > 0
    assert obs.TIMELINE.snapshot()["recorded"] > 0

    profiler.reset_profiler()  # legacy reset delegates to the registry
    assert obs.STEPS_TOTAL.total() == 0
    assert obs.CACHE_MISSES.total() == 0
    snap = obs.TIMELINE.snapshot()
    assert snap["recorded"] == 0 and snap["events"] == []
    # registered metrics survive a reset (series restart from zero)
    exe.run(prog, feed=_feed(), fetch_list=[loss])
    assert obs.STEPS_TOTAL.value(kind="run") == 1


# -- step timeline -------------------------------------------------------

def test_timeline_ring_buffer_bounds_and_drop_accounting():
    tl = obs.StepTimeline(capacity=4)
    for i in range(10):
        tl.record_step("run", wall_ms=float(i))
    snap = tl.snapshot()
    assert snap["capacity"] == 4 and snap["recorded"] == 10
    assert snap["dropped"] == 6 and len(snap["events"]) == 4
    # oldest-first and JSON-able
    assert [e["wall_ms"] for e in snap["events"]] == [6.0, 7.0, 8.0, 9.0]
    json.dumps(snap)


def test_timeline_records_steps_and_compiles_from_executor():
    loss = _tiny_program()
    prog = fluid.default_main_program()
    fp = obs.program_fp(prog)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    seq0 = obs.TIMELINE.snapshot()["recorded"]
    exe.run(prog, feed=_feed(), fetch_list=[loss])
    events = [e for e in obs.TIMELINE.events()
              if e.get("program") == fp and e["seq"] >= seq0]
    kinds = {e["type"] for e in events}
    assert kinds == {"step", "compile"}
    step = next(e for e in events if e["type"] == "step")
    assert step["kind"] == "run" and step["wall_ms"] > 0
    assert step["feed_bytes"] == 40


# -- exposition ----------------------------------------------------------

def test_prometheus_text_format_escapes_and_types():
    reg = obs.MetricRegistry()
    c = reg.counter("esc_total", 'help with "quotes" and \\slash')
    c.inc(label='va"l\nue')
    text = export.to_prometheus(reg)
    assert '# HELP esc_total help with \\"quotes\\" and \\\\slash' in text
    assert 'esc_total{label="va\\"l\\nue"} 1' in text


def test_prometheus_empty_metrics_still_emit_catalogue():
    reg = obs.MetricRegistry()
    reg.counter("never_touched_total", "no samples yet")
    text = export.to_prometheus(reg)
    assert "# TYPE never_touched_total counter" in text
    assert "never_touched_total 0" in text


def test_delta_state_drops_negative_deltas_after_reset():
    reg = obs.MetricRegistry()
    c = reg.counter("neg_total")
    c.inc(5)
    before = export.counters_state(reg)
    reg.reset()  # a mid-phase reset must not surface as -5
    c.inc(2)
    delta = export.delta_state(before, reg)
    assert delta == {}  # 2 - 5 < 0: suppressed, not emitted


def test_executor_close_retires_depth_gauge_series():
    reg_gauge = obs.READER_PREFETCH_DEPTH
    exe = fluid.Executor(fluid.CPUPlace())
    reg_gauge.set(1, exe=exe._obs_exe)
    assert any(l.get("exe") == exe._obs_exe for l, _ in reg_gauge.samples())
    exe.close()
    assert not any(l.get("exe") == exe._obs_exe
                   for l, _ in reg_gauge.samples())


def test_delta_state_isolates_a_phase():
    before = export.counters_state()
    loss = _tiny_program()
    prog = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(prog, feed=_feed(), fetch_list=[loss])
    delta = export.delta_state(before)
    assert any(k.startswith("paddle_tpu_steps_total") for k in delta)
    assert all(v > 0 for v in delta.values())


# -- serving: /metrics endpoint ------------------------------------------

def _export_model(tmp_path):
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


def test_predictor_server_metrics_endpoint(tmp_path):
    from paddle_tpu.inference import Predictor, PredictorServer

    _export_model(tmp_path)
    p = Predictor(str(tmp_path), aot_cache=False)
    server = PredictorServer(p, max_batch=4)
    server.start()
    port = server.start_http(0)
    try:
        fut = server.submit((np.ones(4, np.float32),))
        fut.result(timeout=60)
        base = "http://127.0.0.1:%d" % port
        body = urllib.request.urlopen(base + "/metrics", timeout=30).read()
        text = body.decode("utf-8")
        # the endpoint serves the GLOBAL registry: serving series AND
        # executor series appear on one scrape
        assert "paddle_tpu_predict_latency_ms_bucket" in text
        assert 'paddle_tpu_predict_requests_total{path="server"}' in text
        assert "paddle_tpu_compile_total" in text
        snap = json.loads(urllib.request.urlopen(
            base + "/metrics.json", timeout=30).read().decode("utf-8"))
        assert "metrics" in snap and "timeline" in snap
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope", timeout=30)
    finally:
        server.stop()
    assert server._http is None  # stop() tears the endpoint down too


def test_predictor_direct_path_latency_recorded(tmp_path):
    from paddle_tpu.inference import Predictor

    _export_model(tmp_path)
    before = obs.PREDICT_REQUESTS.value(path="direct")
    p = Predictor(str(tmp_path), aot_cache=False)
    p.run({"x": np.ones((2, 4), np.float32)})
    assert obs.PREDICT_REQUESTS.value(path="direct") - before == 1
    assert obs.PREDICT_BATCH_ROWS.stats(path="direct")["count"] >= 1


# -- legacy profiler shim ------------------------------------------------

def test_profiler_tracks_min_max_and_sorts_by_them(capsys):
    profiler.reset_profiler()
    profiler.start_profiler("All")
    for ms in (5.0, 1.0, 9.0):
        profiler.record_event("ev_a", ms / 1e3)
    profiler.record_event("ev_b", 20.0 / 1e3)
    report = profiler.stop_profiler(sorted_key="max", profile_path="")
    capsys.readouterr()
    lines = [l for l in report.splitlines() if l.startswith("ev_")]
    # ev_b(max 20ms) sorts above ev_a(max 9ms)
    assert lines[0].startswith("ev_b") and lines[1].startswith("ev_a")
    assert "Min(ms)" in report and "Max(ms)" in report
    a_row = lines[1].split()
    #           name calls total   min    max    avg
    assert a_row[1] == "3"
    assert float(a_row[3]) == pytest.approx(1.0, abs=1e-3)  # min
    assert float(a_row[4]) == pytest.approx(9.0, abs=1e-3)  # max

    profiler.reset_profiler()  # stop does NOT clear the table; reset does
    profiler.start_profiler("All")
    profiler.record_event("ev_a", 0.004)
    profiler.record_event("ev_c", 0.002)
    report = profiler.stop_profiler(sorted_key="min", profile_path="")
    capsys.readouterr()
    lines = [l for l in report.splitlines() if l.startswith("ev_")]
    assert lines[0].startswith("ev_a")  # larger min first (descending)


def test_profiler_events_live_in_registry_summary():
    profiler.reset_profiler()
    profiler.start_profiler("All")
    profiler.record_event("reg_ev", 0.010)
    profiler.stop_profiler(profile_path="")
    st = obs.PROFILER_EVENT_MS.stats(event="reg_ev")
    assert st["count"] == 1 and st["sum"] == pytest.approx(10.0)
    # off-window events are NOT recorded (window gates the legacy table)
    profiler.record_event("reg_ev", 0.010)
    assert obs.PROFILER_EVENT_MS.stats(event="reg_ev")["count"] == 1


# -- parallel executor satellite -----------------------------------------

def test_parallel_executor_module_run_stats_shape():
    import paddle_tpu.parallel_executor as pe

    stats = pe.run_stats()
    assert set(stats) == {"steps", "dispatches", "mean_step_ms"}
    assert stats["steps"] >= 0


# -- decode serving: what an admission took and what it passed over ------

@pytest.fixture(scope="module")
def decode_dir(tmp_path_factory):
    """A one-layer LM exported for decode serving (random weights do)."""
    from paddle_tpu.models import transformer as T
    from paddle_tpu.serving.decode import DecodeConfig, save_decode_model

    d = str(tmp_path_factory.mktemp("obs_decode_model"))
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup), fluid.unique_name.guard():
        ids = layers.data(name="ids", shape=[2, 16], dtype="int64",
                          append_batch_size=False)
        T.transformer_lm(ids, ids, 37, n_layer=1, n_head=2, d_model=16,
                         d_inner=32, dropout_rate=0.0, max_len=64,
                         fused_head=False)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        save_decode_model(d, DecodeConfig(
            vocab_size=37, n_layer=1, n_head=2, d_model=16, d_inner=32,
            max_len=64), exe, scope=scope)
    return d


@pytest.mark.parametrize("floor,want", [
    # (admitted, deferred) of each admission of the queue 5, 40, 9, 30
    # the 40 and the 30 wait for the 5 and the 9's step, then go in turn
    (16, [(2, 2), (1, 1), (1, 0)]),
    (512, [(4, 0)])],         # under the floor every bucket shares
    ids=["a-bucket-an-admission", "under-the-floor"])
def test_admit_phase_counts_what_it_took_and_what_it_passed_over(
        decode_dir, floor, want):
    """`decode.loop.admit` carries the count REALLY admitted and the
    count left waiting beside a free slot; the registry counts the
    latter, and the server the rows its prefills ran on beside the
    prompts' own."""
    from paddle_tpu.observability import tracing
    from paddle_tpu.serving.decode import DecodePredictor, DecodeServer

    srv = DecodeServer(DecodePredictor(decode_dir), slots=8, max_seq=64,
                       max_new_tokens=2)
    srv._ADMIT_FLOOR = floor
    lens = [5, 40, 9, 30]
    futs = [srv.submit((np.arange(1, n + 1, dtype=np.int64),)) for n in lens]
    before = (obs.DECODE_ADMIT_DEFERRED.value(),
              obs.DECODE_REQUESTS.value(kind="admitted"))
    tracing.reset()
    tracing.set_sample_rate(1.0)
    try:
        srv.start()
        for f in futs:
            assert len(f.result(timeout=300)[0]) == 2
        srv.stop()
    finally:
        tracing.set_sample_rate(0.0)
    # a phase's counts ride on its iteration's record
    admits = [s for s in tracing.get_recorder().spans()
              if s["name"] == "decode.loop.iter" and "admitted" in s]
    assert [(s["admitted"], s["deferred"]) for s in admits] == want
    assert obs.DECODE_ADMIT_DEFERRED.value() - before[0] == sum(
        d for _a, d in want)
    assert obs.DECODE_REQUESTS.value(kind="admitted") - before[1] == len(lens)
    assert "paddle_tpu_decode_admit_deferred_total" in export.to_prometheus()
    assert srv.prefill_executions == len(want)
    assert srv.prefill_prompt_rows == sum(lens)
    # 2 x 16, 1 x 64 and 1 x 32 rows, or 4 x 64 at once, for 84 live ones
    assert srv.prefill_bucket_rows == (128 if floor == 16 else 256)
