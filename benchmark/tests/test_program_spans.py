"""The program's phases laid over the device's idle (`lib/program_spans`)
on synthetic traces, each reader that rests on it on a synthetic run
(the None cases among them), a small trace recorded here, and the tiny
serving cell end to end with the new readers in place."""
import json
import time

import pytest

from benchmark.lib import harness, program_spans as ps
from test_run_end_to_end import lifted  # noqa: F401  (the fixture)

L = ps.LOOP
MS = 1e6  # the traces below are written in milliseconds


def _host(*spans, thread="loop"):
    """(name, start_ms, end_ms[, counts]) -> host events, sorted as
    `load_xplane` sorts them."""
    out = [(L + s[0], s[1] * MS, (s[2] - s[1]) * MS,
            s[3] if len(s) > 3 else {}, thread) for s in spans]
    return sorted(out, key=lambda h: (h[1], -h[2]))


def _ops(*busy):
    return [("fusion.%d" % i, s * MS, (e - s) * MS, "%fusion = f32[] fusion()")
            for i, (s, e) in enumerate(busy)]


# -- the overlap attribution -------------------------------------------------

def test_gap_is_split_over_the_two_phases_it_overlaps():
    # busy 0-10 and 20-30; the gap 10-20 lies 4 ms under fetch, 6 under
    # retire: by overlap, not by the midpoint (which is retire's)
    host = _host(("iter", 0, 30), ("fetch", 2, 14), ("retire", 14, 25))
    r = ps.idle_by_phase(_ops((0, 10), (20, 30)), host)
    assert r["window"] == 30 * MS and r["idle"] == 10 * MS
    assert r["by_phase"][L + "fetch"] == 4 * MS
    assert r["by_phase"][L + "retire"] == 6 * MS
    assert r["unattributed"] == 0
    assert r["by_group"] == {"step": 10 * MS}


def test_gap_under_no_phase_is_unattributed():
    host = _host(("iter", 0, 12), ("iter", 18, 30))
    r = ps.idle_by_phase(_ops((0, 10), (20, 30)), host)
    # 10-12 and 18-20 under the iterations' own time, 12-18 under none
    assert r["by_phase"] == {L + "iter": 4 * MS}
    assert r["unattributed"] == 6 * MS
    assert sum(r["by_group"].values()) + r["unattributed"] == r["idle"]


def test_gap_under_park_is_its_own_group():
    host = _host(("iter", 0, 30), ("park", 10, 19), ("recv", 19, 21))
    r = ps.idle_by_phase(_ops((0, 10), (20, 30)), host)
    assert r["by_group"] == {"park": 9 * MS, "step": 1 * MS}


def test_nested_phases_choose_the_innermost_and_keep_the_group():
    # admit 10-20 holds prefill 11-13 and first_token 13-18; a dispatch
    # under admit (a prefix extension) stays in admit's group
    host = _host(("iter", 0, 30), ("admit", 10, 20), ("prefill", 11, 13),
                 ("first_token", 13, 18), ("dispatch", 18, 19),
                 ("dispatch", 20, 21, {"active": 8, "attended": 4000}))
    segs = ps.innermost(host)
    assert [(n[len(L):], g, s / MS, e / MS) for n, g, s, e in segs] == [
        ("iter", "step", 0, 10), ("admit", "admit", 10, 11),
        ("prefill", "admit", 11, 13), ("first_token", "admit", 13, 18),
        ("dispatch", "admit", 18, 19), ("admit", "admit", 19, 20),
        ("dispatch", "step", 20, 21), ("iter", "step", 21, 30)]
    r = ps.idle_by_phase(_ops((0, 12), (22, 30)), host)
    assert r["by_phase"][L + "first_token"] == 5 * MS
    assert r["by_group"] == {"admit": 8 * MS, "step": 2 * MS}


def test_a_child_whose_admit_was_cut_stays_in_admissions_group():
    host = _host(("scatter", 10, 20), ("iter", 21, 30))
    r = ps.idle_by_phase(_ops((0, 12), (22, 30)), host)
    assert r["by_group"] == {"admit": 8 * MS, "step": 1 * MS}
    assert r["unattributed"] == 1 * MS


def test_only_the_busiest_thread_nests():
    host = _host(("iter", 0, 30), ("fetch", 5, 25)) + _host(
        ("iter", 3, 12), thread="a second server")
    assert {g for _, g, _, _ in ps.innermost(host)} == {"step"}
    assert ps.idle_by_phase(_ops((0, 10), (20, 30)), host)[
        "by_phase"] == {L + "fetch": 10 * MS, L + "iter": 0.0}


def test_iterations_cut_at_the_edges_come_back_from_the_recorder():
    # six iterations ran; the session saw the middle four whole. The
    # recorder's clock is the wall's: xplane ns = wall s * 1e9 - 7e18
    durs = [27.0, 26.4, 26.9, 75.2, 26.1, 26.6]
    starts = [sum(durs[:i]) + 0.5 * i for i in range(6)]
    recs = []
    for i, (t, d) in enumerate(zip(starts, durs)):
        recs.append({"name": "decode.loop.iter", "seq": 10 + i,
                     "ts": 7e9 + t / 1e3, "dur_ms": d, "phases": [
                         {"name": "decode.loop.fetch",
                          "parent": "decode.loop.iter", "ms": 20.0,
                          "self_ms": 20.0, "n": 1, "end_ms": 25.0},
                         {"name": "decode.loop.first_token",
                          "parent": "decode.loop.admit", "ms": 2.0,
                          "self_ms": 2.0, "n": 2, "end_ms": 4.0}]})
    recs.append({"name": "client.submit", "seq": 3, "ts": 7e9})
    host = _host(*[("iter", starts[i], starts[i] + durs[i] + 0.004)
                   for i in range(1, 5)])
    edges = ps.edge_phases(host, recs)
    got = [(n[len(L):], round(s / MS, 3), round(d / MS, 3))
           for n, s, d, _, _ in edges]
    # the phase that ran twice is a sum in the record: left out
    assert got == [("iter", 0.0, 27.0), ("fetch", 5.0, 20.0),
                   ("iter", starts[5], 26.6),
                   ("fetch", starts[5] + 5.0, 20.0)]
    ops = _ops((0, 8), (20, starts[5] + 2))
    assert ps.idle_by_phase(ops, host)["unattributed"] == 12 * MS
    assert ps.idle_by_phase(ops, host + edges)["by_phase"][L + "fetch"] \
        == 12 * MS
    # durations that match no stretch of records: nothing is invented
    for r in recs[:6]:
        r["dur_ms"] += 3.0
    assert ps.edge_phases(host, recs) == []
    assert ps.edge_phases(host[:2], recs) == []
    assert ps.edge_phases(host, None) == []


def test_no_operations_no_table():
    assert ps.idle_by_phase([], _host(("iter", 0, 1))) is None
    assert ps.first_device({}) == [] and ps.step_of([], 5.0) is None


# -- the readers on synthetic runs -------------------------------------------

def _reader(name):
    return harness.load_layer_metric(name).read


@pytest.fixture
def synthetic(monkeypatch, tmp_path):
    """A run dict whose xplane is `spans`: `load_xplane` is replaced,
    the path exists, and the table lands under tmp_path."""
    monkeypatch.setattr(harness, "OUT_ROOT", str(tmp_path / "out"))

    def make(spans, **run):
        path = tmp_path / ("trace%d.xplane.pb" % len(list(tmp_path.iterdir())))
        path.write_bytes(b"")
        monkeypatch.setattr(ps, "load_xplane", lambda p: spans)
        base = {"trace": {"path": str(path)}, "cell": {"name": "cell"},
                "cfg": {"num_attention_heads": 2, "hidden_size": 256},
                "peaks": {"hbm_bytes_per_s": 1e9}}
        base.update(run)
        return base
    return make


IDLE = ("idle_step_host_pct.serve", "idle_admit_pct.serve",
        "idle_unattributed_pct.serve")
XPLANE = IDLE + ("prefill_busy_pct.serve", "decode_attn_roofline.serve")


def test_idle_readers_sum_to_the_idle_share(synthetic, tmp_path, capsys):
    host = _host(("iter", 0, 50), ("admit", 10, 16, {"admitted": 1}),
                 ("scatter", 12, 16), ("feeds", 16, 17),
                 ("dispatch", 17, 18, {"active": 8, "attended": 4000}),
                 ("fetch", 18, 44), ("retire", 44, 46))
    ops = _ops((0, 10), (14, 15), (18, 40), (48, 50))
    run = synthetic({"host": host, "ops": {"/device:TPU:0": ops,
                                           "/device:TPU:1": []},
                     "modules": {}})
    step, admit, none = (_reader(n)(run) for n in IDLE)
    # gaps 10-14, 15-18, 40-48 of a 50 ms window: admit 4 + 1, step
    # feeds 1 + dispatch 1 + fetch 4 + retire 2 + iter 2, nothing left
    assert admit == pytest.approx(10.0) and step == pytest.approx(20.0)
    assert none == 0.0
    table = json.loads((tmp_path / "out" / "cell" /
                        "idle_by_phase.json").read_text())
    assert table["idle_pct"] == pytest.approx(step + admit + none)
    assert table["decode_steps"] == 1
    assert table["edge_iterations_recovered"] == 0
    assert table["phases"]["decode.loop.fetch"]["idle_ms_per_step"] == \
        pytest.approx(4.0)
    assert table["phase_mean_ms"]["decode.loop.scatter"] == pytest.approx(4.0)
    # printed once, though three readers asked for it
    assert capsys.readouterr().out.count("idle_by_phase ") == 1


def test_prefill_share_and_decode_roofline(synthetic):
    k = ('%ptpu.decode_attn.1 = f32[8,1,256]{2,1,0} custom-call(f32[8,1,256]'
         '{2,1,0} %q, f32[8,64,256]{2,1,0} %k, f32[8,64,256]{2,1,0} %v), '
         'custom_call_target="tpu_custom_call"')
    ops = [("fusion.1", 0.0, 4 * MS, "%fusion.1 = fusion()"),
           ("ptpu.decode_attn.1", 11 * MS, 1 * MS, k),
           ("ptpu.decode_attn.1", 13 * MS, 1 * MS, k),
           # a consumer names the kernel as its operand: not a call
           ("fusion.2", 14 * MS, 2 * MS,
            "%fusion.2 = fusion(f32[8,1,256] %ptpu.decode_attn.1)")]
    modules = [("jit_ptpu_prefill_b1_s64(1)", 0.0, 5 * MS),
               ("jit_ptpu_decode_b8_s64(2)", 10 * MS, 6 * MS)]
    host = _host(("dispatch", 9, 10, {"active": 2, "attended": 1000}),
                 ("dispatch", 30, 31, {"active": 2, "attended": 7}))
    run = synthetic({"host": host, "ops": {"/device:TPU:0": ops},
                     "modules": {"/device:TPU:0": modules}})
    assert _reader("prefill_busy_pct.serve")(run) == pytest.approx(50.0)
    # 2 calls x (2 x 1000 rows x 2 heads x 128 x 4 B) at 1e9 B/s = 4.096
    # ms at the peak, over 2 ms in the trace: the synthetic peak is low
    assert _reader("decode_attn_roofline.serve")(run) == pytest.approx(
        100.0 * 4.096 / 2.0)


@pytest.mark.parametrize("name", XPLANE)
def test_xplane_readers_find_nothing_in_the_parents_trace(synthetic, name):
    """No `ptpu.` phase, program or kernel name: None, never an error;
    so too without a trace at all."""
    ops = [("step_fn.7", 0.0, MS, "%step_fn.7 = f32[8,1,256] custom-call()")]
    run = synthetic({"host": [], "ops": {"/device:TPU:0": ops},
                     "modules": {"/device:TPU:0": [
                         ("jit_step_fn(1)", 0.0, MS)]}})
    assert _reader(name)(run) is None
    assert _reader(name)({"trace": None, "cell": {"name": "c"}}) is None
    assert _reader(name)({"trace": {"path": "/nowhere/x.pb"},
                          "cell": {"name": "c"}}) is None


def _iter(seq, ts, fetch_end_ms, active, park=False, admitted=0):
    phases = [{"name": "decode.loop.fetch", "parent": "decode.loop.iter",
               "self_ms": 1.0, "ms": 1.0, "n": 1, "end_ms": fetch_end_ms}]
    if park:
        phases.append({"name": "decode.loop.park",
                       "parent": "decode.loop.iter", "self_ms": 1.0,
                       "ms": 1.0, "n": 1, "end_ms": 0.5})
    return {"trace_id": "proc", "name": "decode.loop.iter", "ts": ts,
            "dur_ms": fetch_end_ms + 1, "seq": seq, "active": active,
            "admitted": admitted, "phases": phases}


def test_token_gap_weighs_by_live_sequences(monkeypatch):
    from benchmark.lib import stats

    monkeypatch.setattr(stats, "BEYOND", 0)
    read = _reader("token_gap_ms_p95.serve")
    spans = [_iter(0, 100.000, 5.0, 8), _iter(1, 100.010, 5.0, 8),
             # an admission inside the gap: 40 ms for the 8 live ones
             _iter(2, 100.020, 35.0, 8, admitted=1),
             # a parked server: the gap to the next step is nobody's
             _iter(3, 100.100, 5.0, 1, park=True),
             _iter(4, 100.120, 5.0, 1),
             # outside the window, and spans of requests, are not read
             _iter(5, 300.0, 5.0, 8),
             {"trace_id": "t", "name": "client.submit", "ts": 100.0,
              "dur_ms": 0.0, "seq": 6}]
    run = {"window_wall": (99.0, 200.0), "spans": spans}
    # gaps: 10 ms x 8, 40 ms x 8, 20 ms x 1
    assert read(run) == pytest.approx(40.0)
    monkeypatch.setattr(stats, "BEYOND", 10)
    assert read(run) is None  # 17 samples hold no 95th percentile
    assert read({"window_wall": (0.0, 1.0), "spans": []}) is None
    assert read({"window_wall": (0.0, 1.0), "spans": None}) is None


def test_flash_time_share_finds_the_kernels_by_name():
    read = _reader("flash_attn_time_pct.lm")
    fwd = ("%jvp_ptpu.flash_fwd_.3 = bf16[8,2048,4096] custom-call(), "
           'custom_call_target="tpu_custom_call"')
    bwd = ("%ptpu.flash_bwd_dq.1 = bf16[4,2048,2048] custom-call(), "
           'custom_call_target="tpu_custom_call"')  # under shard_map
    dev = [("jvp_ptpu.flash_fwd_.3", 0.0, 10.0, fwd),
           ("ptpu.flash_bwd_dq.1", 10.0, 30.0, bwd),
           # a consumer names the kernel as its operand: not the kernel
           ("fusion.1", 40.0, 60.0,
            "%fusion.1 = fusion(bf16[8] %jvp_ptpu.flash_fwd_.3)")]
    run = {"trace": {"devices": {"/device:TPU:0": dev,
                                 "/device:TPU:1": list(dev)}, "host": []},
           "trace_numbers": {"devices": 2, "busy_s": 100e-9}}
    assert read(run) == pytest.approx(40.0)
    # the parent's names: nothing to read
    old = [("jvp__.3", 0.0, 10.0, fwd.replace("jvp_ptpu.flash_fwd_", "jvp__"))]
    assert read({"trace": {"devices": {"d": old}, "host": []},
                 "trace_numbers": {"devices": 1, "busy_s": 1e-8}}) is None
    assert read({"trace": None, "trace_numbers": {}}) is None


# -- a recorded trace, and the tiny cell end to end --------------------------

def test_load_xplane_reads_the_programs_phases(tmp_path):
    """Phases opened under a profiler session here, on the CPU, come
    back nested, with their counts; a CPU has no device plane."""
    import glob

    import jax

    from paddle_tpu.observability import tracing

    tracing.set_sample_rate(1.0)
    try:
        jax.profiler.start_trace(str(tmp_path))
        with tracing.phase("decode.loop.iter"):
            with tracing.phase("decode.loop.dispatch", active=3,
                               attended=123):
                time.sleep(0.002)
        jax.profiler.stop_trace()
    finally:
        tracing.set_sample_rate(0.0)
        tracing.reset()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    spans = ps.load_xplane(path)
    assert spans["ops"] == {} and spans["modules"] == {}
    it, disp = spans["host"]
    assert it[0] == ps.ITER and disp[0] == ps.DISPATCH
    assert disp[3] == {"active": 3, "attended": 123}
    assert it[1] <= disp[1] and disp[1] + disp[2] <= it[1] + it[2]
    assert ps.step_of(spans["host"], disp[1] + 1) == disp[3]
    assert [s[0] for s in ps.innermost(spans["host"])] == [
        ps.ITER, ps.DISPATCH, ps.ITER]


def test_tiny_serving_cell_reports_the_token_gap(lifted, capsys):  # noqa: F811
    lifted("opt-tiny.json", "chat-tiny.json")
    rc = harness.main(["--workload", "opt-6.7b.serve-closed", "--seed",
                       str(2 ** 31 + 7), "--seconds", "1.5", "--trace", "1"],
                      time.time())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-20:]
    res = json.loads(out[-1])
    assert res["correct"] is True
    got = res["metrics"]
    # the loop's stamps need no device: a number; the readers of the
    # TPU's planes and of the Pallas kernel find nothing, cleanly
    assert got["token_gap_ms_p95.serve"]["value"] > 0
    assert not set(XPLANE) & set(got)
    assert {"admit_ms_p90.serve", "request_ms_p90.serve",
            "decode_tokens_per_s.serve"} <= set(got)
