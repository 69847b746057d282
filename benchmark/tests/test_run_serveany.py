"""`lib/run_serveany.py` end to end at a tiny size on the CPU (the
state-space configuration, and the tiny OPT configuration through the
same runner, equal in `correct` to `run_serve.py`), and the three
readers this runner's cell brought, on synthetic spans and traces."""
import json
import os
import time

import pytest

from benchmark.lib import (harness, peaks, program_spans, serve_bytes, stats,
                           trace_reduce)

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")
CELL = "jamba2-3b.serve-closed"


def _tiny(name):
    with open(os.path.join(TINY, name)) as f:
        return json.load(f)


@pytest.fixture
def lifted(monkeypatch, tmp_path):
    """As `test_run_end_to_end.py` lifts the device check, for one cell
    name with the tiny files put in its place."""
    monkeypatch.setattr(harness, "REQUIRE_PLATFORM", None)
    monkeypatch.setattr(harness, "OUT_ROOT", str(tmp_path / "out"))
    monkeypatch.setattr(harness, "WORK_ROOT", str(tmp_path / "work"))
    monkeypatch.setitem(peaks.PEAKS, "cpu", (1e12, 1e11, 2 ** 34, "test"))
    monkeypatch.setattr(stats, "BEYOND", 0)
    real = trace_reduce.load_xplane
    monkeypatch.setattr(
        trace_reduce, "load_xplane", lambda d: real(
            d, lambda n: n == "/host:CPU",
            ("tf_XLAPjRtCpuClient", "tf_XLAEigen")))
    monkeypatch.setattr(harness, "setup_env",
                        lambda root: str(tmp_path / "cache"))

    def use(cell_name, cfg, mix):
        with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)

        def load_cell(root, name):
            cell = {w["name"]: w for w in bench["workloads"]}[cell_name]
            return bench, dict(cell, chips=1), _tiny(cfg), _tiny(mix)

        monkeypatch.setattr(harness, "load_cell", load_cell)
    return use


def _run(capsys, cell, trace):
    rc = harness.main(["--workload", cell, "--seed", str(2 ** 31 + 5),
                       "--seconds", "1.5", "--trace", str(trace)],
                      time.time())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-20:]
    return json.loads(out[-1]), out


@pytest.mark.parametrize("trace", [0, 1])
def test_state_space_cell_runs_tiny(lifted, capsys, trace):
    lifted(CELL, "jamba-tiny.json", "chat-tiny-any.json")
    res, out = _run(capsys, CELL, trace)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    if trace:
        # host-side readers read on the CPU too; the device-trace ones
        # find no `XLA Modules` line there and leave their metric out
        assert {"slot_occupancy_pct.serve", "decode_tokens_per_s.serve",
                "request_ms_p90.serve"} <= set(res["metrics"])
    else:
        assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    checks = [ln for ln in out if ln.startswith("check ")]
    assert len(checks) == 4 and all(ln.endswith("ok") for ln in checks)


@pytest.mark.parametrize("mix", ["chat-tiny.json", "chat-tiny-any.json"],
                         ids=["run_serve", "run_serveany"])
def test_opt_tiny_is_correct_under_both_runners(lifted, capsys, mix):
    """The tiny OPT configuration through the accepted runner and
    through this one: the same checks, the same verdict."""
    lifted("opt-6.7b.serve-closed", "opt-tiny.json", mix)
    res, out = _run(capsys, "opt-6.7b.serve-closed", 0)
    assert res["correct"] is True and res["failed"] == 0
    checks = [ln.split()[1] for ln in out if ln.startswith("check ")]
    assert checks == ["logits_rel_l2",
                      "server_vs_direct_rollout_mismatches",
                      "failed_requests_in_window",
                      "tail_percentile_shortfall"]


# -- the readers, on synthetic spans -----------------------------------------

CFG = {"hidden_size": 8, "intermediate_size": 16, "mamba_expand": 2,
       "mamba_d_state": 4, "mamba_dt_rank": 2, "mamba_d_conv": 4,
       "num_attention_heads": 2, "num_key_value_heads": 1,
       "attn_layer_period": 2, "attn_layer_offset": 1,
       "num_hidden_layers": 2, "vocab_size": 10}


def _reader(name):
    return harness.load_layer_metric(name)


def _spans(monkeypatch, host, modules, ops):
    monkeypatch.setattr(program_spans, "of_run", lambda run: {
        "host": host, "modules": {"/device:TPU:0": modules},
        "ops": {"/device:TPU:0": ops}})


def test_serve_bytes_counts_a_period():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "jamba2-3b.json")) as f:
        cfg = json.load(f)
    # the configuration file's own arithmetic: 1.599 B parameters
    assert serve_bytes.decode_weight_params(cfg, 14) == 1598556096
    assert serve_bytes.layer_kinds(cfg, 14).count("attention") == 1
    assert serve_bytes.kv_row_bytes(cfg, 14) == 2 * 128 * 4
    # mamba + attention layer of the toy, by hand
    d, f, di, n, r, k = 8, 16, 16, 4, 2, 4
    mlp = 3 * d * f + 2 * d
    mamba = (d * 2 * di + di * k + di + di * (r + 2 * n) + r + 2 * n
             + r * di + di + di * n + di + di * d)
    attn = 2 * d * 8 + 2 * d * 4
    assert serve_bytes.decode_weight_params(CFG, 2) == (
        mamba + attn + 2 * mlp + d + 10 * d)


def test_decode_step_roofline_on_synthetic_trace(monkeypatch):
    disp = program_spans.DISPATCH
    host = [(disp, 0.0, 10.0, {"active": 2, "attended": 100,
                               "state_bytes": 1000}, "t"),
            (disp, 2000.0, 10.0, {"active": 2, "attended": 200,
                                  "state_bytes": 1000}, "t")]
    modules = [("jit_ptpu_decode_b2_s16(1)", 100.0, 1000.0),
               ("jit_ptpu_prefill_b1_s16(2)", 1200.0, 500.0),
               ("jit_ptpu_decode_b2_s16(1)", 2100.0, 1000.0)]
    ops = [("fusion.1", 100.0, 400.0, "%fusion.1 = f32[] fusion()"),
           ("fusion.2", 600.0, 100.0, "%fusion.2 = f32[] fusion()"),
           ("fusion.9", 1200.0, 500.0, "%fusion.9 = f32[] fusion()"),
           ("fusion.1", 2100.0, 500.0, "%fusion.1 = f32[] fusion()")]
    _spans(monkeypatch, host, modules, ops)
    w = 4 * serve_bytes.decode_weight_params(CFG, 2)
    row = serve_bytes.kv_row_bytes(CFG, 2)
    run = {"cfg": CFG, "peaks": {"hbm_bytes_per_s": 1e9}}
    got = _reader("decode_step_roofline.serve").read(run)
    least = (2 * w + 2000 + 300 * row) / 1e9
    assert got == pytest.approx(100.0 * least / 1000e-9)
    # a program whose phases carry no `state_bytes` reads nothing
    host = [(h[0], h[1], h[2], {"active": 2, "attended": 1}, "t")
            for h in host]
    _spans(monkeypatch, host, modules, ops)
    assert _reader("decode_step_roofline.serve").read(run) is None
    # nor does a configuration of another family
    assert _reader("decode_step_roofline.serve").read(
        {"cfg": {"hidden_size": 8}, "peaks": run["peaks"]}) is None


def test_ssm_scan_time_pct_on_synthetic_trace(monkeypatch):
    modules = [("jit_ptpu_prefill_b1_s16(2)", 0.0, 1000.0),
               ("jit_ptpu_decode_b2_s16(1)", 1000.0, 1000.0)]
    ops = [("while.3", 100.0, 300.0, "%while.3 = (f32[]) while()"),
           ("fusion.7", 150.0, 50.0, "%fusion.7 = f32[] fusion()"),
           ("while.4", 500.0, 100.0, "%while.4 = (f32[]) while()"),
           ("custom-call.1", 700.0, 100.0, "%custom-call.1 = custom-call()"),
           ("while.9", 1100.0, 200.0, "%while.9 = (f32[]) while()"),
           ("fusion.8", 1400.0, 200.0, "%fusion.8 = f32[] fusion()")]
    _spans(monkeypatch, [], modules, ops)
    run = {"cfg": CFG}
    # busy: 300 + 100 + 100 + 200 + 200; the decode program's loop is out
    assert _reader("ssm_scan_time_pct.serve").read(run) == pytest.approx(
        100.0 * 400.0 / 900.0)
    _spans(monkeypatch, [], modules, [o for o in ops
                                     if not o[0].startswith("while")])
    assert _reader("ssm_scan_time_pct.serve").read(run) is None


def test_state_scatter_ms_on_synthetic_spans(monkeypatch):
    name = program_spans.LOOP + "scatter"
    host = [(name, 0.0, 2e6, {"entries": 28, "state_slots": 3}, "t"),
            (name, 5e6, 4e6, {"entries": 28, "state_slots": 1}, "t"),
            (program_spans.LOOP + "prefill", 1e6, 9e6, {}, "t")]
    _spans(monkeypatch, host, [], [])
    assert _reader("state_scatter_ms.serve").read({}) == pytest.approx(3.0)
    # the parent's phase carries no `entries`: nothing, and no raise
    _spans(monkeypatch, [(name, 0.0, 2e6, {}, "t")], [], [])
    assert _reader("state_scatter_ms.serve").read({}) is None
