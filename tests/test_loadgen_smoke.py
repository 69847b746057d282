"""Tier-1 smoke for tools/loadgen.py: trace-builder units (pure python)
plus ONE subprocess run driving a scripted 2-second trace through a
1-replica fleet, pinning the ``loadgen/2`` verdict schema. The full
burst/chaos/autoscale traces live in tests/test_traffic_fleet.py (the
heavy variants marked ``slow``) — this file is the cheap in-window
budget pin the ISSUE demands."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOL = os.path.join(_REPO, "tools", "loadgen.py")

sys.path.insert(0, _REPO)

from tools.loadgen import build_shape, load_trace  # noqa: E402


# -- trace builders (no fleet, no jax) ------------------------------------

def test_build_shapes_phase_math():
    t = build_shape("steady", rps=50, duration_s=4.0)
    assert [p["rps"] for p in t["phases"]] == [50]
    assert sum(p["duration_s"] for p in t["phases"]) == pytest.approx(4.0)
    t = build_shape("burst", rps=50, duration_s=5.0, burst_x=4.0)
    assert len(t["phases"]) == 3
    assert t["phases"][1]["rps"] == 200  # the Poisson burst
    assert t["phases"][1]["fanout"]["dist"] == "pareto"  # heavy tail
    assert sum(p["duration_s"] for p in t["phases"]) == pytest.approx(5.0)
    t = build_shape("diurnal", rps=80, duration_s=8.0)
    rates = [p["rps"] for p in t["phases"]]
    assert len(rates) == 8
    assert max(rates) <= 80 and min(rates) >= 20  # trough = peak/4
    assert rates.index(max(rates)) in (3, 4)  # peak mid-trace
    with pytest.raises(ValueError, match="unknown shape"):
        build_shape("square", 1, 1)


def test_load_trace_validates(tmp_path):
    p = tmp_path / "t.json"
    p.write_text(json.dumps({"phases": []}))
    with pytest.raises(ValueError, match="non-empty"):
        load_trace(str(p))
    p.write_text(json.dumps({"phases": [{"rps": 5}]}))
    with pytest.raises(ValueError, match="duration_s"):
        load_trace(str(p))
    p.write_text(json.dumps(
        {"phases": [{"duration_s": 1, "rps": 5}]}))
    t = load_trace(str(p))
    assert t["name"] == "t.json"
    assert "interactive" in t["classes"]  # defaults applied


# -- the scripted-trace subprocess smoke (schema pin) ---------------------

@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.inference import Predictor

    d = str(tmp_path_factory.mktemp("loadgen_model"))
    mp, sp = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(mp, sp):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[4])
            out = layers.fc(x, 6, act="softmax")
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(sp)
        fluid.io.save_inference_model(d, ["x"], [out], exe,
                                      main_program=mp, scope=scope)
    # prime the shared AOT cache so the tool's worker warm-starts
    Predictor(d).run({"x": np.zeros((1, 4), np.float32)})
    return d


def test_scripted_trace_verdict_schema(model_dir, tmp_path):
    trace = {
        "name": "smoke-2s",
        "classes": {
            "interactive": {"priority": 0, "deadline_ms": 30000,
                            "weight": 0.8},
            "batch": {"priority": 2, "weight": 0.2},
        },
        "phases": [
            {"duration_s": 1.0, "rps": 20, "mode": "open"},
            {"duration_s": 1.0, "rps": 40, "mode": "open",
             "fanout": {"dist": "pareto", "alpha": 1.5, "max": 4}},
        ],
    }
    tf = tmp_path / "trace.json"
    tf.write_text(json.dumps(trace))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, _TOOL, "--model-dir", model_dir,
         "--trace", str(tf), "--replicas", "1", "--json", "--seed", "7"],
        capture_output=True, text=True, timeout=560, env=env, cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("{")][-1]
    r = json.loads(line)
    # -- the loadgen/2 schema pin -----------------------------------------
    assert r["schema"] == "loadgen/2"
    assert r["trace"] == "smoke-2s"
    for key in ("duration_s", "offered", "completed", "rejected",
                "errors", "dropped", "achieved_rps", "per_class",
                "phases", "fleet", "ok", "sheds_all_rejected",
                "trace_phases"):
        assert key in r, key
    # tracing was not armed, so the attribution is present but empty
    # (the loadgen/2 addition costs nothing unless --trace-sample is)
    assert r["trace_phases"] == {}
    # every request answered: result or explicit reject, nothing hung
    assert r["offered"] > 0
    assert r["completed"] == r["offered"]
    assert r["dropped"] == 0 and r["errors"] == 0
    assert r["ok"] is True and r["sheds_all_rejected"] is True
    assert len(r["phases"]) == 2
    assert sum(p["offered"] for p in r["phases"]) == r["offered"]
    for k in ("interactive", "batch"):
        pc = r["per_class"][k]
        for key in ("count", "ok", "rejected", "errors", "p50_ms",
                    "p90_ms", "p99_ms", "mean_ms", "deadline_ms",
                    "deadline_met_frac"):
            assert key in pc, (k, key)
    assert r["per_class"]["interactive"]["deadline_ms"] == 30000
    fl = r["fleet"]
    for key in ("replicas_start", "replicas_end", "shed_total",
                "requeued", "misversioned"):
        assert key in fl, key
    assert fl["misversioned"] == 0
    assert fl["replicas_start"] == fl["replicas_end"] == 1
