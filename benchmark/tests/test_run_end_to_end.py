"""`run.py`'s path end to end at a tiny size on the CPU. The device
check is lifted here, by the test; run.py has no option for it."""
import json
import os
import time

import pytest

from benchmark.lib import harness, peaks, stats, trace_reduce

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")


def _tiny(name):
    with open(os.path.join(TINY, name)) as f:
        return json.load(f)


@pytest.fixture
def lifted(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "REQUIRE_PLATFORM", None)
    monkeypatch.setattr(harness, "OUT_ROOT", str(tmp_path / "out"))
    monkeypatch.setattr(harness, "WORK_ROOT", str(tmp_path / "work"))
    monkeypatch.setitem(peaks.PEAKS, "cpu", (1e12, 1e11, 2 ** 34, "test"))
    # a second and a half of tiny requests has no hundred completions
    monkeypatch.setattr(stats, "BEYOND", 0)
    real = trace_reduce.load_xplane
    monkeypatch.setattr(
        trace_reduce, "load_xplane", lambda d: real(
            d, lambda n: n == "/host:CPU",
            ("tf_XLAPjRtCpuClient", "tf_XLAEigen")))
    # jax's own tier stays off on the CPU (aot_cache.enable_compile_cache)
    monkeypatch.setattr(harness, "setup_env",
                        lambda root: str(tmp_path / "cache"))

    def use(cfg, mix):
        with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)

        def load_cell(root, name):
            cell = {w["name"]: w for w in bench["workloads"]}[name]
            return bench, dict(cell, chips=_tiny(cfg)["chips"]), _tiny(cfg), _tiny(mix)

        monkeypatch.setattr(harness, "load_cell", load_cell)
    return use


CASES = [
    ("opt-6.7b.train", "opt-tiny.json", "lm-tiny.json",
     {"train_tokens_per_s"}, {"step_ms.lm", "mfu_pct.lm",
                              "device_idle_pct.lm"}),
    # the same cell's files under a 2x2 mesh of 4 virtual devices
    ("opt-6.7b.train", "opt-tiny-tp2.json", "lm-tiny.json",
     {"train_tokens_per_s"}, {"step_ms.lm", "mfu_pct.lm",
                              "device_idle_pct.lm"}),
    ("opt-6.7b.serve-closed", "opt-tiny.json", "chat-tiny.json",
     {"serve_tokens_per_s"},
     {"admit_ms_p90.serve", "request_ms_p90.serve",
      "slot_occupancy_pct.serve", "decode_tokens_per_s.serve",
      "device_idle_pct.serve"}),
    ("resnet50.train", "resnet-tiny.json", "images-tiny.json",
     {"train_images_per_s"}, {"step_ms.resnet", "mfu_pct.resnet",
                              "device_idle_pct.resnet"}),
]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell,cfg,mix,e2e,layer", CASES,
                         ids=[c[0] + "@" + c[1][:-5] for c in CASES])
def test_cell_runs_tiny(lifted, capsys, cell, cfg, mix, e2e, layer, trace):
    lifted(cfg, mix)
    rc = harness.main(["--workload", cell, "--seed", str(2 ** 31 + 5),
                       "--seconds", "1.5", "--trace", str(trace)],
                      time.time())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-20:]
    res = json.loads(out[-1])
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    want = layer if trace else e2e | {"setup_s"}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    if trace:
        assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
        assert len(res["breakdown"]["device_ops"]) <= 10
    # each number compared is printed beside its limit
    assert sum(ln.startswith("check ") for ln in out) >= 3


def test_refuses_to_run_off_a_tpu(capsys):
    rc = harness.main(["--workload", "opt-6.7b.train", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], time.time())
    assert rc != 0
    assert not capsys.readouterr().out.strip().startswith("{\"correct\"")
