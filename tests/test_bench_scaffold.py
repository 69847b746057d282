"""Unit tests for bench.py's measurement scaffolding: the slope-timing
math, its degenerate-timing fallback, the batch ladder, and the refusals
that keep a run honest (no TPU, an unknown device kind, a failing
phase)."""
import sys

import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
import bench  # noqa: E402


def _fake_clock(monkeypatch, times):
    it = iter(times)
    monkeypatch.setattr(bench.time, "perf_counter", lambda: next(it))


def _runner(log):
    def run_loop(k):
        log.append(k)
        return [np.asarray([1.5])]
    return run_loop


def test_timed_loop_slope(monkeypatch):
    log = []
    # timed windows: T(12) = 10s, T(24) = 16s -> slope (16-10)/12 = 0.5
    _fake_clock(monkeypatch, [100.0, 110.0, 200.0, 216.0])
    dt, loss = bench._timed_loop(_runner(log), warmup=3, steps=12)
    assert log == [3, 12, 24]  # warmup window, then k and 2k
    assert abs(dt - 0.5) < 1e-9
    assert loss == 1.5


def test_timed_loop_negative_slope_falls_back(monkeypatch):
    log = []
    # noise: T(12) = 10s but T(24) = 8s -> slope negative -> fall back
    # to the conservative average t2 / (2 * steps)
    _fake_clock(monkeypatch, [0.0, 10.0, 50.0, 58.0])
    dt, _ = bench._timed_loop(_runner(log), warmup=1, steps=12)
    assert abs(dt - 8.0 / 24.0) < 1e-9


def test_ladder_propagates_a_kernel_error(monkeypatch):
    """Anything but an OOM ends the run: no quiet measurement of another
    head config."""
    calls = []

    def fake_bench_lm(dev, batch, n_head=None):
        calls.append((batch, n_head))
        raise RuntimeError("Mosaic rejected the kernel")

    monkeypatch.setattr(bench, "bench_lm", fake_bench_lm)
    monkeypatch.delenv("BENCH_BATCH", raising=False)
    monkeypatch.delenv("BENCH_HEADS", raising=False)
    with pytest.raises(RuntimeError, match="Mosaic rejected"):
        bench.bench_lm_ladder(dev=None)
    assert calls == [(16, 8)]


def test_phase_order_lstm_strictly_last(monkeypatch):
    """stacked_lstm's compile is the longest by far: it must come after
    every cheaper phase, whose results are flushed before it starts."""
    for v in ("BENCH_RESNET", "BENCH_DEEPFM", "BENCH_LSTM"):
        monkeypatch.delenv(v, raising=False)
    names = [n for n, _ in bench._phase_list()]
    assert names == ["resnet50", "deepfm", "stacked_lstm"]
    monkeypatch.setenv("BENCH_LSTM", "0")
    assert [n for n, _ in bench._phase_list()] == ["resnet50", "deepfm"]


def test_peak_flops_raises_on_unknown_device_kind():
    class Dev:
        device_kind = "TPU v5 lite"

    assert bench._peak_flops(Dev()) == 197e12
    Dev.device_kind = "cpu"
    with pytest.raises(ValueError, match="no peak FLOP/s on record"):
        bench._peak_flops(Dev())


def test_main_refuses_without_a_tpu(monkeypatch, capsys):
    """On this CPU host, with no BENCH_PLATFORM asking for the host on
    purpose, bench.py exits non-zero, prints no headline and opens no
    child process."""
    import subprocess

    # main() setdefaults these: pin them so nothing leaks past the test
    monkeypatch.setenv("BENCH_AMP_LEVEL", "O2")
    monkeypatch.setenv("PADDLE_TPU_FLASH_FUSED_BWD", "1")
    monkeypatch.delenv("BENCH_PLATFORM", raising=False)
    monkeypatch.setattr(subprocess, "run", lambda *a, **k: pytest.fail(
        "bench.py started a process"))
    monkeypatch.setattr(subprocess, "Popen", lambda *a, **k: pytest.fail(
        "bench.py started a process"))
    monkeypatch.setattr(bench, "bench_lm_ladder", lambda dev: pytest.fail(
        "bench ran without a TPU"))
    assert bench.main() == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "refusing to run" in out.err


def test_main_failing_phase_ends_the_run(monkeypatch, capsys):
    """A secondary phase that raises propagates out of main() instead of
    becoming {"error": ...} beside exit code 0."""
    monkeypatch.setenv("BENCH_AMP_LEVEL", "O2")
    monkeypatch.setenv("PADDLE_TPU_FLASH_FUSED_BWD", "1")
    monkeypatch.setenv("BENCH_PLATFORM", "cpu")
    monkeypatch.setenv("BENCH_LM", "0")
    monkeypatch.setenv("BENCH_INPUT_PIPELINE", "0")
    monkeypatch.setenv("BENCH_NO_CACHE", "1")

    def boom(dev):
        raise RuntimeError("phase died")

    monkeypatch.setattr(bench, "_phase_list", lambda: [("resnet50", boom)])
    with pytest.raises(RuntimeError, match="phase died"):
        bench.main()
    assert '"error"' not in capsys.readouterr().out


def test_head_ladder_propagates_oom(monkeypatch):
    def fake_bench_lm(dev, batch, n_head=None):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    monkeypatch.setattr(bench, "bench_lm", fake_bench_lm)
    monkeypatch.delenv("BENCH_BATCH", raising=False)
    monkeypatch.delenv("BENCH_HEADS", raising=False)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        bench.bench_lm_ladder(dev=None)  # heads don't change memory


def test_head_ladder_respects_explicit_heads(monkeypatch):
    def fake_bench_lm(dev, batch, n_head=None):
        return {"value": 1.0, "mfu": 0.4, "step_ms": 1.0, "loss": 1.0,
                "batch": batch, "n_head": n_head}

    monkeypatch.setattr(bench, "bench_lm", fake_bench_lm)
    monkeypatch.setenv("BENCH_HEADS", "16")
    monkeypatch.setattr(bench, "N_HEAD", 16)
    monkeypatch.delenv("BENCH_BATCH", raising=False)
    out = bench.bench_lm_ladder(dev=None)
    assert out["n_head"] == 16
