"""The examples/ scripts are user-facing entry points: run each as a
subprocess with tiny parameters to keep them from rotting."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=420, env_extra=None, cwd=_ROOT, set_pythonpath=True):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    if set_pythonpath:
        env["PYTHONPATH"] = _ROOT + os.pathsep + env.get("PYTHONPATH", "")
    else:
        env.pop("PYTHONPATH", None)
    env["PADDLE_TPU_SYNTH_MNIST_TRAIN"] = "256"
    env["PADDLE_TPU_SYNTH_MNIST_TEST"] = "128"
    res = subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    return res.stdout


def test_train_mnist_example():
    out = _run(["examples/train_mnist.py", "--cpu", "--epochs", "1",
                "--batch-size", "32"])
    assert "test acc" in out


def test_translate_example():
    out = _run(["examples/translate.py", "--cpu", "--steps", "40"])
    assert "best-beam token match" in out


@pytest.mark.slow  # ~25s on the 2-core box; tier-1 no longer fits its 870 s window (PR-11 durations triage)
def test_train_lm_example_single_device():
    out = _run(["examples/train_lm.py", "--cpu", "--layers", "1", "--d-model", "64",
                "--seq", "128", "--vocab", "256", "--batch", "2",
                "--steps", "3", "--no-amp"])
    assert "tokens/s" in out


def test_train_lm_example_pipeline():
    flags = (os.environ.get("XLA_FLAGS", "")
             + " --xla_force_host_platform_device_count=8").strip()
    out = _run(["examples/train_lm.py", "--cpu", "--mesh", "dp=2,pp=4",
                "--pp-microbatches", "4", "--pp-schedule", "interleaved",
                "--layers", "4", "--d-model", "64", "--seq", "32",
                "--vocab", "256", "--batch", "2", "--steps", "2",
                "--no-amp"],
               env_extra={"XLA_FLAGS": flags})
    assert "tokens/s" in out


def test_train_ctr_example_learns():
    """The CTR example asserts held-out AUC > 0.6 itself — rc 0 IS the
    learning check. Run from a neutral cwd with no PYTHONPATH to also pin
    the examples' run-from-anywhere sys.path bootstrap."""
    out = _run([os.path.join(_ROOT, "examples", "train_ctr.py"), "--cpu",
                "--steps", "40", "--features", "5000",
                "--batch-size", "512"],
               cwd="/", set_pythonpath=False)
    assert "held-out auc" in out


def test_serve_example_round_trip():
    """serve.py asserts itself that the exported model fits its batch
    (acc > 0.9) and that every dynamically batched served row matches
    the direct predictor — rc 0 IS the check. Neutral cwd pins the
    run-from-anywhere bootstrap on the export/AOT-cache paths too."""
    out = _run([os.path.join(_ROOT, "examples", "serve.py"), "--cpu",
                "--steps", "150"], cwd="/", set_pythonpath=False)
    assert "every row" in out


@pytest.mark.slow  # ~35s on the 2-core box; tier-1 no longer fits its 870 s window (PR-11 durations triage)
def test_serve_example_decode_round_trip():
    """serve.py --decode asserts itself that every generation served
    through the continuous-batching DecodeServer matches the direct
    DecodePredictor — rc 0 IS the check (the CI serving step's decode
    smoke)."""
    out = _run([os.path.join(_ROOT, "examples", "serve.py"), "--cpu",
                "--decode", "--steps", "10"], cwd="/",
               set_pythonpath=False)
    assert "matches the direct DecodePredictor" in out


def test_train_lm_example_loop_mode():
    out = _run(["examples/train_lm.py", "--cpu", "--layers", "1", "--d-model", "64",
                "--seq", "128", "--vocab", "256", "--batch", "2",
                "--steps", "3", "--no-amp", "--loop"])
    assert "tokens/s" in out
