"""Embeddable (non-Python) inference: the ptrt C ABI.

A pure-C driver (runtime/capi_test.c, compiled here with gcc and linking
only libdl) dlopen's the C ABI .so, loads a save_inference_model
directory, runs a batch, and its logits must match the in-process Python
predictor bit-for-bit-ish (rtol 1e-4)."""
from __future__ import annotations

import os
import subprocess
import sys
import sysconfig

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.runtime.build import capi_build_error, capi_lib_path

_HERE = os.path.dirname(os.path.abspath(__file__))
_RUNTIME = os.path.join(os.path.dirname(_HERE), "paddle_tpu", "runtime")


def _save_model(model_dir):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 17
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[16], dtype="float32")
        h = fluid.layers.fc(img, 24, act="relu")
        logits = fluid.layers.fc(h, 10)
        prob = fluid.layers.softmax(logits)
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ["img"], [prob], exe,
                                      main_program=main)
    return model_dir


@pytest.fixture(scope="module")
def capi_so():
    so = capi_lib_path()
    if so is None:
        pytest.skip("C ABI unavailable: %s" % capi_build_error())
    return so


@pytest.fixture(scope="module")
def c_driver(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("capi") / "capi_test")
    src = os.path.join(_RUNTIME, "capi_test.c")
    res = subprocess.run(["gcc", "-O2", "-I", _RUNTIME, src, "-o", out,
                          "-ldl"],
                         capture_output=True, text=True)
    if res.returncode != 0:
        pytest.skip("gcc unavailable for the C driver: %s" % res.stderr)
    return out


def test_c_embedding_matches_python_predictor(tmp_path, capi_so, c_driver):
    model_dir = _save_model(str(tmp_path / "model"))
    batch = np.random.RandomState(3).randn(4, 16).astype(np.float32)

    # in-process Python predictor gives the expected logits
    from paddle_tpu.inference import Predictor

    expected, = Predictor(model_dir).run({"img": batch})

    feed_file = str(tmp_path / "feed.bin")
    exp_file = str(tmp_path / "expected.bin")
    batch.tofile(feed_file)
    np.asarray(expected, np.float32).tofile(exp_file)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the embedded interpreter needs the repo + this interpreter's
    # site-packages on PYTHONPATH (a venv's packages are not on the
    # embedded default path)
    site = sysconfig.get_paths()["purelib"]
    repo = os.path.dirname(_HERE)
    env["PYTHONPATH"] = os.pathsep.join(
        [repo, site] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))

    res = subprocess.run(
        [c_driver, capi_so, model_dir, "img", "float32",
         ",".join(str(d) for d in batch.shape), feed_file, exp_file,
         "1e-4", "10"],
        env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, (
        "C embedding test failed (rc %d):\nstdout: %s\nstderr: %s"
        % (res.returncode, res.stdout, res.stderr))
    assert "OK" in res.stdout
    # the timing mode prints one parseable BENCH line; the Python Predictor above already populated the AOT cache, so
    # the C load preloads it and the first run pays no deserialization
    bench = [l for l in res.stdout.splitlines() if l.startswith("BENCH ")]
    assert len(bench) == 1, res.stdout
    stats = dict(kv.split("=") for kv in bench[0].split()[1:])
    assert float(stats["run_ms_min"]) > 0
    assert float(stats["load_ms"]) > 0


def test_c_embedding_reports_load_errors(tmp_path, capi_so, c_driver):
    feed_file = str(tmp_path / "feed.bin")
    np.zeros((1, 16), np.float32).tofile(feed_file)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    site = sysconfig.get_paths()["purelib"]
    env["PYTHONPATH"] = os.pathsep.join([os.path.dirname(_HERE), site])
    res = subprocess.run(
        [c_driver, capi_so, str(tmp_path / "no_such_model"), "img",
         "float32", "1,16", feed_file, feed_file, "1e-4"],
        env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 1
    assert "load failed" in res.stderr
