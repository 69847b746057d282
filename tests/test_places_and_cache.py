"""A place means its device, and the compile caches live where ONE rule
says (runtime/aot_cache.py)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu.runtime import aot_cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tpu_place_raises_without_a_tpu():
    with pytest.raises(RuntimeError, match="no tpu device is visible"):
        fluid.TPUPlace().jax_device()
    with pytest.raises(RuntimeError, match="no tpu device is visible"):
        fluid.Executor(fluid.TPUPlace())
    with pytest.raises(RuntimeError, match="no tpu device is visible"):
        fluid.Executor(fluid.CUDAPlace(0))  # the reference's accelerator


def test_executor_runs_on_its_place():
    """The place is resolved once, at construction, and the step's state
    lands on that device — here the second of the virtual CPU devices,
    not JAX's default."""
    assert fluid.Executor()._device == jax.devices()[0]
    assert isinstance(fluid.Executor().place, fluid.CPUPlace)
    exe = fluid.Executor(fluid.CPUPlace(1))
    assert exe._device == jax.devices("cpu")[1]
    x = fluid.layers.data(name="x", shape=[4])
    y = fluid.layers.fc(x, 2)
    exe.run(fluid.default_startup_program())
    out, = exe.run(feed={"x": np.ones((3, 4), np.float32)}, fetch_list=[y],
                   return_numpy=False)
    assert out.devices() == {exe._device}
    w = fluid.global_scope().find_var(
        fluid.default_main_program().global_block().all_parameters()[0].name)
    assert w.devices() == {exe._device}
    with pytest.raises(RuntimeError, match="only 8 cpu device"):
        fluid.CPUPlace(64).jax_device()


def _record_updates(monkeypatch):
    seen = []
    update = jax.config.update
    monkeypatch.setattr(jax.config, "update", lambda k, v: (
        seen.append(k), update(k, v))[1])
    return seen


def test_cache_rule_with_the_variable_set(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax has read it itself, nothing
    updates jax_compilation_cache_dir, and the AOT tier (an Executor's
    disk cache) is a fixed-name subdirectory of it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("PADDLE_TPU_AOT_CACHE_DIR")
    seen = _record_updates(monkeypatch)
    assert aot_cache.enable_compile_cache() == str(tmp_path)
    assert aot_cache.default_cache_dir() == str(tmp_path / "paddle_tpu_aot")
    exe = fluid.Executor()
    assert exe._disk.dir == str(tmp_path / "paddle_tpu_aot")
    assert "jax_compilation_cache_dir" not in seen
    # pretend an accelerator: still jax's own business
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    aot_cache.enable_compile_cache()
    assert "jax_compilation_cache_dir" not in seen


def test_cache_rule_unset_is_under_the_checkout_in_every_process(
        monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("PADDLE_TPU_AOT_CACHE_DIR")
    want = os.path.join(_ROOT, ".xla_cache")
    assert aot_cache.compile_cache_dir() == want
    assert aot_cache.default_cache_dir() == os.path.join(
        want, "paddle_tpu_aot")
    code = ("from paddle_tpu.runtime import aot_cache as a; "
            "print(a.compile_cache_dir()); print(a.default_cache_dir())")
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "PADDLE_TPU_AOT_CACHE_DIR")}
    env["PYTHONPATH"] = _ROOT
    outs = [subprocess.run([sys.executable, "-c", code], env=env, cwd=cwd,
                           capture_output=True, text=True, timeout=120,
                           check=True).stdout.split()
            for cwd in (_ROOT, "/")]
    assert outs[0] == outs[1] == [want, os.path.join(want, "paddle_tpu_aot")]


def test_cache_rule_unset_points_jax_at_the_checkout_on_an_accelerator(
        monkeypatch):
    """Unset, with an accelerator as the default backend, jax's tier is
    pointed at <checkout>/.xla_cache; on this CPU host it is left off."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    seen = _record_updates(monkeypatch)
    aot_cache.enable_compile_cache()
    assert "jax_compilation_cache_dir" not in seen
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        want = os.path.join(_ROOT, ".xla_cache")
        assert aot_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
