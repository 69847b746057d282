"""Real multi-process execution: two jax.distributed CPU
processes run dp training steps through ParallelExecutor and must match
single-process execution exactly; plus hybrid ICI x DCN mesh ordering."""
from __future__ import annotations

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu.parallel import make_hybrid_mesh

_HERE = os.path.dirname(os.path.abspath(__file__))


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _reference_run():
    """Single-process full-batch reference for the worker's program."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 13
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, 32, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        rs = np.random.RandomState(0)
        losses = []
        for _ in range(3):
            xb = rs.randn(8, 16).astype(np.float32)
            yb = (xb[:, :1] * 0.5 + 0.1).astype(np.float32)
            lv, = exe.run(main, feed={"x": xb, "y": yb},
                          fetch_list=[loss])
            losses.append(float(np.squeeze(lv)))
        params = {p.name: np.asarray(scope.find_var(p.name))
                  for p in main.all_parameters()}
    return losses, params


def _reference_run_pp():
    """Single-process full-batch sequential reference for the pp worker:
    the IDENTICAL program (same builder, seed, feed stream), run unsharded
    for the same 3 steps."""
    from _multihost_worker import (PP_MB, PP_MICRO, PP_T, PP_VOCAB,
                                   build_pp_lm)

    main, startup, loss = build_pp_lm(batch=PP_MICRO * PP_MB)
    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        rs = np.random.RandomState(0)
        losses = []
        B = PP_MICRO * PP_MB
        for _ in range(3):
            xb = rs.randint(0, PP_VOCAB, (B, PP_T)).astype(np.int64)
            yb = rs.randint(0, PP_VOCAB, (B, PP_T)).astype(np.int64)
            lv, = exe.run(main, feed={"ids": xb, "lbl": yb},
                          fetch_list=[loss])
            losses.append(float(np.squeeze(lv)))
        params = {p.name: np.asarray(scope.find_var(p.name))
                  for p in main.all_parameters()}
    return losses, params


def _run_two_process(tmp_path, mode):
    """Spawn 2 jax.distributed worker processes in `mode`, compare
    process 0's losses + final params against single-process execution."""
    port = _free_port()
    out = str(tmp_path / "proc0.npz")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo_root = os.path.dirname(_HERE)
    env["PYTHONPATH"] = (repo_root + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else repo_root)
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(_HERE, "_multihost_worker.py"),
             str(i), "2", str(port), out, mode],
            env=env, cwd=os.path.dirname(_HERE),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for i in range(2)
    ]
    logs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-host worker timed out")
        logs.append(stdout)
        assert p.returncode == 0, (
            "worker failed (rc %d):\n%s" % (p.returncode, stdout[-4000:]))
    assert os.path.exists(out), "process 0 wrote no results:\n%s" % logs[0]

    got = np.load(out)
    if mode == "pp":
        # microbatched pipeline vs full-batch sequential: bitwise equality
        # is not expected (summation order differs across microbatches) —
        # same tolerances as the single-process pipeline parity tests
        ref_losses, ref_params = _reference_run_pp()
        loss_rtol, p_rtol, p_atol = 2e-4, 2e-3, 2e-5
    else:
        ref_losses, ref_params = _reference_run()
        loss_rtol, p_rtol, p_atol = 1e-5, 1e-4, 1e-6
    np.testing.assert_allclose(got["losses"], ref_losses, rtol=loss_rtol,
                               err_msg="2-process losses diverged (%s)"
                               % mode)
    for name, want in ref_params.items():
        np.testing.assert_allclose(
            got[name], want, rtol=p_rtol, atol=p_atol,
            err_msg="param %s diverged between 2-process (%s) and "
            "1-process" % (name, mode))


def test_two_process_dp_parity(tmp_path):
    """2 jax.distributed processes x 2 virtual devices each == one
    process, full batch (the reference's multi-trainer capability,
    distribute_transpiler.py:336)."""
    _run_two_process(tmp_path, "dp")


def test_two_process_mp_inside_host(tmp_path):
    """Cross-process MODEL parallelism, placement A:
    dp spans the process boundary over DCN while the Megatron mp axis
    stays inside each host's ICI — the placement make_hybrid_mesh exists
    for. Params are mp-sharded locally, replicated across hosts."""
    _run_two_process(tmp_path, "mp_ici")


def test_two_process_mp_across_hosts(tmp_path):
    """Cross-process MODEL parallelism, placement B: the mp axis itself
    spans the process boundary — every col/row-parallel weight is
    physically split across the two processes (scope holds the full
    value; the executor slices each process's block), and the
    row-parallel all-reduce crosses DCN."""
    _run_two_process(tmp_path, "mp_dcn")


def test_two_process_pp_across_hosts(tmp_path):
    """Cross-process PIPELINE parallelism: the 4-stage
    pp axis spans the two jax.distributed processes (stages 0-1 on host 0,
    2-3 on host 1), so the stage-boundary ppermute activation traffic and
    the gpipe fill-drain schedule cross DCN. Loss + updated params must
    match single-process sequential full-batch execution — the reference's
    multi-trainer pipeline capability (distribute_transpiler.py:336)."""
    _run_two_process(tmp_path, "pp")


def test_hybrid_mesh_ordering_single_process():
    """DCN axes are slowest-varying: emulated host k owns the k-th block
    of prod(ici) consecutive devices, and an axis with dcn factor 1
    never crosses an (emulated) host boundary."""
    devs = jax.devices()[:8]
    # 2 "hosts" x 4 devices: dp crosses hosts, mp stays inside a host
    mesh = make_hybrid_mesh(("dp", "mp"), ici_shape=(1, 4),
                            dcn_shape=(2, 1), devices=devs)
    assert mesh.shape == {"dp": 2, "mp": 4}
    np.testing.assert_array_equal(
        np.vectorize(lambda d: d.id)(mesh.devices),
        [[d.id for d in devs[:4]], [d.id for d in devs[4:]]])

    # dp = dcn(2) x ici(2), mp = ici(2): dp's ici factor packs adjacent
    # device pairs; its dcn factor spans the two hosts
    mesh2 = make_hybrid_mesh(("dp", "mp"), ici_shape=(2, 2),
                             dcn_shape=(2, 1), devices=devs)
    ids = np.vectorize(lambda d: d.id)(mesh2.devices)
    assert mesh2.shape == {"dp": 4, "mp": 2}
    # rows 0-1 (dp's ici factor) from host 0, rows 2-3 from host 1
    base = [d.id for d in devs]
    np.testing.assert_array_equal(
        ids, [[base[0], base[1]], [base[2], base[3]],
              [base[4], base[5]], [base[6], base[7]]])

    with pytest.raises(ValueError, match="must align"):
        make_hybrid_mesh(("dp",), ici_shape=(2, 2), dcn_shape=(2,))
    with pytest.raises(ValueError, match="needs"):
        make_hybrid_mesh(("dp",), ici_shape=(64,), dcn_shape=(4,),
                         devices=devs)


def test_num_trainers_guard():
    """num_trainers>1 without the multi-host runtime fails fast with the
    migration message (previously untested guard)."""
    from paddle_tpu.parallel import ParallelExecutor

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, 1))
    with pytest.raises(RuntimeError, match="init_distributed"):
        ParallelExecutor(loss_name=loss.name, main_program=main,
                         num_trainers=2, trainer_id=0)
