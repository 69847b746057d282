"""Chaos harness: SIGKILL mid-epoch AND mid-checkpoint-write, restart,
assert the elastic-training acceptance contract (tools/chaos_train.py):

1. the resumed process loads the newest COMPLETE checkpoint (the
   mid-write partial is invisible/quarantined),
2. the loss trajectory continues BIT-exact vs an uninterrupted control,
3. no sample is duplicated or dropped across the restart (sample-id
   ledger).

The tier-1 (fast) variant runs a small config through both kill
scenarios; the ``slow`` variant scales it up and adds DataLoader worker
processes. Both inherit the session AOT cache dir, so children reuse
warm executables instead of recompiling.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOL = os.path.join(_REPO, "tools", "chaos_train.py")


def _run_chaos(extra_args, timeout=560):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, _TOOL] + extra_args,
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=_REPO)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln]
    return proc, lines


@pytest.fixture(scope="module")
def fast_chaos():
    # tier-1 budget: the midwrite scenario alone exercises BOTH required
    # kill modes — the victim dies mid-epoch AND inside the checkpoint
    # writer (PADDLE_TPU_FAULT_KILL at ckpt.before_rename on the 2nd
    # save). The between-steps SIGKILL scenario runs in the slow variant.
    proc, lines = _run_chaos([
        "--scenario", "midwrite", "--epochs", "2", "--batches", "5",
        "--batch", "4", "--step-interval", "2"])
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-4000:]
    return lines


def test_chaos_sigkill_mid_epoch_mid_write_resumes_bit_exact(fast_chaos):
    by = {ln.get("scenario"): ln for ln in fast_chaos
          if ln["bench"] == "chaos"}
    assert set(by) == {"midwrite"}
    v = by["midwrite"]
    assert v["verdict"] == "pass", v
    assert v["victim_sigkill"] is True  # died by SIGKILL, not a crash
    assert v["resumed"] is not None  # a complete checkpoint loaded
    checks = v["checks"]
    assert checks["trajectory_bit_exact"]
    assert checks["samples_exact"] and checks["no_duplicates"]
    assert checks["completed"]
    # effective history covers exactly the control's steps
    assert v["steps_effective"] == v["steps_control"] == 10


def test_chaos_midwrite_resumed_before_the_killed_write(fast_chaos):
    """The mid-write kill fires inside the writer's 2nd checkpoint, so
    the resume must come from the 1st — proving the partial was
    skipped, not half-loaded."""
    v = next(ln for ln in fast_chaos
             if ln.get("scenario") == "midwrite")
    assert v["resumed"]["serial"] == 0
    summary = [ln for ln in fast_chaos if ln["bench"] == "chaos_summary"]
    assert summary and summary[0]["verdict"] == "pass"


def test_resume_skips_fabricated_corruption(tmp_path):
    """In-process twin of acceptance check (1): a sentinel-less serial
    AND a tmp- partial newer than the only complete checkpoint must be
    invisible to restore — and retention/sweep must quarantine the
    stale partial (its writer pid is dead)."""
    import numpy as np

    from paddle_tpu.checkpoint import CheckpointManager, layout

    ck = str(tmp_path / "ck")
    with CheckpointManager(ck) as m:
        m.save({"w": np.ones((3,), np.float32)}, {"step": 5}, block=True)
    # fabricate: corrupt sentinel-less serial 7 + dead-pid tmp partial
    os.makedirs(os.path.join(ck, "checkpoint_7"))
    with open(os.path.join(ck, "checkpoint_7",
                           layout.PERSISTABLES_FILE), "wb") as f:
        f.write(b"garbage not an npz")
    os.makedirs(os.path.join(ck, "tmp-checkpoint_8.999999.feedf00d"))

    m2 = CheckpointManager(ck)  # init sweeps dead-pid partials
    try:
        assert m2.latest() == 0
        arrays, meta = m2.restore()
        assert meta["step"] == 5
        np.testing.assert_array_equal(arrays["w"],
                                      np.ones((3,), np.float32))
        # new serials never collide with the corrupt one
        s = m2.save({"w": np.zeros((3,), np.float32)}, {"step": 6},
                    block=True)
        assert s == 8
        assert not [e for e in os.listdir(ck)
                    if e.startswith(layout.TMP_PREFIX)]
    finally:
        m2.close()


@pytest.mark.slow
def test_chaos_full_scale_with_worker_processes():
    """The full chaos battery: bigger run, multiprocess DataLoader
    (worker-side sample skipping on resume), later kill point."""
    proc, lines = _run_chaos([
        "--scenario", "both", "--epochs", "3", "--batches", "12",
        "--batch", "8", "--step-interval", "3", "--workers", "2",
        "--die-after-step", "17"], timeout=1200)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-4000:]
    for v in lines:
        if v["bench"] == "chaos":
            assert v["verdict"] == "pass", v
