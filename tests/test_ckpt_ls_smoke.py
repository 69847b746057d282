"""Tier-1 smoke for tools/ckpt_ls.py: schema pinned (the aot_cache_ls
pattern) over a directory holding a complete checkpoint, a
sentinel-less corrupt serial, and an in-flight tmp- partial."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOL = os.path.join(_REPO, "tools", "ckpt_ls.py")

_TOP_FIELDS = ("schema", "dir", "latest", "complete", "incomplete",
               "total_bytes", "entries")
_ENTRY_FIELDS = ("name", "serial", "complete", "bytes", "age_s", "meta")
_META_FIELDS = ("step", "epoch", "offset", "global_step", "trainer_id",
                "fingerprint")


@pytest.fixture()
def ckdir(tmp_path):
    from paddle_tpu.checkpoint import CheckpointManager

    ck = str(tmp_path / "ck")
    with CheckpointManager(ck) as m:
        m.save({"w": np.ones((4,), np.float32)},
               {"step": 3, "epoch": 1, "global_step": 3}, block=True)
    os.makedirs(os.path.join(ck, "checkpoint_9"))  # sentinel-less
    os.makedirs(os.path.join(ck, "tmp-checkpoint_10.%d.abcd0123"
                             % os.getpid()))  # live partial
    return ck


def test_snapshot_schema(ckdir):
    """The importable snapshot() (what --json serializes)."""
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import ckpt_ls
    finally:
        sys.path.pop(0)
    out = ckpt_ls.snapshot(ckdir)
    for f in _TOP_FIELDS:
        assert f in out, f
    assert out["schema"] == "ckpt_ls/1"
    assert out["latest"] == 0
    assert out["complete"] == 1 and out["incomplete"] == 2
    by_name = {e["name"]: e for e in out["entries"]}
    assert set(by_name) == {"checkpoint_0", "checkpoint_9",
                            "tmp-checkpoint_10.%d.abcd0123" % os.getpid()}
    for e in out["entries"]:
        for f in _ENTRY_FIELDS:
            assert f in e, (e["name"], f)
    good = by_name["checkpoint_0"]
    assert good["complete"] and good["serial"] == 0
    for f in _META_FIELDS:
        assert f in good["meta"], f
    assert good["meta"]["global_step"] == 3
    assert by_name["checkpoint_9"]["complete"] is False
    assert by_name["checkpoint_9"]["meta"] is None


def test_cli_json_and_human(ckdir, capsys, monkeypatch):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, _TOOL, ckdir, "--json"],
        capture_output=True, text=True, timeout=300, env=env, cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout)
    assert out["schema"] == "ckpt_ls/1"
    assert out["latest"] == 0
    # human listing marks the partial loudly (in-process: one subprocess
    # per tier-1 smoke is enough)
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import ckpt_ls
    finally:
        sys.path.pop(0)
    monkeypatch.setattr(sys, "argv", ["ckpt_ls.py", ckdir])
    ckpt_ls.main()
    text = capsys.readouterr().out
    assert "PARTIAL" in text and "complete" in text
