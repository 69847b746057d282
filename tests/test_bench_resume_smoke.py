"""Tier-1 smoke for tools/bench_resume.py: one interleaved replicate on
the smoke-sized config, schema pinned (the bench_coldstart pattern).
Doubles as the acceptance-criteria plumbing check: restart children
must actually RESTORE a checkpoint (resume_loaded_ckpt) and the warm
child must load executables from disk (warm_used_cache), so the
measured gap is cache + checkpoint reuse, not noise."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOL = os.path.join(_REPO, "tools", "bench_resume.py")

_LINE_FIELDS = ("bench", "schema", "config", "steps", "step_interval",
                "replicates", "plain_steps_per_s", "ckpt_steps_per_s",
                "plain_median", "ckpt_median", "overhead_frac",
                "saves_per_arm", "cold_ttfs_s", "warm_ttfs_s",
                "cold_median_s", "warm_median_s", "warm_restart_speedup",
                "restore_median_s", "warm_used_cache",
                "resume_loaded_ckpt")


@pytest.fixture(scope="module")
def bench_lines():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, _TOOL, "--configs", "mlp-tiny", "--steps", "8",
         "--step-interval", "4", "--replicates", "1",
         "--restart-replicates", "1", "--prime-steps", "4"],
        capture_output=True, text=True, timeout=560, env=env, cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return [json.loads(ln) for ln in proc.stdout.splitlines() if ln]


def test_one_json_line_per_config_plus_summary(bench_lines):
    assert [ln["bench"] for ln in bench_lines] == ["resume",
                                                   "resume_summary"]
    line = bench_lines[0]
    for f in _LINE_FIELDS:
        assert f in line, f
    assert line["schema"] == "bench_resume/1"
    assert line["config"] == "mlp-tiny"
    assert len(line["cold_ttfs_s"]) == 1 and len(line["warm_ttfs_s"]) == 1
    assert line["plain_median"] > 0 and line["ckpt_median"] > 0
    assert line["saves_per_arm"] >= 1


def test_restart_children_restored_and_hit_cache(bench_lines):
    line = bench_lines[0]
    assert line["resume_loaded_ckpt"] is True
    assert line["warm_used_cache"] is True
    summary = bench_lines[1]
    assert summary["schema"] == "bench_resume/1"
    assert "max_overhead_frac" in summary
    assert summary["min_warm_restart_speedup"] == \
        line["warm_restart_speedup"]
