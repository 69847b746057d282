"""Tier-1 smoke for tools/bench_coldstart.py: one interleaved replicate
on the smoke-sized config, schema pinned (the bench_serving pattern).
This doubles as the acceptance-criteria subprocess test: the warm child
must actually LOAD executables from disk (warm_used_cache) rather than
recompile, and the cold/warm medians must come from real fresh-process
runs."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOL = os.path.join(_REPO, "tools", "bench_coldstart.py")

_LINE_FIELDS = ("bench", "schema", "config", "replicates", "loop_steps",
                "cold_ttfs_s", "warm_ttfs_s", "cold_median_s",
                "warm_median_s", "warmstart_speedup", "cold_loop_median_s",
                "warm_loop_median_s", "import_median_s", "prime_ttfs_s",
                "warm_used_cache")


@pytest.fixture(scope="module")
def bench_lines():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, _TOOL, "--configs", "mlp-tiny",
         "--replicates", "1", "--loop-steps", "2"],
        capture_output=True, text=True, timeout=600, env=env, cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln]
    return lines


def test_one_json_line_per_config_plus_summary(bench_lines):
    assert [ln["bench"] for ln in bench_lines] == ["coldstart",
                                                   "coldstart_summary"]
    line = bench_lines[0]
    for f in _LINE_FIELDS:
        assert f in line, f
    assert line["schema"] == "bench_coldstart/1"
    assert line["config"] == "mlp-tiny"
    assert len(line["cold_ttfs_s"]) == 1 and len(line["warm_ttfs_s"]) == 1
    assert line["cold_median_s"] > 0 and line["warm_median_s"] > 0


def test_warm_children_hit_the_disk_cache(bench_lines):
    line = bench_lines[0]
    # the warm process deserialized at least one executable — the
    # measured gap is cache reuse, not noise
    assert line["warm_used_cache"] is True
    summary = bench_lines[1]
    assert summary["min_speedup"] == line["warmstart_speedup"]
