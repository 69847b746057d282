"""Tier-1 smoke for tools/bench_transpile.py: one replicate on the
smoke-sized config, schema pinned (the bench_serving/bench_decode/
bench_resume pattern). Doubles as the acceptance plumbing check: the
bench must report parity_ok (raw vs optimized outputs exactly equal on
the measured feeds) and the churn arm must hit the pow2 bucket bound."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOL = os.path.join(_REPO, "tools", "bench_transpile.py")

_LINE_FIELDS = ("bench", "schema", "config", "opt_level", "replicates",
                "ops_before", "ops_after", "op_reduction_frac",
                "passes_ms", "pass_applied", "trace_s_raw",
                "trace_s_opt", "trace_median_raw_s",
                "trace_median_opt_s", "trace_speedup",
                "xla_median_raw_s", "xla_median_opt_s",
                "cold_total_median_raw_s", "cold_total_median_opt_s",
                "cold_total_speedup", "bucketized", "parity_ok")

_CHURN_FIELDS = ("bench", "schema", "config", "batch_sizes",
                 "distinct_sizes", "compiles_raw", "compiles_opt",
                 "cache_misses_raw", "cache_misses_opt", "bucket_bound",
                 "bucket_bound_hit", "parity_close",
                 "parity_max_abs_diff")


@pytest.fixture(scope="module")
def bench_lines():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PADDLE_TPU_OPT", None)
    proc = subprocess.run(
        [sys.executable, _TOOL, "--configs", "mlp-tiny",
         "--replicates", "1", "--churn-config", "mlp-tiny",
         "--churn-sizes", "3,5,6"],
        capture_output=True, text=True, timeout=560, env=env, cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return [json.loads(ln) for ln in proc.stdout.splitlines() if ln]


def test_one_json_line_per_config_plus_churn_and_summary(bench_lines):
    assert [ln["bench"] for ln in bench_lines] == [
        "transpile", "transpile_churn", "transpile_summary"]
    line = bench_lines[0]
    for f in _LINE_FIELDS:
        assert f in line, f
    assert line["schema"] == "bench_transpile/1"
    assert line["config"] == "mlp-tiny"
    assert line["ops_after"] < line["ops_before"]
    assert line["pass_applied"].get("fuse_fc", 0) >= 1
    assert len(line["trace_s_raw"]) == 1


def test_churn_line_hits_bucket_bound(bench_lines):
    churn = bench_lines[1]
    for f in _CHURN_FIELDS:
        assert f in churn, f
    assert churn["schema"] == "bench_transpile/1"
    # 3,5,6 -> buckets {4, 8}: raw compiles 3, bucketized 2
    assert churn["compiles_raw"] == 3
    assert churn["compiles_opt"] == 2
    assert churn["bucket_bound_hit"] is True
    # counter-verified against the compile-cache miss series
    assert churn["cache_misses_raw"] == churn["compiles_raw"]
    assert churn["cache_misses_opt"] == churn["compiles_opt"]


def test_parity_gate_and_summary(bench_lines):
    assert bench_lines[0]["parity_ok"] is True
    churn = bench_lines[1]
    assert churn["parity_close"] is True
    # padded-path drift stays in the GEMM reduction-order ulp class
    assert churn["parity_max_abs_diff"] < 1e-5
    summary = bench_lines[2]
    assert summary["schema"] == "bench_transpile/1"
    assert summary["all_parity_ok"] is True
    assert summary["churn_bucket_bound_hit"] is True
    assert "min_trace_speedup" in summary
    assert "min_cold_total_speedup" in summary
    assert "min_op_reduction_frac" in summary
    assert "churn_parity_max_abs_diff" in summary
