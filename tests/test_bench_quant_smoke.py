"""Tier-1 smoke for tools/bench_quant.py: one round on the smoke-sized
config, schema pinned (the bench_transpile/bench_decode pattern).
Doubles as the acceptance plumbing check: every quant line must report
parity_ok and the slab line must report the 2x capacity ratio vs bf16."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOL = os.path.join(_REPO, "tools", "bench_quant.py")

_LINE_FIELDS = ("bench", "schema", "config", "rounds", "batches",
                "batch_rows", "calib_batches", "quantized_ops",
                "rows_per_s_float", "rows_per_s_int8",
                "rows_per_s_float_median", "rows_per_s_int8_median",
                "rows_per_s_speedup", "parity_max_abs_diff",
                "parity_mean_abs_diff", "parity_metric_agreement",
                "parity_ok")

_SLAB_FIELDS = ("bench", "schema", "config", "seq", "budget_bytes",
                "slots_float32", "slots_bfloat16", "slots_int8",
                "capacity_ratio_vs_bf16", "decode_roundtrip")


@pytest.fixture(scope="module")
def bench_lines():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PADDLE_TPU_OPT", None)
    env.pop("PADDLE_TPU_QUANT", None)
    proc = subprocess.run(
        [sys.executable, _TOOL, "--configs", "mlp-tiny", "--rounds", "1",
         "--batches", "4", "--batch-rows", "32", "--calib-batches", "2"],
        capture_output=True, text=True, timeout=560, env=env, cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return [json.loads(ln) for ln in proc.stdout.splitlines() if ln]


def test_one_json_line_per_config_plus_slab_and_summary(bench_lines):
    assert [ln["bench"] for ln in bench_lines] == [
        "quant", "quant_slab", "quant_summary"]
    line = bench_lines[0]
    for f in _LINE_FIELDS:
        assert f in line, f
    assert line["schema"] == "bench_quant/1"
    assert line["config"] == "mlp-tiny"
    assert line["quantized_ops"] >= 2
    assert line["calib_batches"] == 2
    assert len(line["rows_per_s_float"]) == 1
    assert line["rows_per_s_int8_median"] > 0


def test_parity_gate(bench_lines):
    line = bench_lines[0]
    assert line["parity_ok"] is True
    assert line["parity_max_abs_diff"] < 0.05
    assert line["parity_metric_agreement"] >= 0.95


def test_slab_line_capacity_ratio(bench_lines):
    slab = bench_lines[1]
    for f in _SLAB_FIELDS:
        assert f in slab, f
    assert slab["schema"] == "bench_quant/1"
    assert slab["slots_int8"] == 2 * slab["slots_bfloat16"]
    assert slab["capacity_ratio_vs_bf16"] == pytest.approx(2.0)
    assert slab["decode_roundtrip"] is None  # smoke skips the round trip


def test_summary(bench_lines):
    summary = bench_lines[2]
    assert summary["schema"] == "bench_quant/1"
    assert summary["all_parity_ok"] is True
    assert summary["capacity_ratio_vs_bf16"] == pytest.approx(2.0)
    for f in ("min_speedup", "max_speedup", "max_parity_abs_diff"):
        assert f in summary, f
