"""Tier-1 smoke for the observability exposition: runs tools/metrics_dump.py
(tiny CPU train loop + Predictor round-trip) in a subprocess and checks the
Prometheus text format and JSON snapshot it prints. A format regression in
observability/export.py fails here before it reaches a real scrape job."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOL = os.path.join(_REPO, "tools", "metrics_dump.py")

# the exposition names the acceptance surface pins (ISSUE 1): a rename is
# a dashboard-breaking change and must be deliberate
_REQUIRED_SERIES = (
    "paddle_tpu_compile_total",
    "paddle_tpu_compile_cache_hits_total",
    "paddle_tpu_compile_cache_misses_total",
    "paddle_tpu_step_latency_ms_bucket",
    "paddle_tpu_step_latency_ms_sum",
    "paddle_tpu_step_latency_ms_count",
    "paddle_tpu_steps_total",
    "paddle_tpu_predict_latency_ms_bucket",
    "paddle_tpu_run_loop_window_steps_bucket",
    # the int8 quantization tier (ISSUE 12): calibrate -> quantize ->
    # parity all leave series in the same exposition
    "paddle_tpu_quant_calib_batches_total",
    "paddle_tpu_quant_quantized_ops_total",
    "paddle_tpu_quant_parity_max_abs_diff",
    # bounded-latency load shedding (ISSUE 13): every shed is an
    # explicit reject AND a tick of this per-class series
    "paddle_tpu_fleet_shed_total",
    # decode-serving levers (ISSUE 14): prefix-hit-rate and
    # acceptance-rate are the ROADMAP-named signals — queries/hits and
    # proposed/accepted must ride the same exposition
    "paddle_tpu_decode_prefix_queries_total",
    "paddle_tpu_decode_prefix_hits_total",
    "paddle_tpu_decode_prefix_bytes",
    "paddle_tpu_decode_spec_proposed_total",
    "paddle_tpu_decode_spec_accepted_total",
    # online learning & hot swap (ISSUE 15): the swap controller, the
    # streaming trainer's poisoned-batch sentinel, and the wedged-
    # worker watchdog all leave series in the same exposition
    "paddle_tpu_swap_total",
    "paddle_tpu_swap_ms_bucket",
    "paddle_tpu_train_skipped_batches_total",
    "paddle_tpu_fleet_wedged_total",
    # distributed request tracing (ISSUE 16): the trace_round's fully
    # sampled shed leaves span counts and a per-phase latency sample
    "paddle_tpu_trace_spans_total",
    "paddle_tpu_request_phase_ms_bucket",
    "paddle_tpu_request_phase_ms_count",
)


@pytest.fixture(scope="module")
def dump_output():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, _TOOL, "--steps", "2"],
        capture_output=True, text=True, timeout=600, env=env, cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout


def test_prometheus_exposition_contains_required_series(dump_output):
    text = dump_output.split("\n{", 1)[0]  # prometheus part precedes JSON
    for name in _REQUIRED_SERIES:
        assert name in text, "missing %s in exposition" % name
    # text-format invariants a scraper relies on
    assert "# TYPE paddle_tpu_compile_total counter" in text
    assert "# TYPE paddle_tpu_step_latency_ms histogram" in text
    assert 'le="+Inf"' in text
    # the shed series carries its SLO class as a label, exactly this
    # exposition line (dashboards/alerts key on it)
    assert 'paddle_tpu_fleet_shed_total{class="interactive"} 1' in text
    # prefix hits carry their kind label the same way (full | partial |
    # batch) — the decode_round's miss->insert->hit lands exactly one
    assert 'paddle_tpu_decode_prefix_hits_total{kind="full"} 1' in text
    # ISSUE 15 exact lines: one rejected swap (result label), one
    # NaN-skipped batch and one corrupt chunk (reason labels), one
    # wedge-reaped replica — dashboards/alerts key on these
    assert 'paddle_tpu_swap_total{result="rollback"} 1' in text
    assert ('paddle_tpu_train_skipped_batches_total{reason="nonfinite"}'
            ' 1') in text
    assert ('paddle_tpu_train_skipped_batches_total'
            '{reason="corrupt_chunk"} 1') in text
    assert "paddle_tpu_fleet_wedged_total 1" in text
    # ISSUE 16 exact lines: the trace_round's one sampled request
    # records a client-submit span and a shed span, and the shed folds
    # its whole (queued) life into the phase histogram — these are the
    # lines a tracing dashboard keys on
    assert 'paddle_tpu_trace_spans_total{phase="client.submit"} 1' in text
    assert 'paddle_tpu_trace_spans_total{phase="router.shed"} 1' in text
    assert 'paddle_tpu_request_phase_ms_count{phase="queue"} 1' in text
    # the trace_round sheds under class="batch" (so the interactive pin
    # above stays exact) — its own shed line rides the exposition too
    assert 'paddle_tpu_fleet_shed_total{class="batch"} 1' in text


def test_histogram_buckets_are_cumulative_and_consistent(dump_output):
    # every _bucket line for one series must be monotonically nondecreasing
    # and the +Inf bucket must equal _count
    text = dump_output.split("\n{", 1)[0]
    series = {}
    for line in text.splitlines():
        if line.startswith("paddle_tpu_step_latency_ms_bucket"):
            labels, val = line.rsplit(" ", 1)
            key = labels.split('le="')[0]
            series.setdefault(key, []).append(int(val))
    assert series, "no step-latency buckets emitted"
    for key, counts in series.items():
        assert counts == sorted(counts), "non-cumulative buckets in %s" % key
    counts_by_key = {}
    for line in text.splitlines():
        if line.startswith("paddle_tpu_step_latency_ms_count"):
            labels, val = line.rsplit(" ", 1)
            # "..._count{kind=run}" -> the prefix its bucket lines share
            # ("le" sorts after "kind", so it is the last label)
            counts_by_key[labels.replace("_count{", "_bucket{")
                          .rstrip("}")] = int(val)
    matched = 0
    for key, counts in series.items():
        want = [v for k, v in counts_by_key.items() if key.startswith(k)]
        assert want and counts[-1] == want[0]
        matched += 1
    assert matched == len(counts_by_key)


def test_json_snapshot_parses_and_carries_timeline(dump_output):
    json_part = dump_output[dump_output.index("\n{") + 1:]
    snap = json.loads(json_part)
    assert "metrics" in snap and "timeline" in snap
    assert "paddle_tpu_compile_total" in snap["metrics"]
    tl = snap["timeline"]
    assert tl["recorded"] >= 1 and isinstance(tl["events"], list)
    types = {e["type"] for e in tl["events"]}
    assert "step" in types and "compile" in types
    # each step event carries the fields the timeline promises
    step = next(e for e in tl["events"] if e["type"] == "step")
    for field in ("ts", "kind", "wall_ms", "steps", "feed_bytes",
                  "fetch_bytes", "seq"):
        assert field in step, field


def test_replica_label_and_merge(tmp_path):
    """Two worker-labeled dumps merge collision-free: the replica label
    (PADDLE_TPU_REPLICA / --replica) keeps each process's series
    distinct, and --merge aggregates them into one snapshot."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    dumps = []
    for name in ("w0", "w1"):
        proc = subprocess.run(
            [sys.executable, _TOOL, "--steps", "1", "--no-predict",
             "--json", "--replica", name],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=_REPO)
        assert proc.returncode == 0, proc.stderr[-2000:]
        snap = json.loads(proc.stdout)
        assert snap["replica"] == name
        steps = snap["metrics"]["paddle_tpu_steps_total"]["series"]
        assert all(s["labels"]["replica"] == name for s in steps)
        # the shed series rides every worker dump too (ISSUE 13 +
        # the ISSUE-16 trace_round's batch-class shed): one
        # admission-path shed per class, labeled by class AND replica
        shed = snap["metrics"]["paddle_tpu_fleet_shed_total"]["series"]
        assert sorted(
            (s["labels"]["class"], s["labels"]["replica"], s["value"])
            for s in shed) == [("batch", name, 1),
                               ("interactive", name, 1)]
        path = tmp_path / ("%s.json" % name)
        path.write_text(proc.stdout)
        dumps.append((str(path), snap))

    proc = subprocess.run(
        [sys.executable, _TOOL, "--merge", dumps[0][0], dumps[1][0]],
        capture_output=True, text=True, timeout=600, env=env, cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    merged = json.loads(proc.stdout)
    assert sorted(merged["replicas"]) == ["w0", "w1"]
    series = merged["metrics"]["paddle_tpu_steps_total"]["series"]
    # no collisions: each worker's series is still addressable...
    replicas = {s["labels"]["replica"] for s in series}
    assert replicas == {"w0", "w1"}
    # ...and values survived intact (sum over the fleet = sum of dumps)
    def total(snap_series):
        return sum(s["value"] for s in snap_series)
    want = sum(total(s["metrics"]["paddle_tpu_steps_total"]["series"])
               for _p, s in dumps)
    assert total(series) == want
    # fleet_shed_total merges collision-free too: per-replica AND
    # per-class series stay addressable, the fleet-wide shed count is
    # their sum (interactive + batch, per worker)
    shed = merged["metrics"]["paddle_tpu_fleet_shed_total"]["series"]
    assert sorted((s["labels"]["class"], s["labels"]["replica"])
                  for s in shed) == [
        ("batch", "w0"), ("batch", "w1"),
        ("interactive", "w0"), ("interactive", "w1")]
    assert total(shed) == 4


def test_unlabeled_export_format_unchanged():
    """A process that never sets a replica identity exports EXACTLY the
    pre-fleet format: no replica PROCESS label stamped onto series
    (existing dashboards and scrape configs must not churn).

    Pinned via process_labels() and a fleet-free series rather than the
    whole exposition: an in-process Router (test_decode_serving's fleet
    round trip runs one earlier in the suite) legitimately records
    paddle_tpu_fleet_* series whose own label set includes replica= —
    that is a per-series label, not the process identity this test
    guards."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import export

    assert obs.process_labels() == {}
    text = export.to_prometheus()
    for line in text.splitlines():
        if line.startswith("paddle_tpu_steps_total") \
                or line.startswith("paddle_tpu_compile_total"):
            assert 'replica="' not in line, line
