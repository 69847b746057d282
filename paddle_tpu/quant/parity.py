"""Quantized-vs-float parity: the gate every int8 deployment runs.

Same feeds through both serving paths, compared at two levels —

- **logits tolerance**: max/mean abs difference across every fetch (the
  raw numeric drift the int8 rounding introduced);
- **task-metric delta**: a scalar metric (top-1 agreement by default,
  or any caller-supplied ``metric_fn(outputs, feeds) -> float``)
  evaluated on both arms, so "is the model still the same model" is
  answered in task units, not ulps.

``parity_report`` drives two Predictors (or model dirs) and returns one
JSON-able dict; the observed ``max_abs_diff`` also lands on the
``paddle_tpu_quant_parity_max_abs_diff`` gauge so a serving fleet can
alert on quantization drift.
"""
from __future__ import annotations

from typing import Callable, Dict, Iterable, Optional

import numpy as np

from .. import observability as obs

__all__ = ["parity_report", "top1_agreement"]

SCHEMA = "quant_parity/1"


def _as_predictor(p):
    if isinstance(p, str):
        from ..inference import Predictor

        return Predictor(p, aot_cache=False)
    return p


def top1_agreement(base_outs, quant_outs) -> float:
    """Fraction of rows whose argmax over the FIRST fetch agrees —
    the default task metric for classifier-shaped outputs."""
    a = np.asarray(base_outs[0])
    b = np.asarray(quant_outs[0])
    if a.ndim < 2 or a.shape != b.shape:
        return float(np.array_equal(a, b))
    return float(np.mean(np.argmax(a, -1) == np.argmax(b, -1)))


def parity_report(base, quant, feeds: Iterable[Dict],
                  metric_fn: Optional[Callable] = None,
                  logits_tol: Optional[float] = None,
                  metric_tol: Optional[float] = None) -> Dict:
    """Run every feed dict through both arms and report the drift.

    ``base`` / ``quant``: Predictors or model directories. ``feeds``:
    feed dicts (each arm sees identical inputs). ``metric_fn(base_outs,
    quant_outs) -> float in [0, 1]`` scores per-batch agreement
    (default: top-1 agreement); ``metric_delta`` is ``1 - mean
    agreement``. With tolerances given, ``ok`` reflects both gates;
    without, ``ok`` is True (report-only mode)."""
    base = _as_predictor(base)
    quant = _as_predictor(quant)
    metric_fn = metric_fn or top1_agreement
    max_abs = 0.0
    abs_sum, abs_n = 0.0, 0
    agreements = []
    batches = 0
    for feed in feeds:
        b_outs = base.run(feed)
        q_outs = quant.run(feed)
        for a, b in zip(b_outs, q_outs):
            a64 = np.asarray(a, np.float64)
            b64 = np.asarray(b, np.float64)
            if a64.shape != b64.shape:
                raise ValueError(
                    "parity fetch shapes diverge: %s vs %s"
                    % (a64.shape, b64.shape))
            if a64.size:
                d = np.abs(a64 - b64)
                max_abs = max(max_abs, float(d.max()))
                abs_sum += float(d.sum())
                abs_n += d.size
        agreements.append(float(metric_fn(b_outs, q_outs)))
        batches += 1
    if batches == 0:
        raise ValueError("parity_report needs at least one feed batch")
    metric = float(np.mean(agreements))
    metric_delta = 1.0 - metric
    ok = True
    if logits_tol is not None:
        ok = ok and max_abs <= logits_tol
    if metric_tol is not None:
        ok = ok and metric_delta <= metric_tol
    obs.QUANT_PARITY.set(max_abs)
    return {
        "schema": SCHEMA,
        "batches": batches,
        "max_abs_diff": max_abs,
        "mean_abs_diff": (abs_sum / abs_n) if abs_n else 0.0,
        "metric_agreement": metric,
        "metric_delta": metric_delta,
        "logits_tol": logits_tol,
        "metric_tol": metric_tol,
        "ok": bool(ok),
    }
