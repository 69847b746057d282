"""`tools/spread.py`: the spread a bound is set from."""
import importlib.util
import os

import pytest

from benchmark.lib import harness


def _load():
    path = os.path.join(harness.BENCH_DIR, "tools", "spread.py")
    spec = importlib.util.spec_from_file_location("bench_spread", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_spread_is_interquartile_distance_over_median():
    sp = _load().spread
    # statistics.quantiles(n=4) of 1..6 (exclusive method): 1.75, 3.5, 5.25
    assert sp([1, 2, 3, 4, 5, 6]) == pytest.approx((5.25 - 1.75) / 3.5)
    assert sp([100.0] * 6) == 0.0
