"""The seeded generators: the same requests for the same seed, others
for another, and the same multiset of sizes for every seed."""
import json
import os

import numpy as np

from benchmark.lib import harness, stats, traffic


def _mix(name):
    with open(os.path.join(harness.BENCH_DIR, "traffic", name)) as f:
        return json.load(f)


def test_serve_requests_repeat_and_differ():
    mix = _mix("chat-closed-2x.json")
    big = 2 ** 31 + 12345  # seeds above 32 signed bits are legal
    a = traffic.serve_requests(mix, 50272, big)
    b = traffic.serve_requests(mix, 50272, big)
    c = traffic.serve_requests(mix, 50272, big + 1)
    assert len(a) == mix["requests"]
    assert all((x[0] == y[0]).all() and x[1] == y[1] for x, y in zip(a, b))
    assert any(len(x[0]) != len(y[0]) or (x[0] != y[0]).any()
               for x, y in zip(a, c))
    # every seed: the same sizes in another order
    assert sorted(len(x[0]) for x in a) == sorted(len(x[0]) for x in c)
    assert sorted(x[1] for x in a) == sorted(x[1] for x in c)
    lens = [len(x[0]) for x in a]
    p, g = mix["prompt_len"], mix["max_new"]
    assert min(lens) >= p["min"] and max(lens) <= p["max"]
    assert all(g["min"] <= x[1] <= g["max"] for x in a)
    assert abs(np.median(lens) - p["median"]) < 0.05 * p["median"]
    assert all(x[0].min() >= 1 and x[0].max() < 50272 for x in a)


def test_token_batches_repeat_and_differ():
    a = traffic.token_batches(7, 3, 2, 16, 100)
    assert a.shape == (3, 2, 17) and a.dtype == np.int32
    assert (a == traffic.token_batches(7, 3, 2, 16, 100)).all()
    assert (a != traffic.token_batches(8, 3, 2, 16, 100)).any()


def test_arrival_times_rate_and_burstiness():
    steady = traffic.arrival_times({"rate_per_s": 50.0}, 3, 200.0)
    burst = traffic.arrival_times({"rate_per_s": 50.0, "arrival_cv": 3.0},
                                  3, 200.0)
    assert (steady == traffic.arrival_times({"rate_per_s": 50.0}, 3,
                                            200.0)).all()
    for t in (steady, burst):
        assert abs(len(t) / 200.0 - 50.0) < 5.0 and (np.diff(t) >= 0).all()
    cv = lambda t: np.std(np.diff(t)) / np.mean(np.diff(t))  # noqa: E731
    assert 0.9 < cv(steady) < 1.1 and 2.5 < cv(burst) < 3.5


def test_percentile_rule():
    assert stats.highest_supported_percentile(400) == 0.975
    assert stats.tail(list(range(400)), 0.95)[1] == 0.95
    v, p = stats.tail(list(range(100)), 0.95)
    assert p == 0.9 and v == stats.quantile(range(100), 0.9)
    assert stats.tail([1.0, 2.0, 3.0], 0.95)[1] == 0.5
    # 0.9 is reported as 0.9 from 100 samples on, and as less below
    assert stats.tail(list(range(100)), 0.9)[1] == 0.9
    assert stats.tail(list(range(99)), 0.9)[1] < 0.9
