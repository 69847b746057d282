"""The trace reduction on synthetic traces: unions of overlapping
intervals, exposed collective time, gaps and their attribution."""
import pytest

from benchmark.lib import trace_reduce as tr


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 10)]) == [
        (0, 4), (5, 7)]
    assert tr.total(tr.union([(0, 10), (2, 3), (9, 12)])) == 12


def test_subtract():
    a = [(0, 10), (20, 30)]
    b = [(2, 4), (8, 22), (29, 40)]
    assert tr.subtract(a, b) == [(0, 2), (4, 8), (22, 29)]
    assert tr.subtract(a, []) == a
    assert tr.subtract([], b) == []


def _trace():
    # one device: compute 0-100, all-reduce 90-130 (40 long, 30 exposed),
    # idle 130-200, compute 200-300; the host waits during the idle gap
    dev = [("fusion.1", 0.0, 100.0, ""),
           ("all-reduce.3", 90.0, 40.0, ""),
           ("fusion.2", 200.0, 100.0, "jit(step)/mha_fwd_kernel")]
    host = [("bench.exe_run", 0.0, 120.0), ("bench.wait_step", 125.0, 80.0),
            ("bench.outer", 0.0, 400.0), ("other", 0.0, 400.0)]
    return {"devices": {"/device:TPU:0": dev, "/device:TPU:1": list(dev)},
            "host": [h for h in host if h[0].startswith("bench.")]}


def test_reduce_trace_numbers():
    r = tr.reduce_trace(_trace())
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(300e-9)
    assert r["busy_s"] == pytest.approx(230e-9)
    assert r["collective_s"] == pytest.approx(40e-9)
    assert r["collective_exposed_s"] == pytest.approx(30e-9)
    assert r["device_ops"][0][0] in ("fusion.1", "fusion.2")
    assert dict(map(tuple, r["device_ops"]))["all-reduce.3"] == \
        pytest.approx(40e-9)
    # the one gap (130-200) lies inside bench.wait_step, the innermost
    assert r["idle_gaps"] == [["bench.wait_step", pytest.approx(70e-9)]]


def test_kernel_seconds_matches_name_or_statistics():
    secs, n = tr.kernel_seconds(_trace(), r"mha_fwd")
    assert n == 1 and secs == pytest.approx(100e-9)
    assert tr.kernel_seconds(_trace(), r"^all-reduce")[1] == 1
    assert tr.kernel_seconds(_trace(), r"nothing") == (0.0, 0)


def test_no_device_events():
    assert tr.reduce_trace({"devices": {}, "host": []}) == {"devices": 0}


def test_load_xplane_reads_a_recorded_trace(tmp_path):
    """A small trace recorded here, on the CPU: the benchmark's own
    annotations come back as host events."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.exe_run"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load_xplane(str(tmp_path), device_plane=lambda n: n == "/host:CPU",
                       op_lines=("tf_XLAPjRtCpuClient", "tf_XLAEigen"))
    assert any(n == "bench.exe_run" for n, _, _ in t["host"])
    assert tr.reduce_trace(t)["busy_s"] > 0
