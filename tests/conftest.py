import os

# Force an 8-device virtual CPU mesh so parallel/sharding tests run without
# TPU hardware (chip_smoke.py --chips 4 is the real multi-chip path).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import tempfile

# Isolate the persistent AOT executable cache (runtime/aot_cache.py): the
# suite still exercises the disk tier (warm-start reuse across tests is
# by design — identical fingerprints load instead of recompiling), but in
# a per-session tmp dir instead of the operator's cache — UNCONDITIONAL,
# so a developer's exported PADDLE_TPU_AOT_CACHE_DIR is never polluted
# (or GC-evicted) by test traffic. Subprocess tests (metrics_dump, bench
# smokes) inherit the tmp dir through os.environ.
os.environ["PADDLE_TPU_AOT_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="ptpu-aot-t1-")

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running chaos/bench variants excluded from tier-1 "
        "(tier-1 runs -m 'not slow')")


@pytest.fixture(autouse=True)
def no_leaked_workers():
    """Tier-1 hygiene: a test that leaks worker PROCESSES (DataLoader
    workers, multihost helpers) or non-daemon THREADS fails instead of
    silently poisoning the rest of the suite. Cheap on the clean path
    (two snapshots); only a suspected leak pays the gc + grace joins.
    Library-pool threads (ThreadPoolExecutor) are process-lifetime by
    design and exempt, as are daemon threads."""
    import gc
    import multiprocessing as mp
    import threading
    import time

    procs_before = {p.pid for p in mp.active_children()}
    threads_before = {t.ident for t in threading.enumerate()}
    yield

    def leaked_procs():
        return [p for p in mp.active_children()
                if p.pid not in procs_before and p.is_alive()]

    def leaked_threads():
        return [t for t in threading.enumerate()
                if t.ident not in threads_before and t.is_alive()
                and not t.daemon
                and not t.name.startswith("ThreadPoolExecutor")
                and not t.name.startswith("QueueFeederThread")]

    if leaked_procs() or leaked_threads():
        # grace period: teardown may still be finishing (GC finalizers,
        # worker joins); collect to run weakref cleanups, then re-check
        gc.collect()
        deadline = time.monotonic() + 3.0
        while ((leaked_procs() or leaked_threads())
               and time.monotonic() < deadline):
            time.sleep(0.05)
        procs, threads = leaked_procs(), leaked_threads()
        for p in procs:  # don't poison the NEXT test with the leak
            p.terminate()
        if procs or threads:
            pytest.fail(
                "test leaked workers: processes=%s threads=%s (close() "
                "your DataLoaders / join your threads)"
                % ([p.name for p in procs], [t.name for t in threads]))


# The slowest honest case takes 95 s on a contended box (ROADMAP D1); a
# test still running at this many seconds is hung, not slow.
TEST_LIMIT_S = 300


@pytest.fixture(autouse=True)
def per_test_limit(request):
    """A hang fails ONE test, not the run: a real-time timer around each
    test whose handler writes every thread's stack and fails the test by
    name, so the worker goes on to the next one instead of sitting on the
    hang until the run's own clock cuts it (exit 124, every later test
    unrun). Signals reach the main thread only, which is where pytest
    runs a test; a test that waits on other processes keeps its own
    shorter limits (`fut.result(timeout=...)`)."""
    import faulthandler
    import signal

    def on_alarm(signum, frame):
        with tempfile.TemporaryFile() as f:  # faulthandler wants a real fd
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            stacks = f.read().decode("utf-8", "replace")
        pytest.fail("%s: still running after %g s (the per-test limit of "
                    "tests/conftest.py). Every thread's stack:\n%s"
                    % (request.node.nodeid, TEST_LIMIT_S, stacks),
                    pytrace=False)

    prev = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, prev)


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs / scope / name counters."""
    import paddle_tpu as fluid
    from paddle_tpu.framework import unique_name
    from paddle_tpu.framework.core import Program, switch_main_program, switch_startup_program
    from paddle_tpu.framework.scope import Scope, scope_guard

    prev_main = switch_main_program(Program())
    prev_startup = switch_startup_program(Program())
    with scope_guard(Scope()):
        with unique_name.guard():
            yield
    switch_main_program(prev_main)
    switch_startup_program(prev_startup)


@pytest.fixture
def rng():
    return np.random.RandomState(42)
