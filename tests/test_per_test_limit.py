"""The per-test limit of `tests/conftest.py`: a hang fails one test."""
import os
import subprocess
import sys
import textwrap

_CONFTEST = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "conftest.py")


def test_a_test_past_the_limit_fails_alone_with_every_stack(tmp_path):
    """A pytest run of its own (a child process: loading conftest.py a
    second time sets this session's environment anew) with the limit
    patched down to a second: the test that sleeps past it fails by name
    with every thread's stack in its report, the waiting thread's too,
    and the test after it runs and passes."""
    (tmp_path / "conftest.py").write_text(textwrap.dedent("""
        import importlib.util

        spec = importlib.util.spec_from_file_location("t1_conftest", %r)
        t1 = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(t1)
        t1.TEST_LIMIT_S = 1
        per_test_limit = t1.per_test_limit
        """ % _CONFTEST))
    (tmp_path / "test_hang.py").write_text(textwrap.dedent("""
        import threading
        import time

        def test_hangs():
            gate = threading.Event()
            t = threading.Thread(target=gate.wait, name="waiter", daemon=True)
            t.start()
            try:
                time.sleep(30)
            finally:
                gate.set()

        def test_next_one_runs():
            time.sleep(0.01)
        """))
    run = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path), "-p",
         "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        capture_output=True, text=True, timeout=120, cwd=str(tmp_path))
    out = run.stdout + run.stderr
    assert run.returncode == 1, out
    assert "1 failed, 1 passed" in out, out
    assert "test_hang.py::test_hangs: still running after 1 s" in out, out
    assert "test_next_one_runs" not in out.split("short test summary")[-1], out
    # the main thread where it slept, and the other thread where it waits
    assert "Current thread" in out and "in test_hangs" in out, out
    assert out.count("Thread 0x") >= 1 and "in wait" in out, out
