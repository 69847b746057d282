"""Seconds of a training cell's `setup_s` spent acquiring executables:
the sum of `wall_ms` over every record `observability.observe_acquire`
wrote in the run (`lib/acquire_records.py`; the training runner gives no
window stamp, and the harness refuses a run that acquires anything
inside the window). A `lazy` record is a first call through `jax.jit`
(`ParallelExecutor`): trace + compile + run, not split. Prints the table
of records, slowest first. Nothing where the program writes no such
records."""
from benchmark.lib import acquire_records

LAYER = "model step"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    out = acquire_records.of_run(run)
    return None if out is None else out["acquire_s"]
