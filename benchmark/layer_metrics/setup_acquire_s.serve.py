"""Seconds of `setup_s` the program spent acquiring executables: the sum
of `wall_ms` over the records `observability.observe_acquire` wrote
before the window opened (`lib/acquire_records.py`; one record an
executable loaded, compiled or traced, the caller's program build
included). What a change to how a server comes by its executables has to
bring down. Prints the table of records, slowest first. Nothing where
the program writes no such records (the parent of the PR that added
them)."""
from benchmark.lib import acquire_records

LAYER = "model step"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    out = acquire_records.of_run(run)
    return None if out is None else out["acquire_s"]
