"""Seconds of `setup_s` spent in `AotDiskCache.load` (read +
`deserialize_executable`): the sum of `load_ms` over the records of
executables that came from the disk tier (`path` "warm") before the
window opened (`lib/acquire_records.py`). 0 in a run that compiled
everything. Nothing where the program writes no such records."""
from benchmark.lib import acquire_records

LAYER = "model step"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    out = acquire_records.of_run(run)
    return None if out is None else out["load_s"]
