"""How many executables the run acquired before its window opened: the
count of the records of `lib/acquire_records.py` (a hit in a memory cache
writes none). Nothing where the program writes no such records."""
from benchmark.lib import acquire_records

LAYER = "model step"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    out = acquire_records.of_run(run)
    return None if out is None else out["executables"]
