"""Run one cell of the benchmark once, in this process.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is the result, one JSON object. Everything else
is printed before it or written under `chiprun_out/`. Exits non-zero,
and prints no result, when JAX's default device is not a TPU, when the
cell's chips are not there, or when the device kind has no published
peaks on record.
"""
import os
import sys
import time

T_START = time.time()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
