"""Dataset infrastructure.

Reference: python/paddle/dataset/common.py — download() with md5 checks
into ~/.cache/paddle/dataset. This environment has zero network egress, so
every dataset here is backed by a DETERMINISTIC SYNTHETIC generator with
the exact sample schema of its reference twin (same tuple layout, dtypes,
ranges, vocab handling). Real data dropped into DATA_HOME by the user is
picked up by the modules that support it (mnist idx files, uci_housing
data); otherwise the synthetic source is used transparently.

Synthetic data is class-conditional (not pure noise) so models genuinely
train on it: convergence tests and benchmarks exercise the same code paths
as real data.
"""
from __future__ import annotations

import os

import numpy as np

__all__ = ["DATA_HOME", "data_home", "rng_for", "synthetic_size"]

# beside the package, never under the home directory: the repo reads and
# writes nothing around its checkout (.gitignore lists it)
DATA_HOME = os.path.expanduser(os.environ.get(
    "PADDLE_TPU_DATA_HOME",
    os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".dataset_cache")))


def data_home(*parts: str) -> str:
    return os.path.join(DATA_HOME, *parts)


def rng_for(dataset: str, split: str) -> np.random.RandomState:
    """Deterministic per-(dataset, split) stream: every process sees the
    same data, every epoch replays identically (like files on disk)."""
    import zlib

    seed = zlib.crc32(("%s/%s" % (dataset, split)).encode()) & 0x7FFFFFFF
    return np.random.RandomState(seed)


def synthetic_size(name: str, default: int) -> int:
    """Sample counts are env-tunable (PADDLE_TPU_SYNTH_<NAME>) so CI stays
    fast while benchmarks can scale up."""
    return int(os.environ.get("PADDLE_TPU_SYNTH_" + name.upper(), default))
