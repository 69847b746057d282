"""The sources and mappers `tests/test_dataloader.py` hands to DataLoader
workers. MODULE-LEVEL classes, because the default forkserver start
method pickles them across the process boundary — the same contract real
users live under — and in a module of their own that imports numpy and
nothing else: every worker of every epoch imports the module its source
was pickled from, and from the test file that was `import pytest`, half
a second a worker on a quiet box.
"""
from __future__ import annotations

import os
import time

import numpy as np


class SampleSrc:
    """Yields (feature, label) samples with deterministic contents."""

    def __init__(self, n, d=3):
        self.n, self.d = n, d

    def __call__(self):
        for i in range(self.n):
            yield (np.full((self.d,), i, np.float32), np.int64(i))


class TensorSrc:
    def __init__(self, n, shape=(2, 3)):
        self.n, self.shape = n, shape

    def __call__(self):
        for i in range(self.n):
            yield (np.full(self.shape, i, np.float32),)


class PaddleBatchSrc:
    """paddle.batch convention: yields lists of per-sample tuples."""

    def __init__(self, n_batches, bs=4):
        self.n_batches, self.bs = n_batches, bs

    def __call__(self):
        for b in range(self.n_batches):
            yield [(np.full((2,), b * self.bs + i, np.float64), int(i))
                   for i in range(self.bs)]


class ObjectSrc:
    def __call__(self):
        for i in range(3):
            yield (np.array(["s%d" % i, None], dtype=object),)


class RaisingSrc:
    """Yields a few good samples, then raises."""

    def __init__(self, good=4):
        self.good = good

    def __call__(self):
        for i in range(self.good):
            yield (np.full((3,), i, np.float32),)
        raise ValueError("decode exploded mid-epoch")


class DyingSrc:
    """Simulates a segfaulting worker: hard process death, no message."""

    def __call__(self):
        yield (np.ones(3, np.float32),)
        os._exit(23)


class SlowFirstMapper:
    """Delays the FIRST batch's samples so ordered mode must reorder."""

    def __call__(self, s):
        if float(s[0][0]) < 4:  # first batch of 4
            time.sleep(0.05)
        return s


class RegressionSrc:
    """Deterministic linear-regression samples shared by both readers."""

    def __init__(self, n=24, seed=0):
        r = np.random.RandomState(seed)
        self.x = r.randn(n, 4).astype(np.float32)
        self.y = (self.x @ np.arange(1, 5, dtype=np.float32)
                  ).reshape(n, 1).astype(np.float32)

    def __call__(self):
        for xi, yi in zip(self.x, self.y):
            yield (xi, yi)


class RawImageSrc:
    """(HWC uint8 image, label) samples for the vision-mapper test."""

    def __init__(self, n):
        self.n = n

    def __call__(self):
        r = np.random.RandomState(3)
        for i in range(self.n):
            yield (r.randint(0, 256, (40, 48, 3)).astype(np.uint8),
                   np.int64(i % 10))
