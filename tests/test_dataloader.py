"""Multiprocess DataLoader tests: shared-memory zero-copy transport,
ordered/unordered epochs, worker failure propagation, and the read-op /
run_loop integration (epoch + EOF parity with py_reader).

Sources and mappers are module-level classes of `dataloader_sources.py`
(beside this file), which says why they live there.
"""
from __future__ import annotations

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.io.dataloader import _SLOT_WAIT_S, DataLoader

from dataloader_sources import (DyingSrc, ObjectSrc, PaddleBatchSrc,
                                RaisingSrc, RawImageSrc, RegressionSrc,
                                SampleSrc, SlowFirstMapper, TensorSrc)


@pytest.fixture(scope="module", autouse=True)
def forkserver_of_its_own():
    """The loader warms the fork server with its own module (numpy, the
    frame codec, and through the package jax) so that a worker is one
    fork; `set_forkserver_preload` is silently too late where the
    process's fork server already runs, and an xdist worker that ran a
    Router test before this file has one, cold: every worker of every
    epoch here would then import jax (seconds a worker; ROADMAP D15).
    These tests are of the loader as it is designed to run, so the file
    starts from no fork server, and leaves none to the files after it."""
    from multiprocessing import forkserver

    forkserver._forkserver._stop()
    yield
    forkserver._forkserver._stop()


def _drain(dl):
    out = []
    while True:
        try:
            out.append(dl.next())
        except fluid.EOFException:
            return out


def _rides_shm(arr):
    """Is `arr` a view over a shared-memory slot (not a pickled copy)?"""
    base = arr
    while isinstance(getattr(base, "base", None), np.ndarray):
        base = base.base
    return isinstance(base.base, memoryview)


def test_ordered_matches_serial_across_epochs():
    """A consumer that does not hoard views rides shared memory. What
    that promises on any box: a batch whose worker certainly had a free
    slot is a view over it (each worker's first of an epoch: the ring
    came back whole, but for the one tail batch `b` still names), and no
    batch went by pickle unless its worker first waited out the whole
    slot wait (`_SLOT_WAIT_S`: "slot starvation costs a copy, never
    liveness"), which a consumer starved of the CPU by five other test
    workers does cause and a quiet box never does."""
    dl = DataLoader(["x", "y"], [[-1, 3], [-1]], ["float32", "int64"],
                    num_workers=2, capacity=4)
    dl.decorate_sample_reader(SampleSrc(23), batch_size=4, drop_last=False)
    try:
        for _epoch in range(3):
            dl.start()
            heads, shapes, dtypes, shm = [], [], [], []
            while True:  # consume WITHOUT hoarding views (fast path)
                try:
                    b = dl.next()
                except fluid.EOFException:
                    break
                heads.append(float(b["x"][0, 0]))
                shapes.append(b["x"].shape)
                dtypes.append(b["y"].dtype)
                shm.append(_rides_shm(b["x"]))
            assert heads == [0.0, 4.0, 8.0, 12.0, 16.0, 20.0]
            assert shapes[-1] == (3, 3)  # drop_last=False tail
            assert dtypes[0] == np.int64
            assert shm[:2] == [True, True], shm  # slots came back
        stats = dl.stats()
        assert stats["shm_batches"] + stats["pickle_batches"] == 18
        # stayed zero-copy wherever a slot came free within the wait
        assert (stats["worker_stall_s"]
                >= 0.99 * _SLOT_WAIT_S * stats["pickle_batches"]), stats
    finally:
        dl.close()


def test_ordered_reorders_skewed_workers():
    dl = DataLoader(["x", "y"], None, None, num_workers=2)
    dl.decorate_sample_reader(SampleSrc(24), batch_size=4,
                              mapper=SlowFirstMapper())
    try:
        dl.start()
        got = [b["x"][0, 0] for b in _drain(dl)]
        assert got == [0.0, 4.0, 8.0, 12.0, 16.0, 20.0]
    finally:
        dl.close()


def test_unordered_delivers_every_batch():
    dl = DataLoader(["x"], None, None, num_workers=3, ordered=False)
    dl.decorate_tensor_provider(TensorSrc(9))
    try:
        dl.start()
        vals = sorted(b["x"][0, 0] for b in _drain(dl))
        assert vals == [float(i) for i in range(9)]
    finally:
        dl.close()


def test_paddle_reader_decoration_casts_like_py_reader():
    dl = DataLoader(["a", "b"], [[-1, 2], [-1]], ["float32", "int64"],
                    num_workers=2)
    dl.decorate_paddle_reader(PaddleBatchSrc(5))
    try:
        dl.start()
        got = _drain(dl)
        assert len(got) == 5
        assert got[0]["a"].dtype == np.float32  # cast from float64
        assert got[0]["b"].dtype == np.int64
        np.testing.assert_array_equal(got[2]["a"][:, 0], [8, 9, 10, 11])
    finally:
        dl.close()


def test_zero_copy_and_pickle_fallbacks():
    # numeric batches ride shared memory ...
    dl = DataLoader(["x"], None, None, num_workers=2)
    dl.decorate_tensor_provider(TensorSrc(4))
    try:
        dl.start()
        got = _drain(dl)
        assert dl.stats()["shm_batches"] == 4
        assert _rides_shm(got[0]["x"])  # a view over the slot
    finally:
        dl.close()
    # ... object dtypes fall back to pickle ...
    dl2 = DataLoader(["s"], None, None, num_workers=2)
    dl2.decorate_tensor_provider(ObjectSrc())
    try:
        dl2.start()
        got = _drain(dl2)
        assert len(got) == 3 and got[0]["s"][0] == "s0"
        assert dl2.stats()["pickle_batches"] == 3
    finally:
        dl2.close()
    # ... and so do batches that outgrow the slot
    dl3 = DataLoader(["x"], None, None, num_workers=2, slot_bytes=64)
    dl3.decorate_tensor_provider(TensorSrc(4, shape=(32, 32)))
    try:
        dl3.start()
        assert len(_drain(dl3)) == 4
        assert dl3.stats()["pickle_batches"] == 4
    finally:
        dl3.close()


def test_worker_exception_propagates_not_hangs():
    dl = DataLoader(["x"], None, None, num_workers=2)
    dl.decorate_sample_reader(RaisingSrc(), batch_size=2)
    try:
        dl.start()
        with pytest.raises(ValueError, match="decode exploded"):
            for _ in range(100):
                dl.next()
        # the error is sticky until reset()
        with pytest.raises(ValueError):
            dl.next()
        dl.reset()
        dl.decorate_sample_reader(SampleSrc(4), batch_size=2)
        dl.start()
        assert len(_drain(dl)) == 2  # recovered after reset
    finally:
        dl.close()


def test_worker_hard_death_raises_runtime_error():
    dl = DataLoader(["x"], None, None, num_workers=2)
    dl.decorate_sample_reader(DyingSrc(), batch_size=1)
    try:
        dl.start()
        with pytest.raises(RuntimeError, match="died unexpectedly"):
            for _ in range(100):
                dl.next()
    finally:
        dl.close()


def test_inline_mode_num_workers_zero():
    dl = DataLoader(["x", "y"], None, None, num_workers=0)
    dl.decorate_sample_reader(SampleSrc(8), batch_size=4)
    try:
        dl.start()
        got = _drain(dl)
        assert [b["x"][0, 0] for b in got] == [0.0, 4.0]
        with pytest.raises(fluid.EOFException):
            dl.next()  # stays exhausted until start()/reset()
        # start()-per-epoch restarts inline mode exactly like worker mode
        for _epoch in range(2):
            dl.start()
            assert [b["x"][0, 0] for b in _drain(dl)] == [0.0, 4.0]
    finally:
        dl.close()


def test_iterator_mode_feeds_executor_run():
    x = layers.data(name="x", shape=[3])
    y = layers.data(name="y", shape=[1], dtype="int64")
    out = layers.scale(x, scale=2.0)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    dl = DataLoader(["x", "y"], [[-1, 3], [-1, 1]], ["float32", "int64"],
                    num_workers=2)
    dl.decorate_sample_reader(SampleSrc(12), batch_size=4)
    try:
        for _epoch in range(2):  # __iter__ resets itself between epochs
            firsts = []
            for feed in dl:
                feed = dict(feed)
                feed["y"] = feed["y"].reshape(-1, 1)
                ov, = exe.run(feed=feed, fetch_list=[out])
                firsts.append(float(np.asarray(ov)[0, 0]))
            assert firsts == [0.0, 8.0, 16.0]
    finally:
        dl.close()


def _loss_program(reader_factory):
    """A tiny regression program fed by a read op; returns
    (main, startup, reader_var, loss)."""
    mp_, sp = fluid.Program(), fluid.Program()
    mp_.random_seed = sp.random_seed = 7
    with fluid.program_guard(mp_, sp):
        with fluid.unique_name.guard():
            reader = reader_factory()
            xb, yb = layers.read_file(reader)
            pred = layers.fc(xb, 1, bias_attr=False,
                             param_attr=fluid.ParamAttr(name="w"))
            loss = layers.mean(layers.square_error_cost(pred, yb))
            fluid.optimizer.SGD(0.05).minimize(loss)
    return mp_, sp, reader, loss


def test_read_op_run_loop_epochs_match_py_reader():
    """Acceptance: the DataLoader drives Executor.run_loop through a
    `read` op with epoch-restart + EOF semantics identical to PyReader —
    same window truncation, same EOF points, same losses (same RNG
    stream, same batch sequence)."""
    src = RegressionSrc()
    bs = 6

    def batched():
        for i in range(0, len(src.x), bs):
            yield list(zip(src.x[i:i + bs], src.y[i:i + bs]))

    def make_py_reader():
        r = layers.py_reader(capacity=8, shapes=[(-1, 4), (-1, 1)],
                             dtypes=["float32", "float32"],
                             use_double_buffer=False)
        r.decorate_paddle_reader(batched)
        return r

    def make_data_loader():
        r = layers.data_loader(capacity=8, shapes=[(-1, 4), (-1, 1)],
                               dtypes=["float32", "float32"],
                               num_workers=2)
        r.decorate_sample_reader(src, batch_size=bs)
        return r

    results = {}
    for name, factory in [("py_reader", make_py_reader),
                          ("data_loader", make_data_loader)]:
        mp_, sp, reader, loss = _loss_program(factory)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(sp)
            losses, windows = [], []
            for _epoch in range(4):
                reader.start()
                while True:
                    try:
                        # steps=3 over 4 batches/epoch: second window
                        # truncates at EOF (k=1), third call raises
                        lv, = exe.run_loop(mp_, fetch_list=[loss],
                                           steps=3)
                    except fluid.EOFException:
                        break
                    losses.append(round(float(lv), 6))
            if name == "data_loader":
                reader.close()
        results[name] = losses
    assert results["py_reader"] == results["data_loader"]
    assert results["py_reader"][-1] < results["py_reader"][0]


def test_read_op_plain_run_epoch_loop():
    """DataLoader through Executor.run (single-step pulls): the
    reference catch-EOF-and-restart loop trains to convergence."""
    src = RegressionSrc()

    def make_data_loader():
        r = layers.data_loader(capacity=8, shapes=[(-1, 4), (-1, 1)],
                               dtypes=["float32", "float32"],
                               num_workers=2)
        r.decorate_sample_reader(src, batch_size=6)
        return r

    mp_, sp, reader, loss = _loss_program(make_data_loader)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(sp)
        losses = []
        for _epoch in range(8):
            reader.start()
            steps = 0
            while True:
                try:
                    lv, = exe.run(mp_, fetch_list=[loss])
                except fluid.EOFException:
                    break
                losses.append(float(lv))
                steps += 1
            assert steps == 4  # 24 / 6
        assert losses[-1] < losses[0] * 0.5
        reader.close()


def test_image_simple_transform_mapper_in_workers():
    """dataset.image.SimpleTransform is the picklable decode/augment
    mapper the DataLoader contract needs (a lambda can't cross the
    forkserver boundary)."""
    from paddle_tpu.dataset import image

    dl = DataLoader(["img", "label"], None, None, num_workers=2)
    dl.decorate_sample_reader(
        RawImageSrc(8), batch_size=4,
        mapper=image.SimpleTransform(36, 32, is_train=True, seed=5))
    try:
        dl.start()
        got = _drain(dl)
        assert len(got) == 2
        assert got[0]["img"].shape == (4, 3, 32, 32)  # CHW, cropped
        assert got[0]["img"].dtype == np.float32
        assert got[0]["label"].dtype == np.int64
    finally:
        dl.close()


def test_close_is_idempotent_and_releases_children():
    import multiprocessing as mp

    before = {p.pid for p in mp.active_children()}
    dl = DataLoader(["x"], None, None, num_workers=2)
    dl.decorate_tensor_provider(TensorSrc(64))
    dl.start()
    dl.next()
    dl.close()
    dl.close()
    assert {p.pid for p in mp.active_children()} - before == set()
    with pytest.raises(RuntimeError):
        dl.start()  # closed loaders refuse to restart


# ---------------------------------------------------------------------------
# sample-exact resume (state_dict / load_state_dict)
# ---------------------------------------------------------------------------


def _make_resumable(num_workers, n=20, bs=4):
    dl = DataLoader(["x", "y"], shapes=[[3], []],
                    dtypes=["float32", "int64"], num_workers=num_workers)
    dl.decorate_sample_reader(SampleSrc(n), batch_size=bs)
    return dl


@pytest.mark.parametrize("workers", [0, 2])
def test_state_dict_resume_is_sample_exact(workers):
    """Consume part of an epoch, capture state, resume a FRESH loader:
    the remainder (and the following epoch) match an uninterrupted run
    exactly — nothing replayed, nothing skipped."""
    control = _make_resumable(workers)
    try:
        control.start()
        full = _drain(control)
        control.start()
        full2 = _drain(control)
    finally:
        control.close()

    part = _make_resumable(workers)
    try:
        part.start()
        consumed = [part.next() for _ in range(2)]
        state = part.state_dict()
        assert state["epoch"] == 0 and state["offset"] == 2
    finally:
        part.close()

    resumed = _make_resumable(workers)
    try:
        resumed.load_state_dict(state)
        resumed.start()
        rest = _drain(resumed)
        resumed.start()  # next epoch after resume is a FULL epoch
        nxt = _drain(resumed)
    finally:
        resumed.close()

    def flat(batches):
        return [int(v) for b in batches for v in np.asarray(b["y"]).ravel()]

    assert flat(consumed) + flat(rest) == flat(full)
    assert flat(nxt) == flat(full2)
    assert resumed.state_dict()["epoch"] == state["epoch"] + 2


def test_state_dict_epoch_boundary_semantics():
    dl = _make_resumable(0)
    try:
        dl.start()
        _drain(dl)
        st = dl.state_dict()
        # a finished epoch reads as (next epoch, offset 0)
        assert st["epoch"] == 1 and st["offset"] == 0
    finally:
        dl.close()


def test_load_state_dict_guards():
    dl = DataLoader(["x"], None, None, num_workers=0, ordered=False)
    dl.decorate_tensor_provider(TensorSrc(8))
    with pytest.raises(ValueError, match="ordered=True"):
        dl.load_state_dict({"v": 1, "epoch": 0, "offset": 3})
    dl.load_state_dict({"v": 1, "epoch": 0, "offset": 0})  # 0 is fine
    dl.close()

    dl2 = _make_resumable(0)
    try:
        dl2.start()
        # refused while running — even before the first next(): the
        # current epoch is already being delivered from offset 0
        with pytest.raises(RuntimeError, match="running"):
            dl2.load_state_dict({"v": 1, "epoch": 0, "offset": 1})
        dl2.next()
        with pytest.raises(RuntimeError, match="running"):
            dl2.load_state_dict({"v": 1, "epoch": 0, "offset": 1})
        dl2.reset()
        dl2.load_state_dict({"v": 1, "epoch": 0, "offset": 1})  # ok now
    finally:
        dl2.close()
    with pytest.raises(ValueError):
        dl2.load_state_dict({"bogus": True})
