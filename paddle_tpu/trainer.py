"""High-level Trainer API.

Reference: python/paddle/fluid/trainer.py — wraps program construction,
the (Parallel)Executor loop, event callbacks and checkpointing. The TPU
reading of `parallel=True` is a pjit data-parallel mesh instead of
per-GPU graph clones.
"""
from __future__ import annotations

import itertools
import os
from typing import Callable, List, Optional

import numpy as np

from . import io as io_mod
from .checkpoint import CheckpointManager, check_fingerprint
from .checkpoint.resume import build_meta
from . import optimizer as optimizer_mod
from .data_feeder import DataFeeder
from .executor import Executor
from .framework import core as framework
from .framework.core import Program, program_guard
from .framework.scope import Scope, default_place, scope_guard
from .framework import unique_name

__all__ = [
    "BeginEpochEvent", "EndEpochEvent", "BeginStepEvent", "EndStepEvent",
    "CheckpointConfig", "Trainer", "Inferencer",
]


class BeginEpochEvent(object):
    def __init__(self, epoch_id):
        self.epoch = epoch_id


class EndEpochEvent(object):
    def __init__(self, epoch_id):
        self.epoch = epoch_id


class BeginStepEvent(object):
    def __init__(self, epoch_id, step_id):
        self.epoch = epoch_id
        self.step = step_id
        #: set True to fetch metrics for the matching EndStepEvent
        self.fetch_metrics = True


class EndStepEvent(object):
    def __init__(self, epoch_id, step_id, metrics):
        self.epoch = epoch_id
        self.step = step_id
        self.metrics = metrics


class CheckpointConfig(object):
    """reference trainer.py:CheckpointConfig.

    ``max_pending`` is the async-checkpoint staleness bound used by
    ``Trainer.fit``: snapshots queued for the background writer before
    a save blocks the step loop (block-don't-drop)."""

    def __init__(self, checkpoint_dir=None, max_num_checkpoints=3,
                 epoch_interval=1, step_interval=10, max_pending=2):
        self.checkpoint_dir = checkpoint_dir or os.getcwd()
        self.max_num_checkpoints = max_num_checkpoints
        self.epoch_interval = max(int(epoch_interval), 1)
        self.step_interval = max(int(step_interval), 1)
        self.max_pending = max(int(max_pending), 0)
        self.epoch_id = 0
        self.step_id = 0
        self.load_serial = None


def check_and_get_place(place):
    """Default to the TPU when one is visible (reference
    check_and_get_place prefers CUDA)."""
    return place if place is not None else default_place()


def build_feed_var_list(program: Program, feed_order):
    if feed_order is None:
        feed_var_list = [
            var for var in program.global_block().vars.values()
            if var.is_data
        ]
    elif isinstance(feed_order, (list, tuple)):
        feed_var_list = [program.global_block().var(n) for n in feed_order]
    elif isinstance(feed_order, dict):
        order = sorted(feed_order, key=lambda n: feed_order[n])
        feed_var_list = [program.global_block().var(n) for n in order]
    else:
        raise TypeError("feed_order should be a list, dict or None")
    return feed_var_list


def _feed_windows(feeder, batch_it, steps_per_loop, start_step=0):
    """Yield (first_step_id, [feed dicts]) windows of up to
    steps_per_loop batches. A batch whose feed shapes differ from the
    window's (e.g. a short final batch) closes the window and starts
    its own — stacked per-step feeds must be uniform. ``start_step``
    offsets the step ids (a resumed epoch continues mid-count)."""
    buf, first = [], 0

    def shapes(feed):
        return {n: np.asarray(v).shape for n, v in feed.items()}

    for step_id, data in enumerate(batch_it, start=start_step):
        feed = feeder.feed(data)
        if buf and shapes(feed) != shapes(buf[0]):
            yield first, buf
            buf = []
        buf.append(feed)
        if len(buf) == 1:
            first = step_id
        if len(buf) == steps_per_loop:
            yield first, buf
            buf = []
    if buf:
        yield first, buf


class Trainer(object):
    """reference trainer.py:Trainer.

    train_func() builds the graph and returns loss (or [loss, *metrics]);
    optimizer_func() returns the Optimizer. `parallel=True` runs the step
    under a pjit data-parallel mesh (ParallelExecutor).
    """

    def __init__(self, train_func: Callable, optimizer_func: Callable,
                 param_path: Optional[str] = None, place=None,
                 parallel: bool = False,
                 checkpoint_config: Optional[CheckpointConfig] = None):
        self.__stop = False
        self.parallel = parallel
        self.trainer_id = 0
        self.checkpoint_cfg = checkpoint_config
        self._restored_meta = None  # __init__-time checkpoint restore,
        self._restored_serial = None  # reused by fit(resumable=True)
        if self.checkpoint_cfg:
            if not isinstance(self.checkpoint_cfg, CheckpointConfig):
                raise TypeError("checkpoint_config must be a CheckpointConfig")
            serial = io_mod.get_latest_checkpoint_serial(
                self.checkpoint_cfg.checkpoint_dir)
            self.checkpoint_cfg.load_serial = serial if serial >= 0 else None

        self.scope = Scope()
        self.startup_program = Program()
        self.train_program = Program()
        self.place = check_and_get_place(place)

        with program_guard(self.train_program, self.startup_program):
            with unique_name.guard():
                outs = train_func()
                self.train_func_outputs = list(outs) if isinstance(
                    outs, (list, tuple)) else [outs]
                self.test_program = self.train_program.clone(for_test=True)
                optimizer = optimizer_func()
                if not isinstance(optimizer, optimizer_mod.Optimizer):
                    raise TypeError(
                        "The optimizer should be an instance of Optimizer")
                loss = self.train_func_outputs[0]
                optimizer.minimize(loss)

        self._exe = Executor(self.place)
        with scope_guard(self.scope):
            self._exe.run(self.startup_program)

        if param_path is not None:
            with scope_guard(self.scope):
                io_mod.load_persistables(
                    self._exe, param_path, main_program=self.startup_program)

        if self.checkpoint_cfg and self.checkpoint_cfg.load_serial is not None:
            with scope_guard(self.scope):
                meta = io_mod.load_checkpoint(
                    self._exe, self.checkpoint_cfg.checkpoint_dir,
                    serial=self.checkpoint_cfg.load_serial,
                    main_program=self.train_program)
            # resume the counters so train() continues where the crashed
            # run stopped instead of re-running finished epochs
            self.checkpoint_cfg.epoch_id = int(meta.get("epoch", 0))
            self.checkpoint_cfg.step_id = int(meta.get("step", 0))
            # full meta kept so a subsequent fit(resumable=True) reuses
            # THIS restore instead of re-reading + re-transferring the
            # same checkpoint
            self._restored_meta = meta
            self._restored_serial = self.checkpoint_cfg.load_serial

        self._train_exe = None
        if parallel:
            from .parallel import ParallelExecutor

            with scope_guard(self.scope):
                self._train_exe = ParallelExecutor(
                    loss_name=loss.name, main_program=self.train_program,
                    scope=self.scope)

    def stop(self):
        """Stop training after the current step (callable from the event
        handler)."""
        self.__stop = True

    def train(self, num_epochs: int, event_handler: Callable,
              reader=None, feed_order=None, steps_per_loop: int = 1):
        """Run the train loop: reader yields batches (lists of tuples in
        feed_order), event_handler receives Begin/End Epoch/Step events.

        steps_per_loop > 1 runs windows of that many batches as ONE
        device-side XLA loop (Executor.run_loop) — the TPU-estimator
        "iterations_per_loop" pattern: per-step host round trips disappear,
        and Begin/EndStepEvent fire once per WINDOW (step_id advances by
        the window size; EndStepEvent metrics are the last step's). A
        short final window (epoch tail) runs with its own length."""
        if event_handler is None:
            event_handler = lambda ev: None  # noqa: E731
        if steps_per_loop < 1:
            raise ValueError("steps_per_loop must be >= 1, got %d"
                             % steps_per_loop)
        feed_var_list = build_feed_var_list(self.train_program, feed_order)
        feeder = DataFeeder(feed_list=feed_var_list, place=self.place)
        start_epoch = (self.checkpoint_cfg.epoch_id
                       if self.checkpoint_cfg else 0)

        with scope_guard(self.scope):
            for epoch_id in range(start_epoch, num_epochs):
                event_handler(BeginEpochEvent(epoch_id))
                for step_id, feeds in _feed_windows(feeder, reader(),
                                                    steps_per_loop):
                    if self.__stop:
                        if self.checkpoint_cfg:
                            self._clean_checkpoint()
                        return
                    begin_event = BeginStepEvent(epoch_id, step_id)
                    event_handler(begin_event)
                    fetch_list = (
                        [v.name for v in self.train_func_outputs]
                        if begin_event.fetch_metrics else [])
                    metrics = self._run_window(feeds, fetch_list)
                    if self.checkpoint_cfg:
                        self._save_checkpoint(epoch_id, step_id)
                    event_handler(EndStepEvent(epoch_id, step_id, metrics))
                event_handler(EndEpochEvent(epoch_id))
            if self.checkpoint_cfg:
                self._clean_checkpoint()

    def _run_window(self, feeds, fetch_list):
        """Dispatch one window of feed dicts: single step, parallel
        stepwise, or a fused run_loop window (train()'s inner body,
        shared with fit())."""
        exe = self._train_exe
        if len(feeds) == 1:
            if exe is not None:
                return exe.run(feed=feeds[0], fetch_list=fetch_list)
            return self._exe.run(self.train_program, feed=feeds[0],
                                 fetch_list=fetch_list)
        if exe is not None:
            # ParallelExecutor.run_loop has no per-step feed support
            # yet: run the window stepwise (identical numerics, no
            # device-loop speedup)
            for feed in feeds[:-1]:
                exe.run(feed=feed, fetch_list=[])
            return exe.run(feed=feeds[-1], fetch_list=fetch_list)
        names = list(feeds[0])
        stacked = {n: np.stack([np.asarray(f[n]) for f in feeds])
                   for n in names}
        return self._exe.run_loop(
            self.train_program, feed=stacked, fetch_list=fetch_list,
            steps=len(feeds), per_step_feeds=names)

    def fit(self, num_epochs: int, event_handler: Callable = None,
            reader=None, feed_order=None, steps_per_loop: int = 1,
            resumable: bool = True):
        """Elastic, preemption-proof train loop (same reader/event
        contract as train()):

        - checkpoints are ASYNC — every ``step_interval`` batches (and
          at every epoch boundary) a snapshot of the persistables +
          optimizer state is queued to a background writer
          (checkpoint.CheckpointManager) with at most
          ``CheckpointConfig.max_pending`` in flight, so the step loop
          never waits on disk unless the writer falls that far behind;
        - writes are crash-safe (tmp + fsync + atomic rename +
          ``_COMPLETE`` sentinel): a SIGKILL at ANY instant — including
          mid-checkpoint-write — cannot corrupt the newest checkpoint;
        - with ``resumable=True`` a restart loads the newest COMPLETE
          checkpoint and continues SAMPLE-EXACT: epoch, batch offset
          (already-trained batches of the resumed epoch are skipped,
          never retrained), and the per-program RNG stream all restore,
          so the loss trajectory continues bit-exact vs an
          uninterrupted run;
        - unlike train(), checkpoints are KEPT on completion (the
          elastic contract: re-running a finished fit is a no-op
          resume, and sweeps can always warm-start).

        Requires a ``checkpoint_config``. Warm process restarts also
        reuse compiled executables through the persistent AOT cache, so
        time-to-first-step after preemption is seconds, not a compile.
        """
        if self.checkpoint_cfg is None:
            raise ValueError(
                "fit() checkpoints through CheckpointConfig — construct "
                "the Trainer with checkpoint_config=CheckpointConfig(...)")
        if event_handler is None:
            event_handler = lambda ev: None  # noqa: E731
        if steps_per_loop < 1:
            raise ValueError("steps_per_loop must be >= 1, got %d"
                             % steps_per_loop)
        cfg = self.checkpoint_cfg
        feed_var_list = build_feed_var_list(self.train_program, feed_order)
        feeder = DataFeeder(feed_list=feed_var_list, place=self.place)
        manager = CheckpointManager(
            cfg.checkpoint_dir,
            max_num_checkpoints=cfg.max_num_checkpoints,
            max_pending=cfg.max_pending)
        start_epoch = start_offset = global_step = 0
        # the executor whose RNG step fold actually advances during
        # training: the ParallelExecutor when parallel=True (it keeps
        # its own counter), else the plain Executor
        rng_exe = self._train_exe if self._train_exe is not None \
            else self._exe

        def save(epoch_id, offset, gstep):
            arrays = manager.snapshot(self.train_program, self.scope)
            meta = build_meta(
                self.train_program, rng_exe, epoch=epoch_id,
                offset=offset, global_step=gstep,
                # legacy keys so load_checkpoint-driven loops resume too
                extra={"step": gstep, "trainer_id": self.trainer_id})
            manager.save(arrays, meta)

        with scope_guard(self.scope):
            if resumable:
                if (self._restored_meta is not None
                        and manager.latest() == self._restored_serial):
                    # __init__ already loaded this exact serial into the
                    # scope (and checked its fingerprint): reuse it
                    # instead of re-reading + re-transferring the model
                    meta = self._restored_meta
                else:
                    meta = manager.restore_into(self.scope)
                    if meta is not None:
                        check_fingerprint(meta, self.train_program)
                if meta is not None:
                    start_epoch = int(meta.get("epoch", 0))
                    start_offset = int(meta.get("offset", 0))
                    global_step = int(meta.get("global_step", 0))
                    rng_step = meta.get("rng_step")
                    if rng_step is not None:
                        rng_exe.set_program_steps(self.train_program,
                                                  int(rng_step))
            try:
                for epoch_id in range(start_epoch, num_epochs):
                    event_handler(BeginEpochEvent(epoch_id))
                    offset = (start_offset if epoch_id == start_epoch
                              else 0)
                    batch_it = reader()
                    if offset:
                        # sample-exact: the restored checkpoint already
                        # trained these batches — skip, never retrain
                        batch_it = itertools.islice(batch_it, offset,
                                                    None)
                    for step_id, feeds in _feed_windows(
                            feeder, batch_it, steps_per_loop,
                            start_step=offset):
                        begin_event = BeginStepEvent(epoch_id, step_id)
                        event_handler(begin_event)
                        fetch_list = (
                            [v.name for v in self.train_func_outputs]
                            if begin_event.fetch_metrics else [])
                        metrics = self._run_window(feeds, fetch_list)
                        before = global_step // cfg.step_interval
                        offset += len(feeds)
                        global_step += len(feeds)
                        if global_step // cfg.step_interval != before:
                            save(epoch_id, offset, global_step)
                        event_handler(EndStepEvent(epoch_id, step_id,
                                                   metrics))
                        if self.__stop:
                            save(epoch_id, offset, global_step)
                            return
                    # epoch boundary: a restart never replays this epoch
                    save(epoch_id + 1, 0, global_step)
                    event_handler(EndEpochEvent(epoch_id))
            finally:
                manager.close()  # drain: every queued snapshot lands

    def test(self, reader, feed_order=None):
        """Average the train_func outputs over the reader on the test
        (for_test clone) program."""
        feed_var_list = build_feed_var_list(self.test_program, feed_order)
        feeder = DataFeeder(feed_list=feed_var_list, place=self.place)
        fetch = [v.name for v in self.train_func_outputs]
        accumulated = [0.0] * len(fetch)
        count = 0
        with scope_guard(self.scope):
            for data in reader():
                outs = self._exe.run(self.test_program,
                                     feed=feeder.feed(data), fetch_list=fetch)
                accumulated = [a + float(o.reshape(-1)[0] if hasattr(o, "reshape") else o)
                               for a, o in zip(accumulated, outs)]
                count += 1
        return [a / max(count, 1) for a in accumulated]

    def save_params(self, param_path):
        with scope_guard(self.scope):
            io_mod.save_persistables(self._exe, param_path,
                                     main_program=self.train_program)

    def save_inference_model(self, param_path, feeded_var_names,
                             target_var_indexes):
        with scope_guard(self.scope):
            io_mod.save_inference_model(
                param_path, feeded_var_names,
                [self.train_func_outputs[i] for i in target_var_indexes],
                self._exe, main_program=self.train_program)

    # -- checkpoints -----------------------------------------------------
    def _save_checkpoint(self, epoch_id, step_id):
        cfg = self.checkpoint_cfg
        if epoch_id % cfg.epoch_interval or step_id % cfg.step_interval:
            return
        io_mod.save_checkpoint(
            self._exe, cfg.checkpoint_dir, trainer_id=self.trainer_id,
            main_program=self.train_program,
            max_num_checkpoints=cfg.max_num_checkpoints,
            step=step_id, epoch=epoch_id)

    def _clean_checkpoint(self):
        io_mod.clean_checkpoint(self.checkpoint_cfg.checkpoint_dir)


class Inferencer(object):
    """reference inferencer.py:Inferencer — build infer_func's graph, load
    params from param_path, run the for_test program."""

    def __init__(self, infer_func: Callable, param_path: str, place=None,
                 parallel: bool = False):
        self.param_path = param_path
        self.scope = Scope()
        self.parallel = parallel
        self.place = check_and_get_place(place)

        self.inference_program = Program()
        startup = Program()
        with program_guard(self.inference_program, startup):
            with unique_name.guard():
                self.predict_var = infer_func()

        self.exe = Executor(self.place)
        with scope_guard(self.scope):
            self.exe.run(startup)
            io_mod.load_params(self.exe, param_path,
                               main_program=self.inference_program)
        self.inference_program = self.inference_program.clone(for_test=True)

    def infer(self, inputs: dict, return_numpy: bool = True):
        if not isinstance(inputs, dict):
            raise ValueError(
                "inputs should be a map of {'input_name': input_var}")
        with scope_guard(self.scope):
            results = self.exe.run(
                self.inference_program, feed=inputs,
                fetch_list=[self.predict_var.name],
                return_numpy=return_numpy)
        return results
