"""Chaos harness: SIGKILL training mid-epoch (and mid-checkpoint-write)
and prove the resume contract.

Two subcommand-ish modes:

``--role run`` — one training process: a small deterministic MLP
regression driven by a ResumableLoop + inline DataLoader, emitting one
JSON line per trained step to ``--ledger`` (appended, flushed):

    {"event": "step", "epoch": E, "offset": K, "global": G,
     "loss": <repr float>, "loss_hex": <bit-exact>, "ids": [...]}

plus a ``start`` line carrying what (if anything) it resumed from and a
``done`` line on clean completion. ``--die-after-step N`` SIGKILLs the
process itself right after global step N (a preemption mid-epoch, no
cleanup, async checkpoint writer included); arming
``PADDLE_TPU_FAULT_KILL=ckpt.before_rename`` (etc., checkpoint/faults)
kills it INSIDE the checkpoint writer instead — mid-write.

default (orchestrator) — runs the full chaos experiment and prints a
verdict JSON line per scenario (schema ``chaos_train/1``):

1. control: uninterrupted run, ledger C.
2. victim: same config, killed (mid-epoch SIGKILL, and/or mid-
   checkpoint-write via --kill-point), ledger V1.
3. resume: fresh process, same checkpoint dir; restores the newest
   COMPLETE checkpoint, ledger V2.
4. checks: (a) the resume actually loaded a checkpoint and partials
   were invisible; (b) the effective trajectory — V1 truncated to the
   restored global step, then V2 — matches C BIT-exactly (loss_hex);
   (c) the effective sample-id ledger equals C's: no sample duplicated
   or dropped across the restart.

Usage:
    python tools/chaos_train.py [--scenario sigkill|midwrite|both]
        [--epochs 2] [--batches 8] [--batch 4] [--step-interval 2]
        [--die-after-step 11] [--dim 8] [--workers 0]

tests/test_chaos_train.py runs the small config in tier-1 (fast
variant) and a larger randomized one under ``-m slow``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

SCHEMA = "chaos_train/1"


# ---------------------------------------------------------------------------
# the training process (--role run)
# ---------------------------------------------------------------------------


def _emit(ledger, obj):
    ledger.write(json.dumps(obj) + "\n")
    ledger.flush()
    os.fsync(ledger.fileno())


class _Source:
    """Deterministic sample source: sample i is a fixed function of i,
    so every process (control, victim, resume) sees byte-identical
    batches, and the sample id rides along as its own column for the
    ledger. Module-level class: picklable for DataLoader worker
    processes (--workers > 0)."""

    def __init__(self, n_samples, dim):
        self.n_samples, self.dim = n_samples, dim

    def __call__(self):
        import numpy as np

        for i in range(self.n_samples):
            rs = np.random.RandomState(1000 + i)
            x = rs.randn(self.dim).astype(np.float32)
            y = np.array([x.sum() * 0.5 + 0.1], np.float32)
            yield (np.array([i], np.int64), x, y)


def _run(args):
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.checkpoint import ResumableLoop
    from paddle_tpu.io.dataloader import DataLoader
    from paddle_tpu.io.reader import EOFException

    dim, batch, batches = args.dim, args.batch, args.batches
    source = _Source(batches * batch, dim)

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope), fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = layers.data(name="x", shape=[dim])
            y = layers.data(name="y", shape=[1])
            h = layers.fc(x, 16, act="relu")
            pred = layers.fc(h, 1)
            loss = layers.mean(layers.square_error_cost(input=pred,
                                                        label=y))
            fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)

    loader = DataLoader(["sid", "x", "y"],
                        shapes=[[1], [dim], [1]],
                        dtypes=["int64", "float32", "float32"],
                        num_workers=args.workers)
    loader.decorate_sample_reader(source, batch_size=batch,
                                  drop_last=True)

    ledger = open(args.ledger, "a")
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        loop = ResumableLoop(exe, main, args.ckpt_dir, scope=scope,
                             loader=loader,
                             step_interval=args.step_interval,
                             max_pending=2)
        _emit(ledger, {
            "event": "start", "pid": os.getpid(),
            "resumed": ({"serial": loop.resumed_meta.get("_serial"),
                         "epoch": loop.epoch, "offset": loop.offset,
                         "global": loop.global_step}
                        if loop.resumed_meta else None)})
        try:
            for _epoch in loop.epochs(args.epochs):
                loader.start()
                while True:
                    try:
                        feed = loader.next()
                    except EOFException:
                        break
                    ids = [int(v) for v in
                           np.asarray(feed.pop("sid")).ravel()]
                    (lv,) = exe.run(main, feed=feed, fetch_list=[loss])
                    lv = float(np.asarray(lv).ravel()[0])
                    loop.step_done()
                    _emit(ledger, {
                        "event": "step", "epoch": loop.epoch,
                        "offset": loop.offset,
                        "global": loop.global_step, "loss": lv,
                        "loss_hex": float(lv).hex(), "ids": ids})
                    if args.die_after_step == loop.global_step:
                        os.kill(os.getpid(), signal.SIGKILL)
                loop.end_epoch()
            loop.close()
            _emit(ledger, {"event": "done", "global": loop.global_step})
        finally:
            loader.close()
    ledger.close()


# ---------------------------------------------------------------------------
# the orchestrator
# ---------------------------------------------------------------------------


def _spawn(args, ckpt_dir, ledger, *, die_after=0, kill_point=None,
           timeout=600):
    env = dict(os.environ, JAX_PLATFORMS=os.environ.get(
        "JAX_PLATFORMS", "cpu"))
    env.pop("PADDLE_TPU_FAULT_KILL", None)
    if kill_point:
        env["PADDLE_TPU_FAULT_KILL"] = kill_point
    cmd = [sys.executable, os.path.abspath(__file__), "--role", "run",
           "--ckpt-dir", ckpt_dir, "--ledger", ledger,
           "--epochs", str(args.epochs), "--batches", str(args.batches),
           "--batch", str(args.batch), "--dim", str(args.dim),
           "--step-interval", str(args.step_interval),
           "--workers", str(args.workers),
           "--die-after-step", str(die_after)]
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=_REPO)
    return proc, time.perf_counter() - t0


def _read_ledger(path):
    events = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    events.append(json.loads(line))
    return events


def _steps(events):
    return [e for e in events if e.get("event") == "step"]


def _effective(v1_steps, v2_start, v2_steps):
    """The training history that counts after a restart: everything the
    killed run trained UP TO the restored checkpoint, then everything
    the resumed run trained."""
    resumed = (v2_start or {}).get("resumed") or {}
    cut = int(resumed.get("global", 0))
    return [s for s in v1_steps if s["global"] <= cut] + list(v2_steps)


def _scenario(args, name, *, die_after=0, kill_point=None, control=None):
    work = tempfile.mkdtemp(prefix="ptpu-chaos-%s-" % name)
    ck = os.path.join(work, "ck")
    out = {"bench": "chaos", "schema": SCHEMA, "scenario": name,
           "epochs": args.epochs, "batches": args.batches,
           "batch": args.batch, "step_interval": args.step_interval,
           "die_after_step": die_after, "kill_point": kill_point}
    try:
        led_v1 = os.path.join(work, "v1.jsonl")
        led_v2 = os.path.join(work, "v2.jsonl")
        victim, _ = _spawn(args, ck, led_v1, die_after=die_after,
                           kill_point=kill_point)
        out["victim_rc"] = victim.returncode
        if victim.returncode == 0:
            out["verdict"] = "fail"
            out["why"] = "victim survived its own kill"
            return out
        # the kill must look like a kill, not a crash with a traceback
        out["victim_sigkill"] = victim.returncode == -signal.SIGKILL
        resume, wall = _spawn(args, ck, led_v2)
        out["resume_rc"] = resume.returncode
        out["resume_wall_s"] = round(wall, 3)
        if resume.returncode != 0:
            out["verdict"] = "fail"
            out["why"] = "resume failed: " + resume.stderr[-2000:]
            return out

        v1 = _read_ledger(led_v1)
        v2 = _read_ledger(led_v2)
        v2_start = next((e for e in v2 if e["event"] == "start"), None)
        out["resumed"] = (v2_start or {}).get("resumed")
        if not out["resumed"]:
            out["verdict"] = "fail"
            out["why"] = "resume found no complete checkpoint"
            return out

        eff = _effective(_steps(v1), v2_start, _steps(v2))
        ctl = _steps(control)
        checks = {}
        # (2) bit-exact loss-trajectory continuation
        ctl_by_g = {s["global"]: s["loss_hex"] for s in ctl}
        eff_by_g = {s["global"]: s["loss_hex"] for s in eff}
        checks["trajectory_bit_exact"] = eff_by_g == ctl_by_g
        # (3) zero duplicated / dropped samples: the effective ledger
        # equals the control's, and within every epoch no id repeats
        ctl_ids = [i for s in ctl for i in s["ids"]]
        eff_ids = [i for s in eff for i in s["ids"]]
        checks["samples_exact"] = eff_ids == ctl_ids
        by_epoch = {}
        for s in eff:
            by_epoch.setdefault(s["epoch"], []).append(s["ids"])
        checks["no_duplicates"] = all(
            len([i for ids in chunks for i in ids])
            == len({i for ids in chunks for i in ids})
            for chunks in by_epoch.values())
        checks["completed"] = any(e["event"] == "done" for e in v2)
        out["checks"] = checks
        out["steps_control"] = len(ctl)
        out["steps_effective"] = len(eff)
        out["verdict"] = "pass" if all(checks.values()) else "fail"
        if out["verdict"] == "fail":
            bad_g = sorted(g for g in set(ctl_by_g) | set(eff_by_g)
                           if ctl_by_g.get(g) != eff_by_g.get(g))[:5]
            out["why"] = "first differing global steps: %s" % bad_g
        return out
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--role", default="chaos", choices=["chaos", "run"],
                    help=argparse.SUPPRESS)
    ap.add_argument("--scenario", default="both",
                    choices=["sigkill", "midwrite", "both"])
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batches", type=int, default=8,
                    help="batches per epoch")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--dim", type=int, default=8)
    ap.add_argument("--step-interval", type=int, default=2)
    ap.add_argument("--workers", type=int, default=0,
                    help="DataLoader worker processes (0 = inline)")
    ap.add_argument("--die-after-step", type=int, default=0,
                    help="run role: SIGKILL self after this global step")
    ap.add_argument("--kill-point", default="ckpt.before_rename",
                    help="midwrite scenario: checkpoint/faults barrier "
                         "for PADDLE_TPU_FAULT_KILL")
    ap.add_argument("--ckpt-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--ledger", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.role == "run":
        _run(args)
        return

    total = args.epochs * args.batches
    die_at = args.die_after_step or (total // 2 + 1)

    # control run (shared by every scenario)
    work = tempfile.mkdtemp(prefix="ptpu-chaos-control-")
    try:
        led_c = os.path.join(work, "control.jsonl")
        ctl_proc, _ = _spawn(args, os.path.join(work, "ck"), led_c)
        if ctl_proc.returncode != 0:
            raise SystemExit("control run failed:\n"
                             + ctl_proc.stderr[-4000:])
        control = _read_ledger(led_c)
    finally:
        pass  # control ledger needed below; removed at exit

    verdicts = []
    try:
        if args.scenario in ("sigkill", "both"):
            # mid-epoch preemption: SIGKILL between steps
            verdicts.append(_scenario(args, "sigkill",
                                      die_after=die_at, control=control))
            print(json.dumps(verdicts[-1]), flush=True)
        if args.scenario in ("midwrite", "both"):
            # die INSIDE the checkpoint writer at the named barrier (the
            # 2nd save, so a complete older checkpoint exists)
            verdicts.append(_scenario(
                args, "midwrite", kill_point="%s:2" % args.kill_point,
                control=control))
            print(json.dumps(verdicts[-1]), flush=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    ok = all(v["verdict"] == "pass" for v in verdicts)
    print(json.dumps({"bench": "chaos_summary", "schema": SCHEMA,
                      "scenarios": [v["scenario"] for v in verdicts],
                      "verdict": "pass" if ok else "fail"}), flush=True)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
