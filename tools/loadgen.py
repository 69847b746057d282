"""Traffic generator + SLO verdict for the serving fleet (loadgen/2).

The fleet's latency contract is only as real as the traffic it was
proven under. This tool generates that traffic against a live Router —
open-loop (Poisson arrivals at a target rate: the millions-of-users
shape, where clients do NOT slow down because the fleet did) and
closed-loop (N clients back to back: the benchmark shape) — through
diurnal ramps, bursts, and heavy-tail per-arrival fan-out, with every
request submitted under an SLO class (priority + deadline). It records
per-class latency percentiles, every structured shed reject, and the
fleet counters, and emits ONE JSON verdict line per run (schema
``loadgen/2``; ``--curve`` sweeps offered load and emits one line per
level — the latency-vs-offered-load curve).

loadgen/2 adds ``trace_phases``: per-phase p50/p99 latency attribution
pulled from the distributed-tracing flight recorder
(``router.fleet_trace()``), keyed by span name (router.queue,
server.device, worker.reply, ...). It is ``{}`` unless sampling is
armed (``--trace-sample`` / ``PADDLE_TPU_TRACE_SAMPLE``) — the verdict
costs nothing when tracing is off. All loadgen/1 fields are unchanged.

Traffic is scripted: ``--shape steady|burst|diurnal`` builds a trace,
``--trace FILE`` loads one:

    {"name": "evening-burst",
     "classes": {"interactive": {"priority": 0, "deadline_ms": 500,
                                 "weight": 0.8},
                 "batch": {"priority": 2, "weight": 0.2}},
     "phases": [
       {"duration_s": 2.0, "rps": 50, "mode": "open"},
       {"duration_s": 1.0, "rps": 250, "mode": "open",
        "fanout": {"dist": "pareto", "alpha": 1.4, "max": 16}},
       {"duration_s": 2.0, "rps": 50, "mode": "open"}]}

Chaos riders: ``--chaos-kill T`` SIGKILLs a random ready replica T
seconds into the trace (the PR-8 crash-requeue path must absorb it);
``--autoscale MIN:MAX`` runs the Autoscaler so the trace drives real
scale-up/drain-shrink. The verdict is strict: ``ok`` requires zero
dropped futures (every request answered — result OR explicit reject),
zero non-reject errors, and zero misversioned responses.

Usage:
    JAX_PLATFORMS=cpu python tools/loadgen.py --model-dir DIR \
        --shape burst --rps 100 --duration 6 --replicas 2 \
        --autoscale 1:3 --chaos-kill 3 --json
"""
from __future__ import annotations

import argparse
import json
import math
import os
import random
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCHEMA = "loadgen/2"

DEFAULT_CLASSES = {
    "interactive": {"priority": 0, "deadline_ms": None, "weight": 0.7},
    "batch": {"priority": 2, "deadline_ms": None, "weight": 0.3},
}


# -- traces ----------------------------------------------------------------

def build_shape(shape: str, rps: float, duration_s: float,
                burst_x: float = 4.0, clients: int = 4,
                mode: str = "open", diurnal_slices: int = 8) -> Dict:
    """A scripted trace from a named shape. ``steady`` = one flat phase;
    ``burst`` = baseline, a ``burst_x`` Poisson burst in the middle
    fifth, baseline again; ``diurnal`` = a sinusoidal ramp approximated
    by ``diurnal_slices`` flat slices (peak = ``rps``, trough =
    rps/4)."""
    phases: List[Dict]
    if shape == "steady":
        phases = [{"duration_s": duration_s, "rps": rps, "mode": mode,
                   "clients": clients}]
    elif shape == "burst":
        edge = duration_s * 0.4
        phases = [
            {"duration_s": edge, "rps": rps, "mode": mode,
             "clients": clients},
            {"duration_s": duration_s - 2 * edge, "rps": rps * burst_x,
             "mode": mode, "clients": clients,
             "fanout": {"dist": "pareto", "alpha": 1.4, "max": 16}},
            {"duration_s": edge, "rps": rps, "mode": mode,
             "clients": clients},
        ]
    elif shape == "diurnal":
        phases = []
        for i in range(diurnal_slices):
            # peak at mid-trace; trough = peak/4
            frac = 0.5 - 0.5 * math.cos(2 * math.pi * (i + 0.5)
                                        / diurnal_slices)
            phases.append({"duration_s": duration_s / diurnal_slices,
                           "rps": rps * (0.25 + 0.75 * frac),
                           "mode": mode, "clients": clients})
    else:
        raise ValueError("unknown shape %r (steady|burst|diurnal)" % shape)
    return {"name": shape, "classes": dict(DEFAULT_CLASSES),
            "phases": phases}


def load_trace(path: str) -> Dict:
    with open(path) as f:
        trace = json.load(f)
    if not isinstance(trace.get("phases"), list) or not trace["phases"]:
        raise ValueError("trace %s: 'phases' must be a non-empty list"
                         % path)
    for i, ph in enumerate(trace["phases"]):
        if "duration_s" not in ph:
            raise ValueError("trace %s: phase %d has no duration_s"
                             % (path, i))
    trace.setdefault("name", os.path.basename(path))
    trace.setdefault("classes", dict(DEFAULT_CLASSES))
    return trace


def slo_classes_of(trace: Dict):
    """Router slo_classes built from the trace's class table."""
    from paddle_tpu.serving import SLOClass

    out = {}
    for name, cfg in trace["classes"].items():
        out[name] = SLOClass(name, int(cfg.get("priority", 1)),
                             cfg.get("deadline_ms"))
    out.setdefault("standard", SLOClass("standard", 1))
    return out


# -- recording -------------------------------------------------------------

class _Recorder:
    """Thread-safe per-class outcome ledger fed by done callbacks."""

    def __init__(self, classes):
        self._lock = threading.Lock()
        self._done_ev = threading.Event()
        self.offered = 0
        self.completed = 0
        self.lat: Dict[str, List[float]] = {k: [] for k in classes}
        self.rejected: Dict[str, int] = {k: 0 for k in classes}
        self.errors: Dict[str, int] = {k: 0 for k in classes}

    def submitted(self, klass: str):
        with self._lock:
            self.offered += 1
            self.lat.setdefault(klass, [])
            self.rejected.setdefault(klass, 0)
            self.errors.setdefault(klass, 0)

    def done(self, klass: str, t0: float, fut):
        from paddle_tpu.serving import RejectedError

        try:
            fut.result(timeout=0)
            status = "ok"
        except RejectedError:
            status = "rejected"
        except Exception:
            status = "error"
        with self._lock:
            self.completed += 1
            if status == "ok":
                self.lat[klass].append((time.perf_counter() - t0) * 1e3)
            elif status == "rejected":
                self.rejected[klass] += 1
            else:
                self.errors[klass] += 1
            if self.completed >= self.offered:
                self._done_ev.set()

    def wait_all(self, timeout: float) -> int:
        """Block until every offered request completed (result OR
        reject); returns the number still unanswered — MUST be 0, a
        nonzero value is the hang the shedding contract forbids."""
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                remaining = self.offered - self.completed
                if remaining == 0:
                    return 0
                self._done_ev.clear()
            left = deadline - time.monotonic()
            if left <= 0:
                return remaining
            self._done_ev.wait(min(left, 0.5))


def _pctl(xs: List[float], q: float) -> Optional[float]:
    if not xs:
        return None
    xs = sorted(xs)
    i = min(len(xs) - 1, int(math.ceil(q / 100.0 * len(xs))) - 1)
    return xs[max(0, i)]


# -- request sources -------------------------------------------------------

def dense_sampler(model_dir: str, seed: int = 0, pool: int = 64):
    """(prime the AOT cache, return a sample factory) for a dense model:
    random rows matching the exported feed signature. The direct
    Predictor run here is what makes every fleet worker warm-start."""
    import numpy as np

    from paddle_tpu.inference import Predictor

    p = Predictor(model_dir)
    rs = np.random.RandomState(seed)
    block = p._program.global_block()
    rows = []
    for _ in range(pool):
        sample = []
        for name in p.feed_names:
            var = block.var(name)
            shape = tuple(int(d) for d in var.shape[1:])
            dt = np.dtype(var.dtype)
            if dt.kind in "iu":
                sample.append(rs.randint(0, 8, size=shape).astype(dt))
            else:
                sample.append(rs.uniform(-1, 1, size=shape).astype(dt))
        rows.append(tuple(sample))
    p.run({n: np.stack([r[i] for r in rows[:4]])
           for i, n in enumerate(p.feed_names)})
    idx = [0]

    def next_sample():
        idx[0] = (idx[0] + 1) % pool
        return rows[idx[0]]

    return next_sample


def decode_sampler(vocab: int = 100, max_prompt: int = 24, seed: int = 0,
                   alpha: float = 1.3):
    """Heavy-tail prompt lengths (bounded Pareto) for decode traffic —
    the request-SIZE tail that makes continuous batching earn its
    keep."""
    import numpy as np

    rs = np.random.RandomState(seed)

    def next_sample():
        n = min(max_prompt, max(1, int(rs.pareto(alpha) + 1)))
        return (rs.randint(1, vocab, size=(n,)).astype(np.int32),)

    return next_sample


# -- the trace runner ------------------------------------------------------

def run_trace(router, trace: Dict, next_sample: Callable, seed: int = 0,
              result_timeout: float = 120.0,
              samplers: Optional[Dict[str, Callable]] = None) -> Dict:
    """Drive `trace` through `router.submit` and return the loadgen/1
    report. ``samplers`` optionally maps a class name to its own sample
    factory (e.g. decode-class prompts vs dense rows); everything else
    uses ``next_sample``."""
    from paddle_tpu import observability as obs

    classes = trace["classes"]
    names = sorted(classes)
    weights = [float(classes[k].get("weight", 1.0)) for k in names]
    rec = _Recorder(names)
    rng = random.Random(seed)
    samplers = samplers or {}

    def submit_one(klass: str):
        cfg = classes[klass]
        sample = samplers.get(klass, next_sample)()
        rec.submitted(klass)
        t0 = time.perf_counter()
        try:
            fut = router.submit(
                sample, slo=klass,
                deadline_ms=cfg.get("deadline_ms"),
                priority=cfg.get("priority"))
        except Exception:
            with rec._lock:
                rec.errors[klass] += 1
                rec.completed += 1
            return
        fut.add_done_callback(
            lambda f, k=klass, t=t0: rec.done(k, t, f))

    def draw_class() -> str:
        return rng.choices(names, weights=weights)[0]

    def draw_fanout(ph: Dict) -> int:
        fo = ph.get("fanout")
        if not fo or fo.get("dist", "fixed") == "fixed":
            return int((fo or {}).get("n", 1))
        k = int(rng.paretovariate(float(fo.get("alpha", 1.4))))
        return max(1, min(int(fo.get("max", 16)), k))

    mis0 = obs.FLEET_MISVERSIONED.total()
    shed0 = obs.FLEET_SHED.total()
    req0 = obs.FLEET_REQUEUED.total()
    replicas0 = router.stats()["ready"]
    phase_stats = []
    t_start = time.perf_counter()
    for ph in trace["phases"]:
        ph_offered0 = rec.offered
        dur = float(ph["duration_s"])
        mode = ph.get("mode", "open")
        end = time.perf_counter() + dur
        if mode == "closed":
            stop_ev = threading.Event()

            def client():
                while not stop_ev.is_set():
                    cfg_k = draw_class()
                    sample = samplers.get(cfg_k, next_sample)()
                    rec.submitted(cfg_k)
                    t0 = time.perf_counter()
                    try:
                        fut = router.submit(
                            sample, slo=cfg_k,
                            deadline_ms=classes[cfg_k].get("deadline_ms"),
                            priority=classes[cfg_k].get("priority"))
                        rec.done(cfg_k, t0, _waited(fut, result_timeout))
                    except Exception:
                        with rec._lock:
                            rec.errors[cfg_k] += 1
                            rec.completed += 1

            threads = [threading.Thread(target=client, daemon=True)
                       for _ in range(int(ph.get("clients", 4)))]
            for t in threads:
                t.start()
            time.sleep(dur)
            stop_ev.set()
            for t in threads:
                t.join(timeout=result_timeout)
        else:  # open loop: Poisson arrivals at ph["rps"]
            rps = float(ph.get("rps", 10.0))
            next_t = time.perf_counter()
            while True:
                now = time.perf_counter()
                if now >= end:
                    break
                next_t += rng.expovariate(rps) if rps > 0 else dur
                delay = next_t - time.perf_counter()
                if delay > 0:
                    time.sleep(min(delay, end - now))
                    if time.perf_counter() >= end:
                        break
                for _ in range(draw_fanout(ph)):
                    submit_one(draw_class())
        phase_stats.append({"mode": mode, "rps": ph.get("rps"),
                            "duration_s": dur,
                            "offered": rec.offered - ph_offered0})
    dropped = rec.wait_all(result_timeout)
    wall_s = time.perf_counter() - t_start

    per_class = {}
    for k in sorted(rec.lat):
        lats = rec.lat[k]
        dl = classes.get(k, {}).get("deadline_ms")
        met = (None if dl is None or not lats
               else sum(1 for x in lats if x <= dl) / len(lats))
        per_class[k] = {
            "count": len(lats) + rec.rejected[k] + rec.errors[k],
            "ok": len(lats),
            "rejected": rec.rejected[k],
            "errors": rec.errors[k],
            "p50_ms": _pctl(lats, 50), "p90_ms": _pctl(lats, 90),
            "p99_ms": _pctl(lats, 99),
            "mean_ms": (sum(lats) / len(lats)) if lats else None,
            "deadline_ms": dl, "deadline_met_frac": met,
        }
    st = router.stats()
    report = {
        "schema": SCHEMA,
        "trace": trace.get("name", "trace"),
        "duration_s": round(wall_s, 3),
        "offered": rec.offered,
        "completed": rec.completed,
        "rejected": sum(rec.rejected.values()),
        "errors": sum(rec.errors.values()),
        "dropped": dropped,
        "achieved_rps": round(rec.offered / wall_s, 2) if wall_s else 0.0,
        "per_class": per_class,
        "phases": phase_stats,
        "fleet": {
            "replicas_start": replicas0,
            "replicas_end": st["ready"],
            "shed_total": obs.FLEET_SHED.total() - shed0,
            "requeued": obs.FLEET_REQUEUED.total() - req0,
            "misversioned": obs.FLEET_MISVERSIONED.total() - mis0,
        },
        "ok": (dropped == 0 and sum(rec.errors.values()) == 0
               and obs.FLEET_MISVERSIONED.total() - mis0 == 0),
    }
    # a shed that was never surfaced as a reject would be a silent drop:
    # the shed counter and the rejects the clients saw must agree
    report["sheds_all_rejected"] = (
        report["fleet"]["shed_total"] == report["rejected"])
    # loadgen/2: per-phase latency attribution from the fleet's trace
    # recorders — WHERE the p99 went (queue vs device vs stacking), not
    # just how big it was. Empty unless sampling is armed.
    phase_ms: Dict[str, List[float]] = {}
    fleet_trace = getattr(router, "fleet_trace", None)
    if fleet_trace is not None:
        try:
            for s in fleet_trace(timeout=10.0).get("spans", ()):
                phase_ms.setdefault(s["name"], []).append(
                    float(s.get("dur_ms", 0.0)))
        except Exception:
            pass
    report["trace_phases"] = {
        name: {"count": len(xs),
               "p50_ms": _pctl(xs, 50), "p99_ms": _pctl(xs, 99)}
        for name, xs in sorted(phase_ms.items())}
    return report


def _waited(fut, timeout):
    """Closed-loop helper: wait the future out, hand it back completed
    (Recorder.done re-reads the result with timeout=0)."""
    try:
        fut.result(timeout=timeout)
    except Exception:
        pass
    return fut


def chaos_kill_after(router, delay_s: float) -> threading.Timer:
    """Arm a SIGKILL of a random ready replica `delay_s` seconds from
    now (the mid-burst preemption the crash-requeue path must absorb)."""
    def kill():
        with router._cond:
            ready = [w for w in router._workers if w.state == "ready"]
        if ready:
            victim = random.choice(ready)
            victim.proc.kill()
            sys.stderr.write("[loadgen] chaos: SIGKILLed %s\n"
                             % victim.name)
    t = threading.Timer(delay_s, kill)
    t.daemon = True
    t.start()
    return t


# -- CLI -------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model-dir", required=True)
    ap.add_argument("--trace", help="scripted trace JSON file")
    ap.add_argument("--shape", default="steady",
                    choices=("steady", "burst", "diurnal"))
    ap.add_argument("--rps", type=float, default=50.0)
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--burst-x", type=float, default=4.0)
    ap.add_argument("--mode", default="open", choices=("open", "closed"))
    ap.add_argument("--clients", type=int, default=4,
                    help="closed-loop clients per phase")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="arm this deadline on the interactive class")
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-outstanding", type=int, default=None)
    ap.add_argument("--decode", action="store_true",
                    help="decode fleet: heavy-tail prompts through "
                         "Router(decode=True)")
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--autoscale", metavar="MIN:MAX",
                    help="run the Autoscaler across the trace")
    ap.add_argument("--chaos-kill", type=float, default=None,
                    metavar="T", help="SIGKILL a random replica T "
                    "seconds into the trace")
    ap.add_argument("--curve", metavar="RPS,RPS,...",
                    help="sweep offered load, one loadgen/1 line per "
                         "level (the latency-vs-offered-load curve)")
    ap.add_argument("--trace-sample", type=float, default=None,
                    metavar="RATE", help="arm distributed tracing at "
                    "this sample rate (0..1); fills the verdict's "
                    "trace_phases attribution")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--result-timeout", type=float, default=120.0)
    ap.add_argument("--start-timeout", type=float, default=300.0)
    ap.add_argument("--json", action="store_true",
                    help="emit ONLY the JSON verdict line(s)")
    args = ap.parse_args()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    if args.trace:
        trace = load_trace(args.trace)
    else:
        trace = build_shape(args.shape, args.rps, args.duration,
                            burst_x=args.burst_x, clients=args.clients,
                            mode=args.mode)
    if args.deadline_ms is not None:
        trace["classes"].setdefault("interactive", {"priority": 0,
                                                    "weight": 0.7})
        trace["classes"]["interactive"]["deadline_ms"] = args.deadline_ms

    from paddle_tpu.serving import Autoscaler, Router

    if args.trace_sample is not None:
        # the ONE sampling decision lives at the client edge (here);
        # workers record on header arrival and need no configuration
        from paddle_tpu.observability import tracing
        tracing.set_sample_rate(args.trace_sample)

    levels = ([float(x) for x in args.curve.split(",")] if args.curve
              else [None])
    for level in levels:
        t = json.loads(json.dumps(trace))  # deep copy per level
        if level is not None:
            for ph in t["phases"]:
                if "rps" in ph and ph["rps"]:
                    ph["rps"] = level
            t["name"] = "%s@%g" % (t["name"], level)
        router = Router(
            args.model_dir, replicas=args.replicas,
            max_batch=args.max_batch,
            max_outstanding=args.max_outstanding,
            jax_platform=os.environ.get("JAX_PLATFORMS") or None,
            start_timeout=args.start_timeout,
            decode=args.decode,
            max_new_tokens=args.max_new_tokens,
            slo_classes=slo_classes_of(t))
        if args.decode:
            next_sample = decode_sampler(seed=args.seed)
        else:
            next_sample = dense_sampler(args.model_dir, seed=args.seed)
        router.start()
        scaler = None
        timer = None
        try:
            if args.autoscale:
                lo, hi = (int(x) for x in args.autoscale.split(":"))
                scaler = Autoscaler(router, min_replicas=lo,
                                    max_replicas=hi, interval_s=0.5,
                                    cooldown_s=2.0, down_ticks=4,
                                    spawn_timeout=args.start_timeout)
                scaler.start()
            if args.chaos_kill is not None:
                timer = chaos_kill_after(router, args.chaos_kill)
            report = run_trace(router, t, next_sample, seed=args.seed,
                               result_timeout=args.result_timeout)
            if level is not None:
                report["offered_rps_target"] = level
            if scaler is not None:
                ups = sum(1 for _t, d in scaler.actions if d == "up")
                downs = sum(1 for _t, d in scaler.actions if d == "down")
                heals = sum(1 for _t, d in scaler.actions if d == "heal")
                report["fleet"]["autoscale"] = {
                    "up": ups, "down": downs, "heal": heals}
            print(json.dumps(report, sort_keys=True))
        finally:
            if timer is not None:
                timer.cancel()
            if scaler is not None:
                scaler.stop()
            router.stop()
        if not args.json and not report.get("ok"):
            sys.stderr.write("[loadgen] verdict NOT ok: dropped=%s "
                             "errors=%s misversioned=%s\n"
                             % (report["dropped"], report["errors"],
                                report["fleet"]["misversioned"]))
            sys.exit(1)


if __name__ == "__main__":
    main()
