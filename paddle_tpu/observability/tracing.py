"""Distributed request tracing: per-request spans + a flight recorder.

The fleet's histograms say *how slow*; a trace says *where the time
went*. A trace is a ``trace_id`` (16 hex chars, minted once at the
client edge) plus the spans every process records against it while the
request moves client -> router queue -> dispatch -> worker channel ->
stacking -> device step -> reply. The id rides the serving wire as an
optional ``b"T"`` header (``wire.pack_trace``), so a crash-requeue
re-dispatches the ORIGINAL header-carrying bytes and the trace survives
a SIGKILL for free; a bare pre-trace frame is still valid byte for
byte, and workers strip the header defensively like the SLO one.

Sampling is decided ONCE, at the client edge (``maybe_start``): the
``PADDLE_TPU_TRACE_SAMPLE`` rate (default 0.0 — tracing is OFF and the
wire is byte-identical to the pre-trace form; the PR-15 tap-cost
lesson). Downstream processes never consult the rate — they record
spans iff the header arrived, which is what makes the worker side
zero-config: an un-sampled request takes the exact pre-trace code path.

Each process keeps ONE ``TraceRecorder`` of two bounded rings (the
StepTimeline pattern: O(1) append, ``dropped`` accounting per ring,
never unbounded memory).
``Router.fleet_trace()`` pulls every worker's ring over the existing
control pipe (the ``fleet_metrics()`` pattern) and merges them into a
single span list — exported at ``GET /trace.json`` and rendered by
``tools/trace_dump.py`` as a per-request text waterfall or Chrome
trace-event JSON (Perfetto-loadable).

Span timestamps are wall-clock ``time.time()`` starts: the fleet's
processes share one machine/clock, so cross-process ordering within a
trace is meaningful (to clock granularity). ``ts`` is the span START;
``dur_ms`` may be 0 for instant events.

Work that belongs to NO request (a decode server's loop iteration, a
trainer step, a legacy profiler event) is recorded through ``phase`` /
``record_process_span`` into a ring of its OWN inside the same recorder
(default 32768 records), so that at rate 1 a few thousand loop records
a minute can never evict a request's ``client.submit``. ``phase(name,
**counts)`` is a context manager: the outermost one on a thread is the
record (it makes the one sampling decision, so an iteration is traced
whole or not at all), the ones opened inside it add their self time to
that record under their own name, with the phase they were opened in
as parent. While a phase is open it is also a
``jax.profiler.TraceAnnotation("ptpu." + name, **counts)``: under any
profiler session the span and its counts land on the xplane's host
plane, on the device events' clock, which is what lets a reader lay a
phase over an idle gap of the device. The same rate gates both; at rate
0 ``phase`` returns one shared no-op object (no clock read, no lock)
and ``jax`` is never imported, so a router process that touches no
device can still import this module.

Multi-stage servers (worker recv -> PredictorServer stack -> device ->
reply) correlate through a process-local ``rid -> trace_id`` binding
table: the ingress path binds, every stage records via ``rid_span``
(a dict probe when tracing is live, one falsy check when it is not),
and the future fan-out pops. The ``paddle_tpu_trace_spans_total``
counter hook is injected by ``observability/__init__`` after instrument
registration — tracing.py itself imports nothing above ``metrics``.
"""
from __future__ import annotations

import collections
import os
import random
import threading
import time
from typing import Dict, Iterable, List, Optional

from .metrics import process_labels

__all__ = [
    "TraceRecorder", "RECORDER", "get_recorder", "new_trace_id",
    "sample_rate", "set_sample_rate", "sampled", "maybe_start",
    "record_span", "record_process_span", "phase", "current_phase",
    "bind_rid", "rid_trace", "pop_rid", "rid_span", "bound",
    "process_trace_id", "snapshot", "merge_snapshots", "reset",
]

_DEFAULT_CAP = 4096
# the process ring: ~2,300 decode iterations a minute at rate 1
_DEFAULT_PROCESS_CAP = 32768


def _env_rate() -> float:
    try:
        rate = float(os.environ.get("PADDLE_TPU_TRACE_SAMPLE", "0") or 0.0)
    except ValueError:
        return 0.0
    return min(1.0, max(0.0, rate))


class TraceRecorder:
    """Two bounded rings of span records (one recorder per process; see
    module doc): ``request`` holds the spans of traced requests,
    ``process`` the records that belong to none (loop iterations, train
    steps, profiler events). One ``seq`` orders both."""

    RINGS = ("request", "process")

    def __init__(self, capacity: Optional[int] = None,
                 process_capacity: int = _DEFAULT_PROCESS_CAP):
        if capacity is None:
            try:
                capacity = int(os.environ.get("PADDLE_TPU_TRACE_CAP",
                                              _DEFAULT_CAP))
            except ValueError:
                capacity = _DEFAULT_CAP
        self._lock = threading.Lock()
        self._rings = {
            "request": collections.deque(maxlen=max(1, capacity)),
            "process": collections.deque(maxlen=max(1, process_capacity))}
        self._recorded = dict.fromkeys(self.RINGS, 0)
        self._seq = 0  # total spans ever recorded, over both rings

    def _append(self, ring: str, span: Dict) -> None:
        with self._lock:
            span["seq"] = self._seq
            self._seq += 1
            self._recorded[ring] += 1
            self._rings[ring].append(span)
        if _SPANS_TOTAL is not None:
            _SPANS_TOTAL.inc(phase=span["name"])

    def record(self, trace_id: str, name: str, *,
               ts: Optional[float] = None, dur_ms: float = 0.0,
               **attrs) -> None:
        """Append one span of a request's trace. ``ts`` defaults to
        ``now - dur`` (the span START; callers time a phase then record
        it after the fact)."""
        self._append("request", _span(trace_id, name, ts, dur_ms, attrs))

    def record_process(self, name: str, *, ts: Optional[float] = None,
                       dur_ms: float = 0.0, **attrs) -> None:
        """Append one record that belongs to no request, under the
        process-scoped trace id, to the ring of its own."""
        self._append("process", _span(process_trace_id(), name, ts,
                                      dur_ms, attrs))

    def _all(self) -> List[Dict]:
        # caller holds the lock; each ring is seq-ordered already
        spans = [dict(s) for ring in self.RINGS for s in self._rings[ring]]
        spans.sort(key=lambda s: s["seq"])
        return spans

    def snapshot(self) -> Dict:
        """JSON-able view: the spans of both rings oldest-first plus
        ring accounting (``dropped`` = spans that aged out; the totals
        at the top level, each ring's own under ``rings``), stamped
        with this process's replica identity (empty string in an
        unlabeled process)."""
        with self._lock:
            spans = self._all()
            rings = {}
            for ring in self.RINGS:
                dq, n = self._rings[ring], self._recorded[ring]
                rings[ring] = {"capacity": dq.maxlen, "recorded": n,
                               "dropped": n - len(dq)}
            return {"capacity": self._rings["request"].maxlen,
                    "recorded": self._seq,
                    "dropped": self._seq - len(spans),
                    "rings": rings,
                    "replica": process_labels().get("replica", ""),
                    "spans": spans}

    def spans(self, trace_id: Optional[str] = None) -> List[Dict]:
        with self._lock:
            spans = self._all()
        if trace_id is not None:
            spans = [s for s in spans if s["trace_id"] == trace_id]
        return spans

    def reset(self) -> None:
        with self._lock:
            for ring in self.RINGS:
                self._rings[ring].clear()
                self._recorded[ring] = 0
            self._seq = 0


def _span(trace_id, name, ts, dur_ms, attrs) -> Dict:
    if ts is None:
        ts = time.time() - dur_ms / 1e3
    span = {"trace_id": trace_id, "name": name, "ts": ts,
            "dur_ms": round(float(dur_ms), 4)}
    if attrs:
        span.update(attrs)
    return span


RECORDER = TraceRecorder()

# paddle_tpu_trace_spans_total counter, injected by observability/__init__
# after instrument registration (avoids a circular import at load time)
_SPANS_TOTAL = None

_rate = _env_rate()
_rand = random.Random()

# rid -> trace_id for requests in flight through a multi-stage server in
# THIS process. Bounded by the server's own in-flight bound (futures are
# popped on completion/failure, and _pop hooks pop the binding too).
_rids: Dict[int, str] = {}
_rids_lock = threading.Lock()

# lazily-minted stable id for process-scoped spans (trainer steps,
# profiler events) that belong to no request
_proc_tid: Optional[str] = None


def get_recorder() -> TraceRecorder:
    return RECORDER


def new_trace_id() -> str:
    """16 hex chars of OS entropy — unique across the fleet's processes
    (a PRNG seeded identically in forked workers would collide)."""
    return os.urandom(8).hex()


def sample_rate() -> float:
    return _rate


def set_sample_rate(rate: float) -> None:
    """Runtime override of ``PADDLE_TPU_TRACE_SAMPLE`` for THIS process.
    Only processes that mint traces (clients / the router) need it —
    workers record on header arrival and never consult the rate."""
    global _rate
    _rate = min(1.0, max(0.0, float(rate)))


def sampled() -> bool:
    """One rate check with no id minting — for process-scoped spans
    (an outermost ``phase``, a trainer step) that rate-sample
    individually and record through ``record_process_span`` instead of
    a per-request trace."""
    if _rate <= 0.0:
        return False
    return _rate >= 1.0 or _rand.random() < _rate


def maybe_start() -> Optional[str]:
    """The ONE sampling decision, at the client edge: a fresh trace_id
    at the configured rate, else None (request travels untraced on the
    byte-identical pre-trace wire form)."""
    if _rate <= 0.0:
        return None
    if _rate < 1.0 and _rand.random() >= _rate:
        return None
    return new_trace_id()


def record_span(trace_id: str, name: str, *, ts: Optional[float] = None,
                dur_ms: float = 0.0, **attrs) -> None:
    RECORDER.record(trace_id, name, ts=ts, dur_ms=dur_ms, **attrs)


def process_trace_id() -> str:
    """Stable trace_id for process-scoped spans (train steps, profiler
    events) — one synthetic 'trace' per process lifetime."""
    global _proc_tid
    if _proc_tid is None:
        _proc_tid = "proc" + new_trace_id()[:12]
    return _proc_tid


def record_process_span(name: str, *, ts: Optional[float] = None,
                        dur_ms: float = 0.0, **attrs) -> None:
    """The ONE way in for spans that belong to no request: a closing
    outermost ``phase``, the trainer's ``train.step`` mirror and the
    legacy profiler's events all record here, under
    ``process_trace_id()``, into the recorder's process ring. The
    caller has made the sampling decision."""
    RECORDER.record_process(name, ts=ts, dur_ms=dur_ms, **attrs)


# -- phases (process-scoped spans on the profiler's clock) ----------------

class _NoPhase:
    """What ``phase`` returns while nothing is traced: no clock
    (``t0``/``t1`` are None), no state -- ONE shared instance."""
    __slots__ = ()
    t0 = t1 = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **counts):
        pass


class _Unsampled(_NoPhase):
    """An outermost phase that lost the sampling draw (rates strictly
    between 0 and 1): while it is open every phase inside it is the
    no-op, so an iteration is traced whole or not at all. Shared too:
    its one bit of state is the thread's ``cur``."""
    __slots__ = ()

    def __enter__(self):
        _tls.cur = self
        return self

    def __exit__(self, *exc):
        _tls.cur = None
        return False


_NO_PHASE = _NoPhase()
_UNSAMPLED = _Unsampled()
_tls = threading.local()  # .cur: the innermost open phase of this thread
_annotation = None  # jax.profiler.TraceAnnotation, imported on first use


class _Phase:
    """One open traced phase. ``t0``/``t1`` are its ``perf_counter``
    readings, for a caller that times the same boundary into a
    histogram and would otherwise read the clock twice."""
    __slots__ = ("name", "counts", "parent", "root", "t0", "t1",
                 "child_ms", "ts", "acc", "_ann")

    def __init__(self, name, counts, parent):
        self.name, self.counts, self.parent = name, counts, parent
        self.root = self if parent is None else parent.root
        self.child_ms = 0.0

    def __enter__(self):
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation as _annotation
        self._ann = _annotation("ptpu." + self.name, **self.counts)
        self._ann.__enter__()
        _tls.cur = self
        if self.parent is None:
            self.ts = time.time()
            self.acc = {}  # (parent name, name) -> [self_ms, ms, n, end]
        self.t0 = time.perf_counter()
        return self

    def note(self, **counts):
        """Counts known only once the phase has run (how many of what
        it found it loaded): they join those it was opened with in its
        record; the profiler's span keeps what it was opened with."""
        self.counts.update(counts)

    def __exit__(self, *exc):
        self.t1 = t1 = time.perf_counter()
        _tls.cur = self.parent
        self._ann.__exit__(*exc)
        ms = (t1 - self.t0) * 1e3
        root = self.root
        if self.parent is None:
            phases = [{"name": n, "parent": p, "self_ms": round(a[0], 4),
                       "ms": round(a[1], 4), "n": a[2],
                       "end_ms": round(a[3], 4)}
                      for (p, n), a in root.acc.items()]
            record_process_span(
                self.name, ts=self.ts, dur_ms=ms,
                self_ms=round(ms - self.child_ms, 4), phases=phases,
                **self.counts)
            return False
        self.parent.child_ms += ms
        a = root.acc.setdefault((self.parent.name, self.name),
                                [0.0, 0.0, 0, 0.0])
        a[0] += ms - self.child_ms
        a[1] += ms
        a[2] += 1
        a[3] = (t1 - root.t0) * 1e3
        if self.counts:
            root.counts.update(self.counts)
        return False


def phase(name: str, **counts):
    """Context manager for work that belongs to no request (module
    doc). Off: the shared no-op. The outermost phase of a thread makes
    the sampling decision and becomes one record of the process ring
    when it closes: ``ts``, ``dur_ms``, its own ``self_ms``, the counts
    of every phase opened inside it (the last of a name wins), and
    ``phases``: for each (parent, name) the summed ``self_ms`` and
    ``ms``, how often it ran (``n``) and when it last ended
    (``end_ms`` after ``ts``)."""
    if _rate <= 0.0:
        return _NO_PHASE
    cur = getattr(_tls, "cur", None)
    if cur is None:
        return _Phase(name, counts, None) if sampled() else _UNSAMPLED
    if cur is _UNSAMPLED:
        return _NO_PHASE
    return _Phase(name, counts, cur)


def current_phase() -> Optional[str]:
    """Name of the innermost phase open on this thread, or None: at rate
    0, outside every phase, and inside an outermost phase that lost its
    sampling draw. What an acquisition record gives as the ``phase`` it
    began under."""
    return getattr(getattr(_tls, "cur", None), "name", None)


# -- rid binding (multi-stage servers) -----------------------------------

def bind_rid(rid: int, trace_id: str) -> None:
    with _rids_lock:
        _rids[rid] = trace_id


def rid_trace(rid: int) -> Optional[str]:
    if not _rids:  # the common untraced case: one falsy check, no lock
        return None
    with _rids_lock:
        return _rids.get(rid)


def pop_rid(rid: int) -> Optional[str]:
    if not _rids:
        return None
    with _rids_lock:
        return _rids.pop(rid, None)


def bound() -> bool:
    """True iff any in-flight request in this process is traced — the
    cheap gate server stage loops check before doing span bookkeeping."""
    return bool(_rids)


def rid_span(rid: int, name: str, *, dur_ms: float = 0.0,
             **attrs) -> None:
    """Record a span against the trace bound to ``rid``, if any. The
    untraced fast path is one falsy dict check."""
    tid = rid_trace(rid)
    if tid is not None:
        RECORDER.record(tid, name, dur_ms=dur_ms, **attrs)


# -- snapshots / fleet merge ---------------------------------------------

def snapshot() -> Dict:
    return RECORDER.snapshot()


def merge_snapshots(snaps: Iterable[Dict]) -> Dict:
    """One fleet-wide span list from per-process recorder snapshots
    (the ``merge_json_snapshots`` idea, for traces): each span is
    stamped with its origin replica, the whole list is ts-sorted so a
    single trace reads as a waterfall, and ring accounting sums."""
    spans: List[Dict] = []
    replicas: List[str] = []
    recorded = dropped = 0
    for snap in snaps:
        if not snap:
            continue
        replica = snap.get("replica", "") or "router"
        replicas.append(replica)
        recorded += int(snap.get("recorded", 0))
        dropped += int(snap.get("dropped", 0))
        for s in snap.get("spans", ()):
            s = dict(s)
            s.setdefault("replica", replica)
            spans.append(s)
    spans.sort(key=lambda s: (s["trace_id"], s["ts"], s.get("seq", 0)))
    return {"replicas": replicas, "recorded": recorded,
            "dropped": dropped, "spans": spans}


def reset() -> None:
    """Clear both rings AND the rid binding table (test isolation; the
    ``observability.reset_all()`` hook)."""
    RECORDER.reset()
    with _rids_lock:
        _rids.clear()
