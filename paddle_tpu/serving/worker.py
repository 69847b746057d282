"""Fleet worker: one serving replica = engine + channel loop.

``worker_main`` is the process entry the Router spawns (spawn/forkserver
start methods — fork from a jax-threaded parent deadlocks children, the
PR-3 DataLoader lesson). It builds a ``Predictor`` (or a tp
``ShardedPredictor``) over the SHARED persistent AOT cache — so N
replicas deserialize the executables one process compiled, making a
warm fleet spawn nearly compile-free — wraps it in the PR-2 pipelined
``PredictorServer``, and shuttles binary frames between the router pipe
and the server's C++ channel.

Pipe wire protocol (each message one ``send_bytes`` payload):

router -> worker
    ``b"Z..."`` / ``b"P..."``  request frame, forwarded VERBATIM from
                               the client (the embedded tag is the
                               router-minted request id); a traced
                               request arrives ``b"T"``-prefixed
                               (wire.pack_trace) — the worker strips
                               the header, binds rid -> trace_id, and
                               records recv/stack/device/reply spans
                               into its flight recorder
    ``b"C" + pickle(dict)``    control: {"cmd": "stop" | "ping" |
                               "metrics" | "trace" | "probe"}

worker -> router
    ``b"S" + pickle(dict)``    status: ready/pong/metrics/stopped
    ``b"R" + u8 vlen + version + frame``
                               response: version = this replica's
                               program fingerprint (the router checks
                               it against the version the request was
                               dispatched under — mis-versioned
                               responses must be impossible, and are
                               counted if they ever happen); frame =
                               encoded (rid, fetch rows)
    ``b"E" + pickle((rid, exc))``  per-request failure

Responses stream back from ``_Future.add_done_callback`` (the server's
device/stacking threads), serialized by a send lock. On "stop" the
worker calls ``server.stop()``, which flushes everything still queued
in the stacking stage (the drain contract pinned by
``tests/test_serving_pipeline.py::test_stop_flushes_queued_requests``),
so every response is on the pipe before the final "stopped" status —
the zero-dropped-requests half of the fleet drain story.
"""
from __future__ import annotations

import os
import pickle
import struct
import threading
import time
import traceback

__all__ = ["worker_main"]


def _apply_env(options):
    """Environment overrides BEFORE jax is imported (spawned children
    import everything inside this function for exactly this reason):
    virtual-device XLA_FLAGS for tp-on-CPU tests, cache dirs, etc."""
    for k, v in (options.get("env") or {}).items():
        os.environ[k] = str(v)
    platform = options.get("jax_platform")
    if platform:
        os.environ["JAX_PLATFORMS"] = platform


def worker_main(conn, options):
    """Run one replica until the pipe closes or a stop command arrives.
    ``conn`` is the router end of a duplex multiprocessing Pipe;
    ``options`` is a plain picklable dict (see Router._spawn)."""
    _apply_env(options)

    # chaos barriers (checkpoint/faults.py, armed via PADDLE_TPU_FAULT_*
    # in worker_env): "serving.worker_boot" models a replica dying
    # during bootstrap (the drain_restart double-fault), and
    # "serving.request" — armed with a DELAY — models a slow replica so
    # shedding/priority tests are deterministic instead of racing the
    # scheduler. The env is fixed at spawn for a worker process, so an
    # unarmed worker skips the barrier entirely (zero hot-path cost).
    from ..checkpoint.faults import fault_point

    faults_armed = any(
        os.environ.get(k) for k in ("PADDLE_TPU_FAULT_KILL",
                                    "PADDLE_TPU_FAULT_DELAY",
                                    "PADDLE_TPU_FAULT_IO"))
    if faults_armed:
        fault_point("serving.worker_boot")
        if options.get("swap_boot"):
            # this spawn is a hot-swap's INCOMING replica: a swap.*-
            # scoped chaos spec (SIGKILL/delay the new version mid-swap)
            # fires here without touching regular boots of the same
            # fleet — the rollback-leaves-old-serving contract's barrier
            fault_point("swap.worker_boot")

    import jax

    if options.get("jax_platform"):
        # the Router's `jax_platform` option: this worker's backend,
        # whatever the parent's environment says (dev fleets on "cpu")
        jax.config.update("jax_platforms", options["jax_platform"])

    from .. import observability as obs
    from ..inference import Predictor, PredictorServer, _encode_sample
    from ..observability import tracing as _tracing

    from . import wire

    name = options.get("name") or "worker%d" % os.getpid()
    obs.set_replica(name)

    # outbound coalescing: responses fire from the server's device /
    # stacking threads one future at a time; a dedicated sender drains
    # them and ships everything queued as ONE pipe message (wire.pack),
    # so the per-request syscall disappears under load
    import queue as _queue

    out_q: "_queue.Queue" = _queue.Queue()
    _SENDER_STOP = object()

    def _sender_loop():
        while True:
            item = out_q.get()
            if item is _SENDER_STOP:
                return
            items = [item]
            while True:
                try:
                    nxt = out_q.get_nowait()
                except _queue.Empty:
                    break
                if nxt is _SENDER_STOP:
                    out_q.put(nxt)  # re-deliver after this flush
                    break
                items.append(nxt)
            try:
                conn.send_bytes(wire.pack(items))
            except (OSError, ValueError, BrokenPipeError):
                return  # router gone: nothing left to tell it

    sender = threading.Thread(target=_sender_loop, daemon=True,
                              name="ptpu-worker-send")
    sender.start()

    def send(payload: bytes):
        out_q.put(payload)

    try:
        shard = int(options.get("shard") or 1)
        if options.get("decode"):
            # decode replica: DecodePredictor + continuous-batching
            # DecodeServer — same submit_frame/stop/start_http surface,
            # so the rest of the worker (and the whole Router) is
            # mode-agnostic
            if shard > 1:  # Router raises first; belt for direct callers
                raise ValueError(
                    "decode mode does not support shard > 1")
            from .decode import DecodePredictor, DecodeServer

            pred = DecodePredictor(
                options["model_dir"],
                strategy=options.get("strategy") or "greedy",
                draft_n_layer=options.get("decode_draft_layers"))
            version = options.get("version") or pred.fingerprint()
            server = DecodeServer(
                pred,
                slots=int(options.get("decode_slots", 4)),
                max_seq=options.get("decode_max_seq"),
                max_new_tokens=int(options.get("max_new_tokens", 32)),
                capacity=int(options.get("capacity", 256)),
                speculative=bool(options.get("decode_speculative")),
                spec_k=int(options.get("decode_spec_k", 4)),
                prefix_cache=bool(options.get("decode_prefix_cache")))
        else:
            if shard > 1:
                from .sharded import ShardedPredictor

                pred = ShardedPredictor(options["model_dir"], shard=shard)
            else:
                pred = Predictor(options["model_dir"])
            # the MODEL version label (hot swap: distinct exports of one
            # architecture share a program fingerprint, so the router
            # hands each spawn an explicit label); fingerprint fallback
            # keeps pre-swap fleets byte-identical in behavior
            version = options.get("version") or pred._engine.fingerprint()
            server = PredictorServer(
                pred,
                max_batch=int(options.get("max_batch", 8)),
                max_wait_ms=float(options.get("max_wait_ms", 0.0)),
                in_flight=int(options.get("in_flight", 2)),
                capacity=int(options.get("capacity", 256)))
        server.start()
        port = server.start_http(0) if options.get("http") else 0
    except Exception as e:
        # a replica that cannot come up reports WHY before dying — the
        # router surfaces this instead of a bare dead-pipe error
        send(b"S" + pickle.dumps(
            {"ready": False, "error": repr(e),
             "traceback": traceback.format_exc()}, protocol=4))
        return
    vtag = version.encode("ascii")
    send(b"S" + pickle.dumps(
        {"ready": True, "version": version, "pid": os.getpid(),
         "name": name, "metrics_port": port, "shard": shard}, protocol=4))

    served = [0]  # responses sent (rides each heartbeat)

    def respond(rid, fut, tid=None, t0=0.0):
        try:
            rows = fut.result(timeout=0)
            send(b"R" + struct.pack("<B", len(vtag)) + vtag
                 + _encode_sample(rid, rows))
        except Exception as e:
            send(b"E" + _pickle_error(rid, e))
        if tid is not None:
            # the whole worker residency, channel recv -> reply queued
            _tracing.record_span(tid, "worker.reply", ts=t0,
                                 dur_ms=(time.time() - t0) * 1e3, rid=rid)
        served[0] += 1

    # heartbeats through the control pipe: a dedicated thread, so a
    # main loop stuck in a device dispatch (or a chaos DELAY barrier)
    # still proves pipe/process liveness while the served count exposes
    # the STALL — the router's watchdog reaps live-but-hung replicas on
    # exactly that signal (wedge_timeout_s)
    hb_stop = threading.Event()
    hb_interval = float(options.get("heartbeat_s", 1.0) or 0)

    def _hb_loop():
        while not hb_stop.wait(hb_interval):
            send(b"S" + pickle.dumps(
                {"hb": True, "served": served[0],
                 "depth": len(server._results)}, protocol=4))

    hb_thread = None
    if hb_interval > 0:
        hb_thread = threading.Thread(target=_hb_loop, daemon=True,
                                     name="ptpu-worker-hb")
        hb_thread.start()

    def _pickle_error(rid, e):
        """An error response must ALWAYS reach the router — an exception
        whose state cannot pickle (locks, device handles, tracers) or
        whose class cannot reconstruct degrades to a plain RuntimeError
        carrying its repr, never a silently dropped response (which
        would strand the router's outstanding entry forever)."""
        try:
            payload = pickle.dumps((rid, e), protocol=4)
            pickle.loads(payload)  # reconstruction must work router-side
            return payload
        except Exception:
            return pickle.dumps(
                (rid, RuntimeError("replica error (unpicklable): %r" % (e,))),
                protocol=4)

    from ..runtime import recordio as _rio

    def _probe(cmd):
        """Hot-swap canary probe: run ONE request frame straight
        through the predictor (bypassing the serving queue — the probe
        must not consume a router-minted tag namespace or a batch
        slot) and reply with the output rows over the status pipe."""
        try:
            if options.get("decode"):
                raise RuntimeError(
                    "canary probe is a dense-predictor surface (decode "
                    "replicas generate, they don't score a fixed row)")
            import numpy as _np

            _rid, rows = _rio.decode_frame(memoryview(cmd["frame"]))
            # under the server's device lock: every predictor dispatch
            # is serialized through it (inference.py's single-threaded
            # device invariant) — a probe racing the live device stage
            # would otherwise run/compile concurrently with traffic
            with server._dev_lock:
                outs = pred.run([_np.asarray(r)[None] for r in rows])
            send(b"S" + pickle.dumps(
                {"probe": [_np.asarray(o) for o in outs]}, protocol=4))
        except Exception as e:
            send(b"S" + pickle.dumps({"probe_error": repr(e)},
                                     protocol=4))

    try:
        stop = False
        while not stop:
            try:
                payload = conn.recv_bytes()
            except (EOFError, OSError):
                break  # router gone: drain and exit
            try:
                msgs = list(wire.iter_messages(payload))
            except wire.WireError:
                # a torn multi-message: count, survive, keep serving
                obs.PREDICT_FAILURES.inc(path="wire")
                continue
            for msg in msgs:
                kind = bytes(msg[:1])
                if kind == b"C":
                    try:
                        cmd = pickle.loads(msg[1:])
                        op = cmd.get("cmd")
                    except Exception:
                        # a b"C"-prefixed frame that isn't a pickled
                        # dict must cost a counted drop, not the
                        # replica (same contract as every other frame
                        # kind)
                        obs.PREDICT_FAILURES.inc(path="wire")
                        continue
                    if op == "stop":
                        stop = True
                        break
                    if op == "ping":
                        send(b"S" + pickle.dumps(
                            {"pong": True, "version": version,
                             "pid": os.getpid(),
                             "depth": len(server._results)}, protocol=4))
                    elif op == "metrics":
                        from ..observability import export

                        send(b"S" + pickle.dumps(
                            {"metrics": export.to_json(
                                include_timeline=False)}, protocol=4))
                    elif op == "trace":
                        send(b"S" + pickle.dumps(
                            {"trace": _tracing.snapshot()}, protocol=4))
                    elif op == "probe":
                        _probe(cmd)
                    continue
                if kind == b"Q":
                    # belt-and-braces: the router strips the SLO header
                    # before forwarding, but a direct caller (or a
                    # future router that forwards deadlines) must not
                    # wedge the replica on an unknown prefix
                    try:
                        msg = wire.read_slo(msg)[3]
                    except wire.WireError:
                        obs.PREDICT_FAILURES.inc(path="wire")
                        continue
                tid = None
                if bytes(msg[:1]) == b"T":
                    # traced request: strip the header (defensively,
                    # like b"Q") and remember the id — spans below and
                    # in the server stages correlate through it
                    try:
                        tid, msg = wire.read_trace(msg)
                    except wire.WireError:
                        obs.PREDICT_FAILURES.inc(path="wire")
                        continue
                if faults_armed:
                    fault_point("serving.request")
                # request frame: submit as-is (bytes — the C channel
                # copies from a bytes payload); the response streams
                # back from the completing server thread via the done
                # callback
                msg = bytes(msg)
                try:
                    rid = _rio.frame_tag(msg)
                except Exception:
                    # malformed frame with no recoverable tag: nothing
                    # to address a structured reject TO — count it and
                    # keep the replica alive (the router side gives the
                    # tagless frame's future its reject, when one
                    # exists)
                    obs.PREDICT_FAILURES.inc(path="wire")
                    continue
                t_recv = 0.0
                if tid is not None:
                    t_recv = time.time()
                    _tracing.bind_rid(rid, tid)
                    _tracing.record_span(tid, "worker.recv", ts=t_recv,
                                         rid=rid)
                try:
                    fut = server.submit_frame(msg)
                except Exception as e:
                    if tid is not None:
                        _tracing.pop_rid(rid)
                    send(b"E" + _pickle_error(rid, e))
                    continue
                fut.add_done_callback(
                    lambda f, rid=rid, tid=tid, t0=t_recv:
                    respond(rid, f, tid, t0))
    finally:
        # stop() drains the stacking queue (never drops): every
        # outstanding future completes -> every response is queued
        # BEFORE the stopped status below, and the sender flushes the
        # queue in order before exiting
        hb_stop.set()
        if hb_thread is not None:
            hb_thread.join(timeout=5)
        server.stop()
        send(b"S" + pickle.dumps({"stopped": True}, protocol=4))
        out_q.put(_SENDER_STOP)
        sender.join(timeout=30)
        conn.close()
