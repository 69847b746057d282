"""The gap between two tokens of one sequence, as the server's loop
stamps it: from the `decode.loop.iter` records of the window (one per
loop iteration, `observability/tracing.phase`, sample rate 1 in the
traced run only), the time between the ends of the `fetch` phases of
consecutive decode steps. An admission between two steps is inside the
gap: that is the stall it puts on the sequences already decoding. Each
gap counts once for every live sequence of the later step (`active`); a
gap across a parked server belongs to no sequence. The 95th percentile
by `lib/stats.tail`'s rule, and nothing when it has not ten samples
beyond it. No client can see this yet: the server resolves a future
with the whole reply."""
from benchmark.lib import stats

LAYER = "entry"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"

ITER = "decode.loop.iter"
FETCH = "decode.loop.fetch"
PARK = "decode.loop.park"


def read(run):
    t0, t1 = run["window_wall"]
    gaps, prev = [], None
    for s in sorted(run.get("spans") or [], key=lambda s: s["seq"]):
        if s["name"] != ITER or not t0 <= s["ts"] <= t1:
            continue
        phases = {p["name"]: p for p in s.get("phases") or ()
                  if p["parent"] == ITER}
        if PARK in phases:
            prev = None
        if FETCH not in phases or "active" not in s:
            continue
        end = s["ts"] + phases[FETCH]["end_ms"] / 1e3
        if prev is not None:
            gaps += [(end - prev) * 1e3] * int(s["active"])
        prev = end
    if not gaps:
        return None
    val, p = stats.tail(gaps, 0.95)
    return val if p >= 0.95 else None
