"""Server-side time to first token: from the program's `client.submit`
span to its `decode.admit` span (admission prefills the prompt and
samples the first token), over the requests admitted inside the window;
the 90th percentile, and nothing when it has not ten samples beyond
it. Spans are the program's (`observability/tracing.py`), sampled at
rate 1 in the traced run only."""
from benchmark.lib import stats

LAYER = "entry"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run):
    t0, t1 = run["window_wall"]
    submit, waits = {}, []
    for s in sorted(run.get("spans") or [], key=lambda s: s["seq"]):
        if s["name"] == "client.submit":
            submit[s["trace_id"]] = s["ts"]
        elif s["name"] == "decode.admit" and s["trace_id"] in submit:
            if t0 <= s["ts"] <= t1:
                waits.append((s["ts"] - submit[s["trace_id"]]) * 1e3)
    if not waits:
        return None
    val, p = stats.tail(waits, 0.90)
    return val if p >= 0.90 else None
