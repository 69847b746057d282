"""Tokens the decode steps of the window generated, a second: the sum
of `DecodeServer.step_active_counts` over the window's length, whether
or not their requests completed inside it (scheduler layer). The
steadier statistic beside `serve_tokens_per_s`, which counts a request
whole at the instant it completes: eight requests of up to 256 tokens
are in flight at each end of a window that completes some 15,600
(PERF.md, PR 23). An admission's first token comes from the prefill
and is not counted here."""
LAYER = "scheduler"
UNIT = "tokens/s"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    c = run.get("counts") or {}
    counts = c.get("step_active_counts")
    if not counts or not c.get("window_s"):
        return None
    return sum(counts) / c["window_s"]
