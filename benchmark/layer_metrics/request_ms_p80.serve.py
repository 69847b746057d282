"""What a waiting caller feels: submit -> future resolved, the 80th
percentile over the requests completed inside the window, by the
benchmark's own clock, for a cell whose window completes about a
hundred requests (the runner refuses a window that leaves too few
samples beyond the percentile its mix names: the 90th needs a hundred,
the 80th fifty). `request_ms_p90.serve`, an accepted file, would print
this number under a 90's name. Not an end-to-end metric in a closed loop
that is always full (PERF.md, PR 23). Nothing where the mix names
another tail."""
LAYER = "entry"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "host_clock"


def read(run):
    if run["mix"].get("tail_metric") != "request_ms_p80":
        return None
    return run["end_to_end"].get("request_ms_p80")
