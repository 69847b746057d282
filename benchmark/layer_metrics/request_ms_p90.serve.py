"""What a waiting caller feels: submit -> future resolved, the 90th
percentile over the requests completed inside the window, by the
benchmark's own clock (the runner refuses a window that leaves fewer
than ten samples beyond it). Not an end-to-end metric in a closed loop
that is always full: there the mean latency is clients over completed
requests a second (Little's law), and the tail moves by 7% with the
order of the requests alone (PERF.md, PR 23)."""
LAYER = "entry"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "host_clock"


def read(run):
    return run["end_to_end"].get(run["mix"].get("tail_metric"))
