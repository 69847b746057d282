"""Mean share of the KV slab's slots that held a live sequence, over
the decode steps of the window: `DecodeServer.step_active_counts` over
`slots` (scheduler layer, `DecodeServer._loop`)."""
LAYER = "scheduler"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    c = run.get("counts") or {}
    counts = c.get("step_active_counts")
    if not counts:
        return None
    return 100.0 * sum(counts) / (len(counts) * c["slots"])
