"""How far the traffic reaches past the window: the summary rows of all
the rows a step's EVA attention reads (`eva_summary_rows` over
`eva_summary_rows + eva_window_rows`), summed over the traced steps'
`decode.loop.dispatch` phases. 0 while no context has closed a window
(plain causal attention on the block alone); with contexts of several
windows it nears `(W / C) w / ((W / C) w + W / 2)`. `eva_chunks_closed`
over the steps' live slots, printed beside it, is how often a step
writes a chunk's two summary rows (1 in `chunk_size` at random phase).
Nothing where the phases carry no `eva_window_rows`."""
from benchmark.lib import program_spans

LAYER = "scheduler"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    spans = program_spans.of_run(run)
    if not spans:
        return None
    steps = [c for name, _, _, c, _ in spans["host"]
             if name == program_spans.DISPATCH and "eva_window_rows" in c]
    window = sum(float(c["eva_window_rows"]) for c in steps)
    if not steps or window <= 0:
        return None
    summary = sum(float(c["eva_summary_rows"]) for c in steps)
    closed = sum(float(c["eva_chunks_closed"]) for c in steps)
    active = sum(float(c["active"]) for c in steps)
    print("eva_summary_rows_pct: %d steps, %.0f summary rows and %.0f "
          "window rows a layer, %.0f chunks closed by %.0f live slots"
          % (len(steps), summary, window, closed, active), flush=True)
    return 100.0 * summary / (summary + window)
