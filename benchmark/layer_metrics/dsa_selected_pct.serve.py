"""How much of what is live a step under a selection attends: the rows
the full layers' attention kept (`rows_chosen`: each live slot's
`min(length + 1, index_topk)`) over the live rows (`rows_live`), summed
over the traced steps' `decode.loop.dispatch` phases. 100 while no
context has passed `index_topk`; the lower it is, the more a step that
reads the chosen rows alone would save over one that streams every live
row (`rows_scored` over `rows_live`, printed beside it, is what the
indexer's product ran over: above 1 where it scores dead rows too).
Nothing where the phases carry no `rows_chosen`."""
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
             if name == program_spans.DISPATCH and "rows_chosen" in c]
    live = sum(float(c["rows_live"]) for c in steps)
    if not steps or live <= 0:
        return None
    chosen = sum(float(c["rows_chosen"]) for c in steps)
    scored = sum(float(c["rows_scored"]) for c in steps)
    print("dsa_selected_pct: %d steps, %.0f rows chosen of %.0f live, "
          "%.0f scored (%.2f x live)"
          % (len(steps), chosen, live, scored, scored / live), flush=True)
    return 100.0 * chosen / live
