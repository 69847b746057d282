"""Rows the layers that own no cache entry ran on in the window's
admissions, over the real prompt rows prefilled, x 100: `tail_rows` over
`prompt_rows`, the counts an admission's `decode.loop.scatter` phase
carries (`DecodeServer._scatter_counts`), summed over the admissions of
the traced sub-window. A decoder whose last layers read another
layer's K/V and memory (gated memory units, cross attention) runs them
on each prompt's LAST row alone: 1 / (mean prompt length) x 100, ~0.3 at
prompts of ~330 tokens; 100 where a later change loses the shortcut.
Nothing where the phases carry no `prompt_rows` (a model every layer of
which owns a cache entry, or a program older than the count)."""
from benchmark.lib import program_spans

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"

SCATTER = program_spans.LOOP + "scatter"


def read(run):
    spans = program_spans.of_run(run)
    if not spans:
        return None
    phases = [h[3] for h in spans["host"]
              if h[0] == SCATTER and "prompt_rows" in h[3]]
    rows = sum(float(c["prompt_rows"]) for c in phases)
    if not rows:
        return None
    tail = sum(float(c["tail_rows"]) for c in phases)
    print("prefill_tail_rows_pct: %d admissions, %d prompt rows, %d rows "
          "through the layers that own no cache entry"
          % (len(phases), rows, tail), flush=True)
    return 100.0 * tail / rows
