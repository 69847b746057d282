"""Host time of an admission's cache rebuild: the mean duration of the
`decode.loop.scatter` phase over the admissions of the traced
sub-window (the loop's thread, on the profiler's clock). The phase
carries `entries`, the arrays an admission scatters into (28 for a
14-layer period of 13 state-space layers and one attention layer: 26
states and windows and 2 slabs; 8 in the 4-layer OPT cell; one eager
update each at first, one jitted call for all since the same PR), and
`state_slots`, the slots whose fixed-size state it replaced whole.
Nothing where the program's phase carries no `entries` (a program
older than the count)."""
from benchmark.lib import program_spans

LAYER = "scheduler"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"

SCATTER = program_spans.LOOP + "scatter"


def read(run):
    spans = program_spans.of_run(run)
    if not spans:
        return None
    phases = [h for h in spans["host"]
              if h[0] == SCATTER and "entries" in h[3]]
    if not phases:
        return None
    ms = [h[2] / 1e6 for h in phases]
    print("state_scatter_ms: %d admissions, entries %s, state_slots a mean "
          "of %.2f" % (len(ms), sorted({int(h[3]["entries"])
                                        for h in phases}),
                       sum(float(h[3].get("state_slots", 0))
                           for h in phases) / len(ms)), flush=True)
    return sum(ms) / len(ms)
