"""Device idle that lies under `decode.loop.admit` and the phases
opened inside it (`prefill`: the prefill executable's dispatch,
`first_token`: the host waits for the logits, `scatter`: the slab
rebuild), as a share of the traced sub-window: what every admission
costs the sequences already decoding. See `idle_step_host_pct.serve`."""
from benchmark.lib import program_spans

LAYER = "scheduler"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run):
    return program_spans.group_idle_pct(run, "admit")
