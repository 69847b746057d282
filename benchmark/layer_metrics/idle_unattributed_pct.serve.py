"""Device idle under no phase of the program, as a share of the traced
sub-window: what the spans of `DecodeServer._loop` do not explain (the
loop's thread descheduled between iterations, a gap before the first
phase of the trace). See `idle_step_host_pct.serve`."""
from benchmark.lib import program_spans

LAYER = "device"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run):
    return program_spans.group_idle_pct(run, "unattributed")
