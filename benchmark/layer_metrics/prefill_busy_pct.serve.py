"""Prefill's share of the device's busy time in the traced sub-window:
the time operations run inside the `ptpu_prefill_*` programs (the
module line of the first chip; `DecodePredictor.acquire` names every
executable for what it is) over the busy time. Nothing where no program
of the trace carries a `ptpu_` name."""
from benchmark.lib import program_spans
from benchmark.lib.trace_reduce import subtract, total, union

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    if not spans:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    if not ops or not any("ptpu_" in m[0] for m in modules):
        return None
    busy = union((s, s + d) for _, s, d, _ in ops)
    prefill = program_spans.module_intervals(modules, "ptpu_prefill_")
    inside = total(busy) - total(subtract(busy, prefill))
    return 100.0 * inside / total(busy)
