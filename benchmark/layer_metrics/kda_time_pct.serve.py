"""The delta rule's share of the device's busy time in the traced
sub-window, prefill and decode together, first chip (`ops/kda.py`:
`ptpu.kda_scan`, `ptpu.kda_step`): in a decode step whatever reads or
writes a matrix state, in a prefill the chunked scans' loops (they carry
the state) and whatever builds a chunk's tensors. An XLA fusion or loop
carries no scope in its name on the chip, so the events are told by the
shapes only the delta rule has (`lib/ling_cost.patterns`): a LOWER
bound where the compiler folded a chunk's tensor into another shape.
The projections, the convolutions and the gate are not counted: plain
matmuls and elementwise work, as any mixer has. Nothing where the
configuration has no KDA layer or no event matches."""
from benchmark.lib import ling_cost, program_spans
from benchmark.lib.trace_reduce import total, union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "kda_lower_bound" not in cfg or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    if not ops:
        return None
    step, scan = ling_cost.kda_events(cfg, ops, modules, program_spans)
    if not step and not scan:
        return None
    busy = total(union((s, s + d) for _, s, d, _ in ops))
    print("kda_time_pct: %d events on a state in steps (%.6f s), %d events "
          "of the chunked scans (%.6f s), %.6f s busy"
          % (len(step), total(union(step)) * 1e-9, len(scan),
             total(union(scan)) * 1e-9, busy * 1e-9), flush=True)
    return 100.0 * total(union(step + scan)) / busy
