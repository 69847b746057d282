"""`decode_step_roofline.serve` for a configuration whose layers keep a
window's block and pooled rows (that reader, an accepted file, prices
K/V slabs of a row a position): the decode step's share of its memory
roofline in the traced steps. A step has to read the weights and the
head once (`lib/eva_cost.dense_params` x 4 bytes) and K and V of every
live window row and visible summary row of every layer
(`eva_window_rows`, `eva_summary_rows` of the step's
`decode.loop.dispatch` phase); all of it over the HBM peak is the least
time. The time spent is the union of the operation events inside the
`jit_ptpu_decode_*` module events of the same steps (first chip).
Nothing where the phases carry no `eva_window_rows`."""
from benchmark.lib import eva_cost, program_spans
from benchmark.lib.trace_reduce import union

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "chunk_size" not in cfg or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    busy = union((s, s + d) for _, s, d, _ in ops)
    steps = eva_cost.decode_steps(spans, modules, busy, program_spans)
    spent = sum(t for t, _ in steps)
    if not steps or spent <= 0:
        return None
    nbytes = sum(eva_cost.step_bytes(
        cfg, float(c["eva_window_rows"]), float(c["eva_summary_rows"]))
        for _, c in steps)
    least = nbytes / run["peaks"]["hbm_bytes_per_s"]
    print("decode_step_roofline_eva: %d steps, %.6f s busy in the trace, "
          "%.6f s at the HBM peak (%.3f GB a step)"
          % (len(steps), spent, least, nbytes / len(steps) / 1e9),
          flush=True)
    return 100.0 * least / spent
