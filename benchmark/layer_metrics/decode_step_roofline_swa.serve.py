"""`decode_step_roofline_moe.serve` for a configuration whose sliding and
full layers differ in their key/value heads and whose key heads are
wider than its value heads (that reader, an accepted file, counts from
Laguna's keys): the decode step's share of its memory roofline in the
traced steps. A step has to read the weights outside the routed experts
and the head once (`lib/mimo_cost.dense_params` x 4 bytes), of the held
experts those that received a pair (`experts_active` of the step's
`decode.loop.dispatch` phase x 100.7 MB), the live rows of the full
layers' slabs (`attended` x 10,240 B) and of the sliding layers' rings
(`ring_rows` x 51,200 B); all of it over the HBM peak is the least time.
The time spent is the union of the operation events inside the
`jit_ptpu_decode_*` module events of the same steps (first chip).
Nothing where the phases carry no `ring_rows` or the configuration is of
another family."""
from benchmark.lib import mimo_cost, program_spans
from benchmark.lib.trace_reduce import union

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or not mimo_cost.is_family(cfg) or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    busy = union((s, s + d) for _, s, d, _ in ops)
    steps = mimo_cost.decode_steps(spans, modules, busy, program_spans)
    spent = sum(t for t, _ in steps)
    if not steps or spent <= 0:
        return None
    nbytes = sum(mimo_cost.step_bytes(
        cfg, float(c.get("experts_active", 0)), float(c["attended"]),
        float(c["ring_rows"])) for _, c in steps)
    least = nbytes / run["peaks"]["hbm_bytes_per_s"]
    print("decode_step_roofline_swa: %d steps, %.6f s busy in the trace, "
          "%.6f s at the HBM peak (%.3f GB a step)"
          % (len(steps), spent, least, nbytes / len(steps) / 1e9),
          flush=True)
    return 100.0 * least / spent
