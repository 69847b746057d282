"""`decode_step_roofline.serve` for a configuration with routed experts
and rings (that reader counts a Jamba-family step and is an accepted
file): the decode step's share of its memory roofline in the traced
steps. A step has to read the weights of the decode graph once
(`lib/moe_cost.decode_weight_params` x 4 bytes: the head's own matrix,
and of the held experts those that received a pair, `experts_active` of
the same phase, as `moe_experts_roofline.serve` counts them), the K and
V rows its full layers attend
(`attended`, the count of the step's `decode.loop.dispatch` phase, x
`moe_cost.kv_row_bytes`) and the ring rows its sliding layers attend
(`ring_rows` x `moe_cost.ring_row_bytes`); all of it over the HBM peak
is the least time. The time spent is the union of the operation events
inside the `jit_ptpu_decode_*` module events of the same steps (first
chip). Nothing where the phases carry no `ring_rows` (a program older
than the count) or the configuration is not of this family."""
from benchmark.lib import moe_cost, program_spans
from benchmark.lib.trace_reduce import subtract, total, union

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "moe_intermediate_size" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    row, ring = moe_cost.kv_row_bytes(cfg), moe_cost.ring_row_bytes(cfg)
    busy = union((s, s + d) for _, s, d, _ in ops)
    least = spent = weights = 0.0
    n = 0
    for name, m0, md in modules:
        if "ptpu_decode_" not in name:
            continue
        step = program_spans.step_of(spans["host"], m0)
        if step is None or "ring_rows" not in step:
            continue
        inside = union([(m0, m0 + md)])
        spent += (total(busy) - total(subtract(busy, inside))) * 1e-9
        w = moe_cost.ITEM * moe_cost.decode_weight_params(
            cfg, float(step["experts_active"]))
        weights += w
        least += (w + float(step["attended"]) * row
                  + float(step["ring_rows"]) * ring) / run["peaks"][
                      "hbm_bytes_per_s"]
        n += 1
    if not n or spent <= 0:
        return None
    print("decode_step_roofline_moe: %d steps, %.6f s busy in the trace, "
          "%.6f s at the HBM peak (%.3f GB of weights a step)"
          % (n, spent, least, weights / n / 1e9), flush=True)
    return 100.0 * least / spent
