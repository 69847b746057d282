"""`decode_step_roofline.serve` for a configuration with latent
attention and routed experts (that reader counts a Jamba-family step,
`decode_step_roofline_moe.serve` a Laguna-family one; both are accepted
files): the decode step's share of its memory roofline in the traced
steps. A step has to read the weights outside the routed experts and
the table once (`lib/mla_cost.dense_params` x 4 bytes, 1.13 GB), of the
held experts those that received a pair (`experts_active` of the step's
`decode.loop.dispatch` phase x 100.7 MB) and the live latent rows of
every layer (`latent_rows` x 5,120 B); all of it over the HBM peak is
the least time. The time spent is the union of the operation events
inside the `jit_ptpu_decode_*` module events of the same steps (first
chip). Nothing where the phases carry no `latent_rows` or the
configuration has no latent rank."""
from benchmark.lib import mla_cost, program_spans
from benchmark.lib.trace_reduce import subtract, total, union

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "kv_lora_rank" not in cfg or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    busy = union((s, s + d) for _, s, d, _ in ops)
    spent = nbytes = 0.0
    n = 0
    for name, m0, md in modules:
        if "ptpu_decode_" not in name:
            continue
        step = program_spans.step_of(spans["host"], m0)
        if step is None or "latent_rows" not in step:
            continue
        inside = union([(m0, m0 + md)])
        spent += (total(busy) - total(subtract(busy, inside))) * 1e-9
        nbytes += mla_cost.step_bytes(cfg, float(step["experts_active"]),
                                      float(step["latent_rows"]))
        n += 1
    if not n or spent <= 0:
        return None
    least = nbytes / run["peaks"]["hbm_bytes_per_s"]
    print("decode_step_roofline_mla: %d steps, %.6f s busy in the trace, "
          "%.6f s at the HBM peak (%.3f GB a step)"
          % (n, spent, least, nbytes / n / 1e9), flush=True)
    return 100.0 * least / spent
