"""`decode_step_roofline.serve` for a configuration whose layers share
ONE K/V slab (that reader counts a Jamba-family step, and
`decode_step_roofline_moe.serve` a Laguna-family one: accepted files):
the decode step's share of its memory roofline in the traced steps. A
step has to read the weights of the decode graph once
(`lib/shared_kv_cost.decode_weight_params` x 4 bytes; the tied table is
the head), the fixed-size states in and out (`state_bytes` of the
step's `decode.loop.dispatch` phase), the LIVE rows of the shared slab
once for every layer that attends it (`attended` x 10,240 B x
`slab_readers`) and the live rows of the sliding layers' rings
(`ring_rows` x 10,240 B x the sliding layers); all of it over the HBM
peak is the least time. What a path happens to read (`streamed`: a
kernel's part-dead last blocks; the rings' and any lax path's whole
arrays) is NOT counted: that is what the share falls short by. The time spent is the union of the operation events
inside the `jit_ptpu_decode_*` module events of the same steps (first
chip). Nothing where the phases carry no `slab_readers` (a program
without cross layers, or older than the count)."""
from benchmark.lib import program_spans, shared_kv_cost
from benchmark.lib.trace_reduce import subtract, total, union

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or cfg.get("model_type") != "phi4flash":
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    busy = union((s, s + d) for _, s, d, _ in ops)
    least = spent = moved = 0.0
    n = 0
    for name, m0, md in modules:
        if "ptpu_decode_" not in name:
            continue
        step = program_spans.step_of(spans["host"], m0)
        if step is None or "slab_readers" not in step:
            continue
        inside = union([(m0, m0 + md)])
        spent += (total(busy) - total(subtract(busy, inside))) * 1e-9
        b = shared_kv_cost.step_bytes(cfg, step)
        moved += b
        least += b / run["peaks"]["hbm_bytes_per_s"]
        n += 1
    if not n or spent <= 0:
        return None
    print("decode_step_roofline_shared: %d steps, %.6f s busy in the trace, "
          "%.6f s at the HBM peak (%.3f GB a step, %.3f GB of it weights)"
          % (n, spent, least, moved / n / 1e9,
             shared_kv_cost.ITEM * shared_kv_cost.decode_weight_params(cfg)
             / 1e9), flush=True)
    return 100.0 * least / spent
