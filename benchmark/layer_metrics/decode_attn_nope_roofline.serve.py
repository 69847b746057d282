"""The grouped-query decode kernel's share of its roofline over the
position-free (NoPE) layer's live rows in the traced decode steps
(`ops/kv_cache.py`, `ptpu.decode_attn_grouped`: 64 query heads on a slab
of 8 key/value heads of 128, no rotation; `decode_attn_grouped_roofline.
serve`, an accepted file, reads `layer_types`, which this family's files
do not have): the least time the chip could take to stream the K and V
rows the step's softmax layer attends (`attended`, the count of the
step's `decode.loop.dispatch` phase, x `lib/solar_cost.kv_row_bytes`,
over the HBM peak; the kernel is memory-bound, 8 query rows a slot
against a head's block) over the time the trace gives the Mosaic calls
of that name inside the step's `jit_ptpu_decode_*` program. The kernel
fetches each slot's length rounded up to its block (`streamed` of the
same phase), so the share is bounded by `attended / streamed`. Nothing
where no event carries the name or the configuration is of another
family."""
from benchmark.lib import program_spans, solar_cost
from benchmark.lib.trace_reduce import union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or not solar_cost.is_family(cfg) or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    calls = solar_cost.kernel_events(ops, solar_cost.DECODE_ATTN)
    steps = [(t, c) for t, c in solar_cost.decode_steps(
        spans, modules, union(calls), program_spans) if t > 0]
    spent = sum(t for t, _ in steps)
    if not steps or spent <= 0:
        return None
    attended = sum(float(c["attended"]) for _, c in steps)
    streamed = sum(float(c.get("streamed", 0)) for _, c in steps)
    least = (attended * solar_cost.kv_row_bytes(cfg)
             / run["peaks"]["hbm_bytes_per_s"])
    print("decode_attn_nope_roofline: %d steps, %.6f s in the kernel's "
          "calls, %.6f s at the HBM peak, attended / streamed %.3f"
          % (len(steps), spent, least,
             attended / streamed if streamed else 0.0), flush=True)
    return 100.0 * least / spent
