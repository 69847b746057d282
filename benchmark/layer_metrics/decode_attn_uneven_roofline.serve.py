"""The full layers' decode attention's share of its roofline in the
traced decode steps (`ops/kv_cache.py`, `ptpu.decode_attn_uneven`: 64
query heads on slabs of FLAT rows, 4 key heads of 192 beside 4 value
heads of 128): the least time the chip could take to stream the K and V
rows the step's full layers attend (`attended`, the count of the step's
`decode.loop.dispatch` phase, x `lib/mimo_cost.kv_row_bytes` = 4 x 320 x
4 B a layer, over the HBM peak; the attention is memory-bound, 16 query
rows a slot against a head's block) over the time the trace gives the
events under that SCOPE inside the step's `jit_ptpu_decode_*` program,
whatever implements it: the Mosaic calls of that name, or the lax
path's events by `lib/scope_time.py`'s join. The kernel fetches each
slot's length rounded up to its block (`streamed` of the same phase), so
its share is bounded by `attended / streamed`; the lax path reads every
row of every slot. Nothing where no event carries the scope or the
configuration is of another family."""
from benchmark.lib import mimo_cost, program_spans, scope_time
from benchmark.lib.trace_reduce import union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or not mimo_cost.is_family(cfg) or "serve" not in cfg:
        return None
    modules = program_spans.first_device(spans["modules"])
    calls = mimo_cost.scope_events(run, mimo_cost.DECODE_ATTN,
                                   "jit_ptpu_decode_", scope_time)
    if not calls:
        return None
    steps = [(t, c) for t, c in mimo_cost.decode_steps(
        spans, modules, union(calls), program_spans) if t > 0]
    spent = sum(t for t, _ in steps)
    if not steps or spent <= 0:
        return None
    attended = sum(float(c["attended"]) for _, c in steps)
    streamed = sum(float(c.get("streamed", 0)) for _, c in steps)
    least = (attended * mimo_cost.kv_row_bytes(cfg)
             / run["peaks"]["hbm_bytes_per_s"])
    print("decode_attn_uneven_roofline: %d steps, %.6f s under the scope, "
          "%.6f s at the HBM peak, attended / streamed %.3f"
          % (len(steps), spent, least,
             attended / streamed if streamed else 0.0), flush=True)
    return 100.0 * least / spent
