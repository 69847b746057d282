"""The grouped-query decode kernel's share of its roofline in the traced
decode steps (`ops/kv_cache.py`, `ptpu.decode_attn_grouped`: the in-place
kernel over a slab of fewer key/value heads than query heads): the least
time the chip could take to stream the K and V rows the step's full
layers attend (`attended`, the count of the step's `decode.loop.dispatch`
phase, x `lib/moe_cost.kv_row_bytes`: every full layer's K and V row, over
the HBM peak; the kernel is memory-bound, g query rows a slot against a
head's block) over the time the trace gives the Mosaic calls of that
name inside the step's `jit_ptpu_decode_*` program. The kernel fetches
each slot's length rounded up to its block (`streamed` of the same
phase), so the share is bounded by `attended / streamed`. Nothing where
no event carries the name (a program whose grouped slabs take the lax
path: a fusion has no scope in its name) or the configuration is not of
this family."""
from benchmark.lib import moe_cost, program_spans

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

KERNEL = "ptpu.decode_attn_grouped"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "layer_types" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    # by the call's own name: a consumer's text names it as an operand
    kernels = sorted((s, d) for n, s, d, _ in ops if KERNEL in n)
    if not kernels:
        return None
    row = moe_cost.kv_row_bytes(cfg)
    least = spent = 0.0
    attended = streamed = 0
    n = 0
    for name, m0, md in modules:
        if "ptpu_decode_" not in name:
            continue
        step = program_spans.step_of(spans["host"], m0)
        if step is None:
            continue
        inside = [d for s, d in kernels if m0 <= s < m0 + md]
        if not inside:
            continue
        least += float(step["attended"]) * row / run["peaks"][
            "hbm_bytes_per_s"]
        spent += sum(inside) * 1e-9
        attended += int(step["attended"])
        streamed += int(step.get("streamed", 0))
        n += len(inside)
    if not n or spent <= 0:
        return None
    print("decode_attn_grouped_roofline: %d calls, %.6f s in the trace, "
          "%.6f s at the HBM peak, attended / streamed %.3f"
          % (n, spent, least, attended / streamed if streamed else 0.0),
          flush=True)
    return 100.0 * least / spent
