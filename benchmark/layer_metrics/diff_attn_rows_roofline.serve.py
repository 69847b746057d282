"""The flat-row decode kernel's share of its roofline in the traced
decode steps (`ops/diff_attn.py`, `ptpu.diff_attn_rows`: the kernel over
the ONE shared slab of a decoder whose cross layers read another
layer's K/V): the least time the chip could take to stream the K and V
rows a call attends (`attended`, the count of the step's
`decode.loop.dispatch` phase, x `lib/shared_kv_cost.kv_row_bytes`: one
layer's K and V row, 10,240 B, once for EVERY call, since each of the
slab's readers streams it again; over the HBM peak: the kernel is
memory-bound, 4 query rows a pair-head against its block) over the
time the trace gives the Mosaic calls of that name inside the step's
`jit_ptpu_decode_*` program. The kernel fetches each slot's length
rounded up to its block (`streamed` of the same phase), so the share is
bounded by `attended / streamed`, which the printed line gives. Nothing
where no event carries the name (a program whose slab takes the lax
path: a fusion has no scope in its name) or the configuration is not of
this family."""
from benchmark.lib import program_spans, shared_kv_cost

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

KERNEL = "ptpu.diff_attn_rows"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or cfg.get("model_type") != "phi4flash":
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    # by the call's own name: a consumer's text names it as an operand
    kernels = sorted((s, d) for n, s, d, _ in ops if KERNEL in n)
    if not kernels:
        return None
    row = shared_kv_cost.kv_row_bytes(cfg)
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
        least += (len(inside) * float(step["attended"]) * row
                  / run["peaks"]["hbm_bytes_per_s"])
        spent += sum(inside) * 1e-9
        attended += int(step["attended"])
        streamed += int(step.get("streamed", 0))
        n += len(inside)
    if not n or spent <= 0:
        return None
    print("diff_attn_rows_roofline: %d calls, %.6f s in the trace, %.6f s "
          "at the HBM peak, attended / streamed %.3f"
          % (n, spent, least, attended / streamed if streamed else 0.0),
          flush=True)
    return 100.0 * least / spent
