"""The decode steps' EVA attention against its memory roofline
(`ptpu.eva_attn`: the two-pass streamed kernel over a slot's live range;
a lax form in its place would carry no such name and the reader would
fall silent). A step's layers MUST read K and V of every live row of
the window's block and of every visible summary:
`lib/eva_cost.eva_step_bytes` of the step's `decode.loop.dispatch` phase
(`eva_window_rows`, `eva_summary_rows`); that over the HBM peak is the
least time. The time spent is the union of the kernel's events inside
the `jit_ptpu_decode_*` module events of the same steps (first chip).
The kernel streams whole blocks of 128 rows and a free slot's one block,
so it reads more than the live rows and scores lower; none can score
over 100. Nothing where the phases carry no `eva_window_rows`."""
from benchmark.lib import eva_cost, program_spans
from benchmark.lib.trace_reduce import union

LAYER = "kernels"
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
    steps = eva_cost.decode_steps(
        spans, modules, union(eva_cost.attn_kernel_events(ops)),
        program_spans)
    spent = sum(t for t, _ in steps)
    if not steps or spent <= 0:
        return None
    nbytes = sum(eva_cost.eva_step_bytes(
        cfg, float(c["eva_window_rows"]), float(c["eva_summary_rows"]))
        for _, c in steps)
    least = nbytes / run["peaks"]["hbm_bytes_per_s"]
    print("eva_decode_roofline: %d steps, %.6f s in ptpu.eva_attn, %.6f s "
          "at the HBM peak (%.3f GB a step)"
          % (len(steps), spent, least, nbytes / len(steps) / 1e9),
          flush=True)
    return 100.0 * least / spent
