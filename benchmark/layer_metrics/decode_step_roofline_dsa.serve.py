"""`decode_step_roofline_mla.serve` for a configuration whose full
layers attend under a learned indexer beside sliding latent layers and
routed experts (that reader, an accepted file, prices one latent
geometry and every live row): the decode step's share of its memory
roofline in the traced steps. A step has to read the weights outside
the routed experts and the head once (`lib/dsa_cost.dense_params` x 4
bytes), of the held experts those that received a pair
(`experts_active` of the step's `decode.loop.dispatch` phase x 94.4 MB),
every live row's index key and the chosen rows' latent rows
(`rows_live`, `rows_chosen`) and the rings' live rows (`ring_rows`); all
of it over the HBM peak is the least time. The time spent is the union
of the operation events inside the `jit_ptpu_decode_*` module events of
the same steps (first chip). Nothing where the phases carry no
`rows_chosen`."""
from benchmark.lib import dsa_cost, program_spans
from benchmark.lib.trace_reduce import union

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "index_topk" not in cfg or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    busy = union((s, s + d) for _, s, d, _ in ops)
    steps = dsa_cost.decode_steps(spans, modules, busy, program_spans)
    spent = sum(t for t, _ in steps)
    if not steps or spent <= 0:
        return None
    nbytes = sum(dsa_cost.step_bytes(
        cfg, float(c["experts_active"]), float(c["rows_live"]),
        float(c["rows_chosen"]), float(c["ring_rows"])) for _, c in steps)
    least = nbytes / run["peaks"]["hbm_bytes_per_s"]
    print("decode_step_roofline_dsa: %d steps, %.6f s busy in the trace, "
          "%.6f s at the HBM peak (%.3f GB a step)"
          % (len(steps), spent, least, nbytes / len(steps) / 1e9),
          flush=True)
    return 100.0 * least / spent
