"""The decode steps' attention under a selection against its memory
roofline (`ptpu.dsa_index`, `ptpu.dsa_select`, `ptpu.dsa_attend`: the
kernels `ptpu.dsa_index_step` and `ptpu.dsa_attend_step` over a slot's
live blocks, the choice between them lax; the lax forms before them and
the gather that may replace them are measured against the same
yardstick). A
step's full layers MUST read every live row's index key (128 floats: the
indexer scores them all) and the latent rows of the `min(live, 2048)`
rows it keeps (576 floats each): `lib/dsa_cost.dsa_step_bytes` of the
step's `decode.loop.dispatch` phase (`rows_live`, `rows_chosen`); that
over the HBM peak is the least time. The time spent is the union of the
events inside the `jit_ptpu_decode_*` module events of the same steps
that the indexer, the choice and the attention under it are told by
(`lib/dsa_cost.patterns`; first chip). An implementation that streams
every live latent row, or every row of every slot as the lax forms do,
reads more and scores lower; none can score over 100. Nothing where the
phases carry no `rows_chosen`."""
from benchmark.lib import dsa_cost, program_spans
from benchmark.lib.trace_reduce import union

LAYER = "kernels"
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
    steps = dsa_cost.decode_steps(
        spans, modules, union(dsa_cost.dsa_events(cfg, ops)), program_spans)
    spent = sum(t for t, _ in steps)
    if not steps or spent <= 0:
        return None
    nbytes = sum(dsa_cost.dsa_step_bytes(
        cfg, float(c["rows_live"]), float(c["rows_chosen"]))
        for _, c in steps)
    least = nbytes / run["peaks"]["hbm_bytes_per_s"]
    print("dsa_decode_roofline: %d steps, %.6f s under a selection in the "
          "trace, %.6f s at the HBM peak (%.3f GB a step)"
          % (len(steps), spent, least, nbytes / len(steps) / 1e9),
          flush=True)
    return 100.0 * least / spent
