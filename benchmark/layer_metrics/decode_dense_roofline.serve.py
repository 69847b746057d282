"""The weight-streaming share of a decode step's dense products: in the
`jit_ptpu_decode_*` programs, the bytes of the DISTINCT weights that the
dense events stream from HBM themselves (`fl.mul|matmul|fc` against a
weight, the event's own scope or a member of its fusion; the weights
those scopes name, where the map's `reads` has them and its `copied`
does not, their bytes from its `params`), each as often as the event
that streams it most ran (once a step), over the HBM peak, against those
events' time. A weight that another operation moves first (the
compiler's prefetch, which streams under other events; its `copy`, an
event of its own that may round the weight to bfloat16 and leave it in
on-chip memory) is not read from HBM by the product that uses it, and is
left out on both sides, so the bytes counted must pass from HBM inside
the time counted and the share cannot pass 100; it is a LOWER bound on
those events' bytes (activations, and a weight read twice, are not
counted). Nothing where no decode program of the trace has a scoped map
or none of its events streams a weight."""
from benchmark.lib import scope_time

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = scope_time.of_run(run)
    if not found:
        return None
    out = scope_time.dense_roofline(found[1], "jit_ptpu_decode_",
                                    run["peaks"]["hbm_bytes_per_s"])
    if out is None:
        return None
    print("decode_dense_roofline: %.0f bytes of weights over %.6f s of "
          "dense events" % (out["bytes"], out["seconds"]), flush=True)
    return out["pct"]
