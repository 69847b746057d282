"""How unevenly the router loads the held experts: the busiest held
expert's pairs over the mean of the held experts', the worst sparse
layer's, over the measured server's life (probes, ramp, window and
traced sub-window: the closed loop's mix is the same throughout). An
expert-parallel deployment waits for its busiest chip, and a grouped
product for its largest group. Read from the program's own gauge
`paddle_tpu_moe_load_max_over_mean{layer}`, which the decode server
keeps from the loads its programs return (prefills and steps; real
tokens only). 1 = even. Nothing where the program has no such gauge
(the parent of the PR that added it) or it was never set."""

LAYER = "model step"
UNIT = "x"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    try:
        from paddle_tpu import observability as obs
    except Exception:
        return None
    gauge = getattr(obs, "MOE_LOAD_MAX_OVER_MEAN", None)
    if gauge is None:
        return None
    by_layer = {k.get("layer"): float(v) for k, v in gauge.samples()}
    if not by_layer:
        return None
    print("moe_load_max_over_mean: by layer %s" % (
        {k: round(v, 3) for k, v in sorted(by_layer.items())},), flush=True)
    return max(by_layer.values())
