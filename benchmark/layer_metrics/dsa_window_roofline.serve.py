"""A round's two-position kernels against their memory roofline: the
index scores of both positions on one fetch of a slot's live keys
(`ptpu.dsa_index_step`) and the attention of 2 x 64 query rows under
two masks on one stream of a slot's live latent rows
(`ptpu.dsa_attend_step`), in six layers. They MUST read every live
row's index key (128 floats) and the latent rows the first position
keeps (`min(live, 2048)` of 576 floats each): `lib/glm5_cost.
window_bytes` of the round's `decode.loop.dispatch` phase (`rows_live`,
`rows_chosen`); that over the HBM peak is the least time. The time spent
is the union of the events of those two names inside the
`jit_ptpu_round_*` module events of the same rounds (first chip). The
kernels stream every live latent row and mask the rest, so they read
more than the least and score under it; none can score over 100.
Nothing where the phases carry no `round_positions`."""
from benchmark.lib import glm5_cost, program_spans
from benchmark.lib.trace_reduce import union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "num_nextn_predict_layers" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    rounds = glm5_cost.rounds(
        spans, modules, union(glm5_cost.window_events(ops)), program_spans)
    spent = sum(t for t, _ in rounds)
    if not rounds or spent <= 0:
        return None
    nbytes = sum(glm5_cost.window_bytes(
        cfg, float(c["rows_live"]), float(c["rows_chosen"]))
        for _, c in rounds)
    least = nbytes / run["peaks"]["hbm_bytes_per_s"]
    print("dsa_window_roofline: %d rounds, %.6f s in the window kernels "
          "(%.3f ms a round), %.6f s at the HBM peak (%.3f GB a round)"
          % (len(rounds), spent, 1e3 * spent / len(rounds), least,
             nbytes / len(rounds) / 1e9), flush=True)
    return 100.0 * least / spent
