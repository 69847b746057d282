"""`decode_step_roofline_dsa.serve` for a server that runs ROUNDS over a
prediction layer (that reader, an accepted file, finds `ptpu_decode_`
programs and prices dots3's layers): a round's share of its memory
roofline in the traced rounds. A round has to read every matrix outside
the routed experts once for its two positions (the mixers, the
prediction layer's with them, the dense MLP, the shared experts,
`eh_proj`, the head: `lib/glm5_cost.round_bytes`, in the type the
configuration holds them in), of the held experts those that received a
pair (`experts_active` of the round's `decode.loop.dispatch` phase x
75.5 MB), every live row's index key and the rows the first position
keeps (`rows_live`, `rows_chosen`), in six layers; all of it over the
HBM peak is the least time. The time spent is the union of the operation
events inside the `jit_ptpu_round_*` module events of the same rounds
(first chip). Nothing where the phases carry no `round_positions`."""
from benchmark.lib import glm5_cost, program_spans
from benchmark.lib.trace_reduce import union

LAYER = "model step"
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
    busy = union((s, s + d) for _, s, d, _ in ops)
    rounds = glm5_cost.rounds(spans, modules, busy, program_spans)
    spent = sum(t for t, _ in rounds)
    if not rounds or spent <= 0:
        return None
    nbytes = sum(glm5_cost.round_bytes(
        cfg, float(c.get("experts_active", 0)), float(c["rows_live"]),
        float(c["rows_chosen"])) for _, c in rounds)
    least = nbytes / run["peaks"]["hbm_bytes_per_s"]
    print("decode_step_roofline_mtp: %d rounds, %.6f s busy in the trace "
          "(%.3f ms a round), %.6f s at the HBM peak (%.3f GB a round)"
          % (len(rounds), spent, 1e3 * spent / len(rounds), least,
             nbytes / len(rounds) / 1e9), flush=True)
    return 100.0 * least / spent
