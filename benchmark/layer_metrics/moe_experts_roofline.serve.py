"""The routed product's share of its roofline in the traced decode
steps: the least time the chip could take for it, over the time the
events that read the held experts' weights took (`ops/moe.py`,
`ptpu.moe_experts`; first chip).

The least: the larger of the byte time (the weights of the (layer,
expert) that received at least one pair, once each, plus the tokens'
activations, over the HBM peak) and the FLOP time (`expert_pairs` x
6.29 MFLOP over the bf16 peak: the stated arithmetic rounds operands to
bfloat16). Both from the counts the step's `decode.loop.dispatch` phase
carries, `experts_active` and `expert_pairs`: the loads the program
fetched with its ids (those of the last step the host had read at
dispatch: the closed loop's loads are steady, so the sum over a
window's steps is the window's). It counts what the product MUST read,
not what its form did (the dense form reads every held expert), so it
cannot pass 100%. Nothing where the phases carry no `expert_pairs` or
no event reads an expert weight."""
from benchmark.lib import moe_cost, program_spans
from benchmark.lib.trace_reduce import total, union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "moe_intermediate_size" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    pats = moe_cost.patterns(cfg)["experts"]
    events = sorted((s, s + d) for n, s, d, text in ops
                    if any(p in text for p in pats))
    least = spent = 0.0
    n = 0
    for name, m0, md in modules:
        if "ptpu_decode_" not in name:
            continue
        step = program_spans.step_of(spans["host"], m0)
        if step is None or "expert_pairs" not in step:
            continue
        inside = [(a, b) for a, b in events if m0 <= a < m0 + md]
        if not inside or not float(step["expert_pairs"]):
            continue
        flops, nbytes = moe_cost.routed_product(
            cfg, float(step["expert_pairs"]), float(step["experts_active"]),
            float(step["active"]))
        least += max(nbytes / run["peaks"]["hbm_bytes_per_s"],
                     flops / run["peaks"]["flops"])
        spent += total(union(inside)) * 1e-9
        n += 1
    if not n or spent <= 0:
        return None
    print("moe_experts_roofline: %d steps, %.6f s in the events that read "
          "expert weights, %.6f s at the roofline" % (n, spent, least),
          flush=True)
    return 100.0 * least / spent
