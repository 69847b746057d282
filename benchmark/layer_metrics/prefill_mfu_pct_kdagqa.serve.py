"""The prefill's share of the chip's bf16 peak in the traced admissions
of a configuration with delta-rule (KDA) layers beside a softmax layer
of K/V rows (`prefill_mfu_pct_kda.serve`, an accepted file, counts from
Ling's keys): the model FLOPs of their LIVE prompt rows
(`lib/solar_cost.prefill_flops`: every row through the mixers'
projections, the routers and the shared experts; the held pairs the
program counted; the chunked delta rule; causal attention of the one
softmax layer over the live (query, key) pairs counted once; the head on
one row a prompt; not the bucket's padding) over the peak x the time
inside the `jit_ptpu_prefill_*` module events (first chip). The counts
are those of the admission's `decode.loop.scatter` phase, the first
that opens after the program has started. Model FLOPs over the peak
cannot pass 100%. Nothing where the phases carry no `kda_tokens` beside
`attn_pairs` (the parent of the PR that added them) or the
configuration is of another family."""
from benchmark.lib import program_spans, solar_cost
from benchmark.lib.trace_reduce import union

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or not solar_cost.is_family(cfg) or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    busy = union((s, s + d) for _, s, d, _ in ops)
    admits = solar_cost.admissions(spans, modules, busy, program_spans)
    spent = sum(t for t, _ in admits)
    if not admits or spent <= 0:
        return None
    flops = sum(solar_cost.prefill_flops(
        cfg, float(c["prompt_rows"]), float(c.get("expert_pairs", 0)),
        float(c["attn_pairs"]), float(c["prompts"])) for _, c in admits)
    print("prefill_mfu_pct_kdagqa: %d admissions, %.0f live rows of %.0f "
          "bucket rows, %.3f TFLOP of the model in %.6f s busy"
          % (len(admits), sum(float(c["prompt_rows"]) for _, c in admits),
             sum(float(c["bucket_rows"]) for _, c in admits),
             flops / 1e12, spent), flush=True)
    return 100.0 * flops / (run["peaks"]["flops"] * spent)
