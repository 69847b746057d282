"""The prefill's share of the chip's bf16 peak in the traced admissions
of a configuration whose every layer attends under a learned indexer and
whose prediction layer walks the prompt too (`prefill_mfu_pct_dsa.serve`,
an accepted file, counts dots3's two layer kinds): the model FLOPs of
their LIVE prompt rows (`lib/glm5_cost.prefill_flops`: every row through
six mixers' projections, the dense MLP, the routers, the shared experts
and `eh_proj`; the held pairs the program counted; in every mixer the
indexer's 32 heads over every (query, key) pair under the causal mask
and attention over the pairs a layer KEEPS, `min(t + 1, 2048)` a query;
the head on two rows a prompt; not the bucket's padding, nor the pairs
the flash kernel computes and masks, nor the zero channels it is handed)
over the peak x the time inside the `jit_ptpu_prefill_*` module events
(first chip). The counts are those of the admission's
`decode.loop.scatter` phase, the first that opens after the program has
started. Model FLOPs over the peak cannot pass 100%. Nothing where the
phases carry no `chosen_pairs`."""
from benchmark.lib import dsa_cost, glm5_cost, program_spans
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
    admits = dsa_cost.admissions(spans, modules, busy, program_spans)
    spent = sum(t for t, _ in admits)
    if not admits or spent <= 0:
        return None
    flops = sum(glm5_cost.prefill_flops(
        cfg, float(c["prompt_rows"]), float(c["expert_pairs"]),
        float(c["index_pairs"]), float(c["chosen_pairs"]),
        float(c["prompts"])) for _, c in admits)
    print("prefill_mfu_pct_mtp: %d admissions, %.0f live rows of %.0f "
          "bucket rows, %.3f TFLOP of the model in %.6f s busy"
          % (len(admits), sum(float(c["prompt_rows"]) for _, c in admits),
             sum(float(c["bucket_rows"]) for _, c in admits),
             flops / 1e12, spent), flush=True)
    return 100.0 * flops / (run["peaks"]["flops"] * spent)
