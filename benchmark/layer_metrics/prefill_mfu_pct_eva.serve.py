"""The prefill's share of the chip's bf16 peak in the traced admissions
of a configuration of EVA layers (`prefill_mfu_pct_mla.serve`, an
accepted file, counts latent attention over every causal pair): the
model FLOPs of their LIVE prompt rows (`lib/eva_cost.prefill_flops`:
every row through every layer's seven matrices; attention's score and
weighted sum over the (query, key or summary) pairs a layer attends, a
query at t `t mod 2048 + 1` keys and 128 summaries a closed window; the
head on one row a prompt; not the bucket's padding, nor the pairs the
flash kernel computes and masks) over the peak x the time inside the
`jit_ptpu_prefill_*` module events (first chip). The counts are those
of the admission's `decode.loop.scatter` phase, the first that opens
after the program has started. Model FLOPs over the peak cannot pass
100%. Nothing where the phases carry no `eva_summary_rows`."""
from benchmark.lib import eva_cost, program_spans
from benchmark.lib.trace_reduce import union

LAYER = "model step"
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
    busy = union((s, s + d) for _, s, d, _ in ops)
    admits = eva_cost.admissions(spans, modules, busy, program_spans)
    spent = sum(t for t, _ in admits)
    if not admits or spent <= 0:
        return None
    flops = sum(eva_cost.prefill_flops(
        cfg, float(c["prompt_rows"]), float(c["attn_pairs"]),
        float(c["prompts"])) for _, c in admits)
    print("prefill_mfu_pct_eva: %d admissions, %.0f live rows of %.0f "
          "bucket rows, %.3f TFLOP of the model in %.6f s busy"
          % (len(admits), sum(float(c["prompt_rows"]) for _, c in admits),
             sum(float(c["bucket_rows"]) for _, c in admits),
             flops / 1e12, spent), flush=True)
    return 100.0 * flops / (run["peaks"]["flops"] * spent)
