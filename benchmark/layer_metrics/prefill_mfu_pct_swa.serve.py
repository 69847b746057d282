"""The prefill's share of the chip's bf16 peak in the traced admissions
of a configuration with sliding layers beside full ones of other
key/value heads (`prefill_mfu_pct_mla.serve` and its siblings, accepted
files, count from other families' keys): the model FLOPs of their LIVE
prompt rows (`lib/mimo_cost.prefill_flops`: every row through the
mixers' projections, the dense MLP and the routers; the held pairs the
program counted; attention inside the causal triangle of a full layer
and inside the window of a sliding one, each (query, key) pair counted
once at 192 + 128 channels a query head; the head on one row a prompt;
not the bucket's padding, the repeated keys or the padded channels) over
the peak x the time inside the `jit_ptpu_prefill_*` module events (first
chip): the share of the WHOLE prefill. The counts are those of the
admission's `decode.loop.scatter` phase, the first that opens after the
program has started. Model FLOPs over the peak cannot pass 100%.
Nothing where the phases carry no `window_pairs` beside `attn_pairs` or
the configuration is of another family."""
from benchmark.lib import mimo_cost, program_spans
from benchmark.lib.trace_reduce import union

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or not mimo_cost.is_family(cfg) or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    busy = union((s, s + d) for _, s, d, _ in ops)
    admits = mimo_cost.admissions(spans, modules, busy, program_spans)
    spent = sum(t for t, _ in admits)
    if not admits or spent <= 0:
        return None
    flops = sum(mimo_cost.prefill_flops(
        cfg, float(c["prompt_rows"]), float(c.get("expert_pairs", 0)),
        float(c["attn_pairs"]), float(c["window_pairs"]),
        float(c["prompts"])) for _, c in admits)
    print("prefill_mfu_pct_swa: %d admissions, %.0f live rows of %.0f "
          "bucket rows, %.3f TFLOP of the model in %.6f s busy"
          % (len(admits), sum(float(c["prompt_rows"]) for _, c in admits),
             sum(float(c["bucket_rows"]) for _, c in admits),
             flops / 1e12, spent), flush=True)
    return 100.0 * flops / (run["peaks"]["flops"] * spent)
