"""The prefill's share of the chip's bf16 peak in the traced
admissions: the model FLOPs of their LIVE prompt rows
(`lib/mla_cost.prefill_flops`: projections, router and shared expert a
row a layer, the held pairs the program counted, causal attention over
the live (query, key) pairs counted once, the head on one row a
prompt; not the bucket's padding) over the peak x the time inside the
`jit_ptpu_prefill_*` module events (first chip): the share of the WHOLE
prefill, which is most of this cell's busy time. The counts are those of
the admission's `decode.loop.scatter` phase, the first that opens after
the program has started (`prompt_rows`, `attn_pairs`, `expert_pairs`,
`prompts`). Model FLOPs over the peak cannot pass 100%; what the padding
of a bucket and the expanded keys cost shows as a lower share. Nothing
where the phases carry no `attn_pairs` (the parent of the PR that added
the counts)."""
from benchmark.lib import mla_cost, program_spans
from benchmark.lib.trace_reduce import subtract, total, union

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

SCATTER = program_spans.LOOP + "scatter"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "kv_lora_rank" not in cfg or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    busy = union((s, s + d) for _, s, d, _ in ops)
    scatters = [(s, c) for name, s, _, c, _ in spans["host"]
                if name == SCATTER and "attn_pairs" in c]
    flops = spent = rows = bucket = 0.0
    n = 0
    for name, m0, md in sorted(modules, key=lambda m: m[1]):
        if "ptpu_prefill_" not in name:
            continue
        after = [c for s, c in scatters if s >= m0]
        if not after:
            continue  # the session ended before its scatter opened
        c = after[0]
        inside = union([(m0, m0 + md)])
        spent += (total(busy) - total(subtract(busy, inside))) * 1e-9
        flops += mla_cost.prefill_flops(
            cfg, float(c["prompt_rows"]), float(c["expert_pairs"]),
            float(c["attn_pairs"]), float(c["prompts"]))
        rows += float(c["prompt_rows"])
        bucket += float(c["bucket_rows"])
        n += 1
    if not n or spent <= 0:
        return None
    print("prefill_mfu_pct_mla: %d admissions, %.0f live rows of %.0f "
          "bucket rows, %.3f TFLOP of the model in %.6f s busy"
          % (n, rows, bucket, flops / 1e12, spent), flush=True)
    return 100.0 * flops / (run["peaks"]["flops"] * spent)
