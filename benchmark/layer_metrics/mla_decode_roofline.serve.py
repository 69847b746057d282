"""The absorbed attention's share of its roofline in the traced decode
steps: the least time the chip could take for it, over the time the
events that read a latent slab took (`ops/mla.py`, `ptpu.mla_decode`
and the appends; first chip).

The least: the larger of the byte time (`latent_rows`, the live rows
the step attends, x 5,120 B, every layer's row once, over the HBM peak)
and the FLOP time (`latent_rows` x 4 layers x 32 heads x (320 + 256) x
2 FLOP over the bf16 peak: the stated arithmetic rounds operands to
bfloat16), both from the counts of the step's `decode.loop.dispatch`
phase and `lib/mla_cost`. It counts the work the ABSORBED form must do
over the LIVE rows, whatever implements it (the lax path reads every
slot's whole 16,384 rows), so it cannot pass 100%. Nothing where the
phases carry no `latent_rows` (the parent of the PR that added the
count) or no event reads a slab."""
from benchmark.lib import mla_cost, program_spans
from benchmark.lib.trace_reduce import total, union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "kv_lora_rank" not in cfg or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    pats = mla_cost.patterns(cfg)["slab"]
    events = sorted((s, s + d) for n, s, d, text in ops
                    if not n.startswith("while")
                    and any(p in text for p in pats))
    least = spent = rows = 0.0
    n = 0
    for name, m0, md in modules:
        if "ptpu_decode_" not in name:
            continue
        step = program_spans.step_of(spans["host"], m0)
        if step is None or "latent_rows" not in step:
            continue
        inside = [(a, b) for a, b in events if m0 <= a < m0 + md]
        if not inside or not float(step["latent_rows"]):
            continue
        live = float(step["latent_rows"])
        least += max(
            live * mla_cost.row_bytes(cfg) / run["peaks"]["hbm_bytes_per_s"],
            live * mla_cost.absorbed_flops_per_row(cfg)
            / run["peaks"]["flops"])
        spent += total(union(inside)) * 1e-9
        rows += live
        n += 1
    if not n or spent <= 0:
        return None
    print("mla_decode_roofline: %d steps, %.0f live rows a step, %.6f s in "
          "the events that read a latent slab, %.6f s at the roofline"
          % (n, rows / n, spent, least), flush=True)
    return 100.0 * least / spent
