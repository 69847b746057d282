"""Attention's share of the device's busy time in the traced sub-window,
prefill and decode together, first chip: the Pallas calls of a prefill
(`ptpu.attn_window` on the sliding layers, `ptpu.flash_fwd` on the full
ones: named after their scope) and, in a decode step, whatever reads or
writes a slab or a ring (`ptpu.decode_attn_grouped`,
`ptpu.decode_attn_ring`, the appends: lax paths, told by the feed's
name or the shape of what they read, `lib/moe_cost.patterns`). With
`moe_time_pct.serve` and the head it says which mechanism a cell works.
Nothing where the configuration has no window or no event matches."""
from benchmark.lib import moe_cost, program_spans
from benchmark.lib.trace_reduce import total, union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

KERNELS = ("ptpu.attn_window", "ptpu.flash_fwd")


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "sliding_window" not in cfg or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    if not ops:
        return None
    pats = moe_cost.patterns(cfg)["cache"]
    kernels = [(s, s + d) for n, s, d, _ in ops
               if any(k in n for k in KERNELS)]
    cache = [(s, s + d) for n, s, d, text in ops
             if not any(k in n for k in KERNELS)
             and not n.startswith("while")
             and any(p in text for p in pats)]
    if not kernels and not cache:
        return None
    busy = total(union((s, s + d) for _, s, d, _ in ops))
    print("attn_time_pct: %d prefill kernel calls (%.6f s), %d events on a "
          "slab or a ring (%.6f s), %.6f s busy"
          % (len(kernels), total(union(kernels)) * 1e-9, len(cache),
             total(union(cache)) * 1e-9, busy * 1e-9), flush=True)
    return 100.0 * total(union(kernels + cache)) / busy
