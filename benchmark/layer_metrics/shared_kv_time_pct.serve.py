"""The shared slab's share of the device's busy time in the traced
sub-window (first chip): the events that read or write the ONE K/V
slab that the full-attention layer owns and every cross layer after it
attends. The calls of the kernel over flat rows by name
(`ptpu.diff_attn_rows`: in a step the slab's readers, in a prefill the
cross layers' one query row on the prompt's K and V); whatever else
reads or writes the slab in a step (the append; the lax path
`ptpu.diff_attn_slab` / `ptpu.attn_cross` where the kernel does not
run) told as `attn_time_pct.serve` tells its own: an XLA fusion carries
no scope in its name on the chip, so by the slab's feed names
(`kcache_<i>`, `vcache_<i>`, i the full layer) or its shape in the
event's text (`lib/shared_kv_cost.patterns`); and in a prefill the full
layer's flash kernel (`ptpu.flash_fwd`: the sliding layers run
`ptpu.attn_window`). Not seen: a full layer of a prompt bucket under 256
rows and a cross layer's of one under 128 (XLA paths on K and V of the
sliding layers' shape). With `decode_step_roofline_shared.serve` it
says how much of a step the sharing costs as the paths stand. Nothing
where the configuration is not of this family or no event matches."""
from benchmark.lib import program_spans, shared_kv_cost
from benchmark.lib.trace_reduce import total, union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

KERNELS = ("ptpu.flash_fwd", "ptpu.diff_attn_rows")


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or cfg.get("model_type") != "phi4flash":
        return None
    ops = program_spans.first_device(spans["ops"])
    if not ops:
        return None
    pats = shared_kv_cost.patterns(cfg)
    kernels = [(s, s + d) for n, s, d, _ in ops
               if any(k in n for k in KERNELS)]
    slab = [(s, s + d) for n, s, d, text in ops
            if not any(k in n for k in KERNELS)
            and not n.startswith("while") and any(p in text for p in pats)]
    if not kernels and not slab:
        return None
    busy = total(union((s, s + d) for _, s, d, _ in ops))
    print("shared_kv_time_pct: %d kernel calls (%.6f s), %d other events "
          "on the shared slab (%.6f s), %.6f s busy"
          % (len(kernels), total(union(kernels)) * 1e-9, len(slab),
             total(union(slab)) * 1e-9, busy * 1e-9), flush=True)
    return 100.0 * total(union(kernels + slab)) / busy
