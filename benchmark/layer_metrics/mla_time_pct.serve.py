"""Latent attention's share of the device's busy time in the traced
sub-window, prefill and decode together, first chip (`ops/mla.py`): in a
decode step whatever reads or writes a latent slab (the absorbed
attention `ptpu.mla_decode` and the appends `ptpu.mla_append`: lax
paths, told by the slab's feed name or its shape, since an XLA fusion
carries no scope in its name on the chip: `lib/mla_cost.patterns`); in
a prefill the flash calls (`ptpu.flash_fwd`, named after their scope),
`W_kvb`'s product (`ptpu.mla_expand`, told by the parameter's name) and
what writes the latent rows. The down and up projections of the queries
and `W_o` are not counted: plain matmuls, as any attention has. With
`prefill_busy_pct.serve` it says how much of the cell the mechanism is.
Nothing where the configuration has no latent rank or no event
matches."""
from benchmark.lib import mla_cost, program_spans
from benchmark.lib.trace_reduce import total, union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

KERNELS = ("ptpu.flash_fwd",)


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "kv_lora_rank" not in cfg or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    if not ops:
        return None
    pats = mla_cost.patterns(cfg)
    touch = pats["slab"] + pats["rows"] + pats["expand"]
    kernels = [(s, s + d) for n, s, d, _ in ops
               if any(k in n for k in KERNELS)]
    latent = [(s, s + d) for n, s, d, text in ops
              if not any(k in n for k in KERNELS)
              and not n.startswith("while")
              and any(p in text for p in touch)]
    if not kernels and not latent:
        return None
    busy = total(union((s, s + d) for _, s, d, _ in ops))
    print("mla_time_pct: %d flash calls (%.6f s), %d events on latent "
          "rows or W_kvb (%.6f s), %.6f s busy"
          % (len(kernels), total(union(kernels)) * 1e-9, len(latent),
             total(union(latent)) * 1e-9, busy * 1e-9), flush=True)
    return 100.0 * total(union(kernels + latent)) / busy
