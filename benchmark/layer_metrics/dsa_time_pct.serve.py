"""Learned sparse attention's share of the device's busy time in the
traced sub-window, prefill and decode together, first chip
(`ops/dsa.py`): the indexer's products (`ptpu.dsa_index`), the exact
choice of `index_topk` rows (`ptpu.dsa_select`) and the attention under
it (`ptpu.dsa_attend`: a prefill's flash calls under the (query, key)
mask, named after their scope; a step's absorbed attention on the latent
slab under the chosen rows' mask, and the appends to the two slabs). An
XLA fusion or loop carries no scope in its name on the chip, so the lax
events are told by what only these three build or read
(`lib/dsa_cost.patterns`; a loop's own event is left out and its body's
events told one by one): a LOWER bound where the compiler folded a
tensor into another shape. The latent projections and `W_o` are not
counted: plain matmuls, as any attention has. With
`prefill_busy_pct.serve` it says how much of the cell the mechanism is.
Nothing where the configuration has no indexer or no event matches."""
from benchmark.lib import dsa_cost, program_spans
from benchmark.lib.trace_reduce import total, union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "index_topk" not in cfg or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    if not ops:
        return None
    told = dsa_cost.dsa_events(cfg, ops)
    if not told:
        return None
    busy = total(union((s, s + d) for _, s, d, _ in ops))
    spent = total(union(told))
    print("dsa_time_pct: %d events of the indexer, the choice and the "
          "attention under it (%.6f s), %.6f s busy"
          % (len(told), spent * 1e-9, busy * 1e-9), flush=True)
    return 100.0 * spent / busy
