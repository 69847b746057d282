"""EVA's own share of the device's busy time in the traced sub-window,
prefill and decode together, first chip (`ops/eva.py`): the pooling of
chunks (`ptpu.eva_summaries`), a prefill's flash calls over each
window's own rows and over the summaries it sees (`ptpu.eva_prefill`),
a step's writes into the window's block and the summary rows and a
prefill's packing (`ptpu.eva_append`), and a step's attention over the
live range (`ptpu.eva_attn`). The kernels are told by name; an XLA
fusion carries no scope in its name on the chip, so the lax parts are
told by what only they build or read (`lib/eva_cost.patterns`): a LOWER
bound where the compiler folded a tensor into another shape (the merge
of a window's two flash calls by their log-sum-exp is such a part). The
projections and `W_o` are not counted: plain matmuls, as any attention
has. With `prefill_busy_pct.serve` it says how much of the cell the
mechanism is. Nothing where the configuration has no `chunk_size` or no
event matches."""
from benchmark.lib import eva_cost, program_spans
from benchmark.lib.trace_reduce import total, union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "chunk_size" not in cfg or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    if not ops:
        return None
    told = eva_cost.eva_events(cfg, ops)
    if not told:
        return None
    busy = total(union((s, s + d) for _, s, d, _ in ops))
    spent = total(union(told))
    by = [(what, total(union(eva_cost.eva_events(cfg, ops, k, p))) * 1e-9)
          for what, k, p in (
              ("ptpu.eva_attn", eva_cost.KERNELS[:1], []),
              ("ptpu.eva_prefill", eva_cost.KERNELS[1:], []),
              ("entries", (), eva_cost.patterns(cfg)[:1]),
              ("chunks", (), eva_cost.patterns(cfg)[1:]))]
    print("eva_time_pct: %d events of the pooling, the appends and the "
          "attention (%.6f s: %s), %.6f s busy"
          % (len(told), spent * 1e-9,
             ", ".join("%s %.6f" % w for w in by), busy * 1e-9), flush=True)
    return 100.0 * spent / busy
