"""The expert layers' share of the device's busy time in the traced
sub-window, prefill and decode together (`ops/moe.py`: `ptpu.moe_route`,
`ptpu.moe_experts`, `ptpu.moe_shared`), first chip.

An XLA fusion carries no scope in its own name on the chip, so the
events are told by what they read (`lib/moe_cost.patterns`): an operand
of the held experts' shape, or a parameter of a `.moe.` name (router,
shared expert), or, for the grouped product, by the TPU compiler's own
name for it (`ragged-dot-none.N`); and, inside the
`ptpu_prefill_*` and `ptpu_decode_*` programs, the `while` events: the
grouped product's loop over blocks of sorted pairs (with its row gather
and scatter-add) and the sort of the pairs are the only loops such a
program has (attention there is a custom call or straight-line). What
is neither (the top-k, elementwise work between them) is not seen: a
lower bound, a little short. Nothing where the configuration has no
experts or no event matches."""
from benchmark.lib import moe_cost, program_spans
from benchmark.lib.trace_reduce import total, union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "moe_intermediate_size" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    if not ops:
        return None
    pats = moe_cost.patterns(cfg)["moe"]
    programs = program_spans.module_intervals(modules, "ptpu_")

    def in_program(s):
        return any(a <= s < b for a, b in programs)

    named = [(s, s + d) for n, s, d, text in ops
             if not n.startswith("while") and (
                 n.startswith("ragged-dot")
                 or any(p in text for p in pats))]
    loops = [(s, s + d) for n, s, d, _ in ops
             if n.startswith("while") and in_program(s)]
    if not named and not loops:
        return None
    busy = total(union((s, s + d) for _, s, d, _ in ops))
    moe = total(union(named + loops))
    print("moe_time_pct: %d events by operand (%.6f s), %d loops "
          "(%.6f s), %.6f s busy"
          % (len(named), total(union(named)) * 1e-9, len(loops),
             total(union(loops)) * 1e-9, busy * 1e-9), flush=True)
    return 100.0 * moe / busy
