"""The sliding latent layers' attention as a share of the device's busy
time in the traced sub-window, prefill and decode together, first chip
(`ptpu.latent_ring_attend`): a prefill's flash calls over the window,
named after their scope, and in a step whatever reads or writes a ring
of latent rows (told by the ring's shape, `lib/dsa_cost.patterns`: an
XLA fusion carries no scope in its name on the chip). The projections
are not counted. Nothing where the configuration has no such layer or
no event matches."""
from benchmark.lib import dsa_cost, program_spans
from benchmark.lib.trace_reduce import total, union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "swa_kv_lora_rank" not in cfg or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    if not ops:
        return None
    told = dsa_cost.ring_events(cfg, ops)
    if not told:
        return None
    busy = total(union((s, s + d) for _, s, d, _ in ops))
    spent = total(union(told))
    print("latent_ring_time_pct: %d events of the window's attention and on "
          "the rings (%.6f s), %.6f s busy"
          % (len(told), spent * 1e-9, busy * 1e-9), flush=True)
    return 100.0 * spent / busy
