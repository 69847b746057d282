"""The expert layers (router with its selection bias, the sort of the
pairs, the grouped product's loop; no shared expert)' share of the
device's busy time in the traced sub-window, prefill and decode
together, first chip, of a MiMo-V2-family configuration: the events
inside the `jit_ptpu_*` programs that a scope, a fused member, the scope
their result goes to or a weight they read marks as theirs
(`lib/mimo_cost.of_part`, over `lib/scope_time.py`'s join of the trace
with the executables' scope maps). An elementwise event anchored at a
temporary's name is nobody's: a lower bound. Nothing where no program of
the trace has a scoped map (the parent of the PR that added the scopes)
or the configuration is of another family."""
from benchmark.lib import mimo_cost, scope_time

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    cfg = run["cfg"]
    if not mimo_cost.is_family(cfg):
        return None
    return scope_time.share_of_busy(run, "jit_ptpu_",
                                    mimo_cost.of_part(cfg, "experts"))
