"""The expert layers (router, the sort of the pairs, the grouped product's loop, the shared expert)' share of the device's busy time
in the traced sub-window, prefill and decode together, first chip, of a
Solar-Open2-family configuration: the events inside the `jit_ptpu_*`
programs that a scope, a fused member, the scope their result goes to
or a weight they read marks as theirs (`lib/solar_cost.of_mixer`, over
`lib/scope_time.py`'s join of the trace with the executables' scope
maps). An elementwise event anchored at a temporary's name is nobody's:
a lower bound. Nothing where no program of the trace has a scoped map
(the parent of the PR that added the scopes) or the configuration is of
another family."""
from benchmark.lib import scope_time, solar_cost

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    if not solar_cost.is_family(run["cfg"]):
        return None
    return scope_time.share_of_busy(run, "jit_ptpu_",
                                    solar_cost.of_mixer("experts"))
