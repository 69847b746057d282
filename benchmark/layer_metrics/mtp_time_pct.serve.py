"""The prediction layer's share of the device's busy time, rounds and
prefills together, first chip: the events inside the `jit_ptpu_*`
programs that a scope, a fused member's scope or the scope of the
instruction their result goes to lays under a parameter of the
prediction layer (`models/jamba.py: _mtp` names every one of them
`<prefix>.mtp.*`: the two norms, `eh_proj`, its decoder layer's mixer,
router and experts, and the kernels called under them). A LOWER bound:
an elementwise operation between two of its products has no parameter
to be told by, and the shared head's second product counts with the
head. In this cell it is one layer of six where a deployment has one of
79: its share here is thirteen times a deployment's. Nothing where no
program of the trace has a scoped map or none carries such a scope."""
from benchmark.lib import scope_time

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"
MARK = ".mtp."


def _mtp(entry, _map):
    if entry is None:
        return False
    return any(MARK in leaf for key in ("scope", "members", "users")
               for leaf in entry.get(key) or ())


def read(run):
    val = scope_time.share_of_busy(run, "jit_ptpu_", _mtp)
    return val if val else None
