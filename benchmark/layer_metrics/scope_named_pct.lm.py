"""How much of the chip's time the program can name (`opt-6.7b.train`): of the
operation time inside the programs of the traced sub-window whose
executable has a scoped map (`paddle_tpu.observability.scopes`), the
share in events whose HLO instruction, or an instruction inside its
fusion, carries a Fluid op's scope `fl.<type>:<anchor>` or a
mechanism's `ptpu.*`, or, where the compiler made it with neither (the
wait for a weight's prefetch, a `ragged-dot`), whose result goes to one
that does (`lib/scope_time.py`, which prints the table by program and
by class; first chip; `while` and `call` left out). What is left is
the compiler's own: copies and layout changes nothing scoped reads.
Nothing where no program of the trace has a scoped map (the parent of
the PR that opened the scopes; a trainer that acquires lazily)."""
from benchmark.lib import scope_time

LAYER = "model step"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return scope_time.named_pct(run)
