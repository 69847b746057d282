"""Tokens a live slot commits a round: one (the model's own choice after
its current token) and the drafted tokens it accepted, averaged over the
`decode.spec_round` spans of the run (`1 + accepted`: 1.0 at an
acceptance of zero, `1 + p` at `p`; a round's device work is the same at
any). What a round's time has to be divided by to read a token's.
Nothing where the program records no such span."""
from benchmark.lib import glm5_cost

LAYER = "scheduler"
UNIT = "tokens"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    rounds = glm5_cost.spec_rounds(run)
    if not rounds:
        return None
    return 1.0 + sum(a for a, _ in rounds) / len(rounds)
