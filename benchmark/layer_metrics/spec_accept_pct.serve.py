"""How often the model takes what its prediction layer drafted: the
drafted tokens ACCEPTED over those proposed, summed over the
`decode.spec_round` spans of the run (one a live slot a round:
`DecodeServer._spec_commit`; the recorder's ring keeps the newest). On
weights from a seed the draft agrees with the model at chance over the
vocabulary, so this reads about 0: the cell's rounds then commit one
token each and its rate is the round's cost at an acceptance of zero
(PERF.md section 7); a deployment's 85-90% (DeepSeek-V3, section 5.4.3)
would commit `1 + p`. Nothing where the program records no such span."""
from benchmark.lib import glm5_cost

LAYER = "scheduler"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(run):
    rounds = glm5_cost.spec_rounds(run)
    proposed = sum(p for _, p in rounds)
    if not proposed:
        return None
    accepted = sum(a for a, _ in rounds)
    print("spec_accept_pct: %d slot-rounds, %.0f drafted tokens accepted "
          "of %.0f" % (len(rounds), accepted, proposed), flush=True)
    return 100.0 * accepted / proposed
