"""Device idle that lies under the per-token round trip of
`DecodeServer._loop`, as a share of the traced sub-window: the gaps
between device operations on the first chip, split by overlap over the
innermost `ptpu.decode.loop.*` phase (`observability/tracing.phase`,
read out of the xplane by `lib/program_spans.py`), summed over the
phases of an iteration outside admission and parking: `recv`, `feeds`,
`dispatch`, `fetch`, `retire`, the iteration's own time between them,
and `draft` on a speculative server. With `idle_admit_pct.serve` and
`idle_unattributed_pct.serve` it sums to `device_idle_pct.serve` less
the idle under `park`. The whole table by phase is printed and written
to `idle_by_phase.json`."""
from benchmark.lib import program_spans

LAYER = "scheduler"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run):
    return program_spans.group_idle_pct(run, "step")
