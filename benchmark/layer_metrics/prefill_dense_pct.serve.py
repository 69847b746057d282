"""The dense products' share of the device's busy time inside the
prefill programs: events inside `jit_ptpu_prefill_*` whose scope is a
Fluid `mul`, `matmul` or `fc` against a weight, outside any `ptpu.*`
scope, and the waits for such a weight's prefetch (`lib/scope_time.
is_dense`, `is_dense_wait`): the projections, the FFNs and the head of
a prefill, which pay for every row of the BUCKET. Times the
padding share (`prompt_rows` beside `bucket_rows` on `scatter`) it is
what prefilling live rows alone could win; what is left of
`prefill_busy_pct.serve` after it and the mechanisms' `*_time_pct` is a
printed row each of `lib/scope_time.py`'s table. Lower is better at a
given traffic: the same prompts prefilled in less dense time. Nothing
where no prefill program of the trace has a scoped map."""
from benchmark.lib import scope_time

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return scope_time.share_of_busy(
        run, "jit_ptpu_prefill_",
        lambda entry, m: scope_time.is_dense(entry, m["params"])
        or scope_time.is_dense_wait(entry))
