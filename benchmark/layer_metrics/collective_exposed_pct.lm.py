"""Share of the traced sub-window in which a collective runs on a
device and no other operation does (parallel layer: `ParallelExecutor`,
`ShardingPlan`), averaged over the chips. Nothing to read on one chip."""
LAYER = "parallel"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    tn = run.get("trace_numbers") or {}
    if not tn.get("devices") or run["chips"] < 2:
        return None
    return 100.0 * tn["collective_exposed_s"] / tn["window_s"]
