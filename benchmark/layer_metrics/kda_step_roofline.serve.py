"""The decode steps' delta-rule updates against their memory roofline
(`ptpu.kda_step`, exact lax, no kernel yet: its roofline all the same,
so that the kernel that replaces it is measured against the same
yardstick). An update HAS to read and write a live slot's matrix states
once (`2 x kda_state_bytes` of the step's `decode.loop.dispatch` phase)
and move its q, k, v, g and o rows (`lib/ling_cost.kda_step_bytes`);
that over the HBM peak is the least time. The time spent is the union of
the events inside the `jit_ptpu_decode_*` module events of the same
steps that touch a matrix state (told by its shape,
`lib/ling_cost.patterns`; first chip). The lax form reads EVERY slot's
states, live or not, and passes over a state three times (the two
products, then the update): both show as a lower share. Nothing where
the phases carry no `kda_state_bytes`."""
from benchmark.lib import ling_cost, program_spans
from benchmark.lib.trace_reduce import union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or "kda_lower_bound" not in cfg or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    step, _ = ling_cost.kda_events(cfg, ops, modules, program_spans)
    steps = ling_cost.decode_steps(spans, modules, union(step),
                                   program_spans)
    spent = sum(t for t, _ in steps)
    if not steps or spent <= 0:
        return None
    nbytes = sum(ling_cost.kda_step_bytes(
        cfg, float(c["kda_state_bytes"]), float(c["active"]))
        for _, c in steps)
    least = nbytes / run["peaks"]["hbm_bytes_per_s"]
    print("kda_step_roofline: %d steps, %.6f s on the states in the trace, "
          "%.6f s at the HBM peak (%.3f GB a step)"
          % (len(steps), spent, least, nbytes / len(steps) / 1e9),
          flush=True)
    return 100.0 * least / spent
