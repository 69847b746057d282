"""The decode steps' delta-rule updates at 64 heads against their memory
roofline (`ops/kda.py`, `ptpu.kda_step`: one Pallas call a KDA layer
whose block of heads' states is read once and written once in place;
`kda_step_roofline.serve`, an accepted file, reads Ling's keys and its
32 heads). An update HAS to read and write a live slot's matrix states
once (`2 x kda_state_bytes` of the step's `decode.loop.dispatch` phase)
and move its q, k, v, g and o rows (`lib/solar_cost.kda_step_bytes`);
that over the HBM peak is the least time. The time spent is that of the
Mosaic calls named `ptpu.kda_step` inside the `jit_ptpu_decode_*`
module events of the same steps (first chip). The kernel walks EVERY
slot's states, live or not, which shows as a lower share where slots
stand empty. Nothing where no event carries the name (the lax form) or
the configuration is of another family."""
from benchmark.lib import program_spans, solar_cost
from benchmark.lib.trace_reduce import union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if not spans or not solar_cost.is_family(cfg) or "serve" not in cfg:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    calls = solar_cost.kernel_events(ops, solar_cost.KDA_STEP)
    steps = [(t, c) for t, c in solar_cost.decode_steps(
        spans, modules, union(calls), program_spans) if t > 0]
    spent = sum(t for t, _ in steps)
    if not steps or spent <= 0:
        return None
    nbytes = sum(solar_cost.kda_step_bytes(
        cfg, float(c["kda_state_bytes"]), float(c["active"]))
        for _, c in steps)
    least = nbytes / run["peaks"]["hbm_bytes_per_s"]
    print("kda_wide_step_roofline: %d steps, %.6f s in the kernel's calls, "
          "%.6f s at the HBM peak (%.3f GB a step)"
          % (len(steps), spent, least, nbytes / len(steps) / 1e9),
          flush=True)
    return 100.0 * least / spent
