"""The selective scans' share of the device's busy time in the traced
sub-window: the time of the scan loops inside the `jit_ptpu_prefill_*`
programs (`ops/ssm.py`, `ptpu.ssm_scan`: a plain `lax.scan`, one
`while` a state-space layer a prefill) over the busy time (first chip).

Anchor: the `while` events that lie inside a prefill program's
module-line event. A `while` instruction carries no scope in its own
name on the chip (`while.12`; the `ptpu.ssm_scan` scope is in the
metadata of the operations of its body, not in its name), and a prefill
program of this family has no other loop: attention there is a custom
call and everything else is straight-line. Where an event's own text
does name the scope, those events are taken instead (said in the
output). When the scan becomes a kernel, its roofline share is that
PR's to add, under the kernel's own name. Nothing where no prefill
program of the trace holds a loop."""
from benchmark.lib import program_spans
from benchmark.lib.trace_reduce import total, union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

SCOPE = "ptpu.ssm_scan"


def read(run):
    spans = program_spans.of_run(run)
    if not spans or "mamba_d_state" not in run["cfg"]:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    prefill = program_spans.module_intervals(modules, "ptpu_prefill_")
    if not ops or not prefill:
        return None

    def inside(s):
        return any(a <= s < b for a, b in prefill)

    loops = [(s, s + d) for n, s, d, _ in ops
             if n.startswith("while") and inside(s)]
    named = [(s, s + d) for n, s, d, text in ops
             if SCOPE in text.split(" = ", 1)[0] and inside(s)]
    anchor, found = ("scope", named) if named else ("while", loops)
    if not found:
        return None
    busy = total(union((s, s + d) for _, s, d, _ in ops))
    scan = total(union(found))
    print("ssm_scan_time_pct: anchor %s, %d events, %.6f s of %.6f s busy"
          % (anchor, len(found), scan * 1e-9, busy * 1e-9), flush=True)
    return 100.0 * scan / busy
