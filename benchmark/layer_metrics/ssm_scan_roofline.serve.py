"""The prefills' selective scans against their roofline (`ops/ssm.py`,
`ptpu.ssm_scan`; since PR 41 one Pallas call a state-space layer, the
lax form's `while` where the gate leaves a bucket to it). The least
time: what the LIVE rows of the traced admissions must move to and from
HBM, whatever implements the scan: `ssm_tokens` of the admission's
`decode.loop.scatter` phase (the first that opens after the prefill's
program started) x the state-space layers x (x and delta read, y
written: 3 x `d_inner`; B and C read: 2 x `d_state`) x 4 B, over the
HBM peak. The time spent: the scans' events inside the
`jit_ptpu_prefill_*` module events of those admissions (first chip),
found by `ssm_scan_time_pct.serve`'s two anchors, program by program:
the events whose own name holds the scope (the kernel's calls, 13 a
program), else the program's `while` events (a `while` carries no scope
in its name, and a prefill of this family has no other loop). The
bucket's padding moves nothing that counts: a form that walks it reads
lower.

The share is bounded by bytes, and the exponentials are a ceiling of
the same size: 81,920 a position a row on one transcendental slot are
~0.085 us where the bytes are 0.075 us at the peak, so ~45-50 is what a
perfect kernel reads and 105 cannot be reached. Nothing where the
phases carry no `ssm_tokens` (a program from before PR 41), where no
admission fell in the window, or for a configuration of another
family."""
from benchmark.lib import program_spans
from benchmark.lib.trace_reduce import total, union

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

SCOPE = "ptpu.ssm_scan"


def layer_bytes_per_token(cfg):
    """Bytes a live position of ONE state-space layer must move."""
    d_inner = int(cfg["mamba_expand"]) * int(cfg["hidden_size"])
    return 4 * (3 * d_inner + 2 * int(cfg["mamba_d_state"]))


def ssm_layers(cfg):
    """State-space layers of a Jamba-family stack: every layer that is
    not the period's attention layer."""
    period, offset = int(cfg["attn_layer_period"]), int(
        cfg["attn_layer_offset"])
    return sum(i % period != offset
               for i in range(int(cfg["num_hidden_layers"])))


def read(run):
    spans = program_spans.of_run(run)
    cfg = run["cfg"]
    if (not spans or "mamba_d_state" not in cfg
            or "attn_layer_period" not in cfg):
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    scatter = program_spans.LOOP + "scatter"
    scatters = sorted((s, c) for name, s, _, c, _ in spans["host"]
                      if name == scatter and "ssm_tokens" in c)
    if not ops or not modules or not scatters:
        return None
    spent = live = padded = 0.0
    anchors = {"scope": 0, "while": 0}
    for name, m0, md in sorted(modules, key=lambda m: m[1]):
        if "ptpu_prefill_" not in name:
            continue
        after = [c for s, c in scatters if s >= m0]
        if not after:  # the session ended before its scatter opened
            continue
        inside = [(n, s, d, text) for n, s, d, text in ops
                  if m0 <= s < m0 + md]
        named = [(s, s + d) for _, s, d, text in inside
                 if SCOPE in text.split(" = ", 1)[0]]
        loops = [(s, s + d) for n, s, d, _ in inside
                 if n.startswith("while")]
        anchor, found = ("scope", named) if named else ("while", loops)
        if not found:
            continue
        anchors[anchor] += len(found)
        spent += total(union(found)) * 1e-9
        live += float(after[0]["ssm_tokens"])
        padded += float(after[0].get("ssm_pad_tokens", 0))
    if spent <= 0 or live <= 0:
        return None
    nbytes = live * ssm_layers(cfg) * layer_bytes_per_token(cfg)
    least = nbytes / run["peaks"]["hbm_bytes_per_s"]
    print("ssm_scan_roofline: %d events by scope and %d by while, %.0f "
          "live and %.0f padded tokens a layer, %.3f GB of the scans, "
          "%.6f s at the HBM peak, %.6f s in the trace"
          % (anchors["scope"], anchors["while"], live, padded,
             nbytes / 1e9, least, spent), flush=True)
    return 100.0 * least / spent
