"""The prefills' chunked delta-rule scans against their roofline
(`ptpu.kda_scan`, composed lax, no kernel yet: its roofline all the
same). For the LIVE tokens of the traced admissions
(`kda_tokens`, `prompts` of the admission's `decode.loop.scatter`
phase), every KDA layer: the larger of the chunked form's FLOPs over
the bf16 peak and its bytes over the HBM peak (`lib/ling_cost.
kda_scan_cost`: q, k, v and g read and o written a token, a state
written a prompt) is the least time. The time spent is the union of the
events inside the `jit_ptpu_prefill_*` module events that touch a matrix
state or a chunk's tensors (`lib/ling_cost.patterns`; first chip). The
bucket's padding is scanned too and shows as a lower share. Nothing
where the phases carry no `kda_tokens`."""
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
    _, scan = ling_cost.kda_events(cfg, ops, modules, program_spans)
    admits = ling_cost.admissions(spans, modules, union(scan),
                                  program_spans)
    spent = sum(t for t, _ in admits)
    if not admits or spent <= 0:
        return None
    costs = [ling_cost.kda_scan_cost(cfg, float(c["kda_tokens"]),
                                     float(c["prompts"]))
             for _, c in admits]
    flops, nbytes = (sum(x) for x in zip(*costs))
    least = max(flops / run["peaks"]["flops"],
                nbytes / run["peaks"]["hbm_bytes_per_s"])
    print("kda_scan_roofline: %d admissions, %.0f live and %.0f padded "
          "tokens, %.3f TFLOP and %.3f GB of the scans, %.6f s at the "
          "roofline, %.6f s in the trace"
          % (len(admits), sum(float(c["kda_tokens"]) for _, c in admits),
             sum(float(c["kda_pad_tokens"]) for _, c in admits),
             flops / 1e12, nbytes / 1e9, least, spent), flush=True)
    return 100.0 * least / spent
