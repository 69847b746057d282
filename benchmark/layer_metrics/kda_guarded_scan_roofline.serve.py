"""The prefills' chunked delta-rule scans under a gate with NO lower
bound against their roofline (`ops/kda.py`, `ptpu.kda_scan` in its
GUARDED form: one Pallas call a KDA layer). For the LIVE tokens of the
traced admissions (`kda_tokens`, `prompts` of the admission's
`decode.loop.scatter` phase), every KDA layer: the larger of the
chunked form's FLOPs over the bf16 peak and its bytes over the HBM peak
(`lib/solar_cost.kda_scan_cost`: q, k, v and g read and o written a
token, a state written a prompt) is the least time. The time spent is
that of the Mosaic calls named `ptpu.kda_scan` inside the
`jit_ptpu_prefill_*` module events (first chip). The bucket's padding
past a row's last live block is neither fetched nor computed; inside
that block it is, and shows as a lower share. The guarded form's own
block costs 15 exponentials a (token, channel) on the vector units,
which neither peak counts: a perfect kernel reads well under 100.
Nothing where no event carries the name (the composed lax form: a TPU
program traced onto it is a fault, and `paddle_tpu_kda_scan_traces_total
{path="lax",form="guarded"}` says so) or the configuration is of
another family."""
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
    scans = solar_cost.kernel_events(ops, solar_cost.KDA_SCAN)
    admits = solar_cost.admissions(spans, modules, union(scans),
                                   program_spans)
    spent = sum(t for t, _ in admits)
    if not admits or spent <= 0:
        return None
    costs = [solar_cost.kda_scan_cost(cfg, float(c["kda_tokens"]),
                                      float(c["prompts"]))
             for _, c in admits]
    flops, nbytes = (sum(x) for x in zip(*costs))
    least = max(flops / run["peaks"]["flops"],
                nbytes / run["peaks"]["hbm_bytes_per_s"])
    print("kda_guarded_scan_roofline: %d admissions, %d calls, %.0f live "
          "and %.0f padded tokens, %.3f TFLOP and %.3f GB of the scans, "
          "%.6f s at the roofline, %.6f s in the trace"
          % (len(admits), len(scans),
             sum(float(c["kda_tokens"]) for _, c in admits),
             sum(float(c["kda_pad_tokens"]) for _, c in admits),
             flops / 1e12, nbytes / 1e9, least, spent), flush=True)
    return 100.0 * least / spent
