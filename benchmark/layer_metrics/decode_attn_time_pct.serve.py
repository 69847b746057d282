"""The Pallas decode-attention kernel's share of the device's busy time
in the traced sub-window (kernels layer, `ops/kv_cache.py`). Its
roofline share needs the positions attended per call, which only the
program knows: left to the `tracing` issue."""
from benchmark.lib import trace_reduce

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# No name of its own in the trace yet: the decode kernel is the Mosaic
# call whose result is one query row per slot, f32[slots, 1, heads * d]
# (the prefill's flash kernel returns whole sequences).
KERNEL = (r'= f32\[\d+,1,\d+(,\d+)?\]\S* custom-call\(.*'
          r'custom_call_target="tpu_custom_call"')


def read(run):
    tn = run.get("trace_numbers") or {}
    if not run.get("trace") or not tn.get("devices"):
        return None
    secs, n = trace_reduce.kernel_seconds(run["trace"], KERNEL)
    if not n:
        return None
    return 100.0 * secs / tn["busy_s"]
