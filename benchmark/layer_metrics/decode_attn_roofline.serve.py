"""The Pallas decode-attention kernel's share of its roofline in the
traced decode steps (`ops/kv_cache.py`, `ptpu.decode_attn`): the least
time the chip could take to stream the K and V rows the step attends
(2 x `attended` x heads x head_dim x itemsize bytes a call over the HBM
peak; one call a layer; the kernel is memory-bound, a single query row
per slot; itemsize from the slab operand's type in the call's own
text) over the time the trace gives those calls. `attended` is the
count the `decode.loop.dispatch` phase of the same step carries: the
live slots' lengths, which only the program knows. A call is matched to
its step through the `ptpu_decode_*` program it ran in."""
import re

from benchmark.lib import program_spans

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

KERNEL = "ptpu.decode_attn"
# the call's second operand is the K slab: `custom-call(f32[8,1,4096]{..}
# %q, f32[8,2048,4096]{..} %k, ...`
SLAB_TYPE = re.compile(r"custom-call\(\S+ \S+, (\w+)\[")
ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s8": 1}


def read(run):
    spans = program_spans.of_run(run)
    if not spans:
        return None
    ops = program_spans.first_device(spans["ops"])
    modules = program_spans.first_device(spans["modules"])
    # by the call's own name: a consumer's text names it as an operand
    calls = [op for op in ops if KERNEL in op[0]]
    kernels = sorted((s, d) for _, s, d, _ in calls)
    slab = SLAB_TYPE.search(calls[0][3]) if calls else None
    if not slab or slab.group(1) not in ITEMSIZE:
        return None
    cfg = run["cfg"]
    heads = cfg["num_attention_heads"]
    row = (2 * heads * (cfg["hidden_size"] // heads)
           * ITEMSIZE[slab.group(1)])
    least = spent = 0.0
    n = 0
    for name, m0, md in modules:
        if "ptpu_decode_" not in name:
            continue
        step = program_spans.step_of(spans["host"], m0)
        if step is None:
            continue
        for s, d in kernels:
            if m0 <= s < m0 + md:
                least += step["attended"] * row / run["peaks"][
                    "hbm_bytes_per_s"]
                spent += d * 1e-9
                n += 1
    if not n or spent <= 0:
        return None
    print("decode_attn_roofline: %d calls, %.6f s in the trace, %.6f s at "
          "the HBM peak" % (n, spent, least), flush=True)
    return 100.0 * least / spent
