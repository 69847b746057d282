"""The Pallas flash-attention kernels' share of the device's busy time
in the traced training steps (`ops/attention.py`): the events whose text
names `ptpu.flash_fwd` or `ptpu.flash_bwd*` (the kernels' own names,
`ops.attention.named_pallas_call`), summed and averaged over the chips.
Found under `shard_map` too, where the compiler names the call after
the kernel's scope alone: the first kernel metric the mesh cell reports.
Prints the seconds, beside those `flash_attn_roofline.lm` prints."""
from benchmark.lib import trace_reduce

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"

# anchored on the instruction's own name (`%jvp_ptpu.flash_fwd_.3 = ...`,
# `%ptpu.flash_bwd_dq.1 = ...`): a consumer's text names it as an operand
FWD = r"^%?[\w.]*ptpu\.flash_fwd[\w.]*( = |$)"
BWD = r"^%?[\w.]*ptpu\.flash_bwd[\w.]*( = |$)"


def read(run):
    tn = run.get("trace_numbers") or {}
    if not run.get("trace") or not tn.get("devices"):
        return None
    t_fwd, n_fwd = trace_reduce.kernel_seconds(run["trace"], FWD)
    t_bwd, n_bwd = trace_reduce.kernel_seconds(run["trace"], BWD)
    if not n_fwd + n_bwd:
        return None
    print("flash_attn_time_pct: fwd %d events %.6f s, bwd %d events %.6f s"
          % (n_fwd, t_fwd, n_bwd, t_bwd), flush=True)
    return 100.0 * (t_fwd + t_bwd) / tn["busy_s"]
