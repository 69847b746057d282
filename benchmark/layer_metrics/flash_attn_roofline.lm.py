"""The Pallas flash-attention kernels' share of their roofline in the
training step: the least time the chip could take for the calls seen in
the trace (the larger of FLOPs over the bf16 peak and bytes over the HBM
peak, from the shapes, `lib/flops.flash_attention_cost`) over the time
the trace gives the kernels. Prints which bound holds."""
from benchmark.lib import flops, trace_reduce

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"

# The kernels carry no name of their own in the trace yet (PERF.md, Open
# questions): in the training step every Mosaic kernel is flash
# attention, the forward under autodiff's `jvp`, the backward under
# `transpose(jvp)`.
MOSAIC = r'custom_call_target="tpu_custom_call"'
FWD = r"^%?jvp_\S* = .*" + MOSAIC
BWD = r"^%?transpose_jvp\S* = .*" + MOSAIC


def read(run):
    if not run.get("trace"):
        return None
    t_fwd, n_fwd = trace_reduce.kernel_seconds(run["trace"], FWD)
    t_bwd, n_bwd = trace_reduce.kernel_seconds(run["trace"], BWD)
    if not n_fwd or t_fwd + t_bwd <= 0:
        return None
    cfg, mix = run["cfg"], run["mix"]
    dp = mp = 1
    if cfg.get("mesh"):
        sizes = dict(zip(cfg["mesh"]["axes"], cfg["mesh"]["shape"]))
        dp, mp = sizes.get("dp", 1), sizes.get("mp", 1)
    heads = cfg["num_attention_heads"]
    cost = flops.flash_attention_cost(
        mix["batch"] // dp, mix["seq"], heads // mp,
        cfg["hidden_size"] // heads, itemsize=2)
    # a split backward is two kernels per call of the backward
    n_bwd_calls = n_fwd
    least_f, bound_f = flops.roofline_seconds(
        cost["fwd_flops"], cost["fwd_bytes"], run["peaks"])
    least_b, bound_b = flops.roofline_seconds(
        cost["bwd_flops"], cost["bwd_bytes"], run["peaks"])
    least = n_fwd * least_f + (n_bwd_calls * least_b if n_bwd else 0.0)
    print("flash_attn_roofline: fwd %d calls %.6f s (%s-bound), bwd %d "
          "events %.6f s (%s-bound)" % (n_fwd, t_fwd, bound_f, n_bwd, t_bwd,
                                        bound_b), flush=True)
    return 100.0 * least / (t_fwd + t_bwd)
