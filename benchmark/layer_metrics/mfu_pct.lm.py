"""Model FLOP/s utilization of the LM training step: FLOPs the forward
and backward passes need per token (`lib/flops.py`: causal attention
counted once, recomputation and padding not counted) times the tokens
per second of this run's untraced window, over chips times the bf16
peak."""
from benchmark.lib import flops

LAYER = "model step"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "host_clock"


def read(run):
    rate = run["end_to_end"].get("train_tokens_per_s")
    if not rate:
        return None
    cfg, mix = run["cfg"], run["mix"]
    per_token = flops.lm_train_flops_per_token(
        cfg, int(cfg["num_hidden_layers"]["train"]), mix["seq"])
    return 100.0 * per_token * rate / (run["chips"] * run["peaks"]["flops"])
