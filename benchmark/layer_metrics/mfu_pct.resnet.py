"""Model FLOP/s utilization of the ResNet-50 training step: 3 x the
forward FLOPs per image times images per second over the bf16 peak."""
from benchmark.lib import flops

LAYER = "model step"
UNIT = "%"
MOVES = "train_images_per_s"
SOURCE = "host_clock"


def read(run):
    rate = run["end_to_end"].get("train_images_per_s")
    if not rate:
        return None
    return (100.0 * flops.resnet50_train_flops_per_image() * rate
            / (run["chips"] * run["peaks"]["flops"]))
