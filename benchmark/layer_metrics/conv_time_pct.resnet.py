"""The convolutions' share of the device's busy time (`resnet50.train`):
events whose class is `fl.conv2d`, forward and backward, by their own
scope, their fusion's first member's or, for the wait on a filter's
prefetch, the convolution it is for (`lib/scope_time.leaf_of`): the
matrix unit's work. The table's other rows (`fl.batch_norm`,
`fl.elementwise_add`, `fl.pool2d`, `fl.momentum`, `unnamed` copies) are
the step's reduction by KIND. Nothing where the step's program has no
scoped map."""
from benchmark.lib import scope_time

LAYER = "model step"
UNIT = "%"
MOVES = "train_images_per_s"
SOURCE = "device_trace"


def read(run):
    return scope_time.share_of_busy(
        run, "", lambda entry, m: (scope_time.leaf_of(entry) or "")
        .startswith("fl.conv2d"))
