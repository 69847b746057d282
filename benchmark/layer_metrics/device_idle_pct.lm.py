"""Share of the traced sub-window in which no operation ran on the
device: 1 - (union of device-operation intervals) / window, averaged
over the chips of the cell."""
from benchmark.lib import trace_reduce

LAYER = "device"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


read = trace_reduce.idle_pct
