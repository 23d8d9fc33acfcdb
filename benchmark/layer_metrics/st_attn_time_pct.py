"""The SmallThinker cell's window and full attention kernels' device time
over the device's busy time in the window."""
from benchmark import smallthinker_trace


def read(trace, obs, cell, chip, say):
    if trace is None:
        return None
    kernel_s = trace.op_seconds(
        smallthinker_trace.attention_matcher(trace, cell))
    busy_s = trace.busy_s()
    if kernel_s <= 0 or busy_s <= 0:
        return None
    return 100.0 * kernel_s / busy_s
