"""The SmallThinker cell's window and full attention kernels' share of their
roofline: the least time the chip could take for the attention the window's
steps need (``benchmark/work_smallthinker.py``: the band a window layer
needs, the triangle a full layer needs, at the published 28 query heads on
4 key/value heads of 128, k and v bytes at the 4; the larger of operations
over peak and bytes over bandwidth) over the device time of the Pallas
kernels whose operand is [batch, kv heads, group, sequence, head size],
found by shape."""
from benchmark import smallthinker_trace, work_smallthinker


def read(trace, obs, cell, chip, say):
    if trace is None or not obs.get("steps"):
        return None
    is_attn = smallthinker_trace.attention_matcher(trace, cell)
    kernel_s = trace.op_seconds(is_attn)
    if kernel_s <= 0:
        return None                 # the kernels are off the path: silent
    tr = cell["traffic"]
    least, bound = work_smallthinker.attention_seconds(
        cell["config"], int(tr["batch"]), int(tr["seq"]), chip)
    say(f"attention kernels: {trace.op_count(is_attn)} events, "
        f"{kernel_s:.4f} s; roofline bound by {bound}")
    return 100.0 * obs["steps"] * least / kernel_s
