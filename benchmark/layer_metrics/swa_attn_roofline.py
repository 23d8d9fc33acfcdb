"""The window and full attention kernels' share of their roofline: the
least time the chip could take for the attention the window's steps need
(``benchmark/work_afmoe.py``: the band a window layer needs, the triangle a
full layer needs, k and v at the key/value heads; the larger of operations
over peak and bytes over bandwidth) over the device time of the Pallas
attention events, found by operand shape."""
from benchmark import afmoe_trace, work_afmoe


def read(trace, obs, cell, chip, say):
    if trace is None or not obs.get("steps"):
        return None
    is_attn = afmoe_trace.attention_matcher(trace, cell)
    kernel_s = trace.op_seconds(is_attn)
    if kernel_s <= 0:
        return None                 # the kernels are off the path: silent
    tr = cell["traffic"]
    least, bound = work_afmoe.attention_seconds(
        cell["config"], int(tr["batch"]), int(tr["seq"]), chip)
    say(f"attention kernels: {trace.op_count(is_attn)} events, "
        f"{kernel_s:.4f} s; roofline bound by {bound}")
    return 100.0 * obs["steps"] * least / kernel_s
