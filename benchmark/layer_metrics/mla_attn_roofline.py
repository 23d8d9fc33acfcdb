"""The latent attention kernels' share of their roofline: the least time
the chip could take for the causal triangle the window's steps need at the
PUBLISHED head sizes (``benchmark/work_deepseek_v3.py``: q, k, dq, dk at
192 columns, v, o, do, dv at 128; the larger of operations over peak and
bytes over bandwidth) over the device time of the Pallas attention events,
found by operand shape. Columns a kernel pads on are in the time alone."""
from benchmark import deepseek_v3_trace, work_deepseek_v3


def read(trace, obs, cell, chip, say):
    if trace is None or not obs.get("steps"):
        return None
    is_attn = deepseek_v3_trace.attention_matcher(trace, cell)
    kernel_s = trace.op_seconds(is_attn)
    if kernel_s <= 0:
        return None                 # the kernels are off the path: silent
    tr = cell["traffic"]
    least, bound = work_deepseek_v3.attention_seconds(
        cell["config"], int(tr["batch"]), int(tr["seq"]), chip)
    say(f"latent attention kernels: {trace.op_count(is_attn)} events, "
        f"{kernel_s:.4f} s; roofline bound by {bound}")
    return 100.0 * obs["steps"] * least / kernel_s
