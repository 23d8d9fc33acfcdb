"""The flash kernels' share of their roofline: the least time the chip
could take for the attention the window's steps need (the larger of
operations over peak and bytes over bandwidth, causal as the needed
half) over the device time of the forward and backward kernel events."""
from benchmark import flash as _flash


def read(trace, obs, cell, chip, say):
    if trace is None or not obs.get("steps"):
        return None
    is_flash = _flash.matcher(trace, cell)
    kernel_s = trace.op_seconds(is_flash)
    if kernel_s <= 0:
        return None                 # the kernel is off the path: silent
    least, bound = _flash.needed_seconds(obs, cell, chip)
    say(f"flash kernels: {trace.op_count(is_flash)} events, "
        f"{kernel_s:.4f} s; roofline bound by {bound}")
    return 100.0 * least / kernel_s
