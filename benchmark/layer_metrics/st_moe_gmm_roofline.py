"""The SmallThinker cell's grouped expert products' share of their roofline:
the least time for the held experts' three products forward and six backward
at the assignments that really landed on them (``obs["landed_by_layer"]``: a
step's, as the mean over the window's steps, from the program's running
``expert_load_total`` counts; the weights read once a pass), over the device
time of whatever multiplies the sorted rows with the stack of expert
matrices, found by that operand's shape."""
from benchmark import smallthinker_trace, work_smallthinker


def read(trace, obs, cell, chip, say):
    if trace is None or not obs.get("steps") \
            or "landed_by_layer" not in obs:
        return None
    is_gmm = smallthinker_trace.gmm_matcher(trace, cell)
    kernel_s = trace.op_seconds(is_gmm)
    if kernel_s <= 0:
        return None
    least, bound = work_smallthinker.expert_seconds(
        cell["config"], obs["landed_by_layer"], chip)
    say(f"grouped products: {trace.op_count(is_gmm)} events, "
        f"{kernel_s:.4f} s; roofline bound by {bound}")
    return 100.0 * obs["steps"] * least / kernel_s
