"""The SmallThinker cell's expert layer's device time over the device's busy
time in the window: every operation whose path in ``TrainStep.op_scopes()``
runs through the scopes ``router`` (a child of the block, ahead of the
attention) or ``experts`` (dispatch, grouped products, combine), forward,
recomputed and backward."""
from benchmark import smallthinker_trace


def read(trace, obs, cell, chip, say):
    if trace is None or not obs.get("op_scopes"):
        return None
    moe_s, by = smallthinker_trace.moe_scope_seconds(trace,
                                                     obs["op_scopes"])
    busy_s = trace.busy_s()
    if moe_s <= 0 or busy_s <= 0:
        return None
    say("expert layer, s by scope: " + ", ".join(
        f"{k} {v:.4f}" for k, v in sorted(by.items())))
    return 100.0 * moe_s / busy_s
