"""Mean host time of one call into the step (`TrainStep.__call__`): the
benchmark's own ``dispatch`` span, host clock, over the window's steps."""


def read(trace, obs, cell, chip, say):
    spans = [e - s for n, s, e in obs.get("spans", ()) if n == "dispatch"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
