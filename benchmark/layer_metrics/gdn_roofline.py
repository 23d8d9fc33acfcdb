"""The Olmo Hybrid cell's gated delta rule's share of its roofline: the
least time the chip could take for the delta rule the window's steps need
(``benchmark/work_olmo_hybrid.py``: every linear layer forward and backward
once, counted at a fixed chunk of 64; the larger of operations over peak
and bytes over bandwidth) over the device time under the ``delta_rule``
scope in every pass (a recomputed forward is time spent and work not
needed), by the step program's own table (``benchmark/step_scopes.py``)."""
from benchmark import step_scopes, work_olmo_hybrid


def read(trace, obs, cell, chip, say):
    if not obs.get("steps"):
        return None
    got = step_scopes.read(trace, obs, say)
    if got is None:
        return None
    spent = step_scopes.under(got, "delta_rule")
    if sum(spent.values()) <= 0:
        return None                 # the rule is off the path: silent
    tr = cell["traffic"]
    least, bound = work_olmo_hybrid.delta_rule_seconds(
        cell["config"], int(tr["batch"]), int(tr["seq"]), chip)
    say(f"delta rule, s by pass: {spent}; roofline bound by {bound}")
    return 100.0 * obs["steps"] * least / sum(spent.values())
