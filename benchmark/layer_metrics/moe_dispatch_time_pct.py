"""The expert layer's dispatch: device time of the operations under its
``sort`` (the stable sort by expert, the group sizes) or ``dispatch``
(slot and token indices, the live mask, the gather of each sorted row's
token; backward, the gather's transpose: a scatter-add) scope, which only
the expert layer opens, every pass, over the device's busy time in the
window, by the step program's own table (``benchmark/step_scopes.py``).
Scope names alone: any family's sizes."""
from benchmark import step_scopes


def read(trace, obs, cell, chip, say):
    return step_scopes.scope_share(
        trace, obs, say, "expert layer's sort and dispatch", "sort",
        "dispatch")
