"""The expert layer's combine: device time of the operations under its
``combine`` scope (the live mask on the products' rows, each row's weight
and the scatter-add of the rows back to their tokens; backward, the
gathers that are its transpose), every pass, over the device's busy time
in the window, by the step program's own table
(``benchmark/step_scopes.py``). Scope names alone: any family's sizes."""
from benchmark import step_scopes


def read(trace, obs, cell, chip, say):
    return step_scopes.scope_share(
        trace, obs, say, "expert layer's combine", "combine")
