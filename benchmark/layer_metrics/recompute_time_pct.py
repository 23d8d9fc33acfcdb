"""What recomputation costs: the device time of the forward operations
that run a second time inside the backward pass
(``checkpoint/rematted_computation`` on the path, in the step program's
own table, ``benchmark/step_scopes.py``) over the device's busy time in
the window. The ceiling of any memory-for-recomputation lever."""
from benchmark import step_scopes


def read(trace, obs, cell, chip, say):
    return step_scopes.pass_share(trace, obs, say, "recomputed forward",
                                  "recompute")
