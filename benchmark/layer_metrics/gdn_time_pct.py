"""The Olmo Hybrid cell's gated delta rule: device time of every operation
under the linear layers' ``delta_rule`` scope (the chunked kernel and what
its call puts round it), forward, recomputed and backward, over the
device's busy time in the window, by the step program's own table
(``benchmark/step_scopes.py``)."""
from benchmark import step_scopes


def read(trace, obs, cell, chip, say):
    return step_scopes.scope_share(trace, obs, say, "delta rule",
                                   "delta_rule")
