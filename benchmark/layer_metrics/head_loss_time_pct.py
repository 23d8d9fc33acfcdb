"""The head and the loss: device time of every operation under the
trainers' ``head_loss`` scope, every pass (the chunked head takes its
gradient products inside its forward rule: they read backward), over the
device's busy time in the window, by the step program's own table
(``benchmark/step_scopes.py``)."""
from benchmark import step_scopes


def read(trace, obs, cell, chip, say):
    return step_scopes.scope_share(trace, obs, say, "head and loss",
                                   "head_loss")
