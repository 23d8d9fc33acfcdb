"""The backward pass's device time over the device's busy time in the
window: every operation whose path in the step program's own table
(``benchmark/step_scopes.py``) is marked ``transpose(`` by JAX and is
neither recomputed forward nor the update; where the expert layer's
backward conditional runs its rung again before the transpose, that
second run reads backward too."""
from benchmark import step_scopes


def read(trace, obs, cell, chip, say):
    return step_scopes.pass_share(trace, obs, say, "backward pass",
                                  "backward")
