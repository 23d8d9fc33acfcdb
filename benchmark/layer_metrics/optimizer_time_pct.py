"""The update's device time over the device's busy time in the window:
every operation under the trainers' ``optimizer`` or ``grad_accumulate``
scope (the pass ``update`` of ``runtime_profile.read_scope``), by the
step program's own table (``benchmark/step_scopes.py``)."""
from benchmark import step_scopes


def read(trace, obs, cell, chip, say):
    return step_scopes.pass_share(trace, obs, say, "optimizer update",
                                  "update")
