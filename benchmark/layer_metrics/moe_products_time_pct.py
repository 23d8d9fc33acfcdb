"""The expert layer's products: device time of the operations under its
``products`` scope (the three grouped products and the gate's activation;
backward, their six), every pass, over the device's busy time in the
window, by the step program's own table (``benchmark/step_scopes.py``).
Scope names alone: any family's sizes."""
from benchmark import step_scopes


def read(trace, obs, cell, chip, say):
    return step_scopes.scope_share(
        trace, obs, say, "expert layer's products", "products")
