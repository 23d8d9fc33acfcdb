"""The Olmo Hybrid cell's linear-attention mixers, whole: device time of
every operation under the ``linear_attn`` scope (projections, short
convolutions, norms and gates, the delta rule, the gated norm and the
output projection), every pass, over the device's busy time in the window
(``benchmark/step_scopes.py``)."""
from benchmark import step_scopes


def read(trace, obs, cell, chip, say):
    return step_scopes.scope_share(trace, obs, say, "linear attention",
                                   "linear_attn")
