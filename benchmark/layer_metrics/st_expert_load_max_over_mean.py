"""The fullest held expert's tokens over the mean held expert's, over the
held experts of all the SmallThinker cell's layers, at the window's last
fetch (the ``expert_load`` buffers of the live step). 1 is an even
routing."""
import numpy as np

from benchmark.reference.smallthinker import sizes


def read(trace, obs, cell, chip, say):
    load = obs.get("expert_load")
    if load is None:
        return None
    z = sizes(cell["config"])
    held = np.asarray(load, np.float64)[:, z["offset"]:z["offset"]
                                        + z["held"]]
    if held.size == 0 or held.mean() <= 0:
        return None
    return float(held.max() / held.mean())
