"""Programs XLA compiled between the window's two syncs (the program's
``compilation.counters.xla_compiles()``, after minus before). Anything
but 0 is a finding."""


def read(trace, obs, cell, chip, say):
    return obs.get("compiles_in_window")
