"""Seconds of set-up spent tracing functions to jaxprs and lowering
them to MLIR modules: the program's ``compile.trace`` and
``compile.lower`` spans that ended before the window, overlaps once (an
inner function's trace lies inside its caller's)."""
from benchmark import ring
from benchmark.trace import total, union


def read(trace, obs, cell, chip, say):
    evs = ring.before_window(obs, ("compile.trace", "compile.lower"), say)
    if evs is None:
        return None
    say(f"set-up traced or lowered {len(evs)} times; longest: "
        f"{ring.longest(evs)}")
    return total(union((s, e) for _, s, e, _ in evs))
