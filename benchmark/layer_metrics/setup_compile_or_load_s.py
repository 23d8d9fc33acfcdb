"""Seconds of set-up inside the backend's compile path, which compiles
or loads from the persistent cache (``first_step_s``'s line says
which): the program's ``compile.backend`` spans that ended before the
window, summed."""
from benchmark import ring


def read(trace, obs, cell, chip, say):
    evs = ring.before_window(obs, ("compile.backend",), say)
    if evs is None:
        return None
    say(f"set-up compiled or loaded {len(evs)} programs; longest: "
        f"{ring.longest(evs)}")
    return sum(e - s for _, s, e, _ in evs)
