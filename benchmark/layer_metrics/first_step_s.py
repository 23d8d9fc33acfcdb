"""Host clock around the first call of the step: compile, or load from
the persistent cache (the hits are on an earlier line)."""


def read(trace, obs, cell, chip, say):
    if "first_step_s" not in obs:
        return None
    say(f"first step: {obs.get('first_step_cache_hits')} persistent-cache "
        f"hits, {obs.get('first_step_compiles')} compiles")
    return obs["first_step_s"]
