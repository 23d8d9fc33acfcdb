"""The whole step's share of the chip's bf16 peak: matmul operations
that forward and backward need (``benchmark/work.py``: causal attention
as the needed half, nothing recomputed counted) for the window's steps,
over the host-clock time from the sync before the first of them to the
sync after the last, over chips times peak."""


def read(trace, obs, cell, chip, say):
    if not obs.get("steps") or "step_flops" not in obs:
        return None
    flops = obs["step_flops"]["total"] * obs["steps"]
    say("step work by group, GFLOP a step: " + ", ".join(
        f"{k} {v / 1e9:.1f}" for k, v in obs["step_flops"].items()))
    return 100.0 * flops / obs["window_s"] / (cell["chips"]
                                              * chip.peak_flops)
