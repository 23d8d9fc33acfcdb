"""The host's own time to prepare a step inside ``TrainStep.__call__``
(learning rate, step number, key fold-in, batch: the program's own
``train.step.prep`` span, read from its ring): the median over the
window's steps. The median and not the mean, because the tiny device
programs of a prep are where the host waits when the runtime's queue is
full: after each sync seven or eight steps go through in about 2 ms,
then each prep waits a whole step (PERF.md, PR 28). The mean, which
holds that wait, is ``step_prep_mean_ms.train``; the means of the three
parts are said here."""
import statistics

from benchmark import ring


def read(trace, obs, cell, chip, say):
    parts = ring.step_parts(obs, say)
    if parts is None:
        return None
    mean_ms = {k: sum(v) / len(v) for k, v in parts.items()}
    outside = [e - s for n, s, e in obs["spans"] if n == "dispatch"]
    whole = sum(mean_ms.values())
    say("inside a step's call, mean ms: " + ", ".join(
        f"{k} {v:.3f}" for k, v in mean_ms.items())
        + (f"; their sum is {100 * whole * len(outside) / (1e3 * sum(outside)):.1f}% "
           "of the driver's span round the call" if outside else "")
        + f"; median enqueue {statistics.median(parts['enqueue']):.3f} ms")
    return statistics.median(parts["prep"])
