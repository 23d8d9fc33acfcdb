"""The mean of the program's own ``train.step.prep`` span over the
window's steps: the host's own preparation (``step_prep_ms.train``, the
median) and the wait for the runtime's queue, which the host meets in a
prep's tiny device programs once it is as far ahead of the device as
the runtime lets it run. Near a step's length times the share of preps
that waited while the device is the bottleneck; it falls to the median
when the host is (PERF.md, PR 28)."""
import statistics

from benchmark import ring


def read(trace, obs, cell, chip, say):
    parts = ring.step_parts(obs, say)
    if parts is None:
        return None
    prep = parts["prep"]
    median = statistics.median(prep)
    waited = sum(d > 10 * median for d in prep)
    say(f"{waited} of {len(prep)} preps waited for the queue (over ten "
        f"times the median {median:.3f} ms); longest {max(prep):.3f} ms")
    return sum(prep) / len(prep)
