"""What the afmoe family's readers share: which trace events are the
attention kernels and the grouped products (by operand shape, as
``benchmark/flash.py`` finds its kernels: names change with how the step
was traced), and the device time under the expert layer's scopes."""
import re

from benchmark import trace as trace_mod
from benchmark.reference.afmoe import sizes

# a segment of an ``op_name`` path that is one of the expert layer's
# registered scopes, bare or inside jvp(..) / transpose(..)
_MOE_SCOPE = re.compile(r"(^|[/(])(router|experts|shared_expert)([/)]|$)")


def _kernels_with(trace, shapes):
    names = {n for n, hlo in trace.kernels().items()
             if any(s in hlo for s in shapes)}
    return names.__contains__


def attention_matcher(trace, cell):
    """Pallas kernels that work on the cell's query array: grouped
    [batch, kv heads, group, sequence, head size] as the MQA calls take
    it, or [batch, heads, sequence, head size] where every head has its
    own keys."""
    z, tr = sizes(cell["config"]), cell["traffic"]
    b, s = int(tr["batch"]), int(tr["seq"])
    return _kernels_with(trace, (
        "[%d,%d,%d,%d,%d]" % (b, z["nkv"], z["nh"] // z["nkv"], s, z["hd"]),
        "[%d,%d,%d,%d]" % (b, z["nh"], s, z["hd"])))


def gmm_matcher(trace, cell):
    """Pallas kernels one of whose operands is a stack of the held
    experts' matrices, [held, hidden, expert width] or its transpose."""
    z = sizes(cell["config"])
    return _kernels_with(trace, (
        "[%d,%d,%d]" % (z["held"], z["H"], z["Fe"]),
        "[%d,%d,%d]" % (z["held"], z["Fe"], z["H"])))


def moe_scope_seconds(trace, op_scopes):
    """Device seconds of the first chip inside the window, by each
    operation's own time (what is nested inside it taken out), of the
    operations whose path in ``op_scopes`` runs through the expert
    layer's scopes; and the seconds by scope."""
    lo, hi = trace.window()
    evs = [(n, max(s, lo), min(e, hi)) for n, s, e in trace.chips[0].ops
           if min(e, hi) > max(s, lo)]
    by = {}
    for name, ns in trace_mod.self_times(evs).items():
        m = _MOE_SCOPE.search(op_scopes.get(name.strip().lstrip("%"), ""))
        if m:
            by[m.group(2)] = by.get(m.group(2), 0.0) + ns / 1e9
    return sum(by.values()), by
