"""What the SmallThinker family's readers share: which trace events are the
attention kernels and the grouped products (by operand shape, as
``benchmark/flash.py`` finds its kernels: names change with how the step
was traced). The device time under the expert layer's scopes is
``benchmark/afmoe_trace.py``'s ``moe_scope_seconds``, which reads nothing
of a family."""
from benchmark.afmoe_trace import _kernels_with, moe_scope_seconds  # noqa: F401
from benchmark.reference.smallthinker import sizes


def attention_matcher(trace, cell):
    """Pallas kernels that work on the cell's query array as the grouped
    (MQA) calls take it: [batch, kv heads, group, sequence, head size], or
    where the batch is one row [kv heads, group, sequence, head size]: the
    chip's compiler drops the leading 1 from the kernel's operands (my chip
    run, PR 36: ``bf16[4,7,16384,128]``; a compile for a described chip
    shows the 1 before optimisation)."""
    z, tr = sizes(cell["config"]), cell["traffic"]
    batch = int(tr["batch"])
    rest = "%d,%d,%d,%d]" % (z["nkv"], z["nh"] // z["nkv"], int(tr["seq"]),
                             z["hd"])
    return _kernels_with(trace, ("[%d,%s" % (batch, rest),)
                         + (("[" + rest,) if batch == 1 else ()))


def gmm_matcher(trace, cell):
    """Pallas kernels one of whose operands is a stack of the held
    experts' matrices, [held, hidden, expert width] or its transpose."""
    z = sizes(cell["config"])
    return _kernels_with(trace, (
        "[%d,%d,%d]" % (z["held"], z["H"], z["Fe"]),
        "[%d,%d,%d]" % (z["held"], z["Fe"], z["H"])))
