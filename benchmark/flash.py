"""What the flash-attention readers share: which trace events are the
library kernel's, and the needed work of the window's calls."""
from benchmark import work

def matcher(trace, cell):
    """A test of an operation's name: a Pallas kernel of the trace that
    works on the cell's [batch, heads, sequence, head size] arrays, as
    the library flash kernel's forward, dkv and dq calls do. By shape
    and not by name: the names change with how the step was traced
    (``flash_attention``, ``jvp_jit_flash_attention__``) and with JAX's
    location flags (``tpu_custom_call``)."""
    arch, tr = cell["config"], cell["traffic"]
    heads = int(arch["num_heads"])
    shape = "[%d,%d,%d,%d]" % (int(tr["batch"]), heads, int(tr["seq"]),
                               int(arch["hidden_size"]) // heads)
    names = {n for n, hlo in trace.kernels().items() if shape in hlo}
    return names.__contains__


def needed_seconds(obs, cell, chip):
    """Least time for the attention the window's steps need: one forward
    and one backward a layer, row and step. A recomputed forward is time
    spent and work not needed, so it is in the kernel's time only."""
    arch, tr = cell["config"], cell["traffic"]
    seq, hidden = int(tr["seq"]), int(arch["hidden_size"])
    calls = obs["steps"] * int(arch["num_layers"]) * int(tr["batch"])
    total, bound = 0.0, {}
    for backward in (False, True):
        t, by = work.roofline_seconds(
            work.attention_flops(seq, hidden, backward),
            work.attention_bytes(seq, hidden, backward), chip)
        total += calls * t
        bound["backward" if backward else "forward"] = by
    return total, bound
