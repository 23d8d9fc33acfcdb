"""What the deepseek_v3 family's readers share: which trace events are the
latent attention's Pallas kernels (by operand shape, as
``benchmark/flash.py`` finds its kernels: names change with how the step
was traced), and the device time under the attention layer's scopes
outside those kernels."""
import re

from benchmark import trace as trace_mod
from benchmark.reference.deepseek_v3 import sizes

# a segment of an ``op_name`` path that is the attention layer's
# registered scope, bare or inside jvp(..) / transpose(..)
_ATTN_SCOPE = re.compile(r"(^|[/(])attn([/)]|$)")
_INNER_SCOPE = re.compile(
    r"(^|[/(])(q_proj|kv_a_proj|kv_a_norm|kv_b_proj|o_proj)([/)]|$)")


def attention_matcher(trace, cell):
    """Pallas kernels one of whose operands is the cell's value array
    [batch, heads, sequence, v head size] and another a query or key array
    of the same leading sizes: whatever width q and k were padded to, v
    keeps its own."""
    z, tr = sizes(cell["config"]), cell["traffic"]
    lead = "[%d,%d,%d," % (int(tr["batch"]), z["nh"], int(tr["seq"]))
    v_shape = lead + "%d]" % z["dv"]
    wider = re.compile(re.escape(lead) + r"(\d+)\]")
    names = set()
    for n, hlo in trace.kernels().items():
        if v_shape in hlo and any(int(w) >= z["nope"] + z["rope"]
                                  for w in wider.findall(hlo)):
            names.add(n)
    return names.__contains__


def mla_glue_seconds(trace, op_scopes, is_kernel):
    """Device seconds of the first chip inside the window, by each
    operation's own time (what is nested inside it taken out), of the
    operations whose path in ``op_scopes`` runs through the scope ``attn``
    and that are no attention kernel: the projections, the latent norm,
    RoPE, concatenation, broadcast and layout copies; and the seconds by
    the innermost of the layer's scopes (``attn`` itself: the glue)."""
    lo, hi = trace.window()
    evs = [(n, max(s, lo), min(e, hi)) for n, s, e in trace.chips[0].ops
           if min(e, hi) > max(s, lo)]
    by = {}
    for name, ns in trace_mod.self_times(evs).items():
        if is_kernel(name):
            continue
        path = op_scopes.get(name.strip().lstrip("%"), "")
        if not _ATTN_SCOPE.search(path):
            continue
        inner = _INNER_SCOPE.findall(path)
        key = inner[-1][1] if inner else "attn"
        by[key] = by.get(key, 0.0) + ns / 1e9
    return sum(by.values()), by
