"""What the latent attention layer spends round its kernels, over the
device's busy time in the window: self time of every operation whose path
in ``TrainStep.op_scopes()`` runs through the scope ``attn`` and that is no
attention kernel (the four projections, the latent norm, RoPE,
concatenation, broadcast and layout copies), forward, recomputed and
backward."""
from benchmark import deepseek_v3_trace


def read(trace, obs, cell, chip, say):
    if trace is None or not obs.get("op_scopes"):
        return None
    is_attn = deepseek_v3_trace.attention_matcher(trace, cell)
    glue_s, by = deepseek_v3_trace.mla_glue_seconds(
        trace, obs["op_scopes"], is_attn)
    busy_s = trace.busy_s()
    if glue_s <= 0 or busy_s <= 0:
        return None
    say("latent attention layer outside its kernels, s by scope: "
        + ", ".join(f"{k} {v:.4f}" for k, v in sorted(by.items())))
    return 100.0 * glue_s / busy_s
