"""Operations and bytes that the Olmo Hybrid family's algorithm needs, from
shapes alone (``benchmark/work.py`` for the GPT family, whose rule these
follow: 6 operations a weight and token for what every token passes, 2
forward and 4 backward; full attention as the causal triangle; a recomputed
forward pass is not counted). No share built on these counts can pass
100%.

The gated delta rule is counted at a FIXED chunk of ``CHUNK`` positions,
whatever chunk the kernel runs, so that the yardstick does not move with
the implementation: a chunk and head needs Q K^T, K K^T, W = T (K), U = T
(V), P U, W H, Q H and the state's update (K^T U), each 2 operations a
multiply-add; the triangular solve that makes T is left out (a cubic in
the chunk that a kernel may do in more than one way). Its backward needs
two products of each forward product's size. Bytes: q, k, v, g and beta
read once and o written once a forward pass; the backward reads those and
the output's gradient and writes the five gradients.
"""
from __future__ import annotations

from benchmark.reference.olmo_hybrid import sizes
from benchmark.work import roofline_seconds
from benchmark.work_afmoe import attention_flops

__all__ = ["CHUNK", "delta_rule_flops", "delta_rule_bytes",
           "delta_rule_seconds", "matmul_params", "train_flops"]

CHUNK = 64


def delta_rule_flops(arch: dict, seq: int, backward: bool) -> float:
    """One row, one linear layer, every head."""
    z = sizes(arch)
    c, dk, dv = CHUNK, z["dk"], z["dv"]
    one = 2 * c * c * (3 * dk + 2 * dv) + 6 * c * dk * dv
    return (2 if backward else 1) * one * (seq / c) * z["lh"]


def delta_rule_bytes(arch: dict, seq: int, backward: bool,
                     itemsize: int = 2) -> float:
    """One row, one linear layer: q, k, v at ``itemsize``, g and beta in
    float32."""
    z = sizes(arch)
    qkv = seq * z["lh"] * (2 * z["dk"] + z["dv"]) * itemsize
    gates = 2 * seq * z["lh"] * 4
    out = seq * z["lh"] * z["dv"] * itemsize
    return 2 * (qkv + gates) + 2 * out if backward else qkv + gates + out


def delta_rule_seconds(arch: dict, batch: int, seq: int, chip) -> tuple:
    """Least time for one step's delta rule (every linear layer, forward
    and backward once), and which peak binds each pass."""
    z = sizes(arch)
    total, bound = 0.0, {}
    for backward in (False, True):
        t, by = roofline_seconds(delta_rule_flops(arch, seq, backward),
                                 delta_rule_bytes(arch, seq, backward), chip)
        total += z["Llin"] * batch * t
        bound["backward" if backward else "forward"] = by
    return total, bound


def matmul_params(arch: dict) -> dict:
    """Weights that multiply every token, by group (no embedding look-up,
    no norms, no convolution taps)."""
    z = sizes(arch)
    h = z["H"]
    linear = 2 * h * z["QK"] + 3 * h * z["VV"] + 2 * h * z["lh"]
    full = 2 * h * z["Q"] + 2 * h * z["KV"]
    return {"linear_projections": z["Llin"] * linear,
            "full_projections": z["Lfull"] * full,
            "mlp": z["L"] * 3 * h * z["F"], "head": h * z["V"]}


def train_flops(arch: dict, batch: int, seq: int) -> dict:
    """Operations one training step needs, by group. The head sees
    seq - 1 positions a row."""
    z = sizes(arch)
    p = matmul_params(arch)
    tokens = batch * seq
    out = {g: 6 * p[g] * tokens
           for g in ("linear_projections", "full_projections", "mlp")}
    out["head"] = 6 * p["head"] * batch * (seq - 1)
    out["full_attention"] = z["Lfull"] * batch * sum(
        attention_flops(seq, z["nh"], z["hd"], None, bwd)
        for bwd in (False, True))
    out["delta_rule"] = z["Llin"] * batch * sum(
        delta_rule_flops(arch, seq, bwd) for bwd in (False, True))
    out["total"] = sum(out.values())
    return out
