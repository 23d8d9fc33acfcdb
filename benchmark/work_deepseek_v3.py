"""Operations and bytes that the deepseek_v3 family's algorithm needs, from
shapes and from the routing that really happened (``benchmark/work.py`` for
the GPT family, ``benchmark/work_afmoe.py`` for afmoe). Needed work only: 6
operations a weight and token for what every token passes (2 forward, 4
backward); the routed experts by the assignments that landed on experts
held here, never the padded rows; attention as the causal triangle at the
PUBLISHED head sizes (q and k 192 wide, v 128: columns a kernel pads on
are time spent, not work needed); a recomputed forward pass is not
counted. No share built on these counts can pass 100%.
"""
from __future__ import annotations

from benchmark.reference.deepseek_v3 import sizes
from benchmark.work import roofline_seconds
from benchmark.work_afmoe import attended_pairs

__all__ = ["attention_flops", "attention_bytes", "attention_seconds",
           "matmul_params", "train_flops"]


def attention_flops(seq: int, heads: int, d_qk: int, d_v: int,
                    backward: bool) -> int:
    """One row, all heads, causal: forward QK^T (d_qk) and PV (d_v);
    backward dQ and dK (d_qk each), dP and dV (d_v each); 2 operations a
    pair and column."""
    columns = (2 * d_qk + 2 * d_v) if backward else (d_qk + d_v)
    return 2 * columns * heads * attended_pairs(seq, None)


def attention_bytes(seq: int, heads: int, d_qk: int, d_v: int,
                    backward: bool, itemsize: int = 2) -> int:
    """One row: forward reads q, k (d_qk), v and writes o (d_v); backward
    reads q, k, v, o, do and writes dq, dk, dv."""
    qk_like, v_like = (4, 4) if backward else (2, 2)
    return (qk_like * d_qk + v_like * d_v) * heads * seq * itemsize


def attention_seconds(arch: dict, batch: int, seq: int, chip) -> tuple:
    """Least time for one step's attention (every layer, forward and
    backward once), and which peak binds each pass."""
    z = sizes(arch)
    d_qk = z["nope"] + z["rope"]
    total, bound = 0.0, {}
    for backward in (False, True):
        t, by = roofline_seconds(
            attention_flops(seq, z["nh"], d_qk, z["dv"], backward),
            attention_bytes(seq, z["nh"], d_qk, z["dv"], backward), chip)
        total += z["L"] * batch * t
        bound["backward" if backward else "forward"] = by
    return total, bound


def matmul_params(arch: dict) -> dict:
    """Weights that multiply every token, by group, and one routed
    expert's (no embedding look-up, no norms)."""
    z = sizes(arch)
    mla = z["H"] * (z["Q"] + z["KVA"]) + z["R"] * z["KVB"] + z["O"] * z["H"]
    return {"mla_projections": z["L"] * mla,
            "dense_mlp": z["Ld"] * 3 * z["H"] * z["F"],
            "shared_experts": z["Lm"] * 3 * z["H"] * z["Fs"],
            "router": z["Lm"] * z["H"] * z["E"],
            "head": z["H"] * z["V"],
            "one_expert": 3 * z["H"] * z["Fe"]}


def train_flops(arch: dict, batch: int, seq: int,
                landed_by_layer=None) -> dict:
    """Matmul operations one training step needs, by group. The routed
    experts count the assignments that landed on held experts
    (``landed_by_layer``, one number an expert layer; an even routing
    where it is not given). The head sees seq - 1 positions a row."""
    z = sizes(arch)
    p = matmul_params(arch)
    tokens = batch * seq
    if landed_by_layer is None:
        even = tokens * int(arch["num_experts_per_tok"]) * z["held"] / z["E"]
        landed_by_layer = [even] * z["Lm"]
    out = {g: 6 * p[g] * tokens for g in
           ("mla_projections", "dense_mlp", "shared_experts", "router")}
    out["head"] = 6 * p["head"] * batch * (seq - 1)
    out["routed_experts"] = 6 * p["one_expert"] * float(sum(landed_by_layer))
    d_qk = z["nope"] + z["rope"]
    out["attention"] = z["L"] * batch * sum(
        attention_flops(seq, z["nh"], d_qk, z["dv"], backward)
        for backward in (False, True))
    out["total"] = sum(out.values())
    return out
