"""Analytic MFU model for the north star: GPT-6.7B on a v5p-64 pod.

This environment has at most one four-chip host, so the 40% MFU north
star (BASELINE.md) cannot be measured directly. This tool builds the
paper trail for it from two things this environment CAN produce:

  1. the REAL per-step communication schedule: the BASELINE-config-3
     training step (ZeRO-3 + remat, bf16 + fp32 master, fused CE) is
     AOT-compiled through GSPMD on a virtual 64-device dp8 x sharding8
     mesh, and the collective ops are read back out of the optimized
     HLO (kind + tensor bytes). Per-layer marginal comm is isolated by
     compiling two depths and differencing, then scaled to 32 layers.
  2. a measured single-chip anchor (the end-to-end fraction of peak
     `python bench.py` reaches on real hardware) bounds the
     matmul-efficiency term. None is committed: nothing has been
     measured on the installed chip yet, so the table sweeps the
     efficiency term instead of pinning it.

Model (scaling-book accounting):
  step_time = max(T_compute, T_comm)            (XLA overlaps; also
              T_compute + T_comm reported as the no-overlap bound)
  T_compute = tokens_chip * flops_tok * remat_factor / (PEAK * eff)
  T_comm    = sum_kind bytes_kind / ring_bw(axis group size)
  MFU       = tokens_chip * flops_tok / (PEAK * step_time)
              (nominal FLOPs — remat recompute excluded, standard MFU)

Run: JAX_PLATFORMS=cpu python tools/northstar_model.py
(Bootstraps its own 64-device CPU child process; never touches a chip.)
Prints the markdown table for PERF.md §north-star plus one JSON line.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)

# ---- v5p public constants (ONE table shared with tools/tpucost.py's
# roofline). chips.py is dependency-free and loaded STANDALONE so this
# pure-arithmetic planner never pays — or requires — the jax import.
import importlib.util as _ilu  # noqa: E402

_spec = _ilu.spec_from_file_location(
    "_paddle_tpu_chips",
    os.path.join(_ROOT, "paddle_tpu", "analysis", "chips.py"))
_chips = _ilu.module_from_spec(_spec)
# dataclasses resolves cls.__module__ through sys.modules at class
# creation — register before exec or the standalone load AttributeErrors
sys.modules[_spec.name] = _chips
_spec.loader.exec_module(_chips)
CHIP_SPECS = _chips.CHIP_SPECS

_V5P = CHIP_SPECS["v5p"]
PEAK = _V5P.peak_flops   # bf16 FLOP/s per chip
ICI_GBPS = _V5P.ici_gbps / 8   # 600 GB/s aggregate ICI per chip
# a ring over one mesh axis of a 3D torus uses 2 of the 6 links:
RING_BW = ICI_GBPS / 3   # 200 GB/s effective per-axis ring bandwidth
HBM_GB = _V5P.hbm_capacity / 2**30

# ---- GPT-6.7B geometry (BASELINE config 3) --------------------------------
L, H, V, S = 32, 4096, 50304, 2048
N_PARAMS = 12 * L * H * H + 2 * V * H  # untied in/out embeddings
FLOPS_TOK = 6 * N_PARAMS + 6 * L * H * S   # bench.py's accounting
MESH = {"dp": 8, "sharding": 8}
N_CHIPS = MESH["dp"] * MESH["sharding"]
BATCH_PER_CHIP = 16                        # microbatch rows per chip
TOKENS_CHIP = BATCH_PER_CHIP * S           # batch splits over dp AND
                                           # sharding (ZeRO groups are
                                           # data-parallel sub-groups)
REMAT_FACTOR = 4 / 3                       # full remat: fwd replayed in bwd


def _collect_comm(n_layers: int) -> dict:
    """AOT-compile the config-3 step at n_layers depth on a virtual
    64-device mesh (child process) and return collective byte totals
    parsed from the optimized HLO."""
    code = r"""
import json, re, sys
import jax, jax.numpy as jnp
sys.path.insert(0, %(root)r)
import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.models import GPTConfig, GPTForCausalLM

dist.init_mesh(%(mesh)r)
with paddle.LazyGuard():
    model = GPTForCausalLM(GPTConfig(
        hidden_size=%(H)d, num_layers=%(L)d, num_heads=32,
        vocab_size=%(V)d, max_seq_len=%(S)d, tie_embeddings=False,
        fused_loss_chunk=2048))
    model.bfloat16()
opt = paddle.optimizer.AdamW(learning_rate=1e-4, multi_precision=True,
                             parameters=model.parameters())
step = dist.ParallelTrainStep(model, model.make_loss_fn(), opt,
                              zero_stage=3, remat=True)
ids = jax.ShapeDtypeStruct((%(NCHIPS)d * %(BPC)d, %(S)d), jnp.int64)
compiled = step.aot_compile(ids, ids)
hlo = compiled.as_text()

WIDTH = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s64": 8,
         "f64": 8, "s8": 1, "u8": 1, "pred": 1}
def shape_bytes(sig):
    total = 0
    for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]", sig):
        if dt not in WIDTH:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * WIDTH[dt]
    return total

out = {}
for m in re.finditer(
        r"^\s*(?:[%%\w.\-]+|\([^)]*\)) = (\([^)]*\)|[\w\[\],{}\s/]+?) "
        r"(all-gather-start|all-gather|reduce-scatter|"
        r"all-reduce-start|all-reduce|collective-permute-start|"
        r"collective-permute|all-to-all)\(", hlo, re.M):
    sig, kind = m.group(1), m.group(2).replace("-start", "")
    k = out.setdefault(kind, [0, 0])
    k[0] += 1
    k[1] += shape_bytes(sig)
mem = compiled.memory_analysis()
print(json.dumps({"collectives": out,
                  "arg_bytes": mem.argument_size_in_bytes,
                  "temp_bytes": mem.temp_size_in_bytes}))
""" % {"root": _ROOT, "mesh": MESH, "H": H, "L": n_layers, "V": V,
       "S": S, "BPC": BATCH_PER_CHIP, "NCHIPS": N_CHIPS}
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "").replace(
        "--xla_force_host_platform_device_count=8", "").strip()
        + " --xla_force_host_platform_device_count=64").strip()
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=1200,
                       cwd=_ROOT)
    if r.returncode != 0:
        sys.stderr.write(r.stderr[-2000:])
        raise RuntimeError("AOT child failed (L=%d)" % n_layers)
    return json.loads(r.stdout.strip().splitlines()[-1])


def main():
    la, lb = 2, 4
    a, b = _collect_comm(la), _collect_comm(lb)

    # per-layer marginal comm (differencing removes embeddings/head/update)
    per_layer = {}
    base = {}
    kinds = set(a["collectives"]) | set(b["collectives"])
    for k in kinds:
        ca, cb = a["collectives"].get(k, [0, 0]), \
            b["collectives"].get(k, [0, 0])
        pl = (cb[1] - ca[1]) / (lb - la)
        per_layer[k] = pl
        base[k] = ca[1] - pl * la
    comm_32 = {k: base[k] + per_layer[k] * L for k in kinds}

    # Transferred-bytes model per collective kind (ring algorithms over
    # an n=8 group — ZeRO rides "sharding", grad sync rides "dp", both
    # 8-wide here). The parsed bytes are the HLO RESULT signature, so:
    #   all-gather:    result = full gathered tensor -> (n-1)/n of it moves
    #   reduce-scatter: result = the 1/n shard -> (n-1)/n of the FULL
    #                  tensor moves = (n-1) x result bytes
    #   all-reduce:    ring AR = reduce-scatter + all-gather phases
    #                  -> 2(n-1)/n x result bytes
    #   collective-permute: one hop -> result bytes
    #   all-to-all:    (n-1)/n x result bytes
    n = MESH["sharding"]
    xfer = {"all-gather": (n - 1) / n, "reduce-scatter": float(n - 1),
            "all-reduce": 2 * (n - 1) / n, "collective-permute": 1.0,
            "all-to-all": (n - 1) / n}
    t_comm = sum(xfer.get(k, 1.0) * v
                 for k, v in comm_32.items()) / (RING_BW * 1e9)

    flops_chip = TOKENS_CHIP * FLOPS_TOK
    anchors = {}     # not measured yet (docstring item 2)
    rows = []
    for eff in (0.35, 0.45, 0.55, 0.65):
        t_compute = flops_chip * REMAT_FACTOR / (PEAK * eff)
        overlapped = max(t_compute, t_comm)
        serial = t_compute + t_comm
        rows.append({
            "matmul_eff": eff,
            "t_compute_ms": round(t_compute * 1e3, 1),
            "t_comm_ms": round(t_comm * 1e3, 1),
            "mfu_overlap_pct": round(
                100 * flops_chip / (PEAK * overlapped), 1),
            "mfu_serial_pct": round(
                100 * flops_chip / (PEAK * serial), 1),
        })

    print("## north-star analytic model: GPT-6.7B, v5p-64, "
          "dp8 x sharding8 (ZeRO-3 + remat + scan + fused CE)\n")
    print("AOT comm schedule (GSPMD, 64-device mesh, scaled from "
          f"L={la}/L={lb} compiles):\n")
    print("| collective | bytes/step (L=32) | per-layer bytes |")
    print("|---|---|---|")
    for k in sorted(comm_32):
        print(f"| {k} | {comm_32[k]/2**30:.2f} GiB "
              f"| {per_layer[k]/2**20:.1f} MiB |")
    print(f"\nper-chip tokens/step: {TOKENS_CHIP}  "
          f"nominal FLOPs/token: {FLOPS_TOK/1e9:.1f} G  "
          f"remat factor: {REMAT_FACTOR:.2f}")
    print(f"ring bandwidth assumed: {RING_BW:.0f} GB/s/axis "
          f"(v5p 4800 Gbps ICI, 3D torus, 2/6 links per ring)\n")
    print("| matmul eff | T_compute | T_comm | MFU (overlapped) | "
          "MFU (serial bound) |")
    print("|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['matmul_eff']:.2f} | {r['t_compute_ms']} ms "
              f"| {r['t_comm_ms']} ms | {r['mfu_overlap_pct']}% "
              f"| {r['mfu_serial_pct']}% |")
    print(f"\nmeasured single-chip anchors (end-to-end MFU): {anchors}")
    print()
    print(json.dumps({
        "metric": "northstar_analytic_mfu",
        "comm_bytes_step": {k: int(v) for k, v in comm_32.items()},
        "t_comm_ms": round(t_comm * 1e3, 1),
        "arg_bytes_per_dev": a["arg_bytes"],
        "rows": rows,
        "anchors_mfu_pct": anchors,
        "mesh": MESH,
        "tokens_per_chip": TOKENS_CHIP,
        # the live measured counterpart of this analytic model: hapi's
        # fit loop exports per-dispatch MFU on /metrics under this
        # gauge name (paddle_tpu/obs/efficiency.py — ISSUE 14), and
        # tools/bench_train_loop.py records the same formula's value
        "measured_gauge": "ptpu_train_mfu",
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
