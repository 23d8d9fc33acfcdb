#!/usr/bin/env python
"""Quantized ZeRO collectives A/B: fp32 vs bf16 vs int8 wire precision.

Drives the SAME GPT-tiny ParallelTrainStep (ZeRO-2 and ZeRO-3) at each
`comm_precision` over a virtual 64-device dp8 x sharding8 mesh
(ISSUE 17) and reports, per precision:

  * per-chip collective bytes from the compiled HLO inventory
    (analysis/program_lint ring accounting) + the reduction ratio vs
    fp32 — gated at >= 1.8x (bf16) / >= 3.5x (int8) for ZeRO-3;
  * wall time per step (median of measured steps, compile excluded);
  * loss max-rel drift vs the fp32 trajectory over the measured steps
    — gated at the PERF.md bounds (bf16 5e-3, int8 2e-2);
  * the stage-3 overlap schedule: optimization_barrier chain links in
    the lowered module and the gather-interleaving report from the
    scheduled compiled module (analysis/collective_schedule) — gated
    on chained + not front-loaded.

CPU smoke:  JAX_PLATFORMS=cpu python tools/bench_collectives.py --smoke
            (8 virtual devices, dp2 x sharding4, fewer steps)

Stdout is exactly one JSON record (one terminal JSON record);
diagnostics go to stderr. A failing gate is a GOOD record with
"gate": "fail".
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REEXEC_MARK = "_PADDLE_TPU_BENCH_COLL_REEXEC"

# loss-trajectory drift bounds, mirrored in PERF.md (windowed max-rel
# vs the fp32 run; one rounding per wire hop bounds the per-step error,
# drift compounds through the optimizer over the window)
DRIFT_BOUNDS = {"bf16": 5e-3, "int8": 2e-2}
BYTE_GATES = {"bf16": 1.8, "int8": 3.5}


def _want_devices(smoke: bool) -> int:
    return 8 if smoke else 64


def _env_ok(n: int) -> bool:
    flag = f"--xla_force_host_platform_device_count={n}"
    return (os.environ.get(_REEXEC_MARK) == "1"
            or (os.environ.get("JAX_PLATFORMS") == "cpu"
                and flag in os.environ.get("XLA_FLAGS", "")))


def _reexec(n: int):
    """The platform/device-count env must be in place before jax is
    first imported — re-exec with it (tools/tpucost.py idiom)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n}"
                        ).strip()
    # deliberately NO persistent compile cache on this CPU-only run
    # (JAX_PLATFORMS=cpu is forced just above): loading multi-device
    # XLA:CPU shard_map programs back from the on-disk cache has
    # crashed the runtime (the cpu_aot_loader hazard
    # paddle_tpu/__init__.py documents). A chip run never strips the
    # variable — there the cache is the point.
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env[_REEXEC_MARK] = "1"
    import subprocess
    sys.exit(subprocess.call([sys.executable] + sys.argv, env=env))


def _run_variant(prec: str, stage: int, batch, steps: int):
    """Build + run one (precision, stage) variant from a fixed seed.
    Returns (losses, inventory/schedule/timing record)."""
    import jax.numpy as jnp
    from paddle_tpu.analysis import (collective_inventory_from_hlo,
                                     gather_chain_links,
                                     gather_overlap_report)
    from paddle_tpu.compilation.sites import (_gpt_tiny_model,
                                              _train_step_parts)
    from paddle_tpu.distributed.parallel_step import ParallelTrainStep
    from paddle_tpu.framework import random as _rng

    _rng.seed(0)
    model = _gpt_tiny_model()
    loss_fn, opt, _ = _train_step_parts(model)
    step = ParallelTrainStep(model, loss_fn, opt, zero_stage=stage,
                             comm_precision=prec)
    step._build(batch)
    lowered = step._jitted.lower(
        step.params, step.buffers, step.opt_state,
        jnp.asarray(1e-3, jnp.float32), jnp.asarray(1, jnp.float32),
        _rng.default_generator().fold_in(1), *batch)
    low_text = lowered.as_text()
    hlo = lowered.compile().as_text()
    inv = collective_inventory_from_hlo(hlo)
    rec = {
        "collective_bytes": sum(v["bytes"] for v in inv.values()),
        "collectives": {k: {"count": v["count"], "bytes": v["bytes"]}
                        for k, v in sorted(inv.items())},
        "chain_links": gather_chain_links(low_text),
    }
    if stage >= 3:
        rec["overlap"] = gather_overlap_report(hlo)
    losses = []
    times = []
    for i in range(steps):
        t0 = time.perf_counter()
        loss = step(*batch)
        losses.append(float(loss))
        times.append((time.perf_counter() - t0) * 1e3)
    # first step pays dispatch warmup; median of the rest
    rest = sorted(times[1:]) or times
    rec["step_ms"] = round(rest[len(rest) // 2], 3)
    return losses, rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="8 virtual devices (dp2 x sharding4), fewer "
                         "steps — the ci.py comm-smoke geometry")
    ap.add_argument("--steps", type=int, default=None,
                    help="measured steps per variant (default 8, "
                         "smoke 4)")
    args = ap.parse_args()

    n_dev = _want_devices(args.smoke)
    if not _env_ok(n_dev):
        _reexec(n_dev)
    sys.path.insert(0, ROOT)

    import numpy as np
    import jax
    from paddle_tpu.distributed import mesh as mesh_mod

    devs = jax.devices()
    if len(devs) < n_dev:
        print(json.dumps({"error": f"need {n_dev} devices, have "
                          f"{len(devs)}"}))
        return 2

    if args.smoke:
        axes = {"dp": 2, "sharding": 4}
    else:
        axes = {"dp": 8, "sharding": 8}
    steps = args.steps or (4 if args.smoke else 8)
    mesh_mod.init_mesh(axes, devices=devs[:n_dev])
    rows = axes["dp"] * axes["sharding"]
    ids = np.random.default_rng(0).integers(
        0, 100, (rows, 32)).astype(np.int64)
    batch = (ids, ids)

    record = {"version": 1, "devices": n_dev, "mesh": axes,
              "steps": steps, "stages": {}}
    failures = []
    try:
        for stage in (2, 3):
            st = {}
            base_losses = None
            for prec in ("fp32", "bf16", "int8"):
                t0 = time.perf_counter()
                losses, rec = _run_variant(prec, stage, batch, steps)
                rec["build_s"] = round(time.perf_counter() - t0, 1)
                rec["losses"] = [round(x, 6) for x in losses]
                if prec == "fp32":
                    base_losses = losses
                else:
                    drift = max(abs(a - b) / max(abs(b), 1e-9)
                                for a, b in zip(losses, base_losses))
                    rec["loss_maxrel_vs_fp32"] = round(drift, 6)
                    if drift > DRIFT_BOUNDS[prec]:
                        failures.append(
                            f"zero{stage}/{prec}: drift {drift:.2e} > "
                            f"bound {DRIFT_BOUNDS[prec]:.0e}")
                st[prec] = rec
                print(f"[zero{stage}/{prec}] bytes="
                      f"{rec['collective_bytes']} "
                      f"step_ms={rec['step_ms']} "
                      f"build_s={rec['build_s']}", file=sys.stderr)
            fp32_bytes = st["fp32"]["collective_bytes"]
            for prec in ("bf16", "int8"):
                q = st[prec]["collective_bytes"]
                ratio = fp32_bytes / q if q else float("inf")
                st[prec]["byte_reduction_vs_fp32"] = round(ratio, 2)
                if stage == 3 and ratio < BYTE_GATES[prec]:
                    failures.append(
                        f"zero{stage}/{prec}: byte reduction "
                        f"{ratio:.2f}x < {BYTE_GATES[prec]}x")
                if stage == 3:
                    if st[prec]["chain_links"] == 0:
                        failures.append(
                            f"zero{stage}/{prec}: no gather chain "
                            "links — overlap schedule missing")
                    if st[prec].get("overlap", {}).get("front_loaded"):
                        failures.append(
                            f"zero{stage}/{prec}: gathers front-loaded")
            record["stages"][f"zero{stage}"] = st
    except Exception as e:
        import traceback
        traceback.print_exc(file=sys.stderr)
        print(json.dumps({"error": f"{type(e).__name__}: {e}"}))
        return 2

    record["failures"] = failures
    record["gate"] = "fail" if failures else "pass"
    for f in failures:
        print(f"GATE: {f}", file=sys.stderr)
    print(json.dumps(record))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
