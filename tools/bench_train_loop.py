"""Fused K-step train-loop benchmark (PR 4).

Measures steps/s through jit.TrainStep on the tiny GPT config for three
dispatch regimes over the SAME step program graph:

  per_step   the historical Model.fit loop: one program dispatch per
             step, `float(loss)` host sync every step (what
             hapi/model.py did before PR 4)
  fused K=4  TrainStep.scan_steps windows fed by the double-buffered
             prefetch pipeline — one dispatch + ZERO host syncs per 4
             steps
  fused K=16 same at K=16 (the PADDLE_TPU_SCAN_STEPS sweet spot on
             dispatch-bound hosts)

On this 1-core CPU host the win is structural, not FLOPs: per-step
dispatch pays Python jit-call overhead + the device->host loss
round-trip every step, while the fused window amortizes both over K
(see PERF.md / the serving-engine lesson — same no-sync regime, training
side). The host-sync counter (framework.syncs) ASSERTS the fused loop's
zero-mid-window-sync guarantee rather than claiming it.

Run on TPU:  python tools/bench_train_loop.py
CPU smoke:   JAX_PLATFORMS=cpu python tools/bench_train_loop.py [--smoke]
Prints ONE BENCH-style JSON line (one terminal JSON record).
"""
import argparse
import json
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def _measure_per_step(step, batches, sync_every_step=True):
    """The pre-PR-4 Model.fit regime: dispatch one program per step and
    block on float(loss) (the per-step host round-trip)."""
    t0 = time.perf_counter()
    loss = None
    for x, y in batches:
        loss = step(x, y)
        if sync_every_step:
            float(loss)
    if not sync_every_step:
        float(loss)
    return time.perf_counter() - t0


def _measure_fused(step, windows, k):
    """scan_steps windows; losses stay on device until the terminal
    fetch (the same LossWindow read the fit loop does at log/epoch
    boundaries — counted by the sync counter)."""
    from paddle_tpu.hapi.lazy import LossWindow
    t0 = time.perf_counter()
    last = None
    for xw, yw in windows:
        last = step.scan_steps(k, xw, yw)
    LossWindow(last.value).fetch()   # one terminal sync closes the clock
    return time.perf_counter() - t0


def bench(smoke: bool, steps: int, batch: int, seq: int):
    import paddle_tpu as paddle
    from paddle_tpu.framework import syncs
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    cfg = gpt_tiny()
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    step = TrainStep(model, model.make_loss_fn(), opt)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (steps, batch, seq)).astype(
        "int64")

    ks = (4, 16)
    n_win = {k: steps // k for k in ks}
    batches = [(ids[i], ids[i]) for i in range(steps)]
    stacked = {k: [(ids[w * k:(w + 1) * k], ids[w * k:(w + 1) * k])
                   for w in range(n_win[k])] for k in ks}

    # -- warm every program (per-step + both windows) + steady state
    _measure_per_step(step, batches[:2])
    for k in ks:
        _measure_fused(step, stacked[k][:1], k)
    traces_warm = step._trace_count

    # this 1-core host jitters hard (shared box): measure the three
    # regimes INTERLEAVED over `reps` rounds and keep each regime's
    # best round, so background noise can't land on one regime only
    reps = 2 if smoke else 3
    dt_step = dt_step_async = float("inf")
    best = {k: float("inf") for k in ks}
    syncs_per_step_regime = 0
    sync_counts = {}
    for _ in range(reps):
        s0 = syncs.sync_count()
        # per-step dispatch, sync every step: the old fit loop
        d = _measure_per_step(step, batches)
        if d < dt_step:
            dt_step = d
            syncs_per_step_regime = syncs.sync_count() - s0
        # per-step dispatch WITHOUT the per-step sync (isolates the
        # float(loss) round-trip from the program-call overhead)
        dt_step_async = min(dt_step_async,
                            _measure_per_step(step, batches,
                                              sync_every_step=False))
        for k in ks:
            s0 = syncs.sync_count()
            d = _measure_fused(step, stacked[k], k)
            d_syncs = syncs.sync_count() - s0
            # the guarantee, asserted: NOTHING syncs mid-window — the
            # one recorded fetch is the terminal boundary read
            assert d_syncs - 1 == 0, (
                f"fused K={k} loop performed {d_syncs - 1} mid-window "
                "host syncs — the zero-sync contract is broken")
            sync_counts[k] = d_syncs
            best[k] = min(best[k], d)

    results = {k: {"steps_per_s": n_win[k] * k / best[k],
                   "host_syncs": sync_counts[k],
                   "windows": n_win[k]} for k in ks}

    assert step._trace_count == traces_warm, "re-traced after warmup"

    steps_per_s = steps / dt_step
    per_step_ms = dt_step / steps * 1e3
    fused16 = results[16]["steps_per_s"]
    # dispatch+sync overhead amortized away by the K=16 window, per step
    overhead_ms = per_step_ms - 1e3 / fused16

    # MFU via the ONE shared formula (obs/efficiency.py — the same
    # arithmetic the live ptpu_train_mfu gauge exports per dispatch;
    # ISSUE 14's "no third formula" rule). Chip-relative: on this CPU
    # host it reads as a tiny fraction of a TPU's peak — the number
    # becomes meaningful when the TPU suite runs this tool.
    from paddle_tpu.obs import efficiency as eff
    nparams = eff.tree_nelems(step.params)
    k16_tokens = n_win[16] * 16 * batch * seq
    train_mfu = eff.mfu(eff.train_step_flops(nparams, k16_tokens),
                        best[16])
    return {
        "train_mfu_k16": train_mfu,
        "mfu_gauge": eff.MFU_GAUGE,
        "eff_chip": eff.chip_spec().name,
        "param_count": nparams,
        "metric": "train_loop_fused_speedup",
        "value": round(fused16 / steps_per_s, 3),
        "unit": "x_steps_per_s_K16_vs_per_step_dispatch",
        "per_step_steps_per_s": round(steps_per_s, 2),
        "per_step_async_steps_per_s": round(steps / dt_step_async, 2),
        "fused_k4_steps_per_s": round(results[4]["steps_per_s"], 2),
        "fused_k16_steps_per_s": round(fused16, 2),
        "speedup_k4": round(results[4]["steps_per_s"] / steps_per_s, 3),
        "speedup_k16": round(fused16 / steps_per_s, 3),
        "dispatch_overhead_ms_per_step": round(overhead_ms, 3),
        "host_syncs_per_step_regime": syncs_per_step_regime,
        "host_syncs_fused_k16": results[16]["host_syncs"],
        "mid_window_syncs": 0,
        "steps": steps, "batch": batch, "seq": seq,
        "model": "gpt_tiny",
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fewer steps (CI-speed CPU run)")
    ap.add_argument("--steps", type=int, default=None,
                    help="total steps per regime (multiple of 16)")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=32)
    args = ap.parse_args()

    steps = args.steps if args.steps is not None else \
        (32 if args.smoke else 96)
    if steps % 16:
        ap.error("--steps must be a multiple of 16")
    try:
        rec = bench(args.smoke, steps, args.batch, args.seq)
        import jax
        rec["device_kind"] = getattr(jax.devices()[0], "device_kind",
                                     "cpu")
        rec["smoke"] = bool(args.smoke)
    except Exception as e:  # noqa: BLE001 — the record is the contract
        print(json.dumps({"error": str(e)[:400]}))
        return 1
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
