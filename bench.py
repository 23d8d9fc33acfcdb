"""GPT pretraining step on one TPU chip: `python bench.py`.

One process, one chip: the first act is to assert that JAX's default
device is a TPU — without one the run exits non-zero and prints no
record (there is no CPU mode; tests cover the CPU). Prints ONE terminal
JSON record on stdout, naming the device it ran on; staged progress
goes to stderr:

    [bench] stage=backend_up device_kind=...
    [bench] stage=compiling / warmup / measuring / measured

A watchdog thread enforces per-stage deadlines (compile 900 s / 3600 s,
measure 600 s) and exits non-zero on expiry. Any failure — the optional
sweep and scan-window extras included — fails the run.

Metric: causal-LM training tokens/s on one chip, GPT-125M by default
(bf16 compute, f32 master weights) or, with PADDLE_TPU_BENCH_MODEL=
gpt1.3b, the 1.3B-width configuration. The whole step (forward, loss,
backward, AdamW update) is one donated XLA program (jit.TrainStep).
No baseline ratio is reported: nothing has been measured on this
machine yet (PERF.md "Bring-up on the chip"); the benchmark PR defines
the cells.
"""
import json
import os
import sys
import threading
import time

import numpy as np

# PADDLE_TPU_BENCH_MODEL selects the config: "gpt125m" (default) or
# "gpt1.3b" (north-star-width single-chip run — HBM/remat behavior
# differs qualitatively from 125M)
_MODEL_SEL = os.environ.get("PADDLE_TPU_BENCH_MODEL", "gpt125m")
if _MODEL_SEL not in ("gpt125m", "gpt1.3b"):
    sys.stderr.write("[bench] unknown PADDLE_TPU_BENCH_MODEL=%r "
                     "(expected gpt125m | gpt1.3b)\n" % _MODEL_SEL)
    sys.exit(2)
_METRIC = ("gpt1p3b_train_tokens_per_sec_chip" if _MODEL_SEL == "gpt1.3b"
           else "gpt125m_train_tokens_per_sec_chip")

# GPT-1.3B depth that fits one 16 GB v5e chip; widths, sequence and
# vocabulary are never cut. The published 24 layers are refused by the
# chip's compiler (16.23 G of 15.75 G HBM: bf16 weights + two f32 Adam
# moments are 10 B/param, plus the scan backward's stacked bf16
# gradients — state, not batch, overflows). 22 layers pass that count,
# which sees the step program alone — but the trainer also holds the
# model's own bf16 copy of the parameters (TrainStep copies what it
# donates: 2 B/param more), and on the chip loading the program then
# fails ("Attempting to reserve 3.67G at the bottom of memory ... 2.17G
# free"; PR 24 chip run). At about 0.7 GB a layer all told, 18 leaves
# 1.2 GB to spare. chip_smoke.py runs the same cut
# (tests/test_chip_smoke.py holds the two equal).
GPT1P3B_LAYERS_ONE_CHIP = 18


def _log(msg: str) -> None:
    sys.stderr.write("[bench] %s\n" % msg)
    sys.stderr.flush()


def _int_env(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


class _Watchdog:
    """Per-stage deadline enforcement: exits non-zero on expiry."""

    def __init__(self):
        self._deadline = time.monotonic() + 240
        self._stage = "backend_init"
        t = threading.Thread(target=self._run, daemon=True)
        t.start()

    def stage(self, name: str, budget_s: float) -> None:
        self._stage = name
        self._deadline = time.monotonic() + budget_s
        _log("stage=%s budget=%ds" % (name, budget_s))

    def disarm(self) -> None:
        self._deadline = float("inf")

    def _run(self):
        while True:
            time.sleep(5)
            if time.monotonic() > self._deadline:
                _log("watchdog: stage '%s' exceeded its budget"
                     % self._stage)
                os._exit(4)


def main():
    dog = _Watchdog()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _log("no TPU: jax.devices()[0].platform == %r — bench.py "
             "measures the chip and has no CPU mode" % dev.platform)
        return 3

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.analysis.chips import chip_for_device_kind
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    kind = dev.device_kind
    peak = chip_for_device_kind(kind).peak_flops   # unknown kind raises
    _log("stage=backend_up device_kind=%s" % kind)

    multi_precision = True
    seq, batch = 1024, 8
    if _MODEL_SEL == "gpt1.3b":
        # 1.3B widths on one v5e chip (16 GB HBM): bf16 weights, no f32
        # master copy (the Adam moments stay f32: 10 B/param), per-block
        # remat, and scan_layers so the HLO is depth-independent (the
        # scanned program compiles in ~30 s; PADDLE_TPU_BENCH_SCAN=0
        # opts back out). Depth: GPT1P3B_LAYERS_ONE_CHIP above.
        seq, batch = 2048, 4
        cfg = GPTConfig(vocab_size=50304, hidden_size=2048,
                        num_layers=GPT1P3B_LAYERS_ONE_CHIP,
                        num_heads=16, max_seq_len=seq, recompute=True,
                        scan_layers=os.environ.get(
                            "PADDLE_TPU_BENCH_SCAN", "1") != "0",
                        fused_loss_chunk=_int_env(
                            "PADDLE_TPU_BENCH_FUSED_CE", 2048),
                        recompute_policy=os.environ.get(
                            "PADDLE_TPU_BENCH_REMAT_POLICY", "full"))
        multi_precision = False
    else:
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=seq,
                        fused_loss_chunk=_int_env(
                            "PADDLE_TPU_BENCH_FUSED_CE", 0))
        # A/B lever (PADDLE_TPU_BENCH_PURE_BF16=1): drop the f32 master
        # copy (moments stay f32) — an extra record only
        if _int_env("PADDLE_TPU_BENCH_PURE_BF16", 0):
            multi_precision = False

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 multi_precision=multi_precision,
                                 parameters=model.parameters())
    step = TrainStep(model, model.make_loss_fn(), opt)

    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int64"))

    # warmup (compile + 2 steady steps); the unrolled 12-layer program
    # is the slow compile (minutes cold, seconds from the cache)
    dog.stage("compiling",
              _int_env("PADDLE_TPU_BENCH_COMPILE_BUDGET",
                       3600 if _MODEL_SEL == "gpt1.3b" else 900))
    t0 = time.perf_counter()
    loss = step(ids, ids)
    float(loss)
    compile_s = time.perf_counter() - t0
    dog.stage("warmup", 120)
    for _ in range(2):
        loss = step(ids, ids)
    float(loss)

    dog.stage("measuring", 600)
    iters = 20
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(ids, ids)
    float(loss)  # sync
    dt = time.perf_counter() - t0
    dog.disarm()
    _log("stage=measured ms_per_step=%.1f" % (dt / iters * 1e3))

    tokens_per_sec = batch * seq * iters / dt
    # capture the main-geometry dispatch now — the sweep below re-traces
    # at other batches and would overwrite the module-global record
    attention_backend = F.last_attention_dispatch().get("backend")

    # optional batch sweep (PADDLE_TPU_BENCH_SWEEP="16,32"): the same
    # step at other batch sizes, reported as an extra. The watchdog
    # stays disarmed; a failure here fails the run like any other.
    sweep = {}
    for s in os.environ.get("PADDLE_TPU_BENCH_SWEEP", "").split(","):
        if not s.strip():
            continue
        b2 = int(s)
        ids2 = paddle.to_tensor(rng.randint(
            0, cfg.vocab_size, (b2, seq)).astype("int64"))
        _log("stage=sweep_compile b=%d" % b2)
        for _ in range(3):
            loss = step(ids2, ids2)
        float(loss)
        _log("stage=sweep_measure b=%d" % b2)
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(ids2, ids2)
        float(loss)
        dt2 = time.perf_counter() - t0
        sweep[str(b2)] = round(b2 * seq * iters / dt2, 2)
        _log("stage=sweep b=%d tok/s=%.0f" % (b2, sweep[str(b2)]))

    # optional fused-loop A/B (PADDLE_TPU_BENCH_SCAN_STEPS=K): the SAME
    # donated step program dispatched as K-step scanned windows
    # (TrainStep.scan_steps) instead of per-step calls — an extra;
    # tools/bench_train_loop.py is the dedicated dispatch-overhead bench
    scan_extra = {}
    scan_k = _int_env("PADDLE_TPU_BENCH_SCAN_STEPS", 0)
    if scan_k > 1:
        sb = np.stack([np.asarray(ids.value)] * scan_k)
        _log("stage=scan_compile k=%d" % scan_k)
        step.scan_steps(scan_k, sb, sb)          # compile + warm
        n_win = max(1, iters // 2)
        t0 = time.perf_counter()
        for _ in range(n_win):
            last = step.scan_steps(scan_k, sb, sb)
        np.asarray(last.value)                    # terminal sync
        dt_scan = time.perf_counter() - t0
        scan_extra = {
            "scan_steps_k": scan_k,
            "scan_tokens_per_sec": round(
                batch * seq * scan_k * n_win / dt_scan, 2),
        }

    # MFU estimate: 6N per token (fwd+bwd matmuls) + attention
    # 12*L*H*S (PaLM appendix B accounting, causal halved)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    flops_per_token = 6 * n_params + 6 * cfg.num_layers * cfg.hidden_size \
        * seq
    rec = {
        "metric": _METRIC,
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s/chip",
        "mfu_pct": round(100 * tokens_per_sec * flops_per_token / peak, 1),
        "ms_per_step": round(dt / iters * 1e3, 1),
        "compile_s": round(compile_s, 1),
        "params": n_params,
        "num_layers": cfg.num_layers,
        "device": {"platform": dev.platform, "kind": kind,
                   "count": len(jax.devices())},
        # which attention kernel the model actually traced; captured
        # BEFORE the sweep re-traced at other batches
        "attention_backend": attention_backend,
        "peak_bytes_in_use": (dev.memory_stats() or {}).get(
            "peak_bytes_in_use"),
    }
    if sweep:
        rec["batch_sweep_tok_s"] = sweep
    rec.update(scan_extra)
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
