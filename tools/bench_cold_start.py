#!/usr/bin/env python
"""Cold-start bench: fresh-process cold vs executable-store-warm.

The claim under test (ISSUE 5 acceptance): with the persistent
executable store primed, a BRAND-NEW process reaches its first served
token (and its first train step) with ZERO XLA compiles — the programs
deserialize from the store (paddle_tpu/compilation/store.py), the tiny
eager helper ops hit the jax persistent compilation cache — and
time-to-first-token drops by the whole compile bill.

Method: each measurement is a genuinely fresh `python` subprocess (this
file re-invoked with --child), pointed at a bench-scoped store + jax
cache directory created fresh PER MODE. The cold pass starts with both
EMPTY; the warm pass reuses them. The child measures wall time from interpreter start to
first token / first step and reports the process-wide compile counters
(`compilation.counters`: xla_compiles = backend compiles minus
persistent-cache hits — a cache LOAD routes through the backend-compile
event but is not a compile).

  serve: tiny-GPT ContinuousBatchingEngine behind PredictorServer with
         warmup=True — poll /healthz until warming->ready, then POST
         /generate; time-to-first-token includes import, model build,
         warmup (store load), and the request itself.
  fit:   hapi Model.fit(warm_start=True, num_iters=1) on a tiny MLP —
         time-to-first-step through the same store.

The child sets JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0 so even
sub-second eager compiles are cache hits on the warm pass; the
store-loaded big programs never enter jax's compile path at all.

Last stdout line is one JSON record (one terminal JSON record).
Exit 1 if the warm pass compiled anything (the zero-compile claim is
ASSERTED, not just reported). Record lands in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# child measurements (fresh interpreter each)
# ---------------------------------------------------------------------------

def _child_counters():
    from paddle_tpu.compilation import counters, log
    return {"xla_compiles": counters.xla_compiles(),
            "backend_compiles": counters.backend_compiles(),
            "persistent_cache_hits": counters.persistent_cache_hits(),
            "compile_secs": round(counters.compile_secs(), 3),
            "programs_by_source": log.summary()["by_source"]}


def _child_serve(t0: float) -> dict:
    import urllib.request
    import numpy as np                                    # noqa: F401
    import paddle_tpu                                     # noqa: F401
    import paddle_tpu.compilation                         # noqa: F401
    from paddle_tpu.framework import random as _rng
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.inference.serve import PredictorServer
    t_import = time.perf_counter() - t0
    _rng.seed(0)
    model = GPTForCausalLM(GPTConfig(vocab_size=256, hidden_size=64,
                                     num_layers=2, num_heads=4,
                                     max_seq_len=128))
    eng = ContinuousBatchingEngine(model, slots=4, max_len=64,
                                   cache_dtype="float32", tick_tokens=4,
                                   prefill_buckets=(16,))
    srv = PredictorServer(engine=eng, port=0, warmup=True).start()
    t_built = time.perf_counter() - t0
    url = f"http://{srv.host}:{srv.port}"
    while True:                       # warming -> ready transition
        try:
            with urllib.request.urlopen(url + "/healthz") as r:
                if json.loads(r.read()).get("status") == "ready":
                    break
        except urllib.error.HTTPError as e:
            if json.loads(e.read()).get("status") not in ("warming",):
                raise
        time.sleep(0.02)
    t_ready = time.perf_counter() - t0
    req = urllib.request.Request(
        url + "/generate",
        json.dumps({"input_ids": [1, 2, 3, 4],
                    "max_new_tokens": 8}).encode(),
        {"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        out = json.loads(r.read())
    t_first_token = time.perf_counter() - t0
    srv.stop()
    eng.stop()
    return {"mode": "serve", "import_s": round(t_import, 3),
            "built_s": round(t_built, 3), "ready_s": round(t_ready, 3),
            "time_to_first_token_s": round(t_first_token, 3),
            "new_tokens": out["new_tokens"], **_child_counters()}


def _child_fit(t0: float) -> dict:
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.compilation                         # noqa: F401
    from paddle_tpu import nn
    from paddle_tpu.hapi import Model
    from paddle_tpu.optimizer import AdamW
    t_import = time.perf_counter() - t0
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(32, 64), nn.ReLU(), nn.Linear(64, 8))
    m = Model(net)
    m.prepare(AdamW(learning_rate=1e-3,
                    parameters=net.parameters()),
              nn.CrossEntropyLoss())
    rng = np.random.RandomState(0)
    X = rng.randn(64, 32).astype("float32")
    Y = rng.randint(0, 8, (64, 1))

    class ListLoader:
        batches = [(X[i * 16:(i + 1) * 16], Y[i * 16:(i + 1) * 16])
                   for i in range(4)]

        def __iter__(self):
            return iter(self.batches)

        def __len__(self):
            return len(self.batches)

    t_built = time.perf_counter() - t0
    m.fit(ListLoader(), epochs=1, num_iters=1, verbose=0,
          warm_start=True)
    t_first_step = time.perf_counter() - t0
    return {"mode": "fit", "import_s": round(t_import, 3),
            "built_s": round(t_built, 3),
            "time_to_first_step_s": round(t_first_step, 3),
            **_child_counters()}


def _run_child(mode: str, workdir: str) -> dict:
    env = dict(os.environ)
    # this tool measures a COLD start on purpose: each run gets an empty
    # executable store and an empty jax cache of its own under a fresh
    # workdir, overriding the package's placement rule
    # (paddle_tpu/_paths.jax_cache_dir) — never copy this elsewhere
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PADDLE_TPU_EXEC_STORE_DIR": os.path.join(workdir, "exec"),
        "JAX_COMPILATION_CACHE_DIR": os.path.join(workdir, "xla"),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    })
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", mode],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(
            f"child {mode} failed rc={out.returncode}:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", choices=["serve", "fit"], default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--modes", default="serve,fit",
                    help="comma subset of serve,fit")
    ap.add_argument("--keep", action="store_true",
                    help="keep the bench store/cache dir (default: rm)")
    args = ap.parse_args()

    if args.child:
        sys.path.insert(0, ROOT)
        t0 = time.perf_counter()
        rec = (_child_serve if args.child == "serve" else _child_fit)(t0)
        print(json.dumps(rec))
        return 0

    record = {"bench": "cold_start", "results": {}}
    ok = True
    workdirs = []
    try:
        for mode in [m.strip() for m in args.modes.split(",") if m.strip()]:
            # fresh store + jax cache dirs PER MODE: the serve cold
            # pass must not prime helper-op cache entries the fit cold
            # pass would then hit — "cold = both empty" holds for every
            # mode, not just the first
            workdir = tempfile.mkdtemp(
                prefix=f"paddle_tpu_cold_start_{mode}_")
            workdirs.append(workdir)
            cold = _run_child(mode, workdir)
            warm = _run_child(mode, workdir)
            key = ("time_to_first_token_s" if mode == "serve"
                   else "time_to_first_step_s")
            res = {
                "cold": cold, "warm": warm,
                "cold_s": cold[key], "warm_s": warm[key],
                "speedup": round(cold[key] / max(warm[key], 1e-9), 2),
                "warm_xla_compiles": warm["xla_compiles"],
                "zero_compile_warm": warm["xla_compiles"] == 0,
            }
            record["results"][mode] = res
            ok = ok and res["zero_compile_warm"]
            print(f"[{mode}] cold {cold[key]:.2f}s "
                  f"(compiles {cold['xla_compiles']}) -> warm "
                  f"{warm[key]:.2f}s (compiles {warm['xla_compiles']}) "
                  f"= {res['speedup']}x", file=sys.stderr)
    except Exception as e:   # noqa: BLE001 — record the failure
        record["error"] = f"{type(e).__name__}: {e}"
        ok = False
    finally:
        if not args.keep:
            import shutil
            for workdir in workdirs:
                shutil.rmtree(workdir, ignore_errors=True)
        else:
            record["workdirs"] = workdirs
    record["zero_compile_warm_all"] = ok and "error" not in record
    print(json.dumps(record))
    return 0 if record["zero_compile_warm_all"] else 1


if __name__ == "__main__":
    sys.exit(main())
