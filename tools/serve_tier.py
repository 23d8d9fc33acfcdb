#!/usr/bin/env python
"""Serving-tier launcher: a health-aware router over N engine replicas.

Operator CLI over ``paddle_tpu.inference.router`` (the predictor-pool /
fleet-serving role of the reference — MIGRATING.md "Serving tier"):
spawns N replica subprocesses (each a ContinuousBatchingEngine behind a
PredictorServer, AOT-warming from the shared executable store), routes
``POST /generate`` to the least-loaded ready replica with
retry-on-a-different-replica, respawns dead replicas, rolls restarts
one replica at a time (POST /admin/rolling_restart), and autoscales on
queue depth between --min and --max.

Serve mode (default):
    python tools/serve_tier.py --replicas 2 --port 8800 \
        --model '{"kind": "gpt", "vocab_size": 50304, ...}'
    ... SIGINT/SIGTERM drains the tier and exits; the LAST stdout line
    is one JSON record of the tier's lifetime stats
    (one terminal JSON record).

Smoke mode (--smoke): tiny model, 2 replicas, a short closed-loop
workload including one replica kill and one rolling restart; exits
nonzero if any request hung, any connection reset, or the
rolling-restart successors compiled anything (store-warm = 0 XLA
compiles). The terminal JSON record carries the phase latencies.

Replicas are separate PROCESSES: the tier forces JAX_PLATFORMS=cpu into
the children unless --replica-platform says otherwise (N processes
cannot share one TPU chip; a TPU tier spans hosts, one replica each).
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import tempfile
import threading
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

TINY_MODEL = {"kind": "gpt", "vocab_size": 256, "hidden_size": 64,
              "num_layers": 2, "num_heads": 4, "max_seq_len": 128}
TINY_ENGINE = {"slots": 4, "max_len": 64, "cache_dtype": "float32",
               "prefill_buckets": [16], "tick_tokens": 4}


def _request(url, payload=None, timeout=120.0):
    import urllib.error
    import urllib.request
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(
        url, data,
        {"Content-Type": "application/json"} if data else {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read())
        except ValueError:
            return e.code, {"error": f"http_{e.code}"}


def _build_router(args):
    from paddle_tpu.inference.router import (ReplicaSpec, Router,
                                             single_device_child_env)
    model = json.loads(args.model) if args.model else dict(TINY_MODEL)
    engine = json.loads(args.engine) if args.engine else dict(TINY_ENGINE)
    child_env = (single_device_child_env(args.replica_platform)
                 if args.replica_platform else {})
    spec = ReplicaSpec(model, engine, warmup=not args.no_warmup,
                       drain_s=args.drain_s, seed=args.seed,
                       env=child_env)
    return Router(
        spec, replicas=args.replicas,
        min_replicas=args.min or args.replicas,
        max_replicas=args.max or args.replicas,
        host=args.host, port=args.port,
        deadline_s=args.deadline_s,
        exec_store_dir=args.exec_store or None)


def _serve(args) -> int:
    # signal handlers FIRST: a SIGTERM during a multi-minute cold
    # warmup must still drain the tier and print the terminal JSON
    # record, not die on the default disposition
    stop_evt = threading.Event()
    for s in (signal.SIGINT, signal.SIGTERM):
        signal.signal(s, lambda *a: stop_evt.set())
    router = _build_router(args).start()
    print(f"tier on http://{router.host}:{router.port} "
          f"({args.replicas} replicas; warming)", file=sys.stderr,
          flush=True)
    deadline = time.time() + args.ready_timeout
    ok = False
    while not stop_evt.is_set() and not ok and time.time() < deadline:
        ok = router.wait_ready(timeout=1.0)
    print(f"tier ready={ok}", file=sys.stderr, flush=True)
    if not stop_evt.is_set():
        stop_evt.wait()
    stats = router.stats()
    router.stop(drain_s=args.drain_s)
    print(json.dumps({"tool": "serve_tier", "mode": "serve", **stats}))
    return 0


def _smoke(args) -> int:
    t0 = time.time()
    args.model = args.model or json.dumps(
        {"kind": "gpt", "vocab_size": 128, "hidden_size": 32,
         "num_layers": 1, "num_heads": 2, "max_seq_len": 64})
    args.engine = args.engine or json.dumps(
        {"slots": 2, "max_len": 48, "cache_dtype": "float32",
         "prefill_buckets": [8], "tick_tokens": 2})
    store = args.exec_store or tempfile.mkdtemp(prefix="tier_smoke_store_")
    args.exec_store = store
    rec = {"tool": "serve_tier", "mode": "smoke"}
    router = _build_router(args).start()
    try:
        if not router.wait_ready(2, timeout=args.ready_timeout):
            rec["error"] = "tier never became ready"
            print(json.dumps(rec))
            return 1
        rec["ready_s"] = round(time.time() - t0, 1)
        base = f"http://{router.host}:{router.port}"
        codes = []
        for i in range(4):
            c, b = _request(base + "/generate",
                            {"input_ids": [1, 2, 3], "max_new_tokens": 4})
            codes.append(c)
        rec["steady_codes"] = codes
        victim = router.replicas()[0]
        os.kill(victim["pid"], signal.SIGKILL)
        c, b = _request(base + "/generate",
                        {"input_ids": [1, 2, 3], "max_new_tokens": 4})
        rec["post_kill_code"] = c
        rec["respawned"] = router.wait_ready(2, timeout=args.ready_timeout)
        rolled = router.rolling_restart(ready_timeout=args.ready_timeout)
        rec["rolling_ok"] = rolled["ok"]
        compiles = []
        for r in [x for x in router.replicas() if not x["draining"]]:
            code, h = _request(f"http://{router.host}:{r['port']}/healthz",
                               timeout=5.0)
            compiles.append(
                h.get("compilation", {}).get("xla_compiles", -1))
        rec["successor_xla_compiles"] = compiles
        c, b = _request(base + "/generate",
                        {"input_ids": [9], "max_new_tokens": 4})
        rec["post_rolling_code"] = c
        rec["stats"] = dict(router.stats_counters)
        rec["wall_s"] = round(time.time() - t0, 1)
        ok = (all(x == 200 for x in codes) and rec["post_kill_code"] == 200
              and rec["respawned"] and rec["rolling_ok"]
              and all(x == 0 for x in compiles)
              and rec["post_rolling_code"] == 200)
        rec["ok"] = ok
        print(json.dumps(rec))
        return 0 if ok else 1
    except Exception as e:   # noqa: BLE001 — terminal record contract
        rec["error"] = f"{type(e).__name__}: {e}"
        print(json.dumps(rec))
        return 1
    finally:
        router.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--min", type=int, default=None,
                    help="autoscaler floor (default: --replicas)")
    ap.add_argument("--max", type=int, default=None,
                    help="autoscaler ceiling (default: --replicas)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8800)
    ap.add_argument("--model", default=None,
                    help="model spec JSON (default: tiny gpt)")
    ap.add_argument("--engine", default=None,
                    help="ContinuousBatchingEngine kwargs JSON")
    ap.add_argument("--exec-store", default=os.environ.get(
        "PADDLE_TPU_EXEC_STORE_DIR"),
        help="shared executable store dir (successors warm from it)")
    ap.add_argument("--deadline-s", type=float, default=None)
    ap.add_argument("--drain-s", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ready-timeout", type=float, default=300.0)
    ap.add_argument("--no-warmup", action="store_true")
    ap.add_argument("--replica-platform", default="cpu",
                    help="JAX_PLATFORMS forced into replica children "
                         "('' = inherit; N processes cannot share one "
                         "TPU chip)")
    ap.add_argument("--smoke", action="store_true",
                    help="self-test: tiny tier, kill + rolling restart, "
                         "terminal JSON, nonzero on any unclean outcome")
    args = ap.parse_args(argv)
    if args.smoke:
        args.port = 0
        return _smoke(args)
    return _serve(args)


if __name__ == "__main__":
    sys.exit(main())
