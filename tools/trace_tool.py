#!/usr/bin/env python
"""Chrome/Perfetto trace tooling for the obs subsystem (ISSUE 8).

Modes:
  --self-test
      End-to-end smoke of the observability plumbing with NO external
      state: span/ring/export round-trip, metrics render->parse->
      percentile round-trip, then a LIVE tiny engine behind a
      PredictorServer — /generate with a request id, /metrics scraped
      twice (series must parse and be monotonic), /healthz metrics_seq,
      POST /admin/trace resolving the request id to its phase spans.
      Exit 0 on success; wired into tools/ci.py's quick profile.
  --export OUT [--url http://host:port] [--duration S] [--profile]
      Capture a trace: from a live server's POST /admin/trace when
      --url is given (any PredictorServer or router), else from THIS
      process's ring. Writes Chrome-trace JSON to OUT (load it in
      chrome://tracing or ui.perfetto.dev).
  --tier-capture OUT
      Spin a tiny 2-replica tier, run a few traced requests through
      the router, and write ONE merged Chrome trace (router spans +
      the serving replica's engine spans, correlated by request id) to
      OUT.

Prints ONE terminal JSON record;
exit 2 on usage errors with an {"error": ...} record (warmup.py
parity, so the suite watcher never spins on an empty artifact).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import urllib.request

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def _fail(msg: str, code: int = 1) -> int:
    print(json.dumps({"error": msg}))
    return code


def _fetch_trace(base_url: str, duration_s: float, profile: bool) -> dict:
    q = f"?duration_s={duration_s:g}" + ("&profile=1" if profile else "")
    req = urllib.request.Request(base_url.rstrip("/") + "/admin/trace" + q,
                                 b"")
    with urllib.request.urlopen(req, timeout=duration_s + 30) as r:
        return json.loads(r.read())


# ---------------------------------------------------------------------------
# self-test
# ---------------------------------------------------------------------------

def self_test() -> int:
    from paddle_tpu import obs

    # 1. span -> ring -> chrome export round trip
    with obs.span("selftest.scope", cat="selftest", request_id="st-rid"):
        time.sleep(0.002)
    obs.record_span("selftest.raw", time.perf_counter() - 0.001,
                    time.perf_counter(), cat="selftest")
    with tempfile.TemporaryDirectory() as td:
        path = obs.trace.export_chrome(os.path.join(td, "t.json"))
        doc = json.load(open(path))
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"selftest.scope", "selftest.raw"} <= names, names
        for e in doc["traceEvents"]:
            assert e["ph"] == "X" and e["dur"] >= 0, e
        dump = obs.trace.dump_flight("selftest", dir_path=td)
        meta = json.load(open(dump))["metadata"]
        assert meta["reason"] == "selftest", meta

    # 2. metrics render -> parse -> percentile round trip
    reg = obs.metrics.registry
    h = reg.histogram("ptpu_selftest_ms", "selftest latencies")
    for v in (1.0, 4.0, 40.0, 400.0):
        h.observe(v)
    samples = obs.metrics.parse_text(reg.render())
    edges, cum = obs.metrics.samples_to_hist(samples, "ptpu_selftest_ms")
    p50 = obs.metrics.percentile_from_cum(edges, cum, 0.5)
    assert 0 < p50 < 400, p50

    # 3. live server: tiny engine, request-id -> spans, /metrics
    # monotonic across scrapes, /healthz freshness token
    import paddle_tpu as paddle
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.inference.serve import PredictorServer
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
        max_seq_len=48))
    model.eval()
    engine = ContinuousBatchingEngine(
        model, slots=2, max_len=40, cache_dtype="float32",
        prefill_buckets=(8,), tick_tokens=2)
    srv = PredictorServer(engine=engine, port=0).start()
    base = f"http://{srv.host}:{srv.port}"
    try:
        rids = []
        for i in range(2):
            req = urllib.request.Request(
                base + "/generate",
                json.dumps({"input_ids": [1 + i, 2, 3],
                            "max_new_tokens": 4}).encode(),
                {"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                body = json.loads(r.read())
            assert body.get("request_id"), body
            rids.append(body["request_id"])

        def scrape():
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=10) as r:
                return obs.metrics.parse_text(r.read().decode())

        def value(samples, name):
            return sum(v for n, _, v in samples if n == name)

        s1 = scrape()
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            hz = json.loads(r.read())
        assert "metrics_seq" in hz and "uptime_s" in hz, hz
        req = urllib.request.Request(
            base + "/generate",
            json.dumps({"input_ids": [9, 8],
                        "max_new_tokens": 4}).encode(),
            {"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120):
            pass
        s2 = scrape()
        for name in ("ptpu_engine_ticks_total",
                     "ptpu_engine_admits_total",
                     "ptpu_engine_retires_total"):
            v1, v2 = value(s1, name), value(s2, name)
            assert v1 > 0 and v2 > v1, (name, v1, v2)
        assert value(s2, "ptpu_engine_batch_occupancy_count") > 0

        doc = _fetch_trace(base, 0.0, False)
        by_rid = {}
        for e in doc["traceEvents"]:
            rid = e.get("args", {}).get("request_id")
            if rid:
                by_rid.setdefault(rid, set()).add(e["name"])
        for rid in rids:
            assert {"engine.queue_wait", "engine.prefill",
                    "engine.decode"} <= by_rid.get(rid, set()), \
                (rid, by_rid.get(rid))
    finally:
        srv.stop()
        engine.stop()

    print(json.dumps({
        "metric": "obs_selftest", "value": 1, "unit": "pass",
        "ring_size": obs.recorder.size,
        "metrics_seq": reg.seq(),
        "request_ids_checked": len(rids),
    }))
    return 0


# ---------------------------------------------------------------------------
# tier capture
# ---------------------------------------------------------------------------

def tier_capture(out_path: str) -> int:
    from paddle_tpu import obs
    from paddle_tpu.inference.router import (ReplicaSpec, Router,
                                             single_device_child_env)

    model = {"kind": "gpt", "vocab_size": 128, "hidden_size": 32,
             "num_layers": 1, "num_heads": 2, "max_seq_len": 64}
    engine = {"slots": 2, "max_len": 48, "cache_dtype": "float32",
              "prefill_buckets": [8], "tick_tokens": 2}
    store = tempfile.mkdtemp(prefix="trace_tier_store_")
    spec = ReplicaSpec(model, engine, warmup=True, drain_s=5.0, seed=0,
                       env=single_device_child_env("cpu"))
    router = Router(spec, replicas=2, poll_s=0.3, deadline_s=60.0,
                    exec_store_dir=store).start()
    try:
        if not router.wait_ready(2, timeout=300):
            return _fail(f"tier never ready: {router.replicas()}")
        base = f"http://{router.host}:{router.port}"
        rids, served = [], set()
        for i in range(6):
            req = urllib.request.Request(
                base + "/generate",
                json.dumps({"input_ids": [1 + i, 2, 3],
                            "max_new_tokens": 6}).encode(),
                {"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                body = json.loads(r.read())
            rids.append(body.get("request_id"))
            served.add(body.get("served_by"))
        # merge: the router's own ring + every live replica's ring
        # (distinct pids — chrome renders them as separate processes)
        events = obs.trace.capture(0.0)["traceEvents"]
        for rep in router.replicas():
            if rep["port"] is None or rep["draining"]:
                continue
            try:
                doc = _fetch_trace(
                    f"http://{router.host}:{rep['port']}", 0.0, False)
                events += doc["traceEvents"]
            except (OSError, ValueError):
                continue
        obs.trace.export_chrome(
            out_path, events=events,
            metadata={"kind": "tier_capture", "request_ids": rids,
                      "served_by": sorted(x for x in served if x)})
        print(json.dumps({
            "metric": "tier_trace_capture", "value": len(events),
            "unit": "events", "requests": len(rids),
            "replicas_serving": sorted(x for x in served if x),
            "trace_path": out_path,
        }))
        return 0
    finally:
        router.stop()
        import shutil
        shutil.rmtree(store, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--self-test", action="store_true")
    ap.add_argument("--export", metavar="OUT")
    ap.add_argument("--tier-capture", metavar="OUT")
    ap.add_argument("--url", help="live server base URL for --export")
    ap.add_argument("--duration", type=float, default=0.0,
                    help="capture window seconds (0 = snapshot now)")
    ap.add_argument("--profile", action="store_true",
                    help="with --export --url: also trigger a "
                         "programmatic jax.profiler capture")
    args = ap.parse_args(argv)

    if args.self_test:
        try:
            return self_test()
        except AssertionError as e:
            return _fail(f"self-test assertion: {e}")
    if args.tier_capture:
        return tier_capture(args.tier_capture)
    if args.export:
        if args.url:
            try:
                doc = _fetch_trace(args.url, args.duration, args.profile)
            except (OSError, ValueError) as e:
                return _fail(f"fetch failed: {e}")
            from paddle_tpu import obs
            obs.trace.export_chrome(args.export,
                                    events=doc["traceEvents"],
                                    metadata=doc.get("metadata"))
        else:
            from paddle_tpu import obs
            obs.trace.export_chrome(args.export, include_open=True)
        n = len(json.load(open(args.export))["traceEvents"])
        print(json.dumps({"metric": "trace_export", "value": n,
                          "unit": "events", "trace_path": args.export}))
        return 0
    # no mode: usage error with a terminal record (watcher contract)
    print(json.dumps({"error": "need one of --self-test / --export / "
                               "--tier-capture"}))
    return 2


if __name__ == "__main__":
    sys.exit(main())
