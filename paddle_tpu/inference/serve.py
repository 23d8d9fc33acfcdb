"""HTTP serving front-end over the inference predictor.

Serving-path role (BASELINE.json north star: "ERNIE-3.0 served
end-to-end"): the reference serves through AnalysisPredictor embedded in
C++ servers or the FleetExecutor DistModel service
(fleet_executor/dist_model.cc). TPU-native equivalent: the AOT-compiled
predictor (inference/predictor.py) behind a threaded stdlib HTTP server —
zero extra dependencies, JSON tensors in/out.

Endpoints:
  GET  /health    -> {"status": "ok"} (liveness — the process answers)
  GET  /healthz   -> readiness: 200 once the predictor can serve, 503
                     with a reason while degraded (failure streak,
                     saturated queue); with an engine attached the body
                     carries slot occupancy + queue depth; always
                     carries uptime_s + metrics_seq (the obs registry's
                     mutation sequence — stale stats are tellable from
                     live ones)
  GET  /metrics   -> Prometheus-style text from the obs registry
                     (paddle_tpu.obs): engine tick/occupancy/phase
                     histograms, host syncs, XLA compiles, ...
  POST /admin/trace?duration_s=S[&profile=1]
                  -> capture the obs flight recorder for S seconds
                     (0 = snapshot the whole ring now) and return
                     Chrome/Perfetto trace JSON; profile=1 also runs a
                     programmatic jax.profiler capture over the window
  GET  /metadata  -> input/output names (+ dtypes/shapes once known)
  POST /predict   -> {"inputs": {name: nested-list | {"data": ...,
                      "dtype": "float32"}}} -> {"outputs": {name: ...}}
  POST /generate  -> {"input_ids": [...], "max_new_tokens": n,
                      "eos_token_id": opt, "seed": opt} -> {"tokens":
                      [...]} — served by the continuous-batching engine
                      (inference/engine.py): requests from concurrent
                      clients multiplex through ONE compiled batched
                      decode program, each resolved by its own future.
                      With "stream": true the response is incremental
                      NDJSON (read-until-close): one {"t": [tokens]}
                      line per emitted block as the engine produces it
                      (first token at admission, then per tick), then
                      a terminal {"done": {...full body...}} line — or
                      {"err": {"error": ..., "tokens_generated": n,
                      "partial_tokens": [...]}} when the request dies
                      or is cancelled mid-decode, carrying the partial
                      result so a router's token journal can reconcile
                      against engine truth. The router's
                      work-conserving failover and hedged decode ride
                      this side-channel (inference/router.py).
  POST /cancel    -> {"request_id": rid} -> {"cancelled": bool} — real
                      request cancellation: a queued request resolves
                      immediately, an admitted one retires at the next
                      tick boundary (slot freed, KV pages decref'd —
                      leak-free); its waiter gets 409 "cancelled" (or
                      the stream's err line) with the partial result
  POST /admin/inject -> {"site": s, "count": n, "wedge_s": opt} — arm
                      a resilience fault site in THIS live replica
                      (e.g. replica_stall to wedge the decode loop);
                      chaos tooling only, 403 unless the process runs
                      with PADDLE_TPU_CHAOS_ADMIN=1

Graceful degradation (resilience subsystem, distributed/resilience.py):
every /predict carries a deadline (PADDLE_TPU_SERVE_DEADLINE, default
30s) — a wedged backend yields a fast 503, never a hung client; when
more than PADDLE_TPU_SERVE_MAX_QUEUE requests are already waiting the
server sheds load with an immediate 503 instead of queueing into its
own deadline.

Every 503 carries a ``Retry-After`` header (and a ``retry_after_s``
body field) so routers and external clients back off on the server's
word instead of guessing — the contract the serving-tier router
(inference/router.py) builds its retry schedule on.

Draining (rolling restarts): POST /drain flips the server into a
draining state — /healthz goes unready (reason "draining"), new
/predict + /generate admissions shed 503 "draining", in-flight
requests run to completion. ``stop(drain_s=K)`` waits (bounded) for
in-flight work before shutting the listener down; the default
``drain_s=0`` keeps the historical fast-stop behavior.

CLI: python -m paddle_tpu.inference.serve --model m.pdmodel --port 8866
"""
from __future__ import annotations

import argparse
import json
import os
import queue as _queue
import threading
import time
import urllib.parse
import uuid
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeout
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from .. import obs as _obs
from ..distributed import resilience as _resil
from .predictor import Config, create_predictor

__all__ = ["PredictorServer", "main"]

#: request-id propagation header (router -> replica -> engine): one
#: request's spans correlate across the whole tier under this id
REQUEST_ID_HEADER = "X-PTPU-Request-Id"


# the ONE float-knob parser (framework/env.py); the old private name
# stays as a face — router.py and tests import it from here
from ..framework.env import bool_env as _env_bool  # noqa: E402
from ..framework.env import float_env as _env_float  # noqa: E402


# How long a client should wait before retrying each 503 reason. The
# values are advisory backoff hints, not promises: "overloaded" clears
# as soon as a slot frees (fast), "warming_up" waits on an XLA compile
# or store load (slow). Routers treat any 503 carrying one of these as
# retryable-on-another-replica.
RETRY_AFTER_S = {
    "overloaded": 1.0,
    # paged engine's KV page pool is the binding constraint — clears
    # when a request retires and frees its pages (slower than a bare
    # slot freeing, the retiring request must finish decoding)
    "cache_exhausted": 2.0,
    "warming_up": 5.0,
    "deadline_exceeded": 2.0,
    "backend_unavailable": 2.0,
    "draining": 2.0,
    "unready": 1.0,
}


def send_json(handler, code, obj, retry_after=None,
              retry_after_table=None):
    """The ONE json-response writer for serving handlers (this server
    AND the router tier front-end — the Retry-After contract must not
    fork). ``retry_after`` (seconds) rides any 503 as both the HTTP
    ``Retry-After`` header (integer, per spec) and a ``retry_after_s``
    body field (exact float); when omitted on a 503 it is derived from
    the body's ``error`` reason via ``retry_after_table`` so no shed
    response can ship without one."""
    table = RETRY_AFTER_S if retry_after_table is None \
        else retry_after_table
    if code == 503 and retry_after is None:
        reason = str(obj.get("error", "")).split(":")[0]
        retry_after = table.get(reason, table["unready"])
    if retry_after is not None:
        obj.setdefault("retry_after_s", float(retry_after))
    body = json.dumps(obj).encode()
    handler.send_response(code)
    handler.send_header("Content-Type", "application/json")
    if retry_after is not None:
        handler.send_header("Retry-After",
                            str(max(1, int(-(-retry_after // 1)))))
    handler.send_header("Content-Length", str(len(body)))
    handler.end_headers()
    handler.wfile.write(body)


def send_text(handler, code, text,
              content_type="text/plain; version=0.0.4; charset=utf-8"):
    """Plain-text response writer (the /metrics exposition body — the
    Prometheus text format's conventional content type). Shared with
    the router tier front-end."""
    body = text.encode()
    handler.send_response(code)
    handler.send_header("Content-Type", content_type)
    handler.send_header("Content-Length", str(len(body)))
    handler.end_headers()
    handler.wfile.write(body)


def handle_admin_trace(handler, drain_body_fn):
    """POST /admin/trace?duration_s=S[&profile=1] — shared by the
    replica server and the router front-end: capture the obs flight
    recorder over the window and answer Chrome-trace JSON."""
    drain_body_fn()
    q = urllib.parse.parse_qs(
        urllib.parse.urlsplit(handler.path).query)
    try:
        duration = float(q.get("duration_s", ["0"])[0])
    except ValueError:
        send_json(handler, 400, {"error": "bad duration_s"})
        return
    profile = q.get("profile", ["0"])[0] not in ("0", "", "false")
    doc = _obs.trace.capture(min(max(duration, 0.0), 60.0),
                             jax_profile=profile)
    send_json(handler, 200, doc)


class PredictorServer:
    """Owns one predictor and an HTTP server bound to host:port.

    The predictor is not thread-safe (zero-copy handles are shared
    state), so requests serialize on a lock — concurrency comes from the
    XLA program itself, which is where the time goes.
    """

    def __init__(self, model_path_or_config=None, host: str = "127.0.0.1",
                 port: int = 8866, deadline_s: float = None,
                 max_queue: int = None, engine=None, warmup: bool = None):
        if model_path_or_config is None and engine is None:
            raise ValueError(
                "need a model path/Config (predict path), an engine "
                "(generate path), or both")
        self.engine = engine             # ContinuousBatchingEngine|None
        self._owned_predictor = None     # engine whose lifecycle is OURS
        if model_path_or_config is not None:
            cfg = (model_path_or_config
                   if isinstance(model_path_or_config, Config)
                   else Config(model_path_or_config))
            self.predictor = create_predictor(cfg)
            from .engine import GenerationPredictor
            if isinstance(self.predictor, GenerationPredictor):
                # a Config with enable_continuous_batching() serves the
                # GENERATE path: wire its engine in, there is no tensor
                # predictor behind /predict. We created this engine, so
                # stop() must also shut it down (an explicitly-passed
                # `engine=` stays caller-owned)
                if self.engine is None:
                    self.engine = self.predictor.engine
                    self._owned_predictor = self.predictor
                self.predictor = None
        else:
            self.predictor = None
        self._lock = threading.Lock()
        self.deadline_s = (deadline_s if deadline_s is not None
                           else _env_float("PADDLE_TPU_SERVE_DEADLINE",
                                           30.0))
        self.max_queue = int(max_queue if max_queue is not None
                             else _env_float("PADDLE_TPU_SERVE_MAX_QUEUE",
                                             8))
        # ONE predict worker: the predictor serializes anyway (zero-copy
        # handles are shared state); running it in a dedicated thread is
        # what lets a handler ABANDON a wedged call at its deadline —
        # the handler thread is never the one stuck in the runtime
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="predict")
        self._depth = 0                 # requests submitted, not done
        self._depth_lock = threading.Lock()
        self._resp_inflight = 0         # admitted requests whose
        #                                 response is not yet written
        #                                 (BOTH paths — what a drain
        #                                 actually waits on)
        self._draining = False          # /drain flips; stop() waits
        self._failure_streak = 0        # consecutive 5xx-class outcomes
        # AOT warmup (paddle_tpu.compilation): compile-or-load the
        # engine's programs BEFORE the first request instead of on it.
        # /healthz reports "warming" (503) until done and /generate
        # sheds with the 503 contract — an orchestrator keeps traffic
        # off a process that would stall it on a compile.
        if warmup is None:
            from ..framework.env import bool_env
            warmup = bool_env("PADDLE_TPU_SERVE_WARMUP", False)
        self._warmup_requested = bool(warmup)
        self._warm_state = "warming" if self._warmup_requested else "ready"
        self._warm_error = None
        self._warmup_thread = None
        self._started = time.monotonic()
        # the device this process serves from, as jax reports it — on
        # /healthz so a deployment can see a replica that came up on the
        # wrong platform (a server always holds a built model, so the
        # backend is already initialised here)
        import jax
        dev = jax.devices()[0]
        self._device = {"platform": dev.platform,
                        "kind": dev.device_kind,
                        "count": len(jax.devices())}
        self.httpd = ThreadingHTTPServer((host, port),
                                         self._make_handler())
        self.host, self.port = self.httpd.server_address[:2]
        self._thread = None
        if self._warmup_requested:
            # warm on a side thread so the listener binds (and answers
            # /health + a truthful warming /healthz) immediately —
            # readiness flips, liveness never blocks on a compile
            self._warmup_thread = threading.Thread(
                target=self._run_warmup, daemon=True,
                name="serve-warmup")
            self._warmup_thread.start()

    def _run_warmup(self):
        try:
            from ..compilation import prime_helper_ops
            prime_helper_ops()
            if self.engine is not None and hasattr(self.engine, "warmup"):
                self.engine.warmup()
        except Exception as e:   # noqa: BLE001 — a failed warmup must
            # not brick the server: first traffic falls back to the
            # lazy-jit compile it would have paid anyway
            self._warm_error = f"{type(e).__name__}: {e}"
        finally:
            self._warm_state = "ready"

    # ------------------------------------------------------------------
    def inflight(self) -> int:
        """Requests admitted but not yet responded to (both paths) —
        what a drain waits on."""
        with self._depth_lock:
            return self._resp_inflight + self._depth

    def begin_drain(self) -> int:
        """Stop admitting new requests; in-flight ones run to
        completion. /healthz goes unready (reason "draining") so a
        router pulls this replica out of rotation immediately; the
        listener stays up so health polls and in-flight responses still
        flow. Returns the in-flight count at the moment of the flip.
        Idempotent — a second /drain just re-reports. The flip happens
        under the depth lock, atomically against the admission paths'
        own locked check-and-increment — stop(drain_s)'s wait can never
        observe inflight()==0 with an admitted request not yet
        counted."""
        with self._depth_lock:
            self._draining = True
            return self._resp_inflight + self._depth

    # ------------------------------------------------------------------
    def _metadata(self):
        if self.predictor is not None:
            return {"inputs": self.predictor.get_input_names(),
                    "outputs": self.predictor.get_output_names()}
        return {"inputs": ["input_ids"], "outputs": ["tokens"]}

    def _readiness(self):
        """(ready, body) for /healthz. Degraded conditions are reported
        with a reason so an orchestrator can tell shed-load from dead.
        With an engine attached the body carries slot occupancy and
        generate-queue depth so an autoscaler can see saturation."""
        with self._depth_lock:
            draining = self._draining
        body = {"status": "ready",
                "uptime_s": round(time.monotonic() - self._started, 1),
                # obs-registry mutation sequence: moves whenever any
                # metric moves, so a scraper (the router's per-replica
                # view) can tell live stats from a wedged process
                # re-serving stale ones
                "metrics_seq": _obs.metrics.registry.seq(),
                "queue_depth": self._depth,
                "inflight": self.inflight(),
                "draining": draining,
                "max_queue": self.max_queue,
                "failure_streak": self._failure_streak,
                "device": self._device}
        try:
            from ..compilation import log as _clog
            body["compilation"] = _clog.summary()
        except Exception:
            pass
        st = None
        if self.engine is not None:
            st = self.engine.stats()
            body["engine"] = {k: st[k] for k in
                              ("slots", "active", "free", "queued",
                               "max_queue", "ticks",
                               "compiled_programs",
                               # obs.efficiency live gauge mirror: last
                               # tick's modeled-bytes/s fraction of the
                               # efficiency chip's HBM bandwidth
                               "tick_model_eff")}
            body["engine"]["warm"] = getattr(self.engine, "warm", True)
            # mesh geometry (ISSUE 20): a tier replica may be an N-chip
            # TP slice, not a chip — the router's replica snapshot and
            # any autoscaler need the real footprint
            body["engine"]["tp"] = st.get("tp", 1)
            body["engine"]["mesh_devices"] = st.get("mesh_devices", 1)
            if "mesh" in st:
                body["engine"]["mesh"] = st["mesh"]
            if st.get("paged"):
                # paged KV pool health: an autoscaler reads page
                # pressure (pool near-full with slots free = grow
                # cache, not replicas) and the prefix hit rate
                body["engine"].update({
                    k: st[k] for k in
                    ("paged", "page_size", "pages_total", "pages_free",
                     "pages_used", "page_utilization", "prefix_hits",
                     "prefix_misses", "prefix_hit_rate",
                     # chained-crc32 trie node ids — the router's
                     # prefix-affinity routing intersects a prompt's
                     # own chain hashes with this set (ISSUE 16)
                     "prefix_fingerprints") if k in st})
            if st.get("speculative"):
                # speculative decoding health: acceptance rate and
                # accepted-tokens-per-tick are the knobs an operator
                # tunes k / the drafter against
                body["engine"].update({
                    k: st[k] for k in
                    ("speculative", "spec_k", "spec_ticks",
                     "tokens_drafted", "tokens_accepted",
                     "tokens_rejected", "acceptance_rate",
                     "accepted_tokens_per_tick")})
        if draining:
            # draining dominates every other state: in-flight requests
            # are finishing, nothing new may be routed here
            body.update(status="draining", reason="draining for restart")
            return False, body
        if self._warm_state == "warming":
            # truthful readiness: programs are still compiling (or
            # loading from the executable store); traffic sent now
            # would stall behind the compile
            body.update(status="warming", reason="warmup in progress")
            return False, body
        if self._warm_error is not None:
            # warmup failed — the server still serves (lazy compile on
            # first request is the degraded-but-correct fallback), the
            # orchestrator just gets to see why readiness was late
            body["warmup_error"] = self._warm_error
        if st is not None and st["queued"] >= st["max_queue"]:
            body.update(status="unready",
                        reason="engine request queue saturated")
            return False, body
        if self.predictor is None and self.engine is None:
            body.update(status="unready", reason="no predictor loaded")
            return False, body
        if self._failure_streak >= 3:
            body.update(status="unready",
                        reason=f"{self._failure_streak} consecutive "
                               "predict failures (backend unavailable?)")
            return False, body
        if self._depth >= self.max_queue:
            body.update(status="unready", reason="request queue saturated")
            return False, body
        return True, body

    def _predict(self, payload):
        # fault sites: a wedged backend (hangs until the request
        # deadline trips) and an unavailable one (raises; mapped to 503)
        _resil.maybe_inject("serve_hang")
        _resil.maybe_inject("serve_backend")
        if self.predictor is None:
            raise ValueError(
                "no predictor loaded (this server only has a generation "
                "engine — POST /generate)")
        inputs = payload.get("inputs")
        if not isinstance(inputs, dict):
            raise ValueError('body must be {"inputs": {name: tensor}}')
        names = self.predictor.get_input_names()
        unknown = set(inputs) - set(names)
        if unknown:
            raise ValueError(f"unknown input(s) {sorted(unknown)}; "
                             f"expected {names}")
        missing = set(names) - set(inputs)
        if missing:
            raise ValueError(f"missing input(s) {sorted(missing)}")
        with self._lock:
            for name in names:
                v = inputs[name]
                dtype = v.get("dtype") if isinstance(v, dict) else None
                data = v["data"] if isinstance(v, dict) else v
                if dtype is None:
                    # JSON numbers arrive as int64/float64: coerce to the
                    # model's declared input dtype when it is known
                    dtype = self.predictor.get_input_dtype(name)
                arr = np.asarray(data, dtype=dtype)
                self.predictor.get_input_handle(name).copy_from_cpu(arr)
            self.predictor.run()
            outs = {}
            for name in self.predictor.get_output_names():
                a = np.asarray(
                    self.predictor.get_output_handle(name).copy_to_cpu())
                outs[name] = {"data": a.tolist(), "dtype": str(a.dtype),
                              "shape": list(a.shape)}
        return {"outputs": outs}

    # ------------------------------------------------------------------
    def _make_handler(self):
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):        # quiet by default
                pass

            def _send(self, code, obj, retry_after=None):
                send_json(self, code, obj, retry_after=retry_after)

            def do_GET(self):
                if self.path == "/health":
                    self._send(200, {"status": "ok"})
                elif self.path == "/healthz":
                    ready, body = server._readiness()
                    ra = None
                    if not ready:
                        ra = (RETRY_AFTER_S["warming_up"]
                              if body.get("status") == "warming"
                              else RETRY_AFTER_S["draining"]
                              if body.get("status") == "draining"
                              else RETRY_AFTER_S["unready"])
                    self._send(200 if ready else 503, body,
                               retry_after=ra)
                elif self.path == "/metrics":
                    send_text(self, 200, _obs.metrics.registry.render())
                elif self.path == "/metadata":
                    self._send(200, server._metadata())
                else:
                    self._send(404, {"error": f"no route {self.path}"})

            def _drain_body(self):
                """Read (and discard) any unread request body —
                responding with unread POST bytes on the socket resets
                the connection instead of delivering the response."""
                try:
                    self.rfile.read(
                        int(self.headers.get("Content-Length", "0")))
                except (ValueError, OSError):
                    pass

            def _read_json_body(self):
                """Parsed JSON request body, or None when it is
                unreadable or malformed — the ONE body-read idiom for
                every POST route (each caller picks its own error
                response; a half-sent or non-JSON body is the
                client's fault, never a 500)."""
                try:
                    n = int(self.headers.get("Content-Length", "0"))
                    return json.loads(self.rfile.read(n) or b"{}")
                except (ValueError, OSError):
                    return None

            def do_POST(self):
                if self.path == "/drain":
                    # admin: flip into draining (idempotent). The
                    # caller (router rolling restart / serve_tier)
                    # polls /healthz "inflight" to watch it empty, then
                    # terminates the process, whose SIGTERM path runs
                    # stop(drain_s) as a belt-and-braces second wait
                    self._drain_body()
                    n = server.begin_drain()
                    self._send(200, {"status": "draining",
                                     "inflight": n})
                    return
                if self.path.startswith("/admin/trace"):
                    handle_admin_trace(self, self._drain_body)
                    return
                if self.path == "/cancel":
                    self._do_cancel()
                    return
                if self.path.startswith("/admin/inject"):
                    self._do_admin_inject()
                    return
                if self.path == "/generate":
                    self._do_generate()
                    return
                if self.path == "/prewarm":
                    self._do_prewarm()
                    return
                if self.path != "/predict":
                    self._send(404, {"error": f"no route {self.path}"})
                    return
                if server.predictor is None:
                    # mirror of /generate on an engine-less server: the
                    # route does not exist HERE (404), it is not the
                    # client's request that is malformed (400)
                    self._send(404, {"error": "no predictor loaded "
                                              "(engine-only server — "
                                              "POST /generate)"})
                    return
                # load shedding BEFORE reading the body into the queue:
                # a saturated predict worker means every queued request
                # would blow its deadline anyway — 503 now is cheaper
                # for the client than 503 in deadline_s seconds. The
                # draining check lives in the SAME locked block as the
                # depth increment (atomic against begin_drain's flip),
                # and every shed drains the unread body first — a 503
                # on unread POST bytes is a connection reset, not a
                # delivered response
                with server._depth_lock:
                    if server._draining:
                        shed, depth = "draining", server._depth
                    elif server._depth >= server.max_queue:
                        shed, depth = "overloaded", server._depth
                    else:
                        shed = None
                        server._depth += 1
                        # depth alone is NOT the drain signal: the
                        # worker releases it when the predict call
                        # finishes, which can be BEFORE this handler
                        # writes the response — the response counter
                        # keeps the drain waiting until the bytes are
                        # actually out
                        server._resp_inflight += 1
                if shed is not None:
                    self._drain_body()
                    self._send(503, {"error": shed,
                                     "queue_depth": depth})
                    return

                def release():
                    with server._depth_lock:
                        server._depth -= 1

                # depth is released by whoever last holds the work: the
                # WORKER once the call actually finishes (a wedged call
                # abandoned at its deadline keeps occupying depth, so
                # the gate above sheds followers immediately), or this
                # handler if the work never reached the worker
                def run_and_release(payload):
                    try:
                        return server._predict(payload)
                    finally:
                        release()

                submitted = False
                try:
                    payload = self._read_json_body()
                    if payload is None:
                        self._send(400, {"error": "bad body"})
                        return
                    fut = server._pool.submit(run_and_release, payload)
                    submitted = True
                    try:
                        out = fut.result(timeout=server.deadline_s)
                    except FutureTimeout:
                        # abandon the call: if still queued the cancel
                        # wins (release here); if running, the worker
                        # stays wedged holding its depth slot and THIS
                        # client gets its 503 now
                        if fut.cancel():
                            release()
                        server._failure_streak += 1
                        self._send(503, {
                            "error": "deadline_exceeded",
                            "deadline_s": server.deadline_s})
                        return
                    server._failure_streak = 0
                    self._send(200, out)
                except (_resil.FaultInjected, ConnectionError) as e:
                    server._failure_streak += 1
                    self._send(503, {"error":
                                     f"backend_unavailable: {e}"})
                except (ValueError, KeyError) as e:
                    self._send(400, {"error": str(e)})
                except Exception as e:   # noqa: BLE001 — report, keep serving
                    server._failure_streak += 1
                    code = 503 if "unavailable" in str(e).lower() else 500
                    self._send(code,
                               {"error": f"{type(e).__name__}: {e}"})
                finally:
                    with server._depth_lock:
                        server._resp_inflight -= 1
                    if not submitted:
                        release()

            def _do_generate(self):
                """Generate through the continuous-batching engine.
                Load shedding is the ENGINE's queue cap (its tick loop
                is the one worker); each request parks on its own
                future until its slot retires it."""
                if server.engine is None:
                    self._send(404, {"error": "no generation engine "
                                              "attached to this server"})
                    return
                if server._warm_state == "warming":
                    # shed with the load-shedding 503 contract instead
                    # of queueing the request behind the compile — an
                    # orchestrator retries against a ready replica.
                    # Drain the request body first: responding with
                    # unread bytes on the socket resets the connection
                    # instead of delivering the 503
                    self._drain_body()
                    self._send(503, {"error": "warming_up",
                                     "queue_depth": 0})
                    return
                # draining check + in-flight increment are ONE atomic
                # step against begin_drain's locked flip: either this
                # request is counted before the drain waiter can read
                # inflight()==0, or it sheds — an admitted request is
                # never abandoned by a graceful shutdown
                with server._depth_lock:
                    draining = server._draining
                    if not draining:
                        server._resp_inflight += 1
                if draining:
                    # rolling restart in progress: nothing new may be
                    # admitted; the router already saw /healthz flip
                    self._drain_body()
                    self._send(503, {"error": "draining"})
                    return
                try:
                    self._generate_admitted()
                finally:
                    with server._depth_lock:
                        server._resp_inflight -= 1

            def _do_cancel(self):
                """POST /cancel {"request_id": rid} — real request
                cancellation through the engine: queued requests
                resolve now, admitted ones retire at the next tick
                boundary (slot + KV pages reclaimed). The cancelled
                request's own waiter gets its 409 / stream err line
                with the partial result; THIS response only reports
                whether a live request matched."""
                payload = self._read_json_body() or {}
                if server.engine is None:
                    self._send(404, {"error": "no generation engine "
                                              "attached to this server"})
                    return
                rid = (payload.get("request_id")
                       or self.headers.get(REQUEST_ID_HEADER))
                if not rid:
                    self._send(400, {"error": "request_id required"})
                    return
                ok = server.engine.cancel(str(rid))
                self._send(200, {"cancelled": bool(ok),
                                 "request_id": str(rid)})

            def _do_prewarm(self):
                """POST /prewarm {"input_ids": [...]} — warm the paged
                KV prefix cache with a prompt WITHOUT a client waiting
                on the output: one-token generate through the normal
                admission path (prefill writes the prompt's pages, the
                trie keeps them as reusable prefix after the slot
                retires), result discarded. The router fires this at a
                STANDBY replica while a journaled stream runs elsewhere,
                so a failover's resumed prefill lands on trie hits
                instead of recomputing the whole prefix (ISSUE 17).
                Best-effort by contract: a busy/warming/unpaged replica
                sheds with the standard 503/200 truth — the caller loses
                nothing but the head start."""
                from .engine import EngineOverloaded
                if server.engine is None:
                    self._send(404, {"error": "no generation engine "
                                              "attached to this server"})
                    return
                if server._warm_state == "warming" or server._draining:
                    self._drain_body()
                    self._send(503, {"error": "warming_up"
                                     if server._warm_state == "warming"
                                     else "draining"})
                    return
                payload = self._read_json_body()
                if payload is None or "input_ids" not in payload:
                    self._send(400, {"error": "input_ids required"})
                    return
                paged = bool(getattr(server.engine, "paged", False))
                try:
                    fut = server.engine.submit(payload["input_ids"], 1,
                                               seed=0)
                except EngineOverloaded as e:
                    self._send(503, {"error": e.reason,
                                     "queue_depth": e.queue_depth})
                    return
                except (ValueError, KeyError, TypeError) as e:
                    self._send(400, {"error": str(e)})
                    return
                except Exception as e:   # noqa: BLE001 — broken engine
                    self._send(503, {"error":
                                     f"backend_unavailable: {e}"})
                    return
                try:
                    fut.result(timeout=server.deadline_s)
                except Exception as e:   # noqa: BLE001 — best-effort
                    self._send(503, {"error":
                                     f"prewarm_failed: {e}"})
                    return
                n = len(np.asarray(payload["input_ids"]).reshape(-1))
                self._send(200, {"prewarmed": paged,
                                 "prompt_len": n, "paged": paged})

            def _do_admin_inject(self):
                """POST /admin/inject {"site": s, "count": n,
                "wedge_s": opt} — arm a resilience fault site in this
                LIVE process (chaos tooling: the tier bench wedges one
                replica's decode loop with `replica_stall` to exercise
                hedged decode). Refused unless the process was started
                with PADDLE_TPU_CHAOS_ADMIN=1 — production replicas
                must not expose a self-sabotage endpoint."""
                payload = self._read_json_body()
                if payload is None:
                    self._send(400, {"error": "bad body"})
                    return
                if not _env_bool("PADDLE_TPU_CHAOS_ADMIN", False):
                    self._send(403, {"error": "chaos admin disabled "
                                              "(PADDLE_TPU_CHAOS_ADMIN)"})
                    return
                site = payload.get("site")
                count = payload.get("count", 1)
                wedge_s = payload.get("wedge_s")
                try:
                    _resil.arm_fault(str(site), int(count),
                                     None if wedge_s is None
                                     else float(wedge_s))
                except (ValueError, TypeError) as e:
                    self._send(400, {"error": str(e)})
                    return
                self._send(200, {"armed": str(site),
                                 "count": int(count),
                                 "wedge_s": wedge_s})

            def _generate_admitted(self):
                # request-id propagation: honor the router's header,
                # mint one otherwise — every response can be resolved
                # to its engine spans (queue-wait/prefill/decode)
                rid = self.headers.get(REQUEST_ID_HEADER) or (
                    uuid.uuid4().hex[:16] if _obs.enabled() else None)
                # the handler-wall span: what the engine phases don't
                # cover (json parse, future wait wakeup, response
                # write) is visible as serve.generate minus their sum
                with _obs.span("serve.generate", cat="serve",
                               request_id=rid):
                    self._generate_traced(rid)

            def _generate_traced(self, rid):
                from .engine import EngineOverloaded
                stream = False
                evq = None
                try:
                    payload = self._read_json_body()
                    if payload is None:
                        self._send(400, {"error": "bad body"})
                        return
                    ids = payload["input_ids"]
                    stream = bool(payload.get("stream"))
                    progress = None
                    if stream:
                        # incremental mode: the engine's per-tick
                        # progress callback feeds an event queue this
                        # handler drains into NDJSON lines — the
                        # token side-channel the router journals
                        evq = _queue.Queue()
                        progress = (lambda toks, q=evq:
                                    q.put(("t", toks)))
                    fut = server.engine.submit(
                        ids,
                        int(payload.get("max_new_tokens", 32)),
                        payload.get("eos_token_id"),
                        int(payload.get("seed", 0)),
                        request_id=rid, progress_cb=progress)
                except EngineOverloaded as e:
                    # identical record shape to the predictor path's
                    # load shedding — orchestrators see ONE contract;
                    # the reason is the engine's truthful verdict
                    # ("cache_exhausted" when the paged KV pool, not
                    # slot count, is what is binding)
                    body = {"error": e.reason,
                            "queue_depth": e.queue_depth}
                    if getattr(e, "free_pages", None) is not None:
                        body["free_pages"] = e.free_pages
                        body["num_pages"] = e.num_pages
                    self._send(503, body)
                    return
                except (_resil.FaultInjected, ConnectionError) as e:
                    server._failure_streak += 1
                    self._send(503, {"error":
                                     f"backend_unavailable: {e}"})
                    return
                except (ValueError, KeyError, TypeError) as e:
                    self._send(400, {"error": str(e)})
                    return
                except Exception as e:   # noqa: BLE001 — broken engine
                    # e.g. submit() on a broken/stopped engine raises
                    # RuntimeError; the client still gets its 503, not
                    # a dropped socket
                    server._failure_streak += 1
                    self._send(503, {"error":
                                     f"backend_unavailable: {e}"})
                    return
                prompt_len = len(np.asarray(ids).reshape(-1))
                if stream:
                    self._generate_stream_body(fut, evq, rid,
                                               prompt_len)
                    return
                from .engine import RequestCancelled
                try:
                    out = fut.result(timeout=server.deadline_s)
                except FutureTimeout:
                    server._failure_streak += 1
                    if rid:
                        # the waiter is giving up: stop decoding for a
                        # client that will never read the result
                        server.engine.cancel(rid)
                    self._send(503, {"error": "deadline_exceeded",
                                     "deadline_s": server.deadline_s})
                    return
                except RequestCancelled:
                    # cancelled via POST /cancel (hedge loser, client
                    # disconnect elsewhere): 409 with the PARTIAL
                    # result — tokens generated before the cancel are
                    # surfaced, never discarded
                    info = getattr(fut, "_ptpu_gen_info", None) or {}
                    body = {"error": "cancelled"}
                    body.update(info)
                    if rid:
                        body["request_id"] = rid
                    self._send(409, body)
                    return
                except Exception as e:   # noqa: BLE001 — engine fault
                    server._failure_streak += 1
                    body = {"error": f"backend_unavailable: {e}"}
                    # partial-result accounting rides the error path
                    # too (engine attaches it in _fail_all)
                    body.update(getattr(fut, "_ptpu_gen_info", None)
                                or {})
                    self._send(503, body)
                    return
                server._failure_streak = 0
                # detokenize/respond phase: array -> JSON body (the
                # closest thing this token server has to detokenizing)
                with _obs.span("serve.detokenize", cat="serve",
                               request_id=rid):
                    body = {"tokens": out.tolist(),
                            "prompt_len": prompt_len,
                            "new_tokens": len(out) - prompt_len}
                    # per-request generation accounting the engine
                    # published on the future at retirement:
                    # tokens_generated (actual emissions, eos padding
                    # excluded) always; drafted/accepted on
                    # speculative engines. The router forwards these
                    # body fields unchanged (test_router.py).
                    info = getattr(fut, "_ptpu_gen_info", None)
                    if info:
                        body.update(info)
                    if rid:
                        body["request_id"] = rid
                self._send(200, body)

            # -- incremental (streaming) generate ----------------------
            def _write_event(self, obj):
                self.wfile.write((json.dumps(obj) + "\n").encode())
                self.wfile.flush()

            def _generate_stream_body(self, fut, evq, rid, prompt_len):
                """Write the NDJSON event stream for one admitted
                request: {"t": [...]} per emitted block, then one
                terminal {"done": body} / {"err": record} line, then
                close (read-until-close framing — no chunked encoding
                needed, and a dead replica is unmistakable: EOF
                without a terminal line). The terminal body is
                authoritative; token lines exist so the reader can
                journal progress and detect stalls."""
                from .engine import RequestCancelled
                fut.add_done_callback(lambda f: evq.put(("fin", None)))
                self.send_response(200)
                self.send_header("Content-Type",
                                 "application/x-ndjson")
                self.send_header("Connection", "close")
                self.end_headers()
                self.close_connection = True
                deadline = time.monotonic() + server.deadline_s
                sent = 0
                try:
                    while True:
                        timeout = deadline - time.monotonic()
                        if timeout <= 0:
                            server._failure_streak += 1
                            if rid:
                                server.engine.cancel(rid)
                            self._write_event({"err": {
                                "error": "deadline_exceeded",
                                "deadline_s": server.deadline_s,
                                "tokens_generated": sent}})
                            return
                        try:
                            kind, toks = evq.get(
                                timeout=min(timeout, 0.5))
                        except _queue.Empty:
                            continue
                        if kind == "t":
                            self._write_event({"t": toks})
                            sent += len(toks)
                            continue
                        break                    # fin: future resolved
                    try:
                        out = fut.result(timeout=0)
                    except RequestCancelled:
                        info = getattr(fut, "_ptpu_gen_info",
                                       None) or {}
                        rec = {"error": "cancelled"}
                        rec.update(info)
                        if rid:
                            rec["request_id"] = rid
                        self._write_event({"err": rec})
                        return
                    except Exception as e:   # noqa: BLE001 — engine
                        server._failure_streak += 1
                        rec = {"error": f"backend_unavailable: {e}"}
                        rec.update(getattr(fut, "_ptpu_gen_info",
                                           None) or {})
                        self._write_event({"err": rec})
                        return
                    server._failure_streak = 0
                    with _obs.span("serve.detokenize", cat="serve",
                                   request_id=rid):
                        body = {"tokens": out.tolist(),
                                "prompt_len": prompt_len,
                                "new_tokens": len(out) - prompt_len}
                        info = getattr(fut, "_ptpu_gen_info", None)
                        if info:
                            body.update(info)
                        if rid:
                            body["request_id"] = rid
                    self._write_event({"done": body})
                except (BrokenPipeError, ConnectionError, OSError):
                    # the reader (router/client) went away mid-stream:
                    # stop generating for a stream nobody reads —
                    # cancellation reclaims the slot and its pages
                    if rid:
                        server.engine.cancel(rid)

        return Handler

    # ------------------------------------------------------------------
    def start(self, background: bool = True):
        if background:
            self._thread = threading.Thread(
                target=self.httpd.serve_forever, daemon=True)
            self._thread.start()
        else:
            self.httpd.serve_forever()
        return self

    def stop(self, drain_s: float = 0.0):
        """Shut the server down. ``drain_s > 0`` is the graceful path:
        flip into draining (new admissions shed 503 "draining", the
        listener keeps answering so in-flight responses and health
        polls still flow), wait — bounded by ``drain_s`` — for every
        admitted request to finish, THEN tear the listener down. The
        default 0 keeps the historical fast stop: shut down now and
        abandon whatever is in flight (a wedged predict call must not
        be able to hold shutdown hostage)."""
        if drain_s and drain_s > 0:
            self.begin_drain()
            deadline = time.monotonic() + float(drain_s)
            while self.inflight() > 0 and time.monotonic() < deadline:
                time.sleep(0.02)
        self.httpd.shutdown()
        self.httpd.server_close()
        # past the (bounded) drain: don't wait for a possibly-wedged
        # predict call — abandon it
        self._pool.shutdown(wait=False, cancel_futures=True)
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        if self._warmup_thread is not None:
            # a mid-compile warmup thread is daemon + side-effect-free
            # past this point; don't block shutdown on it
            self._warmup_thread.join(timeout=1)
            self._warmup_thread = None
        if self._owned_predictor is not None:
            # engine built from OUR Config: stop its tick thread and
            # release the slot cache (an explicitly-passed engine is
            # the caller's to stop)
            self._owned_predictor.close()
            self._owned_predictor = None
            self.engine = None


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Serve a saved paddle_tpu model over HTTP")
    ap.add_argument("--model", required=True,
                    help="path to the saved .pdmodel")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8866)
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-warm the engine's programs before "
                         "accepting /generate traffic (healthz reports "
                         "warming until done); default from "
                         "PADDLE_TPU_SERVE_WARMUP")
    args = ap.parse_args(argv)
    srv = PredictorServer(args.model, args.host, args.port,
                          warmup=args.warmup or None)
    print(f"serving {args.model} on http://{srv.host}:{srv.port}",
          flush=True)
    srv.start(background=False)


if __name__ == "__main__":
    main()
