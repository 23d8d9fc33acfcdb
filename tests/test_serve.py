"""HTTP predictor-server tests (serving north star: model served
end-to-end; reference role: DistModel service / embedded predictor)."""
import json
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.inference.serve import PredictorServer


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 4))
    m.eval()
    path = str(tmp_path_factory.mktemp("serve") / "model")
    paddle.jit.save(m, path,
                    input_spec=[paddle.jit.InputSpec([None, 8])])
    srv = PredictorServer(path + ".pdmodel", port=0).start()
    yield srv, m
    srv.stop()


def _req(srv, path, payload=None):
    code, body, _ = _req_h(srv, path, payload)
    return code, body


def _req_h(srv, path, payload=None):
    """Like _req but also returns the response headers (Retry-After)."""
    url = f"http://{srv.host}:{srv.port}{path}"
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(
        url, data=data,
        headers={"Content-Type": "application/json"} if data else {})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def test_health_and_metadata(server):
    srv, _ = server
    code, body = _req(srv, "/health")
    assert code == 200 and body["status"] == "ok"
    code, meta = _req(srv, "/metadata")
    assert code == 200
    assert len(meta["inputs"]) == 1 and len(meta["outputs"]) == 1


def test_predict_matches_eager(server):
    srv, m = server
    x = np.random.RandomState(0).randn(3, 8).astype("float32")
    _, meta = _req(srv, "/metadata")
    code, body = _req(srv, "/predict", {
        "inputs": {meta["inputs"][0]: {"data": x.tolist(),
                                       "dtype": "float32"}}})
    assert code == 200, body
    out = body["outputs"][meta["outputs"][0]]
    got = np.asarray(out["data"], dtype=out["dtype"])
    want = m(paddle.to_tensor(x)).numpy()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert out["shape"] == [3, 4]


def test_predict_error_paths(server):
    srv, _ = server
    code, body = _req(srv, "/predict", {"inputs": {"nope": [[1.0]]}})
    assert code == 400 and "unknown" in body["error"]
    code, body = _req(srv, "/predict", {"bad": 1})
    assert code == 400
    code, body = _req(srv, "/nothing")
    assert code == 404


# ---------------------------------------------------------------------------
# Retry-After contract: every 503 names its reason AND carries a
# Retry-After header + retry_after_s body field — the router tier and
# external clients back off on the server's word, never by guessing.
# ---------------------------------------------------------------------------

def _assert_retry_after(code, body, headers, reason):
    assert code == 503, body
    assert body["error"].split(":")[0] == reason, body
    assert float(body["retry_after_s"]) > 0, body
    assert int(headers["Retry-After"]) >= 1, headers


@pytest.fixture()
def saved_model_path(tmp_path):
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 4))
    m.eval()
    path = str(tmp_path / "model")
    paddle.jit.save(m, path,
                    input_spec=[paddle.jit.InputSpec([None, 8])])
    return path + ".pdmodel"


def test_503_overloaded_carries_retry_after(saved_model_path):
    srv = PredictorServer(saved_model_path, port=0, max_queue=0).start()
    try:
        code, body, hdr = _req_h(srv, "/predict", {"inputs": {"x": [[1.0]]}})
        _assert_retry_after(code, body, hdr, "overloaded")
    finally:
        srv.stop()


def test_503_deadline_and_backend_carry_retry_after(saved_model_path):
    from paddle_tpu.distributed.resilience import FaultInjector
    srv = PredictorServer(saved_model_path, port=0,
                          deadline_s=0.3).start()
    try:
        _, meta = _req(srv, "/metadata")
        x = np.zeros((1, 8), "float32")
        payload = {"inputs": {meta["inputs"][0]: {"data": x.tolist(),
                                                  "dtype": "float32"}}}
        with FaultInjector({"serve_hang": 1}, wedge_s=1.0):
            code, body, hdr = _req_h(srv, "/predict", payload)
        _assert_retry_after(code, body, hdr, "deadline_exceeded")
        # the abandoned worker is still inside its 1 s wedge and holds
        # its depth slot; wait for it to clear so the next request is
        # admitted and reaches the injected backend fault
        deadline = time.monotonic() + 10
        while srv.inflight() > 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        with FaultInjector({"serve_backend": 1}):
            code, body, hdr = _req_h(srv, "/predict", payload)
        _assert_retry_after(code, body, hdr, "backend_unavailable")
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# engine-backed server: warming 503, drain semantics, graceful stop
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_server():
    from paddle_tpu.framework import random as _rng
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    _rng.seed(0)
    model = GPTForCausalLM(GPTConfig(vocab_size=96, hidden_size=32,
                                     num_layers=1, num_heads=2,
                                     max_seq_len=128))
    eng = ContinuousBatchingEngine(model, slots=2, max_len=96,
                                   cache_dtype="float32", tick_tokens=2,
                                   prefill_buckets=(8,))
    srv = PredictorServer(engine=eng, port=0).start()
    yield srv
    srv.stop()
    eng.stop()


def test_503_warming_carries_retry_after(engine_server):
    srv = engine_server
    srv._warm_state = "warming"     # white-box: deterministic warming
    try:
        code, body, hdr = _req_h(srv, "/generate",
                                 {"input_ids": [1], "max_new_tokens": 2})
        _assert_retry_after(code, body, hdr, "warming_up")
        code, body, hdr = _req_h(srv, "/healthz")
        assert code == 503 and body["status"] == "warming"
        assert int(hdr["Retry-After"]) >= 1
    finally:
        srv._warm_state = "ready"


def test_stop_drain_completes_inflight_and_sheds_new(engine_server):
    """The drain regression (ISSUE 7 satellite): an in-flight
    /generate completes across stop(drain_s=...) while new admissions
    get a 503 "draining" — the serve.py:443 fast-stop abandonment is
    now opt-in (drain_s=0), not the only behavior."""
    import threading
    from paddle_tpu.framework import random as _rng
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    _rng.seed(0)
    model = GPTForCausalLM(GPTConfig(vocab_size=96, hidden_size=32,
                                     num_layers=1, num_heads=2,
                                     max_seq_len=128))
    eng = ContinuousBatchingEngine(model, slots=2, max_len=96,
                                   cache_dtype="float32", tick_tokens=2,
                                   prefill_buckets=(8,))
    srv = PredictorServer(engine=eng, port=0).start()
    results = {}

    def long_request():
        # max_new=60 at tick_tokens=2 is ~30 ticks (plus the first
        # request's compile): reliably in flight when stop() begins
        results["long"] = _req_h(srv, "/generate",
                                 {"input_ids": [3, 1, 4],
                                  "max_new_tokens": 60})

    t = threading.Thread(target=long_request)
    t.start()
    deadline = time.monotonic() + 30
    while srv._resp_inflight < 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert srv._resp_inflight >= 1, "long request never became in-flight"

    stopper = threading.Thread(target=srv.stop, kwargs={"drain_s": 60.0})
    stopper.start()
    while not srv._draining and stopper.is_alive():
        time.sleep(0.005)
    # new admission during the drain: clean 503 "draining" + Retry-After
    code, body, hdr = _req_h(srv, "/generate",
                             {"input_ids": [1], "max_new_tokens": 2})
    _assert_retry_after(code, body, hdr, "draining")
    # /healthz tells the router why this replica left the rotation
    code, body, _ = _req_h(srv, "/healthz")
    assert code == 503 and body["status"] == "draining"

    t.join(timeout=90)
    stopper.join(timeout=90)
    assert not t.is_alive() and not stopper.is_alive()
    code, body, _ = results["long"]
    assert code == 200, body
    assert len(body["tokens"]) == 3 + 60     # completed, not abandoned
    eng.stop()


def test_fast_stop_default_unchanged(engine_server):
    """drain_s=0 (the default) must keep today's behavior: stop()
    returns promptly even with nothing special done about in-flight
    work (the wedged-backend shutdown guarantee)."""
    srv = PredictorServer(engine=engine_server.engine, port=0).start()
    t0 = time.monotonic()
    srv.stop()
    assert time.monotonic() - t0 < 10.0


# ---------------------------------------------------------------------------
# incremental /generate + /cancel + /admin/inject (ISSUE 15)
# ---------------------------------------------------------------------------

def test_generate_stream_ndjson_matches_single_shot(engine_server):
    """"stream": true turns /generate into NDJSON read-until-close:
    {"t": [...]} per emitted block then one terminal {"done": body} —
    the concatenated token events ARE the generated suffix, and the
    terminal body is identical to the single-shot response (the
    contract the router's token journal rides)."""
    srv = engine_server
    payload = {"input_ids": [3, 1, 4, 1, 5], "max_new_tokens": 8}
    _, oneshot, _ = _req_h(srv, "/generate", payload)
    url = f"http://{srv.host}:{srv.port}/generate"
    req = urllib.request.Request(
        url, json.dumps(dict(payload, stream=True)).encode(),
        {"Content-Type": "application/json"})
    events = []
    with urllib.request.urlopen(req, timeout=60) as r:
        assert r.headers["Content-Type"] == "application/x-ndjson"
        for raw in r:
            raw = raw.strip()
            if raw:
                events.append(json.loads(raw))
    assert "done" in events[-1]
    streamed = [t for ev in events[:-1] for t in ev["t"]]
    body = events[-1]["done"]
    assert streamed == body["tokens"][5:5 + body["tokens_generated"]]
    # the terminal body matches the single-shot contract bitwise
    # (request_id differs per request; everything token-shaped equal)
    for k in ("tokens", "prompt_len", "new_tokens", "tokens_generated"):
        assert body[k] == oneshot[k]


def test_cancel_endpoint_mid_decode_409_with_partial(engine_server):
    """POST /cancel retires an admitted request at the next tick
    boundary; its own waiter gets 409 "cancelled" WITH the partial
    result (tokens_generated + partial_tokens) — work surfaced, not
    discarded."""
    import threading
    from paddle_tpu.distributed import resilience as resil
    srv = engine_server
    # warm the decode program first so the wedge below can't be
    # mistaken for compile time
    code, _, _ = _req_h(srv, "/generate",
                        {"input_ids": [2, 7], "max_new_tokens": 2})
    assert code == 200
    rid = "cancel-me-http"
    result = {}

    def waiter():
        url = f"http://{srv.host}:{srv.port}/generate"
        req = urllib.request.Request(
            url, json.dumps({"input_ids": [2, 7, 1, 8],
                             "max_new_tokens": 80}).encode(),
            {"Content-Type": "application/json",
             "X-PTPU-Request-Id": rid})
        try:
            with urllib.request.urlopen(req, timeout=120) as r:
                result["resp"] = (r.status, json.loads(r.read()))
        except urllib.error.HTTPError as e:
            result["resp"] = (e.code, json.loads(e.read()))

    # wedge ONE decode tick (replica_stall, the straggler site): the
    # request is guaranteed mid-decode — admitted, first token out,
    # loop asleep — when the cancel lands, however loaded the host is
    resil.arm_fault("replica_stall", 1, wedge_s=1.5)
    t = threading.Thread(target=waiter)
    t.start()
    # wait until the request is admitted and producing tokens
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        st = srv.engine.stats()
        if st["active"] >= 1:
            break
        time.sleep(0.01)
    code, body = _req(srv, "/cancel", {"request_id": rid})
    assert code == 200 and body["cancelled"] is True, body
    t.join(timeout=90)
    code, body = result["resp"]
    assert code == 409, body
    assert body["error"] == "cancelled"
    assert body["request_id"] == rid
    assert body["tokens_generated"] == len(body["partial_tokens"])
    # a second cancel of the resolved id is a truthful no-op
    code, body = _req(srv, "/cancel", {"request_id": rid})
    assert code == 200 and body["cancelled"] is False
    # /cancel without a request id is a 400
    code, body = _req(srv, "/cancel", {})
    assert code == 400


def test_admin_inject_gated_and_validated(engine_server, monkeypatch):
    """/admin/inject is the chaos bench's way to wedge a LIVE replica
    (replica_stall). It must be locked behind PADDLE_TPU_CHAOS_ADMIN
    (403 otherwise) and reject unknown sites (400) so a typo'd chaos
    script can't silently arm nothing."""
    srv = engine_server
    monkeypatch.delenv("PADDLE_TPU_CHAOS_ADMIN", raising=False)
    code, body = _req(srv, "/admin/inject",
                      {"site": "replica_stall", "count": 1})
    assert code == 403 and "chaos admin" in body["error"]
    monkeypatch.setenv("PADDLE_TPU_CHAOS_ADMIN", "1")
    code, body = _req(srv, "/admin/inject",
                      {"site": "replica_stal", "count": 1})
    assert code == 400 and "unknown fault-injection" in body["error"]
    # armed for real: the next decode tick sleeps the configured wedge
    code, body = _req(srv, "/admin/inject",
                      {"site": "replica_stall", "count": 1,
                       "wedge_s": 0.3})
    assert code == 200 and body["armed"] == "replica_stall"
    t0 = time.monotonic()
    code, body = _req(srv, "/generate",
                      {"input_ids": [5, 3], "max_new_tokens": 2})
    assert code == 200, body
    assert time.monotonic() - t0 >= 0.3     # the wedge really fired


def test_stream_disconnect_frees_slot_and_pages_fps_exported():
    """ISSUE 16: a streaming client that vanishes mid-generation must
    propagate to REAL cancellation on the replica — slot retired at
    the next tick, KV pages decref'd back to the pool (leak-free,
    counter-asserted) — and the paged engine's /healthz carries the
    prefix-trie fingerprints the router's affinity _pick intersects
    with incoming prompts."""
    from paddle_tpu.framework import random as _rng
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.inference.paging import chain_hashes
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    _rng.seed(0)
    model = GPTForCausalLM(GPTConfig(vocab_size=96, hidden_size=32,
                                     num_layers=1, num_heads=2,
                                     max_seq_len=128))
    eng = ContinuousBatchingEngine(model, slots=2, max_len=96,
                                   cache_dtype="float32", tick_tokens=2,
                                   prefill_buckets=(8,), paged=True,
                                   page_size=8)
    srv = PredictorServer(engine=eng, port=0).start()
    try:
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]       # one complete page
        cancelled0 = eng.stats()["cancelled"]
        req = urllib.request.Request(
            f"http://{srv.host}:{srv.port}/generate",
            json.dumps({"input_ids": prompt, "max_new_tokens": 80,
                        "stream": True}).encode(),
            {"Content-Type": "application/json"})
        r = urllib.request.urlopen(req, timeout=60)
        assert r.status == 200
        first = json.loads(r.readline())
        assert first.get("t"), "no first token block"
        r.close()                # the client vanishes mid-stream
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            st = eng.stats()
            if st["cancelled"] > cancelled0 and st["active"] == 0:
                break
            time.sleep(0.05)
        st = eng.stats()
        assert st["cancelled"] == cancelled0 + 1, st
        assert st["active"] == 0                 # slot retired
        # leak-free: only trie-cached prefix pages stay referenced
        assert st["pages_used"] == st["pages_cached_prefix"]
        eng._allocator.check()
        # a later same-prefix request still serves normally...
        code, body, _ = _req_h(srv, "/generate",
                               {"input_ids": prompt,
                                "max_new_tokens": 4})
        assert code == 200, body
        # ...and /healthz exports the cross-process trie fingerprints:
        # the prompt's chain hashes are a subset, so a router hashing
        # this prompt scores the overlap without shipping token ids
        code, body, _ = _req_h(srv, "/healthz")
        assert code == 200
        fps = set(body["engine"]["prefix_fingerprints"])
        assert set(chain_hashes(prompt, 8)) <= fps
    finally:
        srv.stop()
        eng.stop()
