"""Tensor-parallel serving slice (ISSUE 20).

The sharded-inference contract, end to end on the 8-virtual-device
mesh: a ContinuousBatchingEngine with tp>1 runs its programs
pjit-sharded over a dedicated ("mp",) slice — attention heads and MLP
hidden dims Megatron-split, KV pools (and int8 scale planes)
head-sharded, block tables replicated — and its greedy token stream is
BITWISE identical to the single-chip engine across every cache/decode
mode, with zero recompiles under prompt-length drift.

Covered here:
- identity matrix: slot/paged x f32/int8 x plain/speculative at tp=2,
  plus one tp=4 case
- staggered admissions joining a live sharded batch mid-decode
- scan_layers + paged: the stacked pool carries its layer axis and the
  block table broadcasts onto it (the PR 9 follow-up)
- fused-kernel knobs fall back LOUDLY (warning + stats field) under a
  sharded mesh, never silently-wrong Pallas dispatch
- registry/lint completeness for the four *_tp sites
- mesh geometry in stats/snapshots + mixed-tp tier metric summing
- a LIVE 2-replica tier where each replica is a tp=2 slice
"""
import json
import urllib.request
import warnings

import numpy as np
import pytest

from paddle_tpu.framework import random as _rng
from paddle_tpu.inference.engine import ContinuousBatchingEngine
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM


def _gpt(scan_layers=False):
    _rng.seed(0)
    return GPTForCausalLM(GPTConfig(vocab_size=256, hidden_size=64,
                                    num_layers=2, num_heads=4,
                                    max_seq_len=128,
                                    scan_layers=scan_layers))


def _gpt_scan():
    """Scanned GPT with the UNROLLED model's weights: scan init consumes
    RNG in stacked order, so same-seed scan/unrolled models differ —
    parity requires the copy (same idiom as test_gpt_scan_layers)."""
    m_u = _gpt()
    m_s = _gpt(scan_layers=True)
    m_s.gpt.blocks.load_from_blocks(m_u.gpt.blocks)
    sd_u = dict(m_u.named_parameters())
    for n, p in m_s.named_parameters():
        if not n.startswith("gpt.blocks."):
            p.value = sd_u[n].value
    return m_s


def _llama():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    _rng.seed(0)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=176,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128))


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(
        1, 255, size=n).astype(np.int32)


PROMPTS = [(_prompt(s, n)) for s, n in ((1, 5), (2, 9), (3, 13))]


def _engine(tp=None, model=None, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("cache_dtype", "float32")
    kw.setdefault("tick_tokens", 4)
    return ContinuousBatchingEngine(model if model is not None
                                    else _gpt(), tp=tp, **kw)


def _decode_all(eng, max_new=6):
    """Warm up, decode the shared prompts, assert the zero-recompile
    contract, return the token streams."""
    eng.warmup()
    warm = eng.compiled_program_count
    outs = [eng.generate(p, max_new_tokens=max_new, timeout=300)
            for p in PROMPTS]
    assert eng.compiled_program_count == warm, \
        "recompiled under prompt-length drift"
    return outs


_BASELINES = {}


def _baseline(key, **kw):
    """tp=1 token streams for an engine config, computed once per
    module (every tp>1 case compares against the SAME single-chip
    run)."""
    if key not in _BASELINES:
        with _engine(**kw) as eng:
            _BASELINES[key] = _decode_all(eng)
    return _BASELINES[key]


# ---------------------------------------------------------------------------
# identity matrix
# ---------------------------------------------------------------------------

MATRIX = [
    ("slot_f32", {}),
    ("slot_int8", {"cache_dtype": "int8"}),
    ("paged_f32", {"paged": True, "page_size": 16, "num_pages": 24}),
    ("paged_int8", {"paged": True, "page_size": 16, "num_pages": 24,
                    "cache_dtype": "int8"}),
    ("slot_spec", {"speculative": "ngram", "spec_k": 4}),
    ("paged_spec", {"paged": True, "page_size": 16, "num_pages": 24,
                    "speculative": "ngram", "spec_k": 4}),
]


@pytest.mark.parametrize("key,kw", MATRIX,
                         ids=[k for k, _ in MATRIX])
def test_tp2_tokens_bitwise_identical(key, kw):
    """The oracle: a tp=2 slice emits EXACTLY the single-chip token
    stream — sharded partial sums reorder float math, but greedy
    argmax token IDs must not move. Slot and paged, f32 and int8
    caches, plain and speculative decode."""
    want = _baseline(key, **kw)
    with _engine(tp=2, **kw) as eng:
        got = _decode_all(eng)
        st = eng.stats()
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    assert st["tp"] == 2 and st["mesh_devices"] == 2
    assert st["mesh"]["mesh_axis"] == "mp"
    assert len(st["mesh"]["devices"]) == 2


def test_tp4_tokens_bitwise_identical():
    """One degree higher: the 4-way slice (one attention head per
    chip) still matches the single-chip stream."""
    want = _baseline("slot_f32")
    with _engine(tp=4) as eng:
        got = _decode_all(eng)
        assert eng.stats()["mesh_devices"] == 4
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


def test_tp2_llama_gqa_identity():
    """GQA under TP: num_kv_heads=2 over tp=2 puts ONE kv head per
    chip while queries shard 2-per-chip — the uneven head-group split
    the GPT matrix can't exercise."""
    with _engine(model=_llama()) as eng:
        want = _decode_all(eng)
    with _engine(tp=2, model=_llama()) as eng:
        got = _decode_all(eng)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


def test_tp2_quantized_comm_wire_runs():
    """comm_precision="int8"/"bf16" route the per-block all-reduce
    through the EQuARX wire bodies — the programs must trace, run,
    and stay recompile-free; the wire is lossy so the gate here is
    self-consistency (two identical engines produce identical
    streams), not equality with the exact-psum engine."""
    for prec in ("int8", "bf16"):
        with _engine(tp=2, comm_precision=prec) as eng:
            a = _decode_all(eng)
            st = eng.stats()
        assert st["tp_comm_precision"] == prec
        assert st["tp_tick_comm_bytes"] > 0
        with _engine(tp=2, comm_precision=prec) as eng:
            b = _decode_all(eng)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


def test_staggered_admissions_join_live_batch():
    """Requests admitted MID-DECODE into a running sharded batch keep
    the identity oracle: late arrivals join slots while earlier
    requests are ticking, and every stream still matches the
    single-chip engine's for the same prompt."""
    import time
    want = _baseline("slot_f32")
    extra = _prompt(9, 7)
    with _engine() as eng:
        want_first = eng.generate(PROMPTS[0], max_new_tokens=24,
                                  timeout=300)
        want_extra = eng.generate(extra, max_new_tokens=12, timeout=300)
    with _engine(tp=2) as eng:
        eng.warmup()
        warm = eng.compiled_program_count
        first = eng.submit(PROMPTS[0], max_new_tokens=24)
        # admit the rest only once the first is live and ticking (24
        # tokens / 4 per tick leaves plenty of mid-decode window)
        deadline = time.time() + 120
        while eng.stats()["active"] == 0 and not first.done():
            assert time.time() < deadline, "first request never ran"
            time.sleep(0.01)
        rest = [eng.submit(p, max_new_tokens=6)
                for p in PROMPTS[1:]] + [eng.submit(extra,
                                                    max_new_tokens=12)]
        outs = [first.result(timeout=300)] + \
               [f.result(timeout=300) for f in rest]
        assert eng.compiled_program_count == warm
    np.testing.assert_array_equal(outs[0], want_first)
    for got, p_want in zip(outs[1:3], want[1:3]):
        np.testing.assert_array_equal(got, p_want)
    np.testing.assert_array_equal(outs[3], want_extra)


# ---------------------------------------------------------------------------
# scan_layers + paged: the stacked pool's layer axis
# ---------------------------------------------------------------------------

def test_scan_layers_paged_block_table_layer_axis():
    """The PR 9 follow-up: under scan_layers the paged pools stack
    per-layer with a leading L axis ([L, num_pages, page_size, ...])
    and the replicated block table broadcasts onto it inside
    _attach_page_meta — so scanned stacks serve paged, and identically
    to the unrolled model."""
    kw = {"paged": True, "page_size": 16, "num_pages": 24}
    with _engine(model=_gpt_scan(), **kw) as eng:
        k_stack, v_stack = eng._caches
        assert k_stack["pages"].ndim == 5          # [L, NP, PS, nkv, hd]
        assert k_stack["pages"].shape[0] == 2      # num_layers
        scan_tokens = _decode_all(eng)
    with _engine(model=_gpt(scan_layers=False), **kw) as eng:
        unrolled = _decode_all(eng)
    for a, b in zip(scan_tokens, unrolled):
        np.testing.assert_array_equal(a, b)


def test_scan_layers_paged_tp2_identity():
    """Stacked paged pools shard on the head axis (the leading L axis
    stays untouched by the one cache-sharding rule) and the tp=2
    stream matches single-chip."""
    kw = {"paged": True, "page_size": 16, "num_pages": 24}
    with _engine(model=_gpt_scan(), **kw) as eng:
        want = _decode_all(eng)
    with _engine(tp=2, model=_gpt_scan(), **kw) as eng:
        got = _decode_all(eng)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# fused-kernel knobs x TP: loud fallback, never silently-wrong Pallas
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("knob", ["PADDLE_TPU_FUSED_CACHE_WRITE",
                                  "PADDLE_TPU_MEGA_DECODE"])
def test_fused_knob_falls_back_loudly_on_tp_mesh(knob, monkeypatch):
    """A fused-kernel env knob set on a sharded engine must (a) warn
    ONCE, (b) surface in stats()["fused_knobs_disabled_tp"], and
    (c) dispatch the unfused path — token streams stay identical to
    the knob-off engine. The Pallas kernels assume whole-array block
    specs; running them under pjit sharding would be silently wrong,
    so the dispatch refuses, audibly."""
    import importlib
    # the functional package re-exports a flash_attention FUNCTION that
    # shadows the submodule attribute — import the module by name
    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")
    monkeypatch.setenv(knob, "1")
    fa._TP_KNOB_WARNED.discard(knob)   # per-process once: rearm
    want = _baseline("slot_f32")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with _engine(tp=2) as eng:
            st = eng.stats()
            got = _decode_all(eng)
    hits = [w for w in caught if knob in str(w.message)
            and issubclass(w.category, RuntimeWarning)]
    assert len(hits) == 1, "expected exactly one loud fallback warning"
    assert knob in st["fused_knobs_disabled_tp"]
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    # a single-chip engine with the same knob is NOT degraded
    fa._TP_KNOB_WARNED.discard(knob)
    with _engine() as eng:
        assert eng.stats()["fused_knobs_disabled_tp"] == []


# ---------------------------------------------------------------------------
# registry / lint completeness
# ---------------------------------------------------------------------------

TP_SITES = ("gpt_decode_tp", "gpt_decode_tp_q", "gpt_admit_tp",
            "llama_decode_tp")


def test_registry_has_tp_sites():
    """The sharded lifecycle is registry-covered by default: all four
    *_tp sites registered, gated on 2+ devices, with the collective
    inventory compiled."""
    from paddle_tpu.compilation import registry
    from paddle_tpu.compilation.sites import ensure_registered
    ensure_registered()
    names = registry.names(tag="manifest")
    for site in TP_SITES:
        assert site in names, f"{site} missing from the registry"
        prog = registry.get(site)
        assert prog.min_devices == 2
        assert prog.compile_collectives
        assert "serving" in prog.tags and "collectives" in prog.tags


def test_tpulint_baseline_anchors_tp_sites():
    """tpulint's must_stay_clean anchors pin the TP sites' hygiene —
    scatter-free cache writes, donated buffers, argument-threaded RNG,
    no host callbacks — exactly like every other engine site."""
    import os
    base = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "tpulint_baseline.json")
    with open(base) as f:
        clean = json.load(f)["must_stay_clean"]
    for site in TP_SITES:
        for kind in ("scatter-op", "undonated-buffer",
                     "baked-rng-key", "host-callback"):
            assert f"{kind}::{site}" in clean, \
                f"{kind}::{site} not anchored in tpulint baseline"


def test_tpucost_baseline_anchors_tp_sites():
    """tpucost pins the sharded tick: a per-chip decode_hbm anchor on
    gpt_decode_tp and the fp32-vs-int8 comm_bytes ratio floor on the
    _q twin (wire-precision wins must not silently revert)."""
    import os
    base = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "tpucost_baseline.json")
    with open(base) as f:
        b = json.load(f)
    assert b["anchors"]["gpt_decode_tp"]["kind"] == "decode_hbm"
    q = b["anchors"]["gpt_decode_tp_q"]
    assert q["kind"] == "comm_bytes"
    assert q["baseline_program"] == "gpt_decode_tp"
    assert q["min_ratio"] >= 1.1
    for site in TP_SITES:
        assert site in b["budgets"], f"{site} has no tpucost budget"


# ---------------------------------------------------------------------------
# obs: mesh gauge + tier summing over mixed tp
# ---------------------------------------------------------------------------

def test_mesh_gauge_and_mixed_tp_tier_summing():
    """ptpu_engine_mesh_devices reports each engine's slice width, and
    render_tier's ptpu_tier_* summation over a MIXED tier (one tp=1
    replica, one tp=2 replica) yields total serving chips = 3."""
    from paddle_tpu.obs import metrics as _metrics
    reg = _metrics.registry

    def scrape():
        return reg.render()

    def gauge_value(text):
        for name, labels, v in _metrics.parse_text(text):
            if name == "ptpu_engine_mesh_devices" and not labels:
                return v
        raise AssertionError("ptpu_engine_mesh_devices not exported")

    with _engine() as eng:
        eng.warmup()
        text_tp1 = scrape()
        assert gauge_value(text_tp1) == 1
    with _engine(tp=2) as eng:
        eng.warmup()
        text_tp2 = scrape()
        assert gauge_value(text_tp2) == 2

    tier = _metrics.render_tier("", {"r1": text_tp1, "r2": text_tp2})
    totals = {name: v for name, labels, v in _metrics.parse_text(tier)
              if name == "ptpu_tier_engine_mesh_devices"}
    assert totals and list(totals.values())[0] == 3


def test_tp_allreduce_span_recorded():
    """Every sharded tick records an engine.tp_allreduce span carrying
    the modeled per-chip wire bytes (the number tpucost anchors)."""
    from paddle_tpu import obs as _obs
    with _engine(tp=2) as eng:
        eng.generate(PROMPTS[0], max_new_tokens=6, timeout=300)
        spans = [e for e in _obs.recorder.events()
                 if e["name"] == "engine.tp_allreduce"]
        modeled = eng.tp_tick_comm_bytes
    assert spans, "no engine.tp_allreduce span in the flight recorder"
    args = spans[-1]["args"]
    assert args["tp"] == 2
    assert args["modeled_comm_bytes"] == modeled > 0


# ---------------------------------------------------------------------------
# live tier: replica = tp=2 slice
# ---------------------------------------------------------------------------

def test_live_tier_of_tp2_slices(tmp_path):
    """A 2-replica tier where EACH replica is a tp=2 slice: children
    get 2 virtual devices, /healthz snapshots carry the mesh shape,
    and the tier's generate output matches a direct single-chip
    engine — the identity oracle composed through the fleet."""
    from paddle_tpu.inference.router import (ReplicaSpec, Router,
                                             single_device_child_env)
    model_spec = {"kind": "gpt", "vocab_size": 128, "hidden_size": 32,
                  "num_layers": 1, "num_heads": 2, "max_seq_len": 64}
    engine_spec = {"slots": 2, "max_len": 48, "cache_dtype": "float32",
                   "prefill_buckets": [8], "tick_tokens": 2}
    spec = ReplicaSpec(model_spec, engine_spec, warmup=True,
                       drain_s=5.0, seed=0, tp=2,
                       env=single_device_child_env(tp=2))
    router = Router(spec, replicas=2, poll_s=0.25, deadline_s=120.0,
                    workdir=str(tmp_path))
    router.start()
    try:
        assert router.wait_ready(2, timeout=240), router.replicas()
        reps = router.replicas()
        assert all(r["tp"] == 2 and r["mesh_devices"] == 2
                   for r in reps), reps
        assert all(r.get("mesh", {}).get("mesh_axis") == "mp"
                   for r in reps), reps
        req = urllib.request.Request(
            f"http://{router.host}:{router.port}/generate",
            json.dumps({"input_ids": [1, 2, 3, 4],
                        "max_new_tokens": 8}).encode(),
            {"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            body = json.loads(r.read())
    finally:
        router.stop()
    _rng.seed(0)
    direct_model = GPTForCausalLM(GPTConfig(
        **{k: v for k, v in model_spec.items() if k != "kind"}))
    with ContinuousBatchingEngine(
            direct_model,
            **{**engine_spec,
               "prefill_buckets": tuple(engine_spec["prefill_buckets"])}
            ) as eng:
        direct = eng.generate([1, 2, 3, 4], max_new_tokens=8).tolist()
    assert body["tokens"] == direct
