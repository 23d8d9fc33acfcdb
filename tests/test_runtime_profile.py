"""tpuprof measured runtime profiling (ISSUE 14).

Fixture-driven (ZERO compiles): the chrome-trace parser over a
checked-in device-plane trace, the measured<->modeled join against the
mlp_fused HLO fixture, the CPU degrade contract, and the dispatch-
ratchet/anchor gate semantics. Plus one LIVE smoke: a tiny registry
program profiled end-to-end (report names its kernels, the gate
round-trips --update-baseline) and the efficiency gauges the same
issue wires into the engine tick and the fit loop.

Registered in tools/ci.py --quick.
"""
import json
import os

import numpy as np
import pytest

from paddle_tpu.analysis import runtime_profile as rp
from paddle_tpu.analysis.findings import (PROF_ANCHOR, PROF_BUDGET,
                                          STALE_PROF_PROGRAM)
from paddle_tpu.analysis.hlo_cost import collect_kernels, \
    parse_hlo_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HLO_FIXTURES = os.path.join(ROOT, "tests", "fixtures", "hlo")
TRACE_FIXTURE = os.path.join(ROOT, "tests", "fixtures", "trace",
                             "mlp_device.trace.json")


def _fixture_events():
    with open(TRACE_FIXTURE) as fh:
        return json.load(fh)["traceEvents"]


def _mlp_kernels():
    with open(os.path.join(HLO_FIXTURES, "mlp_fused.txt")) as fh:
        return collect_kernels(parse_hlo_module(fh.read()))


# ---------------------------------------------------------------------------
# parser (zero compiles)
# ---------------------------------------------------------------------------

def test_device_op_times_aggregates_xla_ops_lane_only():
    prof = rp.device_op_times(_fixture_events())
    assert prof.had_device
    # two dispatches summed per op; the 5000us "Steps"-lane span and
    # the host events must NOT land in per_op
    assert prof.per_op["dot.14"] == pytest.approx(620.0)
    assert prof.per_op["broadcast_multiply_fusion"] == \
        pytest.approx(220.0)
    assert prof.per_op["copy.99"] == pytest.approx(80.0)
    assert "train_step_like_whole_step" not in prof.per_op
    assert "TfrtCpuExecutable::Execute" not in prof.per_op
    assert prof.op_category["dot.14"] == "matmul"
    assert prof.host_dispatch_events == 2


def test_load_trace_events_reads_gz_and_plain(tmp_path):
    import gzip
    events = _fixture_events()
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    with open(TRACE_FIXTURE) as fh:
        doc = fh.read()
    (d / "a.trace.json").write_text(doc)
    with gzip.open(d / "b.trace.json.gz", "wt") as fh:
        fh.write(doc)
    loaded = rp.load_trace_events(str(tmp_path))
    assert len(loaded) == 2 * len(events)


def test_host_only_trace_degrades():
    host_only = [e for e in _fixture_events() if e.get("pid") == 701]
    prof = rp.device_op_times(host_only)
    assert not prof.had_device
    assert prof.per_op == {}
    assert prof.host_dispatch_events == 2


# ---------------------------------------------------------------------------
# measured <-> modeled join (zero compiles)
# ---------------------------------------------------------------------------

def test_join_against_mlp_fixture():
    prof = rp.device_op_times(_fixture_events())
    join = rp.join_measured_modeled(prof.per_op, _mlp_kernels(),
                                    chip="v5lite", dispatches=2)
    assert join["available"]
    rows = {r["name"]: r for r in join["rows"]}
    # both modeled kernels joined, per-dispatch times
    assert rows["dot.14"]["measured_us"] == pytest.approx(310.0)
    assert rows["broadcast_multiply_fusion"]["measured_us"] == \
        pytest.approx(110.0)
    assert rows["dot.14"]["matmul_flops"] > 0
    assert rows["dot.14"]["measured_vs_roofline"] > 1.0
    # copy.99 is measured but unmodeled: time-weighted join rate is
    # (620 + 220) / 920 and the leftover is named
    assert join["join_rate_time_weighted"] == pytest.approx(840 / 920,
                                                            abs=1e-3)
    assert join["unjoined_top"][0]["name"] == "copy.99"
    assert join["unjoined_us"] == pytest.approx(40.0)


def test_time_weighted_histogram_and_matmul_share():
    prof = rp.device_op_times(_fixture_events())
    join = rp.join_measured_modeled(prof.per_op, _mlp_kernels(),
                                    chip="v5lite", dispatches=2)
    hist = rp.time_weighted_histogram(join)
    assert hist["dot"] == pytest.approx(310.0)
    assert hist["loop"] == pytest.approx(110.0)
    assert hist["unattributed"] == pytest.approx(40.0)
    # histogram sums to the measured total (the honesty property)
    assert sum(hist.values()) == pytest.approx(
        join["measured_total_us"])
    share = rp.matmul_time_share(join)
    assert share == pytest.approx(310.0 / 460.0, abs=1e-3)


def test_time_weighted_chains_reranks_by_seconds():
    from paddle_tpu.analysis.hlo_cost import KernelCost

    def k(name, wr):
        return KernelCost(name=name, opcode="add", klass="unfused",
                          flops=1.0, matmul_flops=0.0, bytes_read=wr,
                          bytes_written=wr, trip=1, path="",
                          operands=())
    # chain A is bytes-heavy, chain B is where the measured time is
    chains = [
        {"kernels": ["a.1", "a.2"], "kernel_count": 2, "ops": [],
         "path": "", "trip": 1, "intermediate_bytes": 10_000_000,
         "savable_bytes": 20_000_000},
        {"kernels": ["b.1", "b.2"], "kernel_count": 2, "ops": [],
         "path": "", "trip": 1, "intermediate_bytes": 1_000,
         "savable_bytes": 2_000},
    ]
    join = {"rows": [
        {"name": "a.1", "measured_us": 1.0},
        {"name": "a.2", "measured_us": 1.0},
        {"name": "b.1", "measured_us": 500.0},
        {"name": "b.2", "measured_us": 400.0},
    ]}
    out = rp.time_weighted_chains(join, chains)
    assert [c["kernels"][0] for c in out] == ["b.1", "a.1"]
    assert out[0]["measured_us"] == pytest.approx(900.0)
    # a chain with no measured time is dropped, not ranked at zero
    chains.append({"kernels": ["c.1", "c.2"], "kernel_count": 2,
                   "ops": [], "path": "", "trip": 1,
                   "intermediate_bytes": 5, "savable_bytes": 10})
    assert all(c["kernels"][0] != "c.1"
               for c in rp.time_weighted_chains(join, chains))


def test_runtime_report_device_and_degraded_paths():
    with open(os.path.join(HLO_FIXTURES, "mlp_fused.txt")) as fh:
        hlo = fh.read()
    rep = rp.runtime_report("mlp", hlo_text=hlo,
                            events=_fixture_events(),
                            dispatch_s=[0.01, 0.012, 0.011],
                            dispatches_profiled=2, chip="v5lite")
    assert rep["had_device_plane"]
    assert rep["dispatch"]["median_ms"] == pytest.approx(11.0)
    assert rep["matmul_time_share"] is not None
    assert rep["measured_vs_roofline"] > 0
    assert "dot.14" in rep["modeled"]["top_kernels"]
    # degraded: host-only events — wall time kept, join marked
    # unavailable with a reason, anchors get nothing to latch onto
    host_only = [e for e in _fixture_events() if e.get("pid") == 701]
    deg = rp.runtime_report("mlp", hlo_text=hlo, events=host_only,
                            dispatch_s=[0.01], chip="v5lite")
    assert not deg["had_device_plane"]
    assert deg["join"]["available"] is False
    assert "device plane" in deg["join"]["reason"]
    assert deg["matmul_time_share"] is None
    assert deg["measured_vs_roofline"] is None
    assert deg["dispatch"]["median_ms"] == pytest.approx(10.0)
    assert deg["modeled"]["top_kernels"]  # still names its kernels


# ---------------------------------------------------------------------------
# baseline gate semantics (zero compiles)
# ---------------------------------------------------------------------------

def _report(median_ms=10.0, matmul_share=0.7, vs_roofline=5.0,
            device=True):
    rep = {"dispatch": {"median_ms": median_ms, "n": 3},
           "had_device_plane": device,
           "matmul_time_share": matmul_share if device else None,
           "measured_vs_roofline": vs_roofline if device else None,
           "join": ({"available": True} if device else
                    {"available": False, "reason": "no device plane"})}
    return rep


def test_gate_budget_tolerance_band():
    base = {"budgets": {"p": {"dispatch_ms": 10.0}}, "anchors": {},
            "tolerance": 2.0}
    ok, _ = rp.check_profile_baseline({"p": _report(19.0)}, base, ["p"])
    assert ok == []
    bad, _ = rp.check_profile_baseline({"p": _report(21.0)}, base,
                                       ["p"])
    assert [f.code for f in bad] == [PROF_BUDGET]
    assert bad[0].site == "dispatch_ms"


def test_gate_unbaselined_stale_and_require_all():
    base = {"budgets": {"gone": {"dispatch_ms": 5.0},
                        "quiet": {"dispatch_ms": 5.0}},
            "anchors": {}}
    fs, _ = rp.check_profile_baseline({"new": _report()}, base,
                                      ["new", "quiet"],
                                      require_all=True)
    codes = {(f.code, f.program) for f in fs}
    assert (STALE_PROF_PROGRAM, "gone") in codes
    assert (PROF_BUDGET, "new") in codes          # unbaselined
    assert (PROF_BUDGET, "quiet") in codes        # live, not measured


def test_gate_anchors_fire_and_skip():
    base = {"budgets": {}, "anchors": {
        "train_step": {"kind": "matmul_time_share_floor",
                       "min_share": 0.5},
        "gpt_decode": {"kind": "measured_vs_roofline",
                       "max_ratio": 10.0}}}
    live = ["train_step", "gpt_decode"]
    # holding
    ok, skipped = rp.check_profile_baseline(
        {"train_step": _report(matmul_share=0.7),
         "gpt_decode": _report(vs_roofline=8.0)}, base, live)
    assert [f for f in ok if f.code == PROF_ANCHOR] == []
    assert skipped == []
    # broken: both must-hold anchors fire
    bad, _ = rp.check_profile_baseline(
        {"train_step": _report(matmul_share=0.3),
         "gpt_decode": _report(vs_roofline=40.0)}, base, live)
    assert sorted(f.site for f in bad if f.code == PROF_ANCHOR) == \
        ["matmul_time_share_floor", "measured_vs_roofline"]
    # degraded (CPU): anchors SKIP with reasons — never silently pass,
    # never spuriously fail
    none, skipped = rp.check_profile_baseline(
        {"train_step": _report(device=False),
         "gpt_decode": _report(device=False)}, base, live)
    assert [f for f in none if f.code == PROF_ANCHOR] == []
    assert {s["program"] for s in skipped} == set(live)
    # a typo'd kind must fail loudly, not disable the invariant
    typo = {"budgets": {}, "anchors": {
        "train_step": {"kind": "matmul_share_floor"}}}
    fs, _ = rp.check_profile_baseline({"train_step": _report()}, typo,
                                      ["train_step"])
    assert [f.site for f in fs if f.code == PROF_ANCHOR] == \
        ["unknown-kind"]


def test_update_baseline_preserves_anchors_and_tolerance():
    base = {"budgets": {"p": {"dispatch_ms": 99.0}},
            "anchors": {"p": {"kind": "measured_vs_roofline",
                              "max_ratio": 3.0}},
            "tolerance": 1.7, "notes": {"p": "why"}}
    new = rp.updated_profile_baseline(base, {"p": _report(12.0)})
    assert new["budgets"]["p"]["dispatch_ms"] == pytest.approx(12.0)
    assert new["anchors"] == base["anchors"]
    assert new["tolerance"] == 1.7
    assert new["notes"] == {"p": "why"}


def test_committed_baseline_parses_and_names_live_programs():
    """tools/tpuprof_baseline.json must stay loadable, carry both
    must-hold anchors, and name only programs the registry still has
    (the stale check runs against the committed file without building
    anything)."""
    path = os.path.join(ROOT, "tools", "tpuprof_baseline.json")
    base = rp.load_profile_baseline(path)
    kinds = {a["kind"] for a in base.get("anchors", {}).values()}
    assert {"matmul_time_share_floor", "measured_vs_roofline"} <= kinds
    from paddle_tpu.compilation import registry
    live = registry.names(tag="manifest")
    stale, _ = rp.check_profile_baseline({}, base, live)
    assert [f for f in stale if f.code == STALE_PROF_PROGRAM] == []


# ---------------------------------------------------------------------------
# live smoke: one tiny registry program end-to-end + the gauges
# ---------------------------------------------------------------------------

@pytest.mark.timeout(280)
def test_live_tpuprof_cli_profiles_and_roundtrips_baseline(tmp_path):
    """Profile ONE tiny registry program end-to-end through the REAL
    CLI, in a SUBPROCESS: the report names its kernels and carries
    real dispatch medians, `--update-baseline` writes a baseline the
    same report re-gates clean, and the terminal line is one
    JSON record. Subprocess on purpose — a jax.profiler
    session permanently slows every later XLA compile in its process
    ~1.5x (measured 2026-08-04), which an in-suite session would tax
    the whole tier-1 tail with."""
    import subprocess
    import sys
    base = tmp_path / "tpuprof_baseline.json"
    art = tmp_path / "report.json"
    env = dict(os.environ)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "tpuprof.py"),
         "--programs", "llama_decode",
         "--baseline", str(base), "--update-baseline",
         "--json", str(art),
         "--rounds", "1", "--inner", "2", "--profile-dispatches", "1"],
        capture_output=True, text=True, timeout=240, cwd=ROOT, env=env)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
    term = json.loads(r.stdout.strip().splitlines()[-1])
    assert term["gate"] == "pass"
    rep = json.load(open(art))["reports"]["llama_decode"]
    assert rep["dispatch"]["median_ms"] > 0
    assert rep["modeled"]["kernel_count"] > 0
    assert rep["modeled"]["top_kernels"]
    if not rep["had_device_plane"]:      # CPU backend: the degrade path
        assert rep["join"]["available"] is False
        assert "device plane" in rep["join"]["reason"]
    # the written baseline re-gates the same report clean (in-process,
    # zero compiles)
    loaded = rp.load_profile_baseline(str(base))
    assert loaded["budgets"]["llama_decode"]["dispatch_ms"] > 0
    fs, _ = rp.check_profile_baseline({"llama_decode": rep}, loaded,
                                      ["llama_decode"],
                                      require_all=True)
    assert fs == []


def test_engine_tick_model_eff_gauge_and_stats():
    """The live serving half of ISSUE 14: a ticking engine exports
    ptpu_engine_tick_model_eff (modeled bytes / measured tick time as
    a bandwidth fraction) and mirrors it in stats() — the same value
    serve.py surfaces under /healthz engine.tick_model_eff."""
    from paddle_tpu import obs
    from paddle_tpu.inference.engine import ContinuousBatchingEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.framework import random as _rng
    _rng.seed(0)
    model = GPTForCausalLM(GPTConfig(vocab_size=128, hidden_size=32,
                                     num_layers=1, num_heads=2,
                                     max_seq_len=64))
    eng = ContinuousBatchingEngine(model, slots=2, max_len=32,
                                   cache_dtype="float32",
                                   tick_tokens=2,
                                   prefill_buckets=(8,))
    try:
        eng.generate(np.zeros(4, np.int64), max_new_tokens=4)
        st = eng.stats()
        assert st["tick_model_eff"] > 0
        g = obs.metrics.registry.get("ptpu_engine_tick_model_eff")
        assert g is not None and g.value() == pytest.approx(
            eng.last_tick_model_eff)
    finally:
        eng.stop()


def test_fit_exports_train_mfu_gauges():
    """The live training half: one tiny fit exports ptpu_train_mfu +
    ptpu_train_step_seconds through the shared obs/efficiency.py
    formula (param count x 6 x tokens over measured seconds)."""
    import paddle_tpu as paddle
    from paddle_tpu import obs
    from paddle_tpu.hapi import Model
    from paddle_tpu.obs import efficiency as eff
    paddle.seed(0)
    net = paddle.nn.Linear(8, 4)
    m = Model(net)
    m.prepare(optimizer=paddle.optimizer.SGD(
        learning_rate=0.1, parameters=net.parameters()),
        loss=lambda out, y: ((out - y) ** 2).mean())
    xs = np.random.RandomState(0).rand(8, 8).astype("float32")
    ys = np.zeros((8, 4), np.float32)
    from paddle_tpu.io.dataloader import DataLoader, TensorDataset
    loader = DataLoader(TensorDataset([xs, ys]), batch_size=4)
    m.fit(loader, epochs=1, verbose=0)
    g_mfu = obs.metrics.registry.get(eff.MFU_GAUGE)
    g_sec = obs.metrics.registry.get(eff.STEP_SECONDS_GAUGE)
    assert g_mfu is not None and g_mfu.value() > 0
    assert g_sec is not None and g_sec.value() > 0
    # the gauge is the shared formula, not a third derivation:
    # batch 4 x 36 params (8x4 + 4) -> 6 * N * B tokens at the
    # recorded seconds reproduces the same order of magnitude
    assert g_mfu.value() == pytest.approx(
        eff.mfu(eff.train_step_flops(36, 4), g_sec.value()), rel=0.5)
