"""tpulint static analyzer (paddle_tpu/analysis): every hazard class
must be detected with its exact finding code, the baseline gate must
ratchet, and the real engine decode program must stay clean (the PR-2
scatter-free + donated-cache regime, now machine-locked).

Registered in tools/ci.py --quick. No test here executes a compiled
program — analysis is trace/lower only.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.analysis import (
    diff_against_baseline, lint_file, lint_program, lint_quarantine,
    load_baseline, recompile_report)
from paddle_tpu.analysis.findings import (
    BAKED_RNG_KEY, DTYPE_PROMOTION, HOST_CALLBACK, JIT_IN_CALL,
    NUMPY_IN_TRACE, RECOMPILE_DIM, RECOMPILE_STRUCTURE, SCATTER_OP,
    STALE_QUARANTINE, TRACED_ATTR_MUTATION, UNDONATED_BUFFER, Finding,
    count_findings)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(findings):
    return {f.code for f in findings}


# ---------------------------------------------------------------------------
# program linter: one synthetic program per hazard class, exact codes
# ---------------------------------------------------------------------------

def test_dtype_promotion_detected():
    def f(x):
        return x.astype(jnp.float32) * 2          # bf16 -> f32 widening

    fs = lint_program("p", f, (jnp.ones(256, jnp.bfloat16),))
    promo = [f_ for f_ in fs if f_.code == DTYPE_PROMOTION]
    assert len(promo) == 1 and promo[0].site == "bfloat16->float32"
    # scalar / tiny converts don't fire (promotion_min_elems)
    fs2 = lint_program("p2", f, (jnp.ones(4, jnp.bfloat16),))
    assert DTYPE_PROMOTION not in _codes(fs2)


def test_scatter_detected_including_nested_scan():
    def f(cache, idx, v):
        def body(c, i):
            return c.at[idx].set(v), i
        out, _ = jax.lax.scan(body, cache, jnp.arange(3))
        return out

    fs = lint_program("p", f, (jnp.zeros((8, 8)), jnp.int32(1),
                               jnp.ones(8)))
    sc = [f_ for f_ in fs if f_.code == SCATTER_OP]
    assert sc and sc[0].site == "scatter"


def test_host_callback_detected():
    def f(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct(x.shape,
                                                          x.dtype), x)

    fs = lint_program("p", f, (jnp.ones(8),))
    assert HOST_CALLBACK in _codes(fs)


def test_baked_rng_key_detected_and_threaded_key_clean():
    baked = jax.random.PRNGKey(7)

    def bad(x):
        return x + jax.random.normal(baked, x.shape)

    def good(x, key):
        return x + jax.random.normal(key, x.shape)

    assert BAKED_RNG_KEY in _codes(lint_program("b", bad, (jnp.ones(8),)))
    assert BAKED_RNG_KEY not in _codes(
        lint_program("g", good, (jnp.ones(8), jax.random.PRNGKey(0))))


def test_undonated_buffer_detected_and_donation_clears_it():
    def f(cache, x):
        return cache + x, x.sum()

    cache = jnp.zeros((64, 256), jnp.float32)    # 64 KiB >= threshold
    x = jnp.ones((64, 256), jnp.float32)
    fs = lint_program("p", jax.jit(f), (cache, x))
    assert UNDONATED_BUFFER in _codes(fs)
    fs2 = lint_program("p", jax.jit(f, donate_argnums=(0, 1)), (cache, x))
    assert UNDONATED_BUFFER not in _codes(fs2)


# ---------------------------------------------------------------------------
# recompile-hazard analyzer
# ---------------------------------------------------------------------------

def test_recompile_dim_exact():
    specs = [(np.zeros((1, p), np.int64), np.zeros((4,), np.float32))
             for p in (7, 9, 13)]
    fs = recompile_report("gen", specs)
    assert len(fs) == 1 and fs[0].code == RECOMPILE_DIM
    assert fs[0].site == "arg0"
    assert fs[0].data["varying_dims"] == [1]
    assert fs[0].data["distinct_programs"] == 3


def test_recompile_stable_specs_clean_and_structure_drift():
    stable = [(np.zeros((1, 8)),)] * 3
    assert recompile_report("gen", stable) == []
    drift = [({"a": np.zeros(3)},), ({"a": np.zeros(3),
                                      "b": np.zeros(3)},)]
    fs = recompile_report("gen", drift)
    assert [f.code for f in fs] == [RECOMPILE_STRUCTURE]


def test_recompile_dtype_drift_flagged():
    fs = recompile_report("gen", [(np.zeros(8, np.float32),),
                                  (np.zeros(8, np.float64),)])
    assert fs and fs[0].code == RECOMPILE_DIM
    assert "dtype varies" in fs[0].message


# ---------------------------------------------------------------------------
# codebase (AST) lint
# ---------------------------------------------------------------------------

_SNIPPET = '''
import jax
import numpy as np
from paddle_tpu.nn import Layer


def hot(x):
    return jax.jit(lambda v: v * 2)(x)            # retrace per call


class Gate(Layer):
    def forward(self, x):
        stats = np.asarray(x)                     # concretizes tracer
        self._last = x * 2                        # tracer on the layer
        self._ok = x.sum()   # tpulint: disable=traced-attr-mutation
        self.training = True                      # constant: trace-safe
        return x


class HostSide:                                   # not a Layer: exempt
    def forward(self, x):
        self.cache = np.asarray(x)
        return x
'''


def test_codebase_lint_synthetic(tmp_path):
    p = tmp_path / "snippet.py"
    p.write_text(_SNIPPET)
    fs = lint_file(str(p), str(tmp_path))
    by_code = {}
    for f in fs:
        by_code.setdefault(f.code, []).append(f)
    assert [f.site for f in by_code[JIT_IN_CALL]] == ["hot"]
    assert [f.site for f in by_code[TRACED_ATTR_MUTATION]] == \
        ["Gate.forward._last"]          # _ok disabled, constants exempt
    assert [f.site for f in by_code[NUMPY_IN_TRACE]] == \
        ["Gate.forward.np.asarray"]     # HostSide is not layer-like


def test_jit_no_donation_on_hot_wrapper_files(tmp_path):
    """HOT_JIT_FILES membership is by repo-relative path: the same
    knob-less jax.jit is info-flagged inside jit/training.py and silent
    elsewhere."""
    from paddle_tpu.analysis.findings import JIT_NO_DONATION
    hot = tmp_path / "paddle_tpu" / "jit" / "training.py"
    hot.parent.mkdir(parents=True)
    hot.write_text("import jax\n\ndef build(f):\n    return jax.jit(f)\n")
    fs = lint_file(str(hot), str(tmp_path))
    assert [f.code for f in fs] == [JIT_NO_DONATION]
    cold = tmp_path / "paddle_tpu" / "other.py"
    cold.write_text("import jax\n\ndef build(f):\n    return jax.jit(f)\n")
    assert lint_file(str(cold), str(tmp_path)) == []


def test_quarantine_machine_check(tmp_path):
    q = tmp_path / "flaky_quarantine.txt"
    q.write_text(
        "# comment\n"
        "tests/test_analysis.py::test_quarantine_machine_check\n"
        "tests/no_such_file.py::test_gone\n"
        "name_that_matches_no_test\n")
    fs = lint_quarantine(ROOT, quarantine_path=str(q))
    stale = sorted(f.site for f in fs)
    assert all(f.code == STALE_QUARANTINE for f in fs)
    assert stale == ["name_that_matches_no_test",
                     "tests/no_such_file.py::test_gone"]


def test_quarantine_class_based_nodeids_and_substrings_resolve(tmp_path):
    """Class-based nodeids (path::TestCls::test_fn) and Test-class -k
    substrings are valid quarantine entries and must not read as stale
    (ci.py's own _quarantine() accepts them; the policies must agree)."""
    q = tmp_path / "q.txt"
    q.write_text(
        "tests/test_analysis.py::TestGateAnchors::test_anchor_is_"
        "segment_bounded\n"
        "TestGateAnchors\n"
        "flash_kernel\n")     # -k also matches MODULE names (whole-file)
    assert lint_quarantine(ROOT, quarantine_path=str(q)) == []


def test_run_manifest_rejects_unknown_program_names():
    from paddle_tpu.analysis import run_manifest
    with pytest.raises(ValueError, match="unknown manifest program"):
        run_manifest(["gpt_deocde"])      # typo must not silently pass


def test_repo_quarantine_entries_all_resolve():
    """The checked-in registry must be clean — known failures stay
    tracked, not rotted (satellite: machine-checked annotations)."""
    assert lint_quarantine(ROOT) == []


# ---------------------------------------------------------------------------
# baseline gate semantics
# ---------------------------------------------------------------------------

def _mk(code, program, site, sev="warn", count=1):
    return Finding(code, sev, program, site, "m",
                   {"count": count} if count != 1 else {})


def test_gate_ratchets_on_counts_and_weights():
    base = {"counts": {"scatter-op::p::scatter": 2}}
    ok = [_mk("scatter-op", "p", "scatter", count=2)]
    assert diff_against_baseline(ok, base) == []
    worse = [_mk("scatter-op", "p", "scatter", count=3)]
    new = diff_against_baseline(worse, base)
    assert len(new) == 1 and "exceeds baseline" in new[0]["reason"]
    # info inventories are count-pinned too: a gather/collective count
    # regression gates exactly like a warn (the documented contract)
    info = [_mk("gather-op", "p", "gather", sev="info", count=3)]
    assert diff_against_baseline(
        info, {"counts": {"gather-op::p::gather": 3}}) == []
    assert diff_against_baseline(
        info, {"counts": {"gather-op::p::gather": 2}})


class TestGateAnchors:
    def test_anchor_beats_counts(self):
        base = {"counts": {"scatter-op::p::scatter": 5},
                "must_stay_clean": ["scatter-op::p"]}
        new = diff_against_baseline([_mk("scatter-op", "p", "scatter")],
                                    base)
        assert len(new) == 1 and "must_stay_clean" in new[0]["reason"]

    def test_anchor_is_segment_bounded(self):
        """Anchor 'x::train_step' must not capture a future program
        named 'train_step_acc' (prefix match is '::'-bounded)."""
        base = {"counts": {"scatter-op::train_step_acc::scatter": 1},
                "must_stay_clean": ["scatter-op::train_step"]}
        ok = [_mk("scatter-op", "train_step_acc", "scatter")]
        assert diff_against_baseline(ok, base) == []
        hit = [_mk("scatter-op", "train_step", "scatter")]
        assert diff_against_baseline(hit, base)


def test_count_findings_weights_op_counts():
    counts = count_findings([_mk("scatter-op", "p", "scatter", count=2),
                             _mk("scatter-op", "p", "scatter")])
    assert counts == {"scatter-op::p::scatter": 3}


# ---------------------------------------------------------------------------
# the acceptance demonstration: a seeded hazard fails the CHECKED-IN
# baseline, and the real engine decode program stays clean
# ---------------------------------------------------------------------------

def test_seeded_scatter_cache_write_fails_checked_in_baseline():
    """Reintroducing a scatter cache write into the decode program (the
    exact PR-2 hazard) must fail the CI gate against the committed
    baseline — the must_stay_clean anchor fires even if counts were
    bumped."""
    def bad_decode(cache, tok, pos):
        # the regression tpulint exists to catch: per-row scatter write
        return cache.at[jnp.arange(cache.shape[0]), pos].set(
            tok.astype(cache.dtype))

    cache = jnp.zeros((4, 64, 8), jnp.float32)
    fs = lint_program(
        "gpt_decode", jax.jit(bad_decode, donate_argnums=(0,)),
        (cache, jnp.zeros((4, 8), jnp.int32), jnp.zeros(4, jnp.int32)))
    base = load_baseline(os.path.join(ROOT, "tools",
                                      "tpulint_baseline.json"))
    new = diff_against_baseline(fs, base)
    assert any(n["code"] == SCATTER_OP and n["program"] == "gpt_decode"
               for n in new), new


def test_real_engine_decode_program_is_clean():
    """The engine's batched decode program: no scatter (one-hot masked
    cache writes), KV cache donated, no baked keys, no host callbacks —
    the donation satellite + PR-2 write regime, asserted on the REAL
    program via the same manifest builder the CLI uses."""
    # the builders moved to compilation/sites.py when the registry
    # became the one program table (PR 5) — build through it, exactly
    # as the CLI's manifest does
    from paddle_tpu.compilation import registry
    r = registry.build("gpt_decode")
    try:
        fs = lint_program("gpt_decode", r.fn, r.args)
    finally:
        if r.cleanup is not None:
            r.cleanup()
    codes = _codes(fs)
    assert SCATTER_OP not in codes
    assert UNDONATED_BUFFER not in codes      # cache donation wired
    assert BAKED_RNG_KEY not in codes
    assert HOST_CALLBACK not in codes
    # and the committed baseline accepts the program as-is
    base = load_baseline(os.path.join(ROOT, "tools",
                                      "tpulint_baseline.json"))
    assert diff_against_baseline(fs, base) == []


def test_tpulint_cli_codebase_only_gate_passes(capsys, monkeypatch):
    """The CLI contract: the last stdout line is one terminal JSON
    record, and the gate passes on HEAD.
    Run in-process (runpy) — a subprocess would pay a cold paddle_tpu
    import (~10 s) for nothing on the 1-core tier-1 budget."""
    import runpy
    monkeypatch.setattr(sys, "argv", ["tpulint.py", "--codebase-only"])
    with pytest.raises(SystemExit) as exc:
        runpy.run_path(os.path.join(ROOT, "tools", "tpulint.py"),
                       run_name="__main__")
    assert exc.value.code == 0
    rec = json.loads(
        capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["gate"] == "pass" and "error" not in rec


# ---------------------------------------------------------------------------
# tpucost (analysis/hlo_cost.py + analysis/fusion.py): the HLO parsers
# run over CHECKED-IN fixtures — zero compiles — so the cost pass is
# exercised even where compile is skipped; the live registry pass and
# the decode anchor ride one shared module-scoped inventory below
# ---------------------------------------------------------------------------

FIXTURES = os.path.join(ROOT, "tests", "fixtures", "hlo")


def _fixture(name):
    with open(os.path.join(FIXTURES, name)) as fh:
        return fh.read()


def test_hlo_parser_fusion_and_dot_flops():
    """mlp_fused.txt: dot [8,64]x[64,128] + one kLoop fusion. The dot's
    FLOPs are exact (2*M*N*K); the fusion counts its internal
    elementwise ops at full shape and pays HBM only at its boundary
    (operands + root output — fused producers are free)."""
    from paddle_tpu.analysis import program_cost
    inv = program_cost(_fixture("mlp_fused.txt"), name="mlp")
    assert inv["matmul_flops"] == 2 * 8 * 128 * 64
    assert inv["fusion_histogram"] == {"dot": 1, "loop": 1}
    assert inv["kernel_count"] == 2
    assert inv["flops"] > inv["matmul_flops"]      # + fused elementwise
    # reads: dot streams w + x; the fusion re-reads the dot's output
    # from HBM plus the bias — nothing INSIDE the fusion pays traffic
    w_x = (64 * 128 + 8 * 64) * 4
    fus_r = (8 * 128 + 128) * 4
    assert inv["bytes_read"] == w_x + fus_r
    assert inv["bytes_written"] == 2 * 8 * 128 * 4
    assert inv["bound"] == "bandwidth"
    assert inv["roofline_seconds"] > 0


def test_hlo_parser_while_trip_count_multiplies():
    """scan_loop.txt: lax.scan(length=5) lowers to a while whose
    condition compares against constant 5 — every body kernel is
    counted 5x (the decode tick / fused train window accounting)."""
    from paddle_tpu.analysis import collect_kernels, parse_hlo_module
    m = parse_hlo_module(_fixture("scan_loop.txt"))
    ks = collect_kernels(m)
    body = [k for k in ks if k.path and k.opcode == "fusion"]
    assert len(body) == 1 and body[0].trip == 5
    # 3 arithmetic ops x 128*128 elems x 5 trips
    assert body[0].flops == 3 * 128 * 128 * 5
    assert body[0].bytes_read == 128 * 128 * 4 * 5


def test_hlo_parser_collective_replica_groups():
    """collectives.txt: a 4-wide psum all-reduce. The inventory counts
    the replica group, so per-chip bytes are 2(n-1)/n of the result —
    the ZeRO-2 byte-accuracy fix (satellite: count groups)."""
    from paddle_tpu.analysis import (collective_inventory_from_hlo,
                                     program_cost)
    txt = _fixture("collectives.txt")
    inv = collective_inventory_from_hlo(txt)
    assert set(inv) == {"all-reduce"}
    rec = inv["all-reduce"]
    assert rec["count"] == 1 and rec["group_size"] == 4
    assert rec["result_bytes"] == 2 * 512 * 4
    assert rec["bytes"] == int(2 * 512 * 4 * 2 * 3 / 4)   # 2(n-1)/n
    cost = program_cost(txt, name="psum")
    assert cost["fusion_histogram"].get("collective") == 1


def test_hlo_parser_unfused_chain_ranked():
    """unfused_chain.txt (synthetic): add -> tanh -> multiply left as
    three separate kernels behind a dot. The fusion report names the
    chain and ranks its intermediate HBM traffic; the dot is not part
    of the elementwise chain."""
    from paddle_tpu.analysis import program_cost
    inv = program_cost(_fixture("unfused_chain.txt"), name="chain")
    assert inv["fusion_histogram"] == {"dot": 1, "unfused": 3}
    top = inv["top_unfused"]
    assert len(top) == 1
    chain = top[0]
    assert chain["kernels"] == ["add.4", "multiply.6", "tanh.5"]
    # exactly the two distinct intermediates (add.4, tanh.5) cross HBM
    # — add.4 fans out to BOTH consumers but is written once
    assert chain["intermediate_bytes"] == 2 * 256 * 256 * 4
    assert chain["savable_bytes"] == 2 * chain["intermediate_bytes"]


def test_collective_empty_replica_groups_means_all_devices():
    """`replica_groups={}` is HLO for ONE all-replica group — the
    inventory must scale by the module's partition count, not read it
    as a degenerate single-device group (which would zero the bytes)."""
    from paddle_tpu.analysis import collective_inventory_from_hlo
    # a real-size entry_computation_layout pushes num_partitions
    # thousands of chars into the header line — the whole first line
    # must be searched, not a fixed byte window
    layout = ", ".join("f32[128,128]{1,0}" for _ in range(200))
    txt = (f"HloModule m, entry_computation_layout={{({layout})->"
           "f32[2,512]{1,0}}, num_partitions=8\n"
           "  %ar = f32[2,512]{1,0} all-reduce(f32[2,512]{1,0} %p), "
           "replica_groups={}, to_apply=%add\n")
    assert txt.index("num_partitions") > 2048
    rec = collective_inventory_from_hlo(txt)["all-reduce"]
    assert rec["group_size"] == 8
    assert rec["bytes"] == int(2 * 512 * 4 * 2 * 7 / 8)   # 2(n-1)/n


def test_collective_permute_bytes_are_per_hop():
    """collective-permute uses source_target_pairs, not replica groups
    — its transferred bytes are the result bytes (one hop), never
    zeroed by the degenerate group size."""
    from paddle_tpu.analysis import collective_inventory_from_hlo
    line = ("  %cp = f32[128,8]{1,0} collective-permute("
            "f32[128,8]{1,0} %x), channel_id=1, "
            "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}\n")
    inv = collective_inventory_from_hlo(line)
    assert inv["collective-permute"]["bytes"] == 128 * 8 * 4


# ---------------------------------------------------------------------------
# tpucost baseline-gate semantics (pure; no compiles)
# ---------------------------------------------------------------------------

def _inv(hbm=1000, kernels=10, share=0.8):
    return {"hbm_bytes": hbm, "kernel_count": kernels,
            "matmul_flop_share": share}


def test_cost_budgets_ratchet():
    from paddle_tpu.analysis import check_cost_baseline
    from paddle_tpu.analysis.findings import COST_BUDGET
    base = {"budgets": {"p": {"hbm_bytes": 1000, "kernel_count": 10,
                              "matmul_flop_share_min": 0.8}}}
    assert check_cost_baseline({"p": _inv()}, base, ["p"]) == []
    worse = check_cost_baseline({"p": _inv(hbm=1001)}, base, ["p"])
    assert [f.code for f in worse] == [COST_BUDGET]
    assert worse[0].site == "hbm_bytes"
    worse = check_cost_baseline({"p": _inv(kernels=11)}, base, ["p"])
    assert worse and worse[0].site == "kernel_count"
    worse = check_cost_baseline({"p": _inv(share=0.79)}, base, ["p"])
    assert worse and worse[0].site == "matmul_flop_share"
    # improvements pass (and --update-baseline locks them in)
    assert check_cost_baseline(
        {"p": _inv(hbm=900, kernels=9, share=0.9)}, base, ["p"]) == []


def test_cost_gate_flags_unbaselined_program():
    """A newly registered program with no pinned budget fails the gate
    — registry completeness is enforced in BOTH directions."""
    from paddle_tpu.analysis import check_cost_baseline
    new = check_cost_baseline({"fresh": _inv()},
                              {"budgets": {}}, ["fresh"])
    assert len(new) == 1 and new[0].site == "unbaselined"


def test_cost_gate_stale_program_detected():
    """A baseline budget or anchor naming a program the registry no
    longer has fails loudly — the registry-rename rot check (the
    stale-quarantine analogue for cost baselines)."""
    from paddle_tpu.analysis import check_cost_baseline
    from paddle_tpu.analysis.findings import STALE_COST_PROGRAM
    base = {"budgets": {"gone": {"hbm_bytes": 1}},
            "anchors": {"also_gone": {"kind": "matmul_share_floor",
                                      "min_share": 0.5}}}
    new = check_cost_baseline({}, base, ["live_prog"])
    assert sorted(f.program for f in new) == ["also_gone", "gone"]
    assert all(f.code == STALE_COST_PROGRAM for f in new)


def test_cost_anchor_decode_hbm_and_share_floor():
    from paddle_tpu.analysis import (analytic_decode_hbm_bytes,
                                     check_cost_baseline)
    from paddle_tpu.analysis.findings import COST_ANCHOR
    geom = {"tick_tokens": 4, "param_bytes": 1000,
            "kv_cache_bytes": 100}
    bound = analytic_decode_hbm_bytes(geom)
    assert bound == 4 * (1000 + 7 * 100)
    base = {"budgets": {"d": {"hbm_bytes": 10 * bound,
                              "kernel_count": 99,
                              "matmul_flop_share_min": 0.0}},
            "anchors": {"d": {"kind": "decode_hbm", "max_ratio": 1.15}}}
    ok = check_cost_baseline({"d": _inv(hbm=int(bound * 1.1))}, base,
                             ["d"], {"d": geom})
    assert ok == []
    bad = check_cost_baseline({"d": _inv(hbm=int(bound * 1.2))}, base,
                              ["d"], {"d": geom})
    assert [f.code for f in bad] == [COST_ANCHOR]
    floor = {"budgets": {"t": {"hbm_bytes": 10, "kernel_count": 1,
                               "matmul_flop_share_min": 0.0}},
             "anchors": {"t": {"kind": "matmul_share_floor",
                               "min_share": 0.85}}}
    assert check_cost_baseline({"t": _inv(hbm=1, kernels=1,
                                          share=0.86)},
                               floor, ["t"]) == []
    assert check_cost_baseline({"t": _inv(hbm=1, kernels=1,
                                          share=0.84)},
                               floor, ["t"])


def test_cost_gate_unknown_anchor_kind_fails_loudly():
    """A typo in a hand-edited anchor must not silently DISABLE the
    invariant — unknown kinds are violations, not no-ops."""
    from paddle_tpu.analysis import check_cost_baseline
    base = {"budgets": {"p": {"hbm_bytes": 10, "kernel_count": 99,
                              "matmul_flop_share_min": 0.0}},
            "anchors": {"p": {"kind": "decode-hbm"}}}     # typo'd kind
    new = check_cost_baseline({"p": _inv(hbm=1)}, base, ["p"])
    assert len(new) == 1 and new[0].site == "unknown-kind"


def test_cost_gate_full_run_requires_every_baselined_program():
    """require_all (a full run): a live baselined program missing from
    the inventories is a violation — a silently skipped site must not
    read as its anchors passing. Partial (--programs) runs still skip
    absent programs."""
    from paddle_tpu.analysis import check_cost_baseline
    base = {"budgets": {"p": {"hbm_bytes": 10, "kernel_count": 99,
                              "matmul_flop_share_min": 0.0}}}
    assert check_cost_baseline({}, base, ["p"]) == []     # partial
    new = check_cost_baseline({}, base, ["p"], require_all=True)
    assert len(new) == 1 and new[0].site == "not-measured"


def test_updated_cost_baseline_preserves_anchors():
    from paddle_tpu.analysis import updated_cost_baseline
    base = {"anchors": {"p": {"kind": "decode_hbm", "max_ratio": 1.15}},
            "notes": {"p": "why"}, "budgets": {}}
    new = updated_cost_baseline(
        base, {"p": {"hbm_bytes": 5, "kernel_count": 2,
                     "matmul_flop_share": 0.51239}})
    assert new["anchors"] == base["anchors"]
    assert new["notes"] == {"p": "why"}
    assert new["budgets"]["p"] == {"hbm_bytes": 5, "kernel_count": 2,
                                   "matmul_flop_share_min": 0.5123}


# ---------------------------------------------------------------------------
# live registry pass: every registered program gets a cost record, the
# committed baseline accepts HEAD, and the decode-tick HBM anchor holds
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def live_inventories():
    """One shared cost pass over the full registry (compiles every
    program once — the warm persistent cache makes repeat runs cheap;
    tools/tpucost.py's collect_inventories is the SAME code path the
    CLI gates on)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tpucost_cli", os.path.join(ROOT, "tools", "tpucost.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.path.insert(0, ROOT)
    return mod.collect_inventories()


@pytest.mark.timeout(600)
def test_every_registered_program_gets_a_cost_record(live_inventories):
    """Registry completeness: a program registered with the manifest
    tag is cost-inventoried BY DEFAULT (same contract as lint/warmup
    coverage — one table serves every consumer)."""
    from paddle_tpu.compilation import registry
    invs, geoms, skipped = live_inventories
    assert skipped == {}        # conftest provides 8 virtual devices
    assert sorted(invs) == sorted(registry.names(tag="manifest"))
    for name, inv in invs.items():
        assert inv["flops"] > 0, name
        assert inv["hbm_bytes"] > 0, name
        assert inv["kernel_count"] > 0, name
        assert 0.0 <= inv["matmul_flop_share"] <= 1.0, name
        assert inv["roofline_seconds"] > 0, name
        assert isinstance(inv["fusion_histogram"], dict), name
        assert isinstance(inv["top_unfused"], list), name


def test_decode_tick_hbm_anchor_holds(live_inventories):
    """The acceptance anchor: the engine decode tick's modeled HBM
    bytes stay within 1.15x of the analytic KV-cache + weight bound
    (7 cache passes per micro-step under the current masked-write
    regime — analysis/hlo_cost.analytic_decode_hbm_bytes). An eighth
    pass appearing (unfused activation chain, dropped fusion) breaks
    this, and CI with it."""
    from paddle_tpu.analysis import analytic_decode_hbm_bytes
    invs, geoms, _ = live_inventories
    bound = analytic_decode_hbm_bytes(geoms["gpt_decode"])
    ratio = invs["gpt_decode"]["hbm_bytes"] / bound
    assert ratio <= 1.15, (invs["gpt_decode"]["hbm_bytes"], bound)
    # and the bound is honest: the model carries MORE traffic than the
    # weights+cache floor, not less (an undercounting parser would
    # silently hollow the anchor out)
    assert ratio > 0.9


def test_committed_cost_baseline_accepts_head(live_inventories):
    """tools/tpucost_baseline.json gates green against HEAD — the same
    check ci.py --quick/--full append after the tests."""
    from paddle_tpu.analysis import (check_cost_baseline,
                                     load_cost_baseline)
    from paddle_tpu.compilation import registry
    invs, geoms, _ = live_inventories
    base = load_cost_baseline(
        os.path.join(ROOT, "tools", "tpucost_baseline.json"))
    assert check_cost_baseline(invs, base,
                               registry.names(tag="manifest"),
                               geoms) == []
