"""The Olmo Hybrid family's benchmark files: the reference's recurrence
against a loop by hand, its layer-by-layer step against the gradient of the
whole model, its planted faults, ``work_olmo_hybrid.py`` against sums by
hand, the three new readers on made-up intervals, a CPU rehearsal of the new
driver through ``run.py`` at toy sizes, and what the cell's files must
say."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rehearsal import REPO, make_tree, run_cell
from benchmark import chips, run as run_mod, trace, work_olmo_hybrid as W
from benchmark.drivers.train_steps_olmo_hybrid import compare
from benchmark.reference import olmo_hybrid as R

jax.config.update("jax_default_matmul_precision", "highest")

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "train-olmo-hybrid-8k"
NEW_READERS = ("gdn_roofline", "gdn_time_pct", "linear_attn_time_pct")


def config(name="olmo-hybrid-7b-4l-vp8"):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


PERIOD = ["linear_attention"] * 3 + ["full_attention"]
TINY = dict(hidden_size=32, intermediate_size=48, num_attention_heads=2,
            num_key_value_heads=2, linear_num_key_heads=2,
            linear_num_value_heads=2, linear_key_head_dim=8,
            linear_value_head_dim=16, linear_conv_kernel_dim=4,
            linear_allow_neg_eigval=True, vocab_size=64,
            num_hidden_layers=4, layer_types=PERIOD * 2,
            layers_kept=[0, 1, 2, 3], rms_norm_eps=1e-6,
            rope_parameters={"rope_theta": None}, initializer_range=0.1)
JOB = dict(compute_dtype="float32", master_weights=True, learning_rate=1e-3,
           beta1=0.9, beta2=0.999, epsilon=1e-8, weight_decay=0.01)


# ------------------------------------------------------------- reference

def test_recurrence_matches_a_loop_by_hand():
    rng = np.random.default_rng(0)
    s, h, dk, dv = 512, 2, 4, 6
    q, k = rng.normal(size=(2, s, h, dk))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(s, h, dv))
    g = -rng.uniform(0, 0.2, (s, h))
    b = rng.uniform(0, 2, (s, h))
    got = np.asarray(R.delta_rule(*(jnp.asarray(x, jnp.float32)
                                    for x in (q, k, v, g, b))))
    want = np.zeros((s, h, dv))
    for hd in range(h):
        st = np.zeros((dv, dk))
        for t in range(s):
            st = np.exp(g[t, hd]) * st
            st = st + b[t, hd] * np.outer(v[t, hd] - st @ k[t, hd], k[t, hd])
            want[t, hd] = st @ q[t, hd]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_layer_by_layer_step_is_the_whole_models_gradient():
    # at a start of 0.1 the first layer's gradient is 50 times the last
    # one's, and the two orders of summation part at 2e-4
    arch = dict(TINY, initializer_range=0.02)
    ids = np.random.default_rng(4).integers(0, 64, (2, 8))
    w = R.init_params(arch, 12, jnp.float32)
    loss, grads = jax.value_and_grad(
        lambda p: R.loss_whole(p, jnp.asarray(ids), arch))(w)
    got = R.train_readings(arch, JOB, 12, [ids])
    assert got["losses"][0] == pytest.approx(float(loss), rel=1e-6)
    for n, g in grads.items():
        np.testing.assert_allclose(
            got["grad_norms"][n],
            np.asarray(R.leaf_norms(g, n, n not in R.TOP_NAMES)), rtol=2e-5)


@pytest.mark.parametrize("fault", R.FAULTS + ("half_batch", "fp8"))
def test_every_fault_and_the_control_move_the_readings(fault):
    ids = [np.random.default_rng(5).integers(0, 64, (2, 8))] * 2
    ref = R.train_readings(TINY, JOB, 13, ids)
    kw = {"half_batch": dict(half_batch=True),
          "fp8": dict(precision="fp8")}.get(fault, dict(fault=fault))
    gaps = compare(R.train_readings(TINY, JOB, 13, ids, **kw), ref)
    assert max(gaps["grad_norm_gap"], gaps["loss_gap_step1"] * 100) > 1e-3
    assert compare(ref, ref)["grad_norm_gap"] == 0.0
    with pytest.raises(ValueError):
        R.settings(TINY, "no_such_fault")


def test_half_of_one_row_is_its_first_half():
    ids = np.random.default_rng(6).integers(0, 64, (1, 16))
    half = R.train_readings(TINY, JOB, 14, [ids], half_batch=True)
    first = R.train_readings(TINY, JOB, 14, [ids[:, :8]])
    assert half["losses"] == first["losses"]


def test_the_decay_starts_as_fla_draws_it():
    w = R.init_params(dict(TINY, linear_num_key_heads=512,
                           linear_num_value_heads=512), 3, jnp.float32)
    a = np.exp(np.asarray(w["A_log"]))
    dt = np.log1p(np.exp(np.asarray(w["dt_bias"])))     # softplus
    assert 0 < a.min() and a.max() < 16 and 6 < a.mean() < 10
    assert 1e-3 * 0.99 < dt.min() and dt.max() < 0.1 * 1.01


def test_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(REPO, "benchmark", "reference", "olmo_hybrid.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    mods = [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    mods += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    assert not any(m.startswith("paddle_tpu") for m in mods)


# ------------------------------------------------------------ the files

def test_parameter_count_and_what_the_files_say():
    arch = config()
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "olmo-hybrid-7b-4l-vp8")
    assert entry["reduced"] == arch["reduced"] == ["num_hidden_layers",
                                                   "vocab_size"]
    shapes = R.leaf_shapes(arch)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 928_862_196
    assert "928,862,196" in arch["why_reduced"]
    assert R.layer_kinds(arch) == tuple(PERIOD)
    assert arch["vocab_size"] * 8 == arch["vocab_size_published"]
    for key in ("linear_mixer", "short_conv", "l2norm", "gates",
                "recurrence", "linear_output", "decay_start", "full_mixer",
                "no_positions", "block", "model"):
        assert key in arch["assumed"]
    limits = run_mod.load_json("benchmark", "limits", CELL + ".json")
    assert limits["attention_backend_differs"] == 0
    assert limits["linear_attention_backend_differs"] == 0


def test_published_is_the_catalogs_row():
    """Every number of the catalog's config at the top level, as it is or
    named in ``reduced``; nested groups copied whole."""
    arch = config()
    with open(CATALOG) as f:
        row = next(json.loads(l) for l in f if '"Olmo-Hybrid-7B"' in l)
    assert arch["published"] == row["config"]
    assert arch["source"].startswith(row["source_url"])
    for k, v in row["config"].items():
        assert arch[k] == v or k in arch["reduced"], k


# ------------------------------------------------------------ work counts

def test_work_counts_by_hand():
    arch, seq = config(), 8192
    fwd = W.delta_rule_flops(arch, seq, False)
    assert fwd == pytest.approx(48.3e9, rel=1e-3)        # ISSUE 40's 48
    assert W.delta_rule_flops(arch, seq, True) == 2 * fwd
    assert W.delta_rule_bytes(arch, seq, False) == pytest.approx(
        0.285e9, rel=1e-2)
    p = W.matmul_params(arch)
    per_token_linear = 2 * (p["linear_projections"] / 3
                            + p["mlp"] / 4) + fwd / seq
    assert per_token_linear / 1e6 == pytest.approx(436.6, abs=0.5)
    got = W.train_flops(arch, 1, seq)
    assert got["total"] == sum(v for k, v in got.items() if k != "total")
    assert got["delta_rule"] == 3 * 3 * fwd
    least, bound = W.delta_rule_seconds(arch, 1, seq,
                                        chips.chip_for("TPU v5 lite"))
    # 48.3 GFLOP in 0.245 ms at the peak, 0.285 GB in 0.348 ms
    assert bound == {"forward": "memory", "backward": "memory"}
    assert least == pytest.approx(3 * sum(
        W.delta_rule_bytes(arch, seq, bwd) for bwd in (False, True)) / 819e9)


# ------------------------------------------------------------ readers

_FWD = "jit(full_step)/jvp(olmohybridforcausallm)/model/block_0/"
_BWD = ("jit(full_step)/transpose(jvp(olmohybridforcausallm))/model/"
        "jvp(olmohybridforcausallm)/model/checkpoint/block_0/")
_RECOMPUTED = ("jit(full_step)/transpose(jvp(olmohybridforcausallm))/model/"
               "jvp(olmohybridforcausallm)/model/checkpoint/"
               "rematted_computation/block_0/")


@pytest.fixture
def no_record_of_another_test(monkeypatch):
    from paddle_tpu.jit import training
    monkeypatch.setattr(training, "_last_program", None)


def made_up():
    """1000 ns busy: the kernel forward, recomputed and backward, the
    mixer's projections, and the MLP."""
    ops = [("fusion.1", 1000, 1100),            # q projection, forward
           ("gated_delta_fwd.2", 1100, 1200),   # kernel, forward
           ("fusion.3", 1200, 1300),            # MLP, forward
           ("gated_delta_fwd.4", 1300, 1400),   # kernel, recomputed
           ("gated_delta_bwd.5", 1400, 1700),   # kernel, backward
           ("fusion.6", 1700, 1800),            # gated norm, backward
           ("fusion.7", 1800, 2000)]            # MLP, backward
    table = {
        "fusion.1": _FWD + "linear_attn/q_proj/dot_general",
        "gated_delta_fwd.2": _FWD + "linear_attn/delta_rule/pallas_call",
        "fusion.3": _FWD + "mlp/gate_proj/dot_general",
        "gated_delta_fwd.4": _RECOMPUTED + "linear_attn/delta_rule/"
        "pallas_call",
        "gated_delta_bwd.5": _BWD + "linear_attn/delta_rule/pallas_call",
        "fusion.6": _BWD + "linear_attn/gated_norm/mul",
        "fusion.7": _BWD + "mlp/down_proj/dot_general"}
    chip = trace.Chip(ops=ops, modules=[("jit_full_step", 1000, 2000)])
    cell = {"config": config(), "chips": 1,
            "traffic": run_mod.load_json("benchmark", "traffic",
                                         "steps-1x8192-gdn.json")}
    return (trace.Trace([chip], [(trace.WINDOW_SPAN, 900, 2100)]), cell,
            {"steps": 1, "op_scopes": table})


def read(metric, tr, obs, cell, chip=None):
    said = []
    return run_mod.load_module("layer_metrics", metric).read(
        tr, obs, cell, chip, said.append), said


def test_readers_by_hand(no_record_of_another_test):
    tr, cell, obs = made_up()
    chip = chips.chip_for("TPU v5 lite")
    assert read("gdn_time_pct", tr, obs, cell)[0] == pytest.approx(50.0)
    assert read("linear_attn_time_pct", tr, obs, cell)[0] == pytest.approx(
        70.0)
    value, said = read("gdn_roofline", tr, obs, cell, chip)
    least, _ = W.delta_rule_seconds(cell["config"], 1, 8192, chip)
    assert value == pytest.approx(100 * least / 500e-9)
    assert "roofline bound by" in said[-1]
    assert read("recompute_time_pct", tr, obs, cell)[0] == pytest.approx(
        10.0)


@pytest.mark.parametrize("metric", NEW_READERS)
def test_readers_return_nothing_where_there_is_nothing(
        metric, no_record_of_another_test):
    tr, cell, obs = made_up()
    assert read(metric, None, {"steps": 1}, cell)[0] is None
    table = {n: p.replace("delta_rule", "x").replace("linear_attn", "y")
             for n, p in obs["op_scopes"].items()}
    assert read(metric, tr, dict(obs, op_scopes=table), cell,
                chips.chip_for("TPU v5 lite"))[0] is None


def test_the_new_metrics_are_declared_for_the_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = {m["name"] for m in run_mod.metrics_of(bench, "per_layer", CELL)}
    assert set(NEW_READERS) | {"recompute_time_pct", "step_mfu",
                               "backward_time_pct"} <= names
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", m["name"] + ".py"))


# ------------------------------------------------------------ rehearsal

TINY_LIMITS = {"loss_gap_step2": 0.01, "grad_norm_gap": 0.05,
               "change_norm_gap": 0.3, "attention_backend_differs": 0,
               "linear_attention_backend_differs": 0}


def add_tiny_cell(root, cell="train-oh-tiny"):
    def dump(obj, *parts):
        with open(os.path.join(root, *parts), "w") as f:
            json.dump(obj, f)
    conf = dict(config(), name="oh-tiny", **TINY)
    conf.update(hidden_size=64, linear_key_head_dim=16,
                linear_value_head_dim=32, intermediate_size=96,
                vocab_size=256, initializer_range=0.02)
    conf["job"] = dict(conf["job"], attention_backend="xla",
                       linear_attention_backend="xla", fused_loss_chunk=16)
    dump(conf, "benchmark", "configs", "oh-tiny.json")
    dump({"driver": "train_steps_olmo_hybrid", "batch": 2, "seq": 64,
          "log_every": 5, "warmup_steps": 3, "compare_steps": 2,
          "trace_seconds": 1}, "benchmark", "traffic", "steps-tiny-oh.json")
    dump(TINY_LIMITS, "benchmark", "limits", cell + ".json")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "oh-tiny", "source": "test",
                             "file": "benchmark/configs/oh-tiny.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({"name": cell, "config": "oh-tiny",
                               "traffic": "steps-tiny-oh", "chips": 1,
                               "why": "rehearsal"})
    dump(bench, "BENCHMARK.json")


@pytest.mark.parametrize("fault", ["none", "half_batch", "state_unchanged"])
def test_rehearse_the_new_driver(tmp_path, fault):
    """``run.py`` finds the new driver, configuration family, work counts
    and readers by name and runs them at toy sizes on the CPU; a timed path
    broken underneath reads ``correct`` false."""
    root = make_tree(str(tmp_path))
    add_tiny_cell(root)
    rc, result, err = run_cell(root, "train-oh-tiny", 2**31 + 77,
                               fault=fault)
    assert rc == 0, err
    assert result["correct"] is (fault == "none"), err
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["compared"]["linear_attention_backend_differs"][
        "value"] == 0
    assert "'backend': 'xla'" in err and "'dk': 16, 'dv': 32" in err


def test_the_parent_has_no_such_cell():
    """``run.py`` on a benchmark without the entry fails at once."""
    bench = {"workloads": [], "configs": []}
    with pytest.raises(SystemExit, match="no workload"):
        run_mod.find_cell(bench, CELL)
