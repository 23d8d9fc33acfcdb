"""The afmoe family's benchmark files: the reference against a two-layer
case written out by hand, ``work_afmoe.py`` against sums by hand, each new
reader on a stored fixture (``data/afmoe_step.json``: the step's four
Pallas kernels' HLO texts and some fusions' ``op_name`` paths as compiled
for a described v5e at the cell's sizes, with made-up times), and a CPU
rehearsal of the new driver through ``run.py`` at toy sizes."""
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rehearsal import REPO, make_tree, run_cell
from benchmark import afmoe_trace, chips, run as run_mod, trace, work_afmoe
from benchmark.reference import afmoe as R

jax.config.update("jax_default_matmul_precision", "highest")


def config(name="trinity-mini-5l-ep8"):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


TINY = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            head_dim=8, intermediate_size=48, moe_intermediate_size=16,
            num_shared_experts=1, num_experts=4, num_experts_published=8,
            expert_offset=2, num_experts_per_tok=3, vocab_size=64,
            num_hidden_layers=2, num_dense_layers=1,
            layer_types=["full_attention", "sliding_attention",
                         "sliding_attention"], layers_kept=[1, 0],
            sliding_window=4, rms_norm_eps=1e-5, rope_theta=10000,
            route_norm=True, route_scale=2.826, load_balance_coeff=0.001,
            initializer_range=0.1, mup_enabled=True,
            max_position_embeddings=64)
JOB = dict(compute_dtype="float32", master_weights=True, learning_rate=1e-3,
           beta1=0.9, beta2=0.999, epsilon=1e-8, weight_decay=0.01)


# ------------------------------------------------------------- reference

def _by_hand(w, ids, bias):
    """Two layers (sliding + dense MLP, then full + experts) and the loss,
    token by token and head by head in numpy float64, from the equations
    of the reference's docstring."""
    w = {n: np.asarray(v, np.float64) for n, v in w.items()}
    rms = lambda x, g: x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * g
    silu = lambda x: x / (1 + np.exp(-x))
    sig = lambda x: 1 / (1 + np.exp(-x))
    B, S = ids.shape
    total, counts = 0.0, np.zeros(8)
    for b in range(B):
        x = w["wte"][ids[b]] * math.sqrt(32)
        for layer, kind in enumerate(("sliding_attention", "full_attention")):
            y = rms(x, w["ln_in_g"][layer])
            q = (y @ w["q_w"][layer]).reshape(S, 4, 8)
            k = (y @ w["k_w"][layer]).reshape(S, 2, 8)
            v = (y @ w["v_w"][layer]).reshape(S, 2, 8)
            q, k = rms(q, w["q_norm_g"][layer]), rms(k, w["k_norm_g"][layer])
            if kind == "sliding_attention":
                def rope(t):
                    out = np.empty_like(t)
                    for p in range(S):
                        for i in range(4):
                            a = p * 10000.0 ** (-i / 4)
                            t0, t1 = t[p, :, 2 * i], t[p, :, 2 * i + 1]
                            out[p, :, 2 * i] = t0 * np.cos(a) - t1 * np.sin(a)
                            out[p, :, 2 * i + 1] = t1 * np.cos(a) + t0 * np.sin(a)
                    return out
                q, k = rope(q), rope(k)
            ctx = np.zeros((S, 4, 8))
            for h in range(4):
                for i in range(S):
                    lo = max(0, i - 3) if kind == "sliding_attention" else 0
                    sc = q[i, h] @ k[lo:i + 1, h // 2].T / math.sqrt(8)
                    p = np.exp(sc - sc.max())
                    ctx[i, h] = (p / p.sum()) @ v[lo:i + 1, h // 2]
            att = (ctx.reshape(S, 32) * sig(y @ w["g_w"][layer])) \
                @ w["o_w"][layer]
            x = x + rms(att, w["ln_post_attn_g"][layer])
            y = rms(x, w["ln_pre_mlp_g"][layer])
            if layer == 0:
                m = (silu(y @ w["mlp_w1"][0]) * (y @ w["mlp_w3"][0])) \
                    @ w["mlp_w2"][0]
            else:
                m = (silu(y @ w["sh_w1"][0]) * (y @ w["sh_w3"][0])) \
                    @ w["sh_w2"][0]
                s = sig(y @ w["router_w"][0])
                for t in range(S):
                    sel = np.argsort(-(s[t] + bias))[:3]
                    wt = s[t, sel] / (s[t, sel].sum() + 1e-20) * 2.826
                    for e, we in zip(sel, wt):
                        counts[e] += 1
                        if 2 <= e < 6:      # the experts held here
                            j = e - 2
                            m[t] += we * ((silu(y[t] @ w["exp_w1"][0, j])
                                           * (y[t] @ w["exp_w3"][0, j]))
                                          @ w["exp_w2"][0, j])
            x = x + rms(m, w["ln_post_mlp_g"][layer])
        logits = rms(x, w["lnf_g"])[:-1] @ w["head_w"]
        logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        total -= logp[np.arange(S - 1), ids[b, 1:]].sum()
    return total / (B * (S - 1)), counts


def test_reference_matches_two_layers_by_hand():
    ids = np.random.default_rng(3).integers(0, 64, (2, 8))
    w = R.init_params(TINY, 11, jnp.float32)
    bias = np.asarray([0.2, -0.1, 0, 0.3, 0, -0.2, 0.1, 0], np.float32)
    assert R.layer_kinds(TINY) == ("sliding_attention", "full_attention")
    loss, counts = R.loss_whole(w, jnp.asarray(bias)[None], jnp.asarray(ids),
                                TINY)
    want, want_counts = _by_hand(w, ids, bias.astype(np.float64))
    # float32 highest against float64: rounding alone
    assert float(loss) == pytest.approx(want, rel=2e-6)
    assert np.asarray(counts)[0].tolist() == want_counts.tolist()


@pytest.mark.parametrize("window", [None, 3, 4, 9])
def test_attention_in_blocks_is_attention_whole(window):
    """Blocks of 4 queries, each against the span of keys its window can
    reach, give what one block of all 16 queries against every key
    gives, forward and backward."""
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((16, 2, 2, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((16, 2, 8)), jnp.float32)
            for _ in range(2))

    def run(q_block):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(jnp.sin(
            R._attention_row(q, k, v, window, q_block))), (0, 1, 2))(q, k, v)
    (a, ga), (b, gb) = run(4), run(16)
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    for x, y in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


def test_layer_by_layer_step_is_the_whole_models_gradient():
    ids = np.random.default_rng(4).integers(0, 64, (2, 8))
    w = R.init_params(TINY, 12, jnp.float32)
    (loss, counts), grads = jax.value_and_grad(
        lambda p: R.loss_whole(p, jnp.zeros((1, 8)), jnp.asarray(ids), TINY),
        has_aux=True)(w)
    got = R.train_readings(TINY, JOB, 12, [ids])
    assert got["losses"][0] == pytest.approx(float(loss), rel=1e-6)
    assert np.array_equal(got["expert_load"], np.asarray(counts))
    for n, g in grads.items():
        np.testing.assert_allclose(
            got["grad_norms"][n],
            np.asarray(R.leaf_norms(g, n, n in R.STACK, (2, 4))), rtol=2e-5)
    np.testing.assert_allclose(
        got["expert_bias"],
        1e-3 * np.sign(np.asarray(counts).mean(-1, keepdims=True)
                       - np.asarray(counts)))


@pytest.mark.parametrize("fault", R.FAULTS + ("half_batch", "fp8"))
def test_every_fault_and_the_control_move_the_readings(fault):
    ids = [np.random.default_rng(5).integers(0, 64, (2, 8))] * 2
    ref = R.train_readings(TINY, JOB, 13, ids)
    kw = {"half_batch": dict(half_batch=True),
          "fp8": dict(precision="fp8")}.get(fault, dict(fault=fault))
    got = R.train_readings(TINY, JOB, 13, ids, **kw)
    from benchmark.drivers.train_steps_afmoe import compare
    gaps = compare(got, ref)
    if fault == "norm_sum_no_grad":
        # a fault of the routers' backward pass alone: the first loss
        # stands, the other leaves hardly move, and only the routers' own
        # number holds it
        assert gaps["loss_gap_step1"] == 0.0
        assert gaps["router_grad_norm_gap"] > 0.2 > 10 * gaps["grad_norm_gap"]
    else:
        assert max(gaps["grad_norm_gap"],
                   gaps["loss_gap_step1"] * 100) > 0.005
    same = compare(ref, ref)
    assert same["grad_norm_gap"] == same["router_grad_norm_gap"] == 0.0


# ------------------------------------------------------------ work counts

def test_work_counts_by_hand():
    arch = config()
    p = work_afmoe.afmoe_matmul_params(arch)
    # a layer's attention: q, gate, o of 2048 x 4096 and k, v of 2048 x 512
    assert p["projections"] == 5 * (3 * 2048 * 4096 + 2 * 2048 * 512)
    assert p["dense_mlp"] == 3 * 2048 * 6144
    assert p["shared_expert"] == 4 * 3 * 2048 * 1024
    assert p["router"] == 4 * 2048 * 128
    assert p["head"] == 2048 * 25024 and p["one_expert"] == 6_291_456
    f = work_afmoe.afmoe_train_flops(arch, batch=2, seq=8192)
    assert f["projections"] == 6 * p["projections"] * 16384
    assert f["head"] == 6 * p["head"] * 2 * 8191
    # an even routing lands 16384 x 8 x 16 / 128 assignments a layer
    assert f["routed_experts"] == 6 * 6_291_456 * 4 * 16384
    # a window row: all pairs less the triangle of the queries past it
    band = 8192 * 8193 // 2 - 6144 * 6145 // 2
    assert work_afmoe.attended_pairs(8192, 2048) == band == 14_681_088
    assert work_afmoe.attended_pairs(8192, None) == 33_558_528
    assert work_afmoe.attended_pairs(8192, 9000) == 33_558_528
    # four window layers: published layer 0 (dense MLP) and layers 4-6
    assert f["window_attention"] == 4 * 2 * 6 * 2 * 128 * 32 * band
    assert f["full_attention"] == 2 * 6 * 2 * 128 * 32 * 33_558_528
    assert f["total"] / 16384 == pytest.approx(2.21e9, rel=3e-3)  # ISSUE 30
    shares = {k: 100 * v / f["total"] for k, v in f.items()}
    assert shares["projections"] == pytest.approx(36.9, abs=0.1)
    assert shares["window_attention"] == pytest.approx(15.9, abs=0.1)
    assert shares["full_attention"] == pytest.approx(9.1, abs=0.1)
    assert shares["head"] == pytest.approx(13.9, abs=0.1)
    # what landed is what counts: half the rows, half the operations
    half = work_afmoe.afmoe_train_flops(arch, 2, 8192, [8192.0] * 4)
    assert half["routed_experts"] * 2 == f["routed_experts"]


def test_kernel_rooflines_by_hand():
    arch, chip = config(), chips.chip_for("TPU v5 lite")
    # forward, one row of a full layer: 2 products x 2 x 128 x 32 x pairs
    assert work_afmoe.attention_flops(8192, 32, 128, None, False) \
        == 4 * 128 * 32 * 33_558_528
    # q and o at 32 heads, k and v at 4; bf16
    assert work_afmoe.attention_bytes(8192, 32, 4, 128, False) \
        == (2 * 32 + 2 * 4) * 8192 * 128 * 2
    assert work_afmoe.attention_bytes(8192, 32, 4, 128, True) \
        == (5 * 32 + 4 * 4) * 8192 * 128 * 2
    least, bound = work_afmoe.attention_seconds(arch, 2, 8192, chip)
    by_hand = 2 * 6 * 2 * 128 * 32 * (4 * 14_681_088 + 33_558_528) / 197e12
    assert least == pytest.approx(by_hand, rel=1e-9)
    assert set(bound.values()) == {"compute"}
    # 16384 assignments: 3 products of 2048 x 1024, 2 ops forward, 4 back
    assert work_afmoe.expert_flops(arch, 16384, False) \
        == 2 * 3 * 2048 * 1024 * 16384
    assert work_afmoe.expert_bytes(arch, 16384, False) \
        == 2 * (3 * 16 * 2048 * 1024 + 16384 * (2 * 2048 + 4 * 1024))
    least, bound = work_afmoe.expert_seconds(arch, [16384.0] * 4, chip)
    assert least == pytest.approx(
        4 * 6 * 3 * 2048 * 1024 * 16384 / 197e12, rel=1e-9)
    # a near-empty layer is bound by reading its weights
    assert work_afmoe.expert_seconds(arch, [16.0], chip)[1]["forward"] \
        == "memory"


# ---------------------------------------------------------------- readers

def fixture():
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "afmoe_step.json")) as f:
        fix = json.load(f)
    chip = trace.Chip(ops=[tuple(o) for o in fix["ops"]],
                      modules=[("jit_full_step", 1000, 3400)],
                      kernels=fix["kernels"])
    tr = trace.Trace([chip], [(trace.WINDOW_SPAN, *fix["window"])])
    cell = {"config": config(), "chips": 1,
            "traffic": run_mod.load_json("benchmark", "traffic",
                                         "steps-2x8192.json")}
    load = np.full((4, 128), 1024.0)
    load[0, 3] = 1536.0
    obs = {"steps": 1, "op_scopes": fix["op_scopes"], "expert_load": load,
           "landed_by_layer": [float(v) for v in load[:, :16].sum(-1)]}
    return tr, cell, obs, fix


def read(metric, tr, obs, cell):
    said = []
    value = run_mod.load_module("layer_metrics", metric).read(
        tr, obs, cell, chips.chip_for("TPU v5 lite"), said.append)
    return value, said


def test_matchers_find_the_kernels_by_shape():
    tr, cell, obs, fix = fixture()
    attn = afmoe_trace.attention_matcher(tr, cell)
    gmm = afmoe_trace.gmm_matcher(tr, cell)
    names = [n for n, _, _ in tr.chips[0].ops]
    assert [n for n in names if attn(n)] == [
        "splash_mqa_fwd_residuals.10", "splash_mqa_dkv_no_residuals.9"]
    assert [n for n in names if gmm(n)] == ["gmm.24", "tgmm"]
    assert tr.op_seconds(attn) == pytest.approx(1300e-9)
    assert tr.op_seconds(gmm) == pytest.approx(500e-9)


def test_attention_readers_on_the_fixture():
    tr, cell, obs, _ = fixture()
    chip = chips.chip_for("TPU v5 lite")
    least, _ = work_afmoe.attention_seconds(cell["config"], 2, 8192, chip)
    value, said = read("swa_attn_roofline", tr, obs, cell)
    assert value == pytest.approx(100 * least / 1300e-9)
    assert "2 events" in said[0]
    value, _ = read("swa_attn_time_pct", tr, obs, cell)
    assert value == pytest.approx(100 * 1300 / 2300)     # busy: 2300 ns


def test_expert_readers_on_the_fixture():
    tr, cell, obs, fix = fixture()
    chip = chips.chip_for("TPU v5 lite")
    least, _ = work_afmoe.expert_seconds(
        cell["config"], [16896.0, 16384.0, 16384.0, 16384.0], chip)
    value, _ = read("moe_gmm_roofline", tr, obs, cell)
    assert value == pytest.approx(100 * least / 500e-9)
    # router + experts + shared_expert fusions and the two grouped kernels
    value, said = read("moe_time_pct", tr, obs, cell)
    assert value == pytest.approx(100 * (300 + 200 + 3 * 100) / 2300)
    assert "experts" in said[0] and "router" in said[0]
    value, _ = read("expert_load_max_over_mean", tr, obs, cell)
    assert value == pytest.approx(1536 / (1024 + 512 / 64))


@pytest.mark.parametrize("metric", [
    "swa_attn_roofline", "swa_attn_time_pct", "moe_gmm_roofline",
    "moe_time_pct", "expert_load_max_over_mean"])
def test_readers_return_nothing_where_there_is_nothing(metric):
    """On the parent's program and cells: a trace with other kernels, no
    table of scopes, no counts."""
    tr, cell, _, _ = fixture()
    chip = trace.Chip(ops=[("fusion.1", 1000, 1100)],
                      modules=[("jit_full_step", 1000, 1100)], kernels={})
    bare = trace.Trace([chip], tr.spans)
    assert read(metric, bare, {"steps": 3}, cell)[0] is None
    assert read(metric, None, {}, cell)[0] is None


# -------------------------------------------------------------- rehearsal

# set as the real cells' are: over what the program reads at this size on
# the CPU in bf16 (loss gaps to 9e-4, gradient norms to 0.02 on the seeds
# tried) and under what the planted faults read (half the batch 0.4, an
# unchanged state 1)
TINY_LIMITS = {"loss_gap_step1": 3e-3, "loss_gap_step2": 3e-3,
               "grad_norm_gap": 0.06, "router_grad_norm_gap": 0.2,
               "change_norm_gap": 0.3,
               "expert_load_gap": 0.05, "attention_backend_differs": 0}


def add_tiny_afmoe_cell(root, cell="train-afmoe-tiny"):
    def dump(obj, *parts):
        with open(os.path.join(root, *parts), "w") as f:
            json.dump(obj, f)
    conf = dict(config(), name="afmoe-tiny", **TINY)
    conf.update(hidden_size=64, head_dim=16, num_hidden_layers=3,
                layers_kept=[0, 1, 3], layer_types=config()["layer_types"],
                vocab_size=256, sliding_window=8, initializer_range=0.02)
    conf["job"] = dict(conf["job"], attention_backend="xla",
                       fused_loss_chunk=16)
    dump(conf, "benchmark", "configs", "afmoe-tiny.json")
    dump({"driver": "train_steps_afmoe", "batch": 2, "seq": 32,
          "log_every": 5, "warmup_steps": 3, "compare_steps": 2,
          "trace_seconds": 1}, "benchmark", "traffic", "steps-tiny-afmoe.json")
    dump(TINY_LIMITS, "benchmark", "limits", cell + ".json")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "afmoe-tiny", "source": "test",
                             "file": "benchmark/configs/afmoe-tiny.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({"name": cell, "config": "afmoe-tiny",
                               "traffic": "steps-tiny-afmoe", "chips": 1,
                               "why": "rehearsal"})
    for m in bench["per_layer"]:
        if "train-trinity-mini-8k" in m.get("workloads", ()):
            m["workloads"].append(cell)
    dump(bench, "BENCHMARK.json")


@pytest.mark.parametrize("fault", ["none", "half_batch", "state_unchanged"])
def test_rehearse_the_new_driver(tmp_path, fault):
    """``run.py`` finds the new driver, configuration family, work counts
    and readers by name and runs them at toy sizes on the CPU; a timed
    path broken underneath reads ``correct`` false."""
    root = make_tree(str(tmp_path))
    add_tiny_afmoe_cell(root)
    rc, result, err = run_cell(root, "train-afmoe-tiny", 2**31 + 77,
                               fault=fault)
    assert rc == 0, err
    assert result["correct"] is (fault == "none"), err
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"expert_load_gap", "router_grad_norm_gap"} <= set(
        result["compared"])
    assert "xla_ragged_dot" in err and "experts_held" in err


def test_the_cells_files_say_what_the_issue_asks():
    arch = config()
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "trinity-mini-5l-ep8")
    assert entry["reduced"] == arch["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    pub = arch["published"]
    for key, value in pub.items():      # every published key, unchanged
        if key not in arch["reduced"]:
            assert arch[key] == value, key
    assert (arch["num_hidden_layers"], arch["num_dense_layers"],
            arch["num_experts"], arch["vocab_size"]) == (5, 1, 16, 25024)
    assert R.layer_kinds(arch) == ("sliding_attention",) * 4 + (
        "full_attention",)
    shapes = R.leaf_shapes(arch)
    n = sum(int(np.prod(s)) for s in shapes.values())
    assert n == 705_473_792             # 705M: ISSUE 30's arithmetic
