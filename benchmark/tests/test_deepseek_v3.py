"""The deepseek_v3 family's benchmark files: the reference against a
two-layer case written out by hand, ``work_deepseek_v3.py`` against sums by
hand, each new reader on a made-up trace (the kernels' operand shapes as the
chip's compiler writes them for the cell's sizes, ``op_name`` paths as
``TrainStep.op_scopes()`` gives them, made-up times), a CPU rehearsal of
the new driver through ``run.py`` at toy sizes, and what the cell's files
must say."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rehearsal import REPO, make_tree, run_cell
from benchmark import (chips, deepseek_v3_trace, run as run_mod, trace,
                       work_deepseek_v3 as W)
from benchmark.drivers.train_steps_deepseek_v3 import compare
from benchmark.reference import deepseek_v3 as R

jax.config.update("jax_default_matmul_precision", "highest")

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def config(name="kanana-2-30b-6l-ep8"):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


TINY = dict(hidden_size=32, num_attention_heads=4, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            intermediate_size=48, moe_intermediate_size=12,
            n_shared_experts=2, n_routed_experts=4,
            n_routed_experts_published=8, expert_offset=2,
            num_experts_per_tok=3, vocab_size=64, num_hidden_layers=2,
            first_k_dense_replace=1, rms_norm_eps=1e-6, rope_theta=1000000,
            norm_topk_prob=True, routed_scaling_factor=2.448,
            bias_update_rate=0.001, initializer_range=0.1,
            max_position_embeddings=64)
JOB = dict(compute_dtype="float32", master_weights=True, learning_rate=1e-3,
           beta1=0.9, beta2=0.999, epsilon=1e-8, weight_decay=0.01)


# ------------------------------------------------------------- reference

def _by_hand(w, ids, bias):
    """Two layers (dense MLP, then experts) and the loss, token by token
    and head by head in numpy float64, from the equations of the
    reference's docstring."""
    w = {n: np.asarray(v, np.float64) for n, v in w.items()}
    rms = lambda x, g: x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * g
    silu = lambda x: x / (1 + np.exp(-x))
    sig = lambda x: 1 / (1 + np.exp(-x))
    B, S = ids.shape

    def rope(t):                        # [S, heads, 4]: two pairs
        out = np.empty_like(t)
        for p in range(S):
            for i in range(2):
                a = p * 1e6 ** (-i / 2)
                t0, t1 = t[p, :, 2 * i], t[p, :, 2 * i + 1]
                out[p, :, 2 * i] = t0 * np.cos(a) - t1 * np.sin(a)
                out[p, :, 2 * i + 1] = t1 * np.cos(a) + t0 * np.sin(a)
        return out
    total, counts = 0.0, np.zeros(8)
    for b in range(B):
        x = w["wte"][ids[b]]
        for layer in range(2):
            h = rms(x, w["ln1_g"][layer])
            q = (h @ w["q_w"][layer]).reshape(S, 4, 12)
            kva = h @ w["kva_w"][layer]
            c, k_pe = rms(kva[:, :16], w["kv_norm_g"][layer]), kva[:, 16:]
            kvb = (c @ w["kvb_w"][layer]).reshape(S, 4, 16)
            q_pe, k_pe = rope(q[:, :, 8:]), rope(k_pe[:, None, :])
            ctx = np.zeros((S, 4, 8))
            for hd in range(4):
                qh = np.concatenate([q[:, hd, :8], q_pe[:, hd]], -1)
                kh = np.concatenate([kvb[:, hd, :8], k_pe[:, 0]], -1)
                for i in range(S):
                    sc = qh[i] @ kh[:i + 1].T / np.sqrt(12)
                    p = np.exp(sc - sc.max())
                    ctx[i, hd] = (p / p.sum()) @ kvb[:i + 1, hd, 8:]
            x = x + ctx.reshape(S, 32) @ w["o_w"][layer]
            y = rms(x, w["ln2_g"][layer])
            if layer == 0:
                m = (silu(y @ w["mlp_w1"][0]) * (y @ w["mlp_w3"][0])) \
                    @ w["mlp_w2"][0]
            else:
                m = (silu(y @ w["sh_w1"][0]) * (y @ w["sh_w3"][0])) \
                    @ w["sh_w2"][0]
                s = sig(y @ w["router_w"][0])
                for t in range(S):
                    sel = np.argsort(-(s[t] + bias))[:3]
                    wt = s[t, sel] / (s[t, sel].sum() + 1e-20) * 2.448
                    for e, we in zip(sel, wt):
                        counts[e] += 1
                        if 2 <= e < 6:      # the experts held here
                            j = e - 2
                            m[t] += we * ((silu(y[t] @ w["exp_w1"][0, j])
                                           * (y[t] @ w["exp_w3"][0, j]))
                                          @ w["exp_w2"][0, j])
            x = x + m
        logits = rms(x, w["lnf_g"])[:-1] @ w["head_w"]
        logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        total -= logp[np.arange(S - 1), ids[b, 1:]].sum()
    return total / (B * (S - 1)), counts


def test_reference_matches_two_layers_by_hand():
    ids = np.random.default_rng(3).integers(0, 64, (2, 8))
    w = R.init_params(TINY, 11, jnp.float32)
    bias = np.asarray([0.2, -0.1, 0, 0.3, 0, -0.2, 0.1, 0], np.float32)
    loss, counts = R.loss_whole(w, jnp.asarray(bias)[None], jnp.asarray(ids),
                                TINY)
    want, want_counts = _by_hand(w, ids, bias.astype(np.float64))
    # float32 highest against float64: rounding alone
    assert float(loss) == pytest.approx(want, rel=2e-6)
    assert np.asarray(counts)[0].tolist() == want_counts.tolist()


def test_attention_in_blocks_is_attention_whole():
    """Blocks of 4 queries give what one block of all 16 gives, forward
    and backward, with v narrower than q and k."""
    rng = np.random.default_rng(6)
    q, k = (jnp.asarray(rng.standard_normal((16, 2, 12)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((16, 2, 8)), jnp.float32)

    def run(q_block):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(jnp.sin(
            R._attention_row(q, k, v, 12 ** -0.5, q_block))), (0, 1, 2))(
                q, k, v)
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
    gaps = compare(R.train_readings(TINY, JOB, 13, ids, **kw), ref)
    assert max(gaps["grad_norm_gap"], gaps["loss_gap_step1"] * 100) > 0.005
    same = compare(ref, ref)
    assert same["grad_norm_gap"] == same["router_grad_norm_gap"] == 0.0
    with pytest.raises(ValueError):
        R.settings(TINY, "no_such_fault")


# ------------------------------------------------------------ work counts

def test_work_counts_by_hand():
    arch = config()
    p = W.matmul_params(arch)
    # a layer's MLA: q 2048 x 6144, kv_a 2048 x 576, kv_b 512 x 8192,
    # o 4096 x 2048
    assert p["mla_projections"] == 6 * (2048 * 6144 + 2048 * 576
                                        + 512 * 8192 + 4096 * 2048)
    assert p["dense_mlp"] == 3 * 2048 * 6144
    assert p["shared_experts"] == 5 * 3 * 2048 * 1536
    assert p["router"] == 5 * 2048 * 128
    assert p["head"] == 2048 * 16032 and p["one_expert"] == 4_718_592
    f = W.train_flops(arch, batch=2, seq=8192)
    assert f["mla_projections"] == 6 * p["mla_projections"] * 16384
    assert f["head"] == 6 * p["head"] * 2 * 8191
    # an even routing lands 16384 x 6 x 16 / 128 assignments a layer
    assert f["routed_experts"] == 6 * 4_718_592 * 5 * 12288
    pairs = 8192 * 8193 // 2
    # a pair and head: forward 2 x (192 + 128), backward 2 x (384 + 256)
    assert f["attention"] == 6 * 2 * 32 * pairs * (2 * 320 + 2 * 640)
    shares = {k: 100 * v / f["total"] for k, v in f.items()}
    assert f["total"] == pytest.approx(53.7e12, rel=2e-3)       # ISSUE 34
    assert shares["attention"] == pytest.approx(46.1, abs=0.1)
    assert shares["mla_projections"] == pytest.approx(28.9, abs=0.1)
    assert shares["shared_experts"] == pytest.approx(8.6, abs=0.1)
    assert shares["dense_mlp"] == pytest.approx(6.9, abs=0.1)
    assert shares["head"] == pytest.approx(6.0, abs=0.1)
    assert shares["routed_experts"] == pytest.approx(3.2, abs=0.1)
    # what landed is what counts: half the rows, half the operations
    half = W.train_flops(arch, 2, 8192, [6144.0] * 5)
    assert half["routed_experts"] * 2 == f["routed_experts"]


def test_kernel_roofline_by_hand():
    arch, chip = config(), chips.chip_for("TPU v5 lite")
    pairs = 8192 * 8193 // 2
    assert W.attention_flops(8192, 32, 192, 128, False) \
        == 2 * (192 + 128) * 32 * pairs
    assert W.attention_flops(8192, 32, 192, 128, True) \
        == 2 * (2 * 192 + 2 * 128) * 32 * pairs
    # q, k at 192 columns and v, o at 128; bf16
    assert W.attention_bytes(8192, 32, 192, 128, False) \
        == (2 * 192 + 2 * 128) * 32 * 8192 * 2
    assert W.attention_bytes(8192, 32, 192, 128, True) \
        == (4 * 192 + 4 * 128) * 32 * 8192 * 2
    least, bound = W.attention_seconds(arch, 2, 8192, chip)
    assert least == pytest.approx(
        6 * 2 * 32 * pairs * 2 * (320 + 640) / 197e12, rel=1e-9)
    assert set(bound.values()) == {"compute"}


# ---------------------------------------------------------------- readers

_FWD = ("%splash_mha_fwd_residuals.3 = (f32[2,1024,128]{2,1,0}, "
        "bf16[2,32,8192,128]{3,2,1,0}, f32[2,32,8192,128]{3,2,1,0}) "
        "custom-call(%a, %b, %q, %k, %v), custom_call_target="
        '"tpu_custom_call", operand_layout_constraints={s8[1,4,8]{2,1,0}, '
        "bf16[2,32,8192,192]{3,2,1,0}, bf16[2,32,8192,192]{3,2,1,0}, "
        "bf16[2,32,8192,128]{3,2,1,0}, s32[8192,128]{1,0}}")
_BWD = ("%splash_mha_dkv_no_residuals.2 = (bf16[2,32,8,8192,192]{4,3,2,1,0}, "
        "bf16[2,32,8192,192]{3,2,1,0}, bf16[2,32,8192,128]{3,2,1,0}) "
        'custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", '
        "operand_layout_constraints={bf16[2,32,8192,192]{3,2,1,0}, "
        "bf16[2,32,8192,192]{3,2,1,0}, bf16[2,32,8192,128]{3,2,1,0}}")
_GMM = ("%gmm.7 = bf16[36864,768]{1,0} custom-call(%x, %w), "
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        "{bf16[36864,2048]{1,0}, bf16[16,2048,768]{2,1,0}}")
_HEAD128 = ("%splash_mha_fwd_residuals.9 = (bf16[2,32,8192,128]{3,2,1,0}) "
            'custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", '
            "operand_layout_constraints={bf16[2,32,8192,128]{3,2,1,0}, "
            "bf16[2,32,8192,128]{3,2,1,0}, bf16[2,32,8192,128]{3,2,1,0}}")
_PRE = "jit(full_step)/jvp(deepseekv3forcausallm)/model/block_1/"


def fixture():
    ops = [("splash_mha_fwd_residuals.3", 1000, 1400),
           ("splash_mha_dkv_no_residuals.2", 1400, 2300),
           ("gmm.7", 2300, 2500), ("fusion.1", 2500, 2600),
           ("fusion.2", 2600, 2750), ("fusion.3", 2750, 2800),
           ("copy.4", 2800, 2900), ("fusion.5", 2900, 3200),
           ("fusion.6", 3200, 3300)]
    chip = trace.Chip(ops=ops, modules=[("jit_full_step", 1000, 3300)],
                      kernels={"splash_mha_fwd_residuals.3": _FWD,
                               "splash_mha_dkv_no_residuals.2": _BWD,
                               "gmm.7": _GMM})
    tr = trace.Trace([chip], [(trace.WINDOW_SPAN, 900, 3400)])
    cell = {"config": config(), "chips": 1,
            "traffic": run_mod.load_json("benchmark", "traffic",
                                         "steps-2x8192-mla.json")}
    scopes = {
        "splash_mha_fwd_residuals.3": _PRE + "attn/vmap(jit(_splash))/"
        "pallas_call",
        "splash_mha_dkv_no_residuals.2": "jit(full_step)/transpose(jvp("
        "deepseekv3forcausallm))/model/block_1/attn/pallas_call",
        "gmm.7": _PRE + "mlp/experts/cond/branch_1_fun/pallas_call",
        "fusion.1": _PRE + "attn/q_proj/linear/dot_general",
        "fusion.2": _PRE + "attn/kv_b_proj/linear/dot_general",
        "fusion.3": _PRE + "attn/kv_a_norm/mul",
        "copy.4": _PRE + "attn/mla_join/concatenate",
        "fusion.5": _PRE + "mlp/shared_expert/gate_proj/dot_general",
        "fusion.6": "jit(full_step)/transpose(jvp(deepseekv3forcausallm))/"
        "model/block_1/attn/o_proj/linear/dot_general"}
    return tr, cell, {"steps": 1, "op_scopes": scopes}


def read(metric, tr, obs, cell):
    said = []
    value = run_mod.load_module("layer_metrics", metric).read(
        tr, obs, cell, chips.chip_for("TPU v5 lite"), said.append)
    return value, said


def test_matcher_finds_the_kernels_by_shape():
    tr, cell, _ = fixture()
    attn = deepseek_v3_trace.attention_matcher(tr, cell)
    names = [n for n, _, _ in tr.chips[0].ops]
    assert [n for n in names if attn(n)] == [
        "splash_mha_fwd_residuals.3", "splash_mha_dkv_no_residuals.2"]
    # q and k padded to 256 are the same kernels; heads of 128 all round
    # (another model's call) are not
    padded = trace.Chip(ops=tr.chips[0].ops, modules=tr.chips[0].modules,
                        kernels={"splash_mha_fwd_residuals.3":
                                 _FWD.replace("8192,192]", "8192,256]"),
                                 "splash_mha_fwd_residuals.9": _HEAD128})
    attn = deepseek_v3_trace.attention_matcher(
        trace.Trace([padded], tr.spans), cell)
    assert attn("splash_mha_fwd_residuals.3")
    assert not attn("splash_mha_fwd_residuals.9") and not attn("gmm.7")


def test_readers_on_the_fixture():
    tr, cell, obs = fixture()
    chip = chips.chip_for("TPU v5 lite")
    least, _ = W.attention_seconds(cell["config"], 2, 8192, chip)
    value, said = read("mla_attn_roofline", tr, obs, cell)
    assert value == pytest.approx(100 * least / 1300e-9)
    assert "2 events" in said[0]
    value, _ = read("mla_attn_time_pct", tr, obs, cell)
    assert value == pytest.approx(100 * 1300 / 2300)     # busy: 2300 ns
    # q_proj 100 + kv_b_proj 150 + kv_a_norm 50 + the join's copy 100 +
    # o_proj backward 100; the kernels and the expert layer are not in it
    value, said = read("mla_proj_time_pct", tr, obs, cell)
    assert value == pytest.approx(100 * 500 / 2300)
    assert said[0].endswith("attn 0.0000, kv_a_norm 0.0000, kv_b_proj "
                            "0.0000, o_proj 0.0000, q_proj 0.0000")


@pytest.mark.parametrize("metric", ["mla_attn_roofline", "mla_attn_time_pct",
                                    "mla_proj_time_pct"])
def test_readers_return_nothing_where_there_is_nothing(metric):
    """On the parent's program and cells: a trace with other kernels, no
    table of scopes."""
    tr, cell, _ = fixture()
    chip = trace.Chip(ops=[("fusion.1", 1000, 1100)],
                      modules=[("jit_full_step", 1000, 1100)],
                      kernels={"splash_mha_fwd_residuals.9": _HEAD128})
    bare = trace.Trace([chip], tr.spans)
    assert read(metric, bare, {"steps": 3}, cell)[0] is None
    assert read(metric, None, {}, cell)[0] is None


# -------------------------------------------------------------- rehearsal

# set as the real cells' are: over what the program reads at this size on
# the CPU in bf16 and under what the planted faults read (half the batch,
# an unchanged state 1)
TINY_LIMITS = {"loss_gap_step1": 3e-3, "loss_gap_step2": 3e-3,
               "grad_norm_gap": 0.06, "router_grad_norm_gap": 0.2,
               "change_norm_gap": 0.3,
               "expert_load_gap": 0.05, "attention_backend_differs": 0}


def add_tiny_cell(root, cell="train-mla-tiny"):
    def dump(obj, *parts):
        with open(os.path.join(root, *parts), "w") as f:
            json.dump(obj, f)
    conf = dict(config(), name="mla-tiny", **TINY)
    conf.update(hidden_size=64, num_hidden_layers=3, vocab_size=256,
                initializer_range=0.02)
    conf["job"] = dict(conf["job"], attention_backend="xla",
                       fused_loss_chunk=16)
    dump(conf, "benchmark", "configs", "mla-tiny.json")
    dump({"driver": "train_steps_deepseek_v3", "batch": 2, "seq": 32,
          "log_every": 5, "warmup_steps": 3, "compare_steps": 2,
          "trace_seconds": 1}, "benchmark", "traffic", "steps-tiny-mla.json")
    dump(TINY_LIMITS, "benchmark", "limits", cell + ".json")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "mla-tiny", "source": "test",
                             "file": "benchmark/configs/mla-tiny.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({"name": cell, "config": "mla-tiny",
                               "traffic": "steps-tiny-mla", "chips": 1,
                               "why": "rehearsal"})
    for m in bench["per_layer"]:
        if "train-kanana-2-8k" in m.get("workloads", ()):
            m["workloads"].append(cell)
    dump(bench, "BENCHMARK.json")


@pytest.mark.parametrize("fault", ["none", "half_batch", "state_unchanged"])
def test_rehearse_the_new_driver(tmp_path, fault):
    """``run.py`` finds the new driver, configuration family, work counts
    and readers by name and runs them at toy sizes on the CPU; a timed
    path broken underneath reads ``correct`` false."""
    root = make_tree(str(tmp_path))
    add_tiny_cell(root)
    rc, result, err = run_cell(root, "train-mla-tiny", 2**31 + 77,
                               fault=fault)
    assert rc == 0, err
    assert result["correct"] is (fault == "none"), err
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"expert_load_gap", "router_grad_norm_gap"} <= set(
        result["compared"])
    assert "xla_ragged_dot" in err and "tiling" in err
    assert "'head_dim_qk': 12" in err and "'head_dim_v': 8" in err


def test_the_parent_has_no_such_cell():
    """``run.py`` on a benchmark without the entry fails at once."""
    bench = {"workloads": [], "configs": []}
    with pytest.raises(SystemExit, match="no workload"):
        run_mod.find_cell(bench, "train-kanana-2-8k")


def test_the_cells_files_say_what_the_issue_asks():
    arch = config()
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "kanana-2-30b-6l-ep8")
    assert entry["reduced"] == arch["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert len(entry["source"]) <= 200 and entry["file"].endswith(
        "kanana-2-30b-6l-ep8.json")
    pub = arch["published"]
    for key, value in pub.items():      # every published key, unchanged
        if key not in arch["reduced"]:
            assert arch[key] == value, key
    assert (arch["num_hidden_layers"], arch["n_routed_experts"],
            arch["vocab_size"]) == (6, 16, 16032)
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (48, 128, 128256)
    # every published width
    assert (arch["hidden_size"], arch["num_attention_heads"],
            arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
            arch["v_head_dim"], arch["kv_lora_rank"],
            arch["intermediate_size"], arch["moe_intermediate_size"],
            arch["n_shared_experts"], arch["n_routed_experts_published"],
            arch["num_experts_per_tok"]) == (
        2048, 32, 128, 64, 128, 512, 6144, 768, 2, 128, 6)
    assert "8 chips" in arch["deployment"] and arch["assumed"]
    cell = next(w for w in bench["workloads"]
                if w["name"] == "train-kanana-2-8k")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kanana-2-30b-6l-ep8", "steps-2x8192-mla", 1)
    shapes = R.leaf_shapes(arch)
    n = sum(int(np.prod(s)) for s in shapes.values())
    # ISSUE 34 counted 687,502,976: with the 128 entries of each expert
    # layer's balancing bias, which is a buffer here and no parameter
    assert n == 687_502_336 and n + 5 * 128 == 687_502_976


def test_published_is_the_catalogs_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    arch = config()
    assert arch["published"] == row["config"]
    assert arch["source"].startswith(row["source_url"])
