"""The SmallThinker family's benchmark files: the reference against a
two-layer case written out by hand, ``work_smallthinker.py`` against sums by
hand and against a brute-force count at a small size, each new reader on a
recorded step (the kernels' operand shapes as the chip's compiler writes them
for the cell's sizes, ``op_name`` paths as ``TrainStep.op_scopes()`` gives
them, made-up times), a CPU rehearsal of the new driver through ``run.py`` at
toy sizes, and what the cell's files must say."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rehearsal import REPO, make_tree, run_cell
from benchmark import (chips, run as run_mod, smallthinker_trace, trace,
                       work_smallthinker as W)
from benchmark.drivers.train_steps_smallthinker import compare
from benchmark.reference import smallthinker as R

jax.config.update("jax_default_matmul_precision", "highest")

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "train-smallthinker-16k"


def config(name="smallthinker-21b-4l-ep4"):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


TINY = dict(hidden_size=32, num_attention_heads=7, num_key_value_heads=1,
            head_dim=4, moe_ffn_hidden_size=12, moe_num_primary_experts=4,
            moe_num_primary_experts_published=8, expert_offset=2,
            moe_num_active_primary_experts=3,
            moe_primary_router_apply_softmax=True, norm_topk_prob=True,
            vocab_size=64, num_hidden_layers=2, rope_layout=[0, 1, 1, 1],
            sliding_window_layout=[0, 1, 1, 1], layers_kept=[0, 1],
            sliding_window_size=3, rms_norm_eps=1e-6, rope_theta=1500000,
            initializer_range=0.1, max_position_embeddings=64)
JOB = dict(compute_dtype="float32", master_weights=True, learning_rate=1e-3,
           beta1=0.9, beta2=0.999, epsilon=1e-8, weight_decay=0.01)


# ------------------------------------------------------------- reference

def _by_hand(w, ids):
    """Two layers (full without positions, then a window of 3 with RoPE)
    and the loss, token by token and head by head in numpy float64, from
    the equations of the reference's docstring."""
    w = {n: np.asarray(v, np.float64) for n, v in w.items()}
    rms = lambda x, g: x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * g
    B, S = ids.shape

    def rope(t):                        # [S, heads, 4]: pairs (0, 2), (1, 3)
        out = np.empty_like(t)
        for p in range(S):
            for i in range(2):
                a = p * 1.5e6 ** (-i / 2)
                t0, t1 = t[p, :, i], t[p, :, i + 2]
                out[p, :, i] = t0 * np.cos(a) - t1 * np.sin(a)
                out[p, :, i + 2] = t1 * np.cos(a) + t0 * np.sin(a)
        return out
    total, counts = 0.0, np.zeros((2, 8))
    for b in range(B):
        x = w["wte"][ids[b]]
        for layer in range(2):
            logits = x @ w["router_w"][layer]       # the block's input
            h = rms(x, w["ln1_g"][layer])
            q = (h @ w["q_w"][layer]).reshape(S, 7, 4)
            k = (h @ w["k_w"][layer]).reshape(S, 1, 4)
            v = (h @ w["v_w"][layer]).reshape(S, 1, 4)
            if layer == 1:
                q, k = rope(q), rope(k)
            ctx = np.zeros((S, 7, 4))
            for hd in range(7):
                for i in range(S):
                    lo = max(0, i - 2) if layer == 1 else 0
                    sc = q[i, hd] @ k[lo:i + 1, 0].T / 2.0
                    p = np.exp(sc - sc.max())
                    ctx[i, hd] = (p / p.sum()) @ v[lo:i + 1, 0]
            x = x + ctx.reshape(S, 28) @ w["o_w"][layer]
            y = rms(x, w["ln2_g"][layer])
            m = np.zeros_like(x)
            for t in range(S):
                sel = np.argsort(-logits[t])[:3]
                e_ = np.exp(logits[t, sel] - logits[t, sel].max())
                for e, we in zip(sel, e_ / e_.sum()):
                    counts[layer, e] += 1
                    if 2 <= e < 6:      # the experts held here
                        j = e - 2
                        m[t] += we * ((np.maximum(
                            y[t] @ w["exp_w1"][layer, j], 0)
                            * (y[t] @ w["exp_w3"][layer, j]))
                            @ w["exp_w2"][layer, j])
            x = x + m
        lg = rms(x, w["lnf_g"])[:-1] @ w["head_w"]
        logp = lg - np.log(np.exp(lg).sum(-1, keepdims=True))
        total -= logp[np.arange(S - 1), ids[b, 1:]].sum()
    return total / (B * (S - 1)), counts


def test_reference_matches_two_layers_by_hand():
    ids = np.random.default_rng(3).integers(0, 64, (2, 8))
    w = R.init_params(TINY, 11, jnp.float32)
    loss, counts = R.loss_whole(w, jnp.asarray(ids), TINY)
    want, want_counts = _by_hand(w, ids)
    # float32 highest against float64: rounding alone
    assert float(loss) == pytest.approx(want, rel=2e-6)
    assert np.asarray(counts).tolist() == want_counts.tolist()


def test_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(REPO, "benchmark", "reference", "smallthinker.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    mods = [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    mods += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    assert not [m for m in mods if m and m.startswith("paddle_tpu")]


def test_layer_by_layer_step_is_the_whole_models_gradient():
    ids = np.random.default_rng(4).integers(0, 64, (2, 8))
    w = R.init_params(TINY, 12, jnp.float32)
    (loss, counts), grads = jax.value_and_grad(
        lambda p: R.loss_whole(p, jnp.asarray(ids), TINY), has_aux=True)(w)
    got = R.train_readings(TINY, JOB, 12, [ids])
    assert got["losses"][0] == pytest.approx(float(loss), rel=1e-6)
    assert np.array_equal(got["expert_load"], np.asarray(counts))
    for n, g in grads.items():
        np.testing.assert_allclose(
            got["grad_norms"][n],
            np.asarray(R.leaf_norms(g, n, n in R.LAYER_NAMES, (2, 4))),
            rtol=2e-5)


@pytest.mark.parametrize("fault", R.FAULTS + ("half_batch", "fp8"))
def test_every_fault_and_the_control_move_the_readings(fault):
    ids = [np.random.default_rng(5).integers(0, 64, (2, 8))] * 2
    ref = R.train_readings(TINY, JOB, 13, ids)
    kw = {"half_batch": dict(half_batch=True),
          "fp8": dict(precision="fp8")}.get(fault, dict(fault=fault))
    gaps = compare(R.train_readings(TINY, JOB, 13, ids, **kw), ref)
    assert max(gaps["grad_norm_gap"], gaps["router_grad_norm_gap"],
               gaps["loss_gap_step1"] * 100) > 0.005
    same = compare(ref, ref)
    assert same["grad_norm_gap"] == same["router_grad_norm_gap"] == 0.0
    with pytest.raises(ValueError):
        R.settings(TINY, "no_such_fault")


def test_half_of_one_row_is_its_first_half():
    """The cell's batch is one row: ``half_batch`` leaves out the second
    half of its positions."""
    ids = np.random.default_rng(6).integers(0, 64, (1, 16))
    half = R.train_readings(TINY, JOB, 14, [ids], half_batch=True)
    first = R.train_readings(TINY, JOB, 14, [ids[:, :8]])
    assert half["losses"] == first["losses"]


# ------------------------------------------------------------ work counts

def test_parameter_count_and_what_the_files_say():
    arch = config()
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "smallthinker-21b-4l-ep4")
    assert entry["reduced"] == arch["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["file"].endswith("smallthinker-21b-4l-ep4.json")
    pub = arch["published"]
    for key, value in pub.items():      # every published key, unchanged
        if key not in arch["reduced"]:
            assert arch[key] == value, key
    assert (arch["num_hidden_layers"], arch["moe_num_primary_experts"],
            arch["vocab_size"]) == (4, 16, 37984)
    assert (pub["num_hidden_layers"], pub["moe_num_primary_experts"],
            pub["vocab_size"]) == (52, 64, 151936)
    # every published width
    assert (arch["hidden_size"], arch["num_attention_heads"],
            arch["num_key_value_heads"], arch["head_dim"],
            arch["moe_ffn_hidden_size"],
            arch["moe_num_primary_experts_published"],
            arch["moe_num_active_primary_experts"],
            arch["sliding_window_size"], arch["rope_theta"]) == (
        2560, 28, 4, 128, 768, 64, 6, 4096, 1500000)
    assert R.layer_layouts(arch) == ((0, 0), (1, 1), (1, 1), (1, 1))
    assert "4 chips" in arch["deployment"] and arch["assumed"]
    assert arch["family"] == "smallthinker"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-4l-ep4", "steps-1x16384", 1)
    assert len(cell["why"]) <= 200
    mine = [m for m in bench["per_layer"] if m["name"].startswith("st_")]
    assert [m["name"] for m in mine] == [
        "st_attn_roofline", "st_attn_time_pct", "st_moe_gmm_roofline",
        "st_moe_time_pct", "st_expert_load_max_over_mean"]
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "train_tokens_per_s" for m in mine)
    traffic = run_mod.load_json("benchmark", "traffic", "steps-1x16384.json")
    assert (traffic["driver"], traffic["batch"], traffic["seq"]) == (
        "train_steps_smallthinker", 1, 16384)
    shapes = R.leaf_shapes(arch)
    n = sum(int(np.prod(s)) for s in shapes.values())
    layer = 2 * 2560 * 3584 + 2 * 2560 * 512 + 2560 * 64 \
        + 16 * 3 * 2560 * 768 + 2 * 2560
    assert layer == 115_512_320
    assert n == 4 * layer + 2 * 37984 * 2560 + 2560 == 656_529_920
    assert round(n / 1e6, 1) == 656.5                   # ISSUE 36


def test_the_start_draws_each_leaf_at_its_own_range():
    """``start_ranges``: the leaves it names are drawn at their own std
    from the same key, every other leaf as without it; a name that is no
    leaf is refused; the cell's file follows GPT-2's rule over the 52
    published layers for the two matrices that write the stream."""
    arch = config()
    ranges = arch["start_ranges"]
    assert set(ranges) == {"wte", "o_w", "exp_w2"}
    rule = arch["initializer_range"] / (2 * 52) ** 0.5
    assert ranges["o_w"] == ranges["exp_w2"] == pytest.approx(rule, rel=1e-6)
    assert "start_ranges" in arch["assumed"]
    small = dict(TINY, hidden_size=64, vocab_size=512)
    key = R.seed_key(5)
    plain = R.canonical_weights(small, key, jnp.float32)
    own = R.canonical_weights(
        dict(small, start_ranges={"wte": 0.5, "o_w": 0.01}), key,
        jnp.float32)
    assert float(jnp.std(own["wte"])) == pytest.approx(0.5, rel=0.05)
    np.testing.assert_allclose(own["wte"], plain["wte"] * 5.0, rtol=1e-6)
    np.testing.assert_allclose(own["o_w"], plain["o_w"] * 0.1, rtol=1e-6)
    for name in set(plain) - {"wte", "o_w"}:
        np.testing.assert_array_equal(own[name], plain[name])
    with pytest.raises(ValueError):
        R.canonical_weights(dict(small, start_ranges={"wq": 0.1}), key,
                            jnp.float32)


def test_the_start_keeps_the_routers_inputs_apart():
    """Why the cell's start is not 0.02 throughout: behind attention that
    averages (random q and k), the part of the stream every position
    shares grows a layer and the routers, which read the stream raw, put
    the tokens on the same few experts; an embedding well above the
    branches' outputs keeps every layer's counts near even. A small
    model, the forward pass alone."""
    arch = dict(TINY, hidden_size=128, num_attention_heads=7, head_dim=16,
                moe_ffn_hidden_size=32, moe_num_primary_experts=16,
                moe_num_primary_experts_published=16, expert_offset=0,
                moe_num_active_primary_experts=2, vocab_size=4096,
                num_hidden_layers=4, layers_kept=[0, 1, 2, 3],
                sliding_window_size=256, initializer_range=0.02)
    ids = np.random.default_rng(0).integers(0, 4096, (1, 512))

    def fullest_over_mean(a):
        _, counts = R.loss_whole(R.init_params(a, 9, jnp.float32), ids, a)
        counts = np.asarray(counts)
        return counts.max(axis=-1) / counts.mean(axis=-1)
    rule = 0.02 / (2 * 52) ** 0.5
    plain = fullest_over_mean(arch)
    start = fullest_over_mean(dict(arch, start_ranges={
        "wte": 0.5, "o_w": rule, "exp_w2": rule}))
    assert plain[0] < 1.6 and plain[-1] > 3.0       # of a possible 8
    assert np.all(start < 1.6)


def test_published_is_the_catalogs_row():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    arch = config()
    assert arch["published"] == row["config"]
    assert arch["source"].startswith(row["source_url"])


def test_work_counts_by_hand():
    arch = config()
    p = W.matmul_params(arch)
    assert p["projections"] == 4 * (2 * 2560 * 3584 + 2 * 2560 * 512)
    assert p["router"] == 4 * 2560 * 64
    assert p["head"] == 2560 * 37984 and p["one_expert"] == 5_898_240
    f = W.train_flops(arch, batch=1, seq=16384)
    assert f["projections"] == 6 * p["projections"] * 16384
    assert f["head"] == 6 * p["head"] * 16383
    # an even routing lands 16384 x 6 x 16 / 64 assignments a layer
    assert f["routed_experts"] == 6 * 5_898_240 * 4 * 24576
    tri = 16384 * 16385 // 2
    band = tri - 12288 * 12289 // 2
    assert f["full_attention"] == 6 * 2 * 128 * 28 * tri
    assert f["window_attention"] == 3 * 6 * 2 * 128 * 28 * band
    shares = {k: 100 * v / f["total"] for k, v in f.items()}
    assert f["total"] == pytest.approx(34.7e12, rel=3e-3)       # ISSUE 36
    assert shares["window_attention"] == pytest.approx(21.8, abs=0.1)
    assert shares["full_attention"] == pytest.approx(16.6, abs=0.1)
    assert shares["projections"] == pytest.approx(23.8, abs=0.1)
    assert shares["routed_experts"] == pytest.approx(10.0, abs=0.1)
    assert shares["head"] == pytest.approx(27.5, abs=0.1)
    assert shares["router"] == pytest.approx(0.2, abs=0.05)
    # what landed is what counts: half the rows, half the operations
    half = W.train_flops(arch, 1, 16384, [12288.0] * 4)
    assert half["routed_experts"] * 2 == f["routed_experts"]


def _brute_force_flops(arch, batch, seq, landed):
    """Every multiply-add of a forward pass counted one at a time from the
    equations, times 2 operations, times 3 for forward and backward."""
    z = R.sizes(arch)
    macs = {"projections": 0, "router": 0, "head": 0, "routed_experts": 0,
            "window_attention": 0, "full_attention": 0}
    for (rope, sliding), rows in zip(R.layer_layouts(arch), landed):
        macs["projections"] += batch * seq * z["H"] * (
            z["Q"] + 2 * z["KV"]) + batch * seq * z["Q"] * z["H"]
        macs["router"] += batch * seq * z["H"] * z["E"]
        macs["routed_experts"] += rows * 3 * z["H"] * z["Fe"]
        pairs = 0
        for i in range(seq):
            for j in range(i + 1):
                if not sliding or i - j < arch["sliding_window_size"]:
                    pairs += 1
        macs["window_attention" if sliding else "full_attention"] += \
            batch * z["nh"] * pairs * 2 * z["hd"]       # QK^T and PV
    macs["head"] = batch * (seq - 1) * z["H"] * z["V"]
    out = {k: 6 * v for k, v in macs.items()}
    out["total"] = sum(out.values())
    return out


def test_work_counts_against_a_brute_force_count():
    arch = dict(TINY, num_hidden_layers=4, layers_kept=[0, 1, 2, 3],
                sliding_window_size=5)
    landed = [10.0, 7.0, 0.0, 33.0]
    assert W.train_flops(arch, 2, 24, landed) == _brute_force_flops(
        arch, 2, 24, landed)


def test_kernel_rooflines_by_hand():
    arch, chip = config(), chips.chip_for("TPU v5 lite")
    tri = 16384 * 16385 // 2
    band = tri - 12288 * 12289 // 2
    least, bound = W.attention_seconds(arch, 1, 16384, chip)
    # 2 products forward and 4 backward of 2 x 128 operations a pair, head
    assert least == pytest.approx(
        6 * 2 * 128 * 28 * (tri + 3 * band) / 197e12, rel=1e-9)
    assert set(bound.values()) == {"compute"} and len(bound) == 4
    # the experts at an even routing: memory binds (16 x 5.9M weights read
    # for 1,536 rows an expert)
    t, by = W.expert_seconds(arch, [24576.0], chip)
    flops = 6 * 5_898_240 * 24576
    assert t >= flops / 197e12
    assert W.expert_flops(arch, 24576, False) * 3 == flops
    assert W.expert_bytes(arch, 0, False) == 2 * 3 * 16 * 2560 * 768


# ---------------------------------------------------------------- readers

_FWD = ("%splash_mqa_fwd_residuals.3 = (f32[1,4,7,16384]{3,2,1,0}, "
        "bf16[1,4,7,16384,128]{4,3,2,1,0}) custom-call(%a, %b, %q, %k, %v), "
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        "{s8[7,16,16]{2,1,0}, bf16[1,4,7,16384,128]{4,3,2,1,0}, "
        "bf16[1,4,16384,128]{3,2,1,0}, bf16[1,4,16384,128]{3,2,1,0}}")
_BWD = ("%splash_mqa_dkv_no_residuals.2 = (bf16[1,4,16,7,16384,128]"
        "{5,4,3,2,1,0}, bf16[1,4,16384,128]{3,2,1,0}) custom-call(%q, %k, "
        '%v), custom_call_target="tpu_custom_call", '
        "operand_layout_constraints={bf16[1,4,7,16384,128]{4,3,2,1,0}, "
        "bf16[1,4,16384,128]{3,2,1,0}, bf16[1,4,16384,128]{3,2,1,0}}")
_GMM = ("%gmm.7 = bf16[73728,768]{1,0} custom-call(%x, %w), "
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        "{bf16[73728,2560]{1,0}, bf16[16,2560,768]{2,1,0}}")
_TGMM = ("%tgmm.8 = bf16[16,768,2560]{2,1,0} custom-call(%x, %g), "
         'custom_call_target="tpu_custom_call", operand_layout_constraints='
         "{bf16[768,73728]{1,0}, bf16[73728,2560]{1,0}}")
_OTHER = ("%splash_mqa_fwd_residuals.9 = (bf16[2,4,8,8192,128]{4,3,2,1,0}) "
          'custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", '
          "operand_layout_constraints={bf16[2,4,8,8192,128]{4,3,2,1,0}, "
          "bf16[2,4,8192,128]{3,2,1,0}, bf16[2,4,8192,128]{3,2,1,0}}")
_PRE = "jit(full_step)/jvp(smallthinkerforcausallm)/model/block_1/"
_BACK = "jit(full_step)/transpose(jvp(smallthinkerforcausallm))/model/block_1/"


def recorded_step():
    ops = [("fusion.0", 900, 1000),
           ("splash_mqa_fwd_residuals.3", 1000, 1400),
           ("splash_mqa_dkv_no_residuals.2", 1400, 2300),
           ("gmm.7", 2300, 2500), ("tgmm.8", 2500, 2600),
           ("fusion.1", 2600, 2750), ("fusion.2", 2750, 2800),
           ("fusion.3", 2800, 2900), ("fusion.4", 2900, 3200)]
    chip = trace.Chip(ops=ops, modules=[("jit_full_step", 900, 3200)],
                      kernels={"splash_mqa_fwd_residuals.3": _FWD,
                               "splash_mqa_dkv_no_residuals.2": _BWD,
                               "gmm.7": _GMM, "tgmm.8": _TGMM})
    tr = trace.Trace([chip], [(trace.WINDOW_SPAN, 800, 3400)])
    cell = {"config": config(), "chips": 1,
            "traffic": run_mod.load_json("benchmark", "traffic",
                                         "steps-1x16384.json")}
    scopes = {
        "fusion.0": _PRE + "router/moe_scores/dot_general",
        "splash_mqa_fwd_residuals.3": _PRE + "attn/vmap(jit(_splash))/"
        "pallas_call",
        "splash_mqa_dkv_no_residuals.2": _BACK + "attn/pallas_call",
        "gmm.7": _PRE + "mlp/experts/cond/branch_1_fun/pallas_call",
        "tgmm.8": _BACK + "mlp/experts/cond/branch_1_fun/pallas_call",
        "fusion.1": _PRE + "mlp/experts/moe_experts/gather",
        "fusion.2": _BACK + "router/moe_weigh/mul",
        "fusion.3": _PRE + "attn/q_proj/linear/dot_general",
        "fusion.4": "jit(full_step)/jvp(smallthinkerforcausallm)/head_loss/"
        "dot_general"}
    load = np.full((4, 64), 1536.0)
    load[2, 3] = 3072.0
    obs = {"steps": 1, "op_scopes": scopes, "expert_load": load,
           "landed_by_layer": [24576.0] * 4}
    return tr, cell, obs


def read(metric, tr, obs, cell):
    said = []
    value = run_mod.load_module("layer_metrics", metric).read(
        tr, obs, cell, chips.chip_for("TPU v5 lite"), said.append)
    return value, said


def test_matchers_find_the_kernels_by_shape():
    tr, cell, _ = recorded_step()
    attn = smallthinker_trace.attention_matcher(tr, cell)
    gmm = smallthinker_trace.gmm_matcher(tr, cell)
    names = [n for n, _, _ in tr.chips[0].ops]
    assert [n for n in names if attn(n)] == [
        "splash_mqa_fwd_residuals.3", "splash_mqa_dkv_no_residuals.2"]
    assert [n for n in names if gmm(n)] == ["gmm.7", "tgmm.8"]
    # the chip's compiler drops the batch of one from the operands
    bare = {n: h.replace("[1,4,", "[4,") for n, h in
            tr.chips[0].kernels.items()}
    assert "bf16[4,7,16384,128]" in bare["splash_mqa_fwd_residuals.3"]
    attn = smallthinker_trace.attention_matcher(trace.Trace(
        [trace.Chip(ops=tr.chips[0].ops, modules=tr.chips[0].modules,
                    kernels=bare)], tr.spans), cell)
    assert [n for n in names if attn(n)] == [
        "splash_mqa_fwd_residuals.3", "splash_mqa_dkv_no_residuals.2"]
    # another cell's grouped call (groups of 8 at 8192) is not this one's
    other = trace.Chip(ops=tr.chips[0].ops, modules=tr.chips[0].modules,
                       kernels={"splash_mqa_fwd_residuals.9": _OTHER})
    assert not smallthinker_trace.attention_matcher(
        trace.Trace([other], tr.spans), cell)("splash_mqa_fwd_residuals.9")


def test_readers_on_the_recorded_step():
    tr, cell, obs = recorded_step()
    chip = chips.chip_for("TPU v5 lite")
    least, _ = W.attention_seconds(cell["config"], 1, 16384, chip)
    value, said = read("st_attn_roofline", tr, obs, cell)
    assert value == pytest.approx(100 * least / 1300e-9)
    assert "2 events" in said[0]
    value, _ = read("st_attn_time_pct", tr, obs, cell)
    assert value == pytest.approx(100 * 1300 / 2300)     # busy: 2300 ns
    least, _ = W.expert_seconds(cell["config"], [24576.0] * 4, chip)
    value, said = read("st_moe_gmm_roofline", tr, obs, cell)
    assert value == pytest.approx(100 * least / 300e-9)
    # router 100 + 50, experts 200 + 100 + 150: the attention's and the
    # head's operations are not in it
    value, said = read("st_moe_time_pct", tr, obs, cell)
    assert value == pytest.approx(100 * 600 / 2300)
    assert said[0] == "expert layer, s by scope: experts 0.0000, router " \
        "0.0000"
    value, _ = read("st_expert_load_max_over_mean", tr, obs, cell)
    assert value == pytest.approx(3072 / (1536 * (1 + 1 / 64)))


def test_scopes_table_of_the_recorded_step():
    """``benchmark/scopes.py``: PERF.md section 5's table, seconds a
    region and pass, and the share of busy time it leaves unscoped."""
    from benchmark import scopes
    tr, _, obs = recorded_step()
    out = scopes.table(tr, obs["op_scopes"])
    assert out["busy_s"] == pytest.approx(2300e-9)
    rows = {(r["region"], r["pass"]): r["seconds"]
            for r in out["by_scope"]["rows"]}
    assert rows[("attn", "forward")] == pytest.approx(400e-9)
    assert rows[("attn", "backward")] == pytest.approx(900e-9)
    assert rows[("head_loss", "forward")] == pytest.approx(300e-9)
    # under a name: its own operations' and its children's
    assert out["under"]["experts|forward"] == pytest.approx(350e-9)
    assert out["under"]["experts|backward"] == pytest.approx(100e-9)
    assert out["under"]["router|backward"] == pytest.approx(50e-9)
    assert out["under"]["attn|forward"] == pytest.approx(500e-9)
    assert out["under"]["q_proj|forward"] == pytest.approx(100e-9)
    assert out["top_ops"][0][:2] == (900e-9, "splash_mqa_dkv_no_residuals.2")
    assert set(out["kernels"]) == {"splash_mqa_fwd_residuals.3",
                                   "splash_mqa_dkv_no_residuals.2",
                                   "gmm.7", "tgmm.8"}
    bare = scopes.table(tr, {})
    assert bare["by_scope"]["unscoped_share"] == pytest.approx(1.0)


@pytest.mark.parametrize("metric", [
    "st_attn_roofline", "st_attn_time_pct", "st_moe_gmm_roofline",
    "st_moe_time_pct", "st_expert_load_max_over_mean"])
def test_readers_return_nothing_where_there_is_nothing(metric):
    """On a program without these kernels, scopes or buffers: a trace with
    other kernels, no table of scopes, no counts."""
    tr, cell, _ = recorded_step()
    chip = trace.Chip(ops=[("fusion.1", 1000, 1100)],
                      modules=[("jit_full_step", 1000, 1100)],
                      kernels={"splash_mqa_fwd_residuals.9": _OTHER})
    bare = trace.Trace([chip], tr.spans)
    assert read(metric, bare, {"steps": 3}, cell)[0] is None
    assert read(metric, None, {}, cell)[0] is None


def test_the_accepted_readers_read_the_new_drivers_record():
    """``step_mfu`` and the other unlisted metrics read what this driver
    leaves under the names the other drivers use."""
    tr, cell, obs = recorded_step()
    obs.update(window_s=1.0, step_flops=W.train_flops(
        cell["config"], 1, 16384, obs["landed_by_layer"]))
    value, said = read("step_mfu", tr, obs, cell)
    assert value == pytest.approx(
        100 * obs["step_flops"]["total"] / 197e12)
    assert "window_attention" in said[0] and "routed_experts" in said[0]


# -------------------------------------------------------------- rehearsal

# set as the real cell's are: over what the program reads at this size on
# the CPU in bf16 and under what the planted faults read (half the batch,
# an unchanged state 1)
TINY_LIMITS = {"loss_gap_step1": 3e-3, "loss_gap_step2": 3e-3,
               "grad_norm_gap": 0.06, "router_grad_norm_gap": 0.2,
               "change_norm_gap": 0.3,
               "expert_load_gap": 0.05, "attention_backend_differs": 0}


def add_tiny_cell(root, cell="train-st-tiny"):
    def dump(obj, *parts):
        with open(os.path.join(root, *parts), "w") as f:
            json.dump(obj, f)
    conf = dict(config(), name="st-tiny", **TINY)
    conf.update(hidden_size=64, head_dim=8, num_hidden_layers=4,
                layers_kept=[0, 1, 2, 3], sliding_window_size=8,
                vocab_size=256, initializer_range=0.02)
    conf["job"] = dict(conf["job"], attention_backend="xla",
                       fused_loss_chunk=16)
    dump(conf, "benchmark", "configs", "st-tiny.json")
    dump({"driver": "train_steps_smallthinker", "batch": 2, "seq": 32,
          "log_every": 5, "warmup_steps": 3, "compare_steps": 2,
          "trace_seconds": 1}, "benchmark", "traffic", "steps-tiny-st.json")
    dump(TINY_LIMITS, "benchmark", "limits", cell + ".json")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "st-tiny", "source": "test",
                             "file": "benchmark/configs/st-tiny.json",
                             "reduced": [], "why": "rehearsal"})
    bench["workloads"].append({"name": cell, "config": "st-tiny",
                               "traffic": "steps-tiny-st", "chips": 1,
                               "why": "rehearsal"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(cell)
    dump(bench, "BENCHMARK.json")


@pytest.mark.parametrize("fault", ["none", "half_batch", "state_unchanged"])
def test_rehearse_the_new_driver(tmp_path, fault):
    """``run.py`` finds the new driver, configuration family, work counts
    and readers by name and runs them at toy sizes on the CPU; a timed path
    broken underneath reads ``correct`` false."""
    root = make_tree(str(tmp_path))
    add_tiny_cell(root)
    rc, result, err = run_cell(root, "train-st-tiny", 2**31 + 77,
                               fault=fault)
    assert rc == 0, err
    assert result["correct"] is (fault == "none"), err
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert {"expert_load_gap", "router_grad_norm_gap"} <= set(
        result["compared"])
    assert "xla_ragged_dot" in err and "'activation': 'relu'" in err
    assert "'score': 'softmax_of_chosen'" in err
    assert "'router_input': 'given'" in err
    assert "'window': 8" in err and "'kv_heads': 1" in err


def test_the_parent_has_no_such_cell():
    """``run.py`` on a benchmark without the entry fails at once."""
    bench = {"workloads": [], "configs": []}
    with pytest.raises(SystemExit, match="no workload"):
        run_mod.find_cell(bench, CELL)
