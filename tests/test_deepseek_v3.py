"""The deepseek_v3 family (kanana-2-30b-a3b) for training: attention with
q and k heads wider than v heads on both paths of the attention functional,
the grouped products' tiles from the shapes, and ``DeepseekV3ForCausalLM``
through ``jit.TrainStep``, each against the plain float32 reference that the
benchmark keeps (``benchmark/reference/deepseek_v3.py``, which imports
nothing of paddle_tpu).

Tolerances: everything here runs in float32 at ``highest`` matmul precision
(tests/conftest.py), so the program and the reference differ by the order
of float32 sums alone: 1e-5 relative on outputs, losses and gradients, with
2e-5 absolute beside it for gradients that are sums of either sign (the
leaves are drawn at 0.02). The splash kernel keeps float32 scores and
accumulators in interpret mode: 2e-5 on its sums, 5e-5 on its gradients,
as ``tests/test_afmoe.py``.
"""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn.functional as F
from benchmark.reference import deepseek_v3 as R
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import moe
from paddle_tpu.distributed.moe import TokenChoiceMoE
from paddle_tpu.jit import TrainStep
from paddle_tpu.jit.functional import load_state
from paddle_tpu.models import DeepseekV3Config, DeepseekV3ForCausalLM

fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")


@pytest.fixture(autouse=True)
def fresh_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


def _rand(*shape, seed=0, scale=1.0):
    return jnp.asarray(scale * np.random.RandomState(seed).randn(*shape)
                       .astype("float32"))


# ------------------------------------------------------------- attention

def _softmax_attention(q, k, v, scale):
    """Plain causal f32 attention on [b, h, s, d]; v narrower than q."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    mask = jnp.arange(k.shape[2])[None, :] <= jnp.arange(q.shape[2])[:, None]
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1), v)


def _operands(B=2, H=2, S=256, dqk=192, dv=128):
    return (_rand(B, H, S, dqk, seed=1, scale=0.3),
            _rand(B, H, S, dqk, seed=2, scale=0.3), _rand(B, H, S, dv, seed=3),
            _rand(B, H, S, dv, seed=4))


@pytest.mark.parametrize("dqk,dv", [(192, 128), (96, 32), (128, 128)])
@pytest.mark.parametrize("path", ["xla", "splash"])
def test_wider_q_and_k_than_v_match_plain_softmax(path, dqk, dv):
    """The kernel itself (interpret mode) and the XLA path, forward and
    all three gradients, at q/k heads wider than v heads (192 / 128 is
    the published pair) and at equal sizes."""
    q, k, v, co = _operands(dqk=dqk, dv=dv)
    scale = dqk ** -0.5

    def kernel(q, k, v):
        return fa._pallas_flash_local(q, k, v, True, scale, head_axis=1)

    def xla(q, k, v):
        sw = lambda t: jnp.swapaxes(t, 1, 2)
        return sw(fa._xla_attention(sw(q), sw(k), sw(v), None, None, True,
                                    scale))

    def run(f):
        return jax.value_and_grad(lambda *a: (f(*a) * co).sum(),
                                  argnums=(0, 1, 2))(q, k, v)
    (out, grads) = run(kernel if path == "splash" else xla)
    (ro, rg) = run(lambda q, k, v: _softmax_attention(q, k, v, scale))
    np.testing.assert_allclose(float(out), float(ro), rtol=2e-5)
    for g, r in zip(grads, rg):
        assert g.shape == r.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-5)


@pytest.mark.parametrize("entry", ["head_major", "flash", "sdpa"])
def test_functionals_take_two_head_sizes_and_say_so(entry, monkeypatch):
    """Every public entry carries (d_qk, d_v): the result has v's width,
    the record names both, and with the gate open the Pallas path (the
    kernel interprets here) gives what the XLA path gives."""
    q, k, v, _ = _operands(S=128)
    want = _softmax_attention(q, k, v, 192 ** -0.5)
    sw = lambda t: jnp.swapaxes(t, 1, 2)

    def call():
        if entry == "head_major":       # q carries the scale
            return fa.head_major_attention(
                Tensor(q * 192 ** -0.5), Tensor(k), Tensor(v)).value
        if entry == "flash":
            return sw(F.flash_attention(Tensor(sw(q)), Tensor(sw(k)),
                                        Tensor(sw(v)), causal=True)[0].value)
        return sw(F.scaled_dot_product_attention(
            Tensor(sw(q)), Tensor(sw(k)), Tensor(sw(v)),
            is_causal=True).value)
    out = call()
    rec = F.last_attention_dispatch()
    assert rec["backend"] == "xla" and (rec["head_dim_qk"],
                                        rec["head_dim_v"]) == (192, 128)
    assert out.shape == v.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-6)
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa, "_splash_kernel", _interpreted(fa._splash_kernel))
    out = call()
    rec = F.last_attention_dispatch()
    assert rec["backend"] == "pallas" and rec["kernel"] == "splash_fused"
    assert (rec["head_dim_qk"], rec["head_dim_v"]) == (192, 128)
    assert rec["layout"] == ("head_major" if entry == "head_major"
                             else "seq_major")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def _interpreted(real):
    """``_splash_kernel`` built to interpret whatever ``_on_tpu`` says."""
    def make(heads, s_q, s_k, causal, interpret, window=None, grouped=False):
        return real(heads, s_q, s_k, causal, True, window, grouped)
    return make


@pytest.mark.parametrize("d,d_v,ok", [
    (64, None, True), (128, None, True), (256, None, True),
    (192, None, False), (192, 128, True), (192, 64, True),
    (320, 128, True), (160, 128, False), (128, 192, False),
    (192, 96, True), (192, 192, False)])
def test_geometry_gate_reads_both_head_sizes(d, d_v, ok):
    assert fa._pallas_geometry_ok(1024, d, 0.0, d_v) is ok


def _jaxpr_text(f, *shapes):
    return str(jax.make_jaxpr(f)(*(jax.ShapeDtypeStruct(s, jnp.bfloat16)
                                   for s in shapes)))


def test_equal_head_sizes_trace_the_program_they_traced(monkeypatch):
    """Guards the three accepted cells: with d_qk == d_v the two entries
    trace the kernel call on their operands as they are (no pad, no slice,
    no concatenate), head-major with no transpose and no multiply, and the
    XLA path is the library call and nothing else."""
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    shape = (2, 4, 256, 128)
    text = _jaxpr_text(lambda q, k, v: fa.head_major_attention(
        Tensor(q), Tensor(k), Tensor(v)).value, shape, shape, shape)
    for op in ("pad", "slice", "concatenate", "transpose", "mul "):
        assert f" {op}" not in text.split("pallas_call")[0], op
    assert "pallas_call" in text
    seq = (2, 256, 4, 128)
    text = _jaxpr_text(lambda q, k, v: F.flash_attention(
        Tensor(q), Tensor(k), Tensor(v), causal=True)[0].value,
        seq, seq, seq)
    assert "pad" not in text and "concatenate" not in text
    assert text.count("transpose[") == 4 and "pallas_call" in text
    fa._splash_kernel.cache_clear()
    monkeypatch.setattr(fa, "_on_tpu", lambda: False)
    text = _jaxpr_text(lambda q, k, v: fa._xla_attention(
        q, k, v, None, None, True, 0.1), seq, seq, seq)
    assert "pad" not in text and "slice" not in text


# ------------------------------------------------------------ tile rule

@pytest.mark.parametrize("rows,k,n,want", [
    (49152, 2048, 1024, (512, 1024, 1024)),     # Trinity's w1, w3
    (49152, 1024, 2048, (512, 1024, 1024)),     # Trinity's w2
    (36864, 2048, 768, (512, 1024, 768)),       # this family's w1, w3
    (36864, 768, 2048, (512, 768, 1024)),       # and w2
    (96, 32, 16, (96, 32, 16))])
def test_grouped_tiles_from_the_shapes(rows, k, n, want):
    assert moe._gmm_tiles(rows, k, n) == want
    tm, tk, tn = want
    assert k % tk == 0 and n % tn == 0          # whole tiles


def test_trinitys_grouped_products_trace_as_they_did(monkeypatch):
    """At expert width 1024 each of the three products of a grouped dot
    (forward, dlhs, drhs) asks the library for (512, 1024, 1024)."""
    backend = moe._megablox()
    asked = []

    def fake_gmm(lhs, rhs, sizes, dtype, tiling, *a, transpose_rhs=False,
                 **kw):
        asked.append(("gmm", tiling, transpose_rhs))
        n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
        return jnp.zeros((lhs.shape[0], n), dtype)

    def fake_tgmm(lhs, rhs, sizes, dtype, tiling, *a, **kw):
        asked.append(("tgmm", tiling))
        return jnp.zeros((sizes.shape[0], lhs.shape[0], rhs.shape[1]), dtype)
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    monkeypatch.setattr(backend, "gmm", fake_gmm)
    monkeypatch.setattr(backend, "tgmm", fake_tgmm)
    for k, n in ((2048, 1024), (1024, 2048)):
        del asked[:]
        lhs = jax.ShapeDtypeStruct((49152, k), jnp.bfloat16)
        rhs = jax.ShapeDtypeStruct((16, k, n), jnp.bfloat16)
        sizes = jax.ShapeDtypeStruct((16,), jnp.int32)
        jax.eval_shape(jax.grad(
            lambda l, r, s: moe._grouped_dot(l, r, s).astype(
                jnp.float32).sum(), argnums=(0, 1)), lhs, rhs, sizes)
        assert asked == [("gmm", (512, 1024, 1024), False),
                         ("gmm", (512, 1024, 1024), True),
                         ("tgmm", (512, 1024, 1024))]


def test_moe_dispatch_says_the_tiles():
    m = TokenChoiceMoE(64, 48, 8, 2, experts_held=4)
    m(paddle.to_tensor(np.zeros((2, 16, 64), "float32")))
    rec = moe.last_moe_dispatch()
    assert rec["tiling"] == {"w1_w3": moe._gmm_tiles(rec["rows_bound"], 64,
                                                     48),
                             "w2": moe._gmm_tiles(rec["rows_bound"], 48, 64)}
    # 32 tokens' top-2 with 4 of 8 held: 1.25 even shares in whole tiles,
    # and every row the share can land, which is 2
    assert rec["rows_ladder"] == (48, 64) and rec["rows_bound"] == 64


# ----------------------------------------------------------------- model

ARCH = dict(hidden_size=32, num_attention_heads=4, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            intermediate_size=48, moe_intermediate_size=12,
            n_shared_experts=2, n_routed_experts=4,
            n_routed_experts_published=8, expert_offset=2,
            num_experts_per_tok=3, vocab_size=64, num_hidden_layers=3,
            first_k_dense_replace=1, rms_norm_eps=1e-6, rope_theta=1000000,
            norm_topk_prob=True, routed_scaling_factor=2.448,
            bias_update_rate=0.001, initializer_range=0.02)
JOB = dict(compute_dtype="float32", master_weights=True, learning_rate=1e-3,
           beta1=0.9, beta2=0.999, epsilon=1e-8, weight_decay=0.01)


def _config(**kw):
    return DeepseekV3Config(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=12, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=4, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        n_routed_experts=8, experts_held=4, expert_offset=2,
        num_experts_per_tok=3, max_seq_len=64, **kw)


def _program(recompute, fused_loss_chunk=8, seed=5):
    from benchmark.drivers.train_steps_deepseek_v3 import program_layout
    model = DeepseekV3ForCausalLM(_config(
        recompute=recompute, fused_loss_chunk=fused_loss_chunk))
    leaves = R.init_params(ARCH, seed, jnp.float32)
    layout = program_layout(ARCH)
    assert set(layout) == {n for n, _ in model.named_parameters()}
    load_state(model, {prog: leaves[leaf] if at is None else leaves[leaf][at]
                       for prog, (leaf, at) in layout.items()})
    return model, leaves, layout


def test_defaults_are_the_published_config():
    cfg = DeepseekV3Config()
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.kv_lora_rank, cfg.qk_head_dim, cfg.v_head_dim) == (
        2048, 48, 32, 512, 192, 128)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.n_shared_experts,
            cfg.routed_scaling_factor, cfg.vocab_size) == (
        128, 6, 768, 2, 2.448, 128256)


def test_logits_match_the_reference():
    model, leaves, _ = _program(False)
    model.eval()
    ids = np.random.default_rng(1).integers(0, 64, (2, 16))
    logits = model(paddle.to_tensor(ids))
    assert tuple(logits.shape) == (2, 16, 64)
    cfg = R.settings(ARCH)
    w = {n: v.astype(jnp.float32) for n, v in leaves.items()}
    x = w["wte"][jnp.asarray(ids)]
    for i in range(3):
        x, _ = R.layer_forward(R.layer_params(w, i, cfg), x,
                               jnp.zeros((8,)), i >= 1, cfg, R._dot)
    want = R._dot(R._rms(x, w["lnf_g"], 1e-6), w["head_w"])
    np.testing.assert_allclose(np.asarray(logits.value), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    loss, _ = R.loss_whole(leaves, jnp.zeros((2, 8)), jnp.asarray(ids), ARCH)
    got = model.make_loss_fn()(logits, paddle.to_tensor(ids))
    np.testing.assert_allclose(float(got), float(loss), rtol=1e-5)
    assert all(float(np.abs(np.asarray(b.value)).max()) == 0.0
               for n, b in model.named_buffers())


@pytest.mark.parametrize("recompute", [False, True])
def test_model_two_train_steps_match_the_reference(recompute):
    """``DeepseekV3ForCausalLM`` + ``make_loss_fn()`` + ``AdamW`` +
    ``TrainStep``: both losses, every leaf's first gradient (Adam's first
    moment over 1 - beta1), the counts of tokens by expert of the first
    step's routing and the expert bias after both, against the reference's
    two steps; with and without per-block recomputation."""
    model, leaves, layout = _program(recompute)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, beta1=0.9, beta2=0.999, epsilon=1e-8,
        weight_decay=0.01, parameters=model.parameters())
    step = TrainStep(model, model.make_loss_fn(), opt)
    ids = np.random.default_rng(0).integers(0, 64, (2, 16))
    (_, counts1), grads = jax.value_and_grad(
        lambda p: R.loss_whole(p, jnp.zeros((2, 8)), jnp.asarray(ids), ARCH),
        has_aux=True)(leaves)
    ref = R.train_readings(ARCH, JOB, 5, [ids, ids])

    loss1 = float(step(paddle.to_tensor(ids), paddle.to_tensor(ids)))
    load1 = np.stack([np.asarray(step.buffers[f"model.block_{i}.mlp."
                                              "expert_load"]) for i in (1, 2)])
    for prog, (leaf, at) in layout.items():
        got = np.asarray(step.opt_state[prog]["moment1"]) / (1 - 0.9)
        want = np.asarray(grads[leaf] if at is None else grads[leaf][at])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-5,
                                   err_msg=prog)
    loss2 = float(step(paddle.to_tensor(ids), paddle.to_tensor(ids)))
    np.testing.assert_allclose([loss1, loss2], ref["losses"], rtol=1e-5)
    assert np.array_equal(load1, np.asarray(counts1))
    assert np.array_equal(load1, ref["expert_load"])
    bias = np.stack([np.asarray(step.buffers[f"model.block_{i}.mlp."
                                             "expert_bias"]) for i in (1, 2)])
    np.testing.assert_allclose(bias, ref["expert_bias"], atol=1e-7)
    assert np.abs(bias).max() > 0          # the step moved it
    # every leaf's change over the two steps, by the norms `correct` reads
    from benchmark.drivers.train_steps_afmoe import _by_leaf
    start = {n: p.value for n, p in model.named_parameters()}
    change = _by_leaf({
        n: np.asarray(R.leaf_norms(step.params[n] - start[n], layout[n][0],
                                   held=(2, 4))) for n in layout}, layout)
    for leaf, want in ref["change_norms"].items():
        np.testing.assert_allclose(change[leaf], want, rtol=2e-4,
                                   err_msg=leaf)


def test_attention_layer_on_the_kernel_path_matches_the_reference(
        monkeypatch):
    """The layer at the published head sizes (192 / 128, two heads) with
    the gate open (the kernel interprets): q, k, v reach the kernel
    head-major, and the output and gradients are the reference's."""
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa, "_splash_kernel", _interpreted(fa._splash_kernel))
    from paddle_tpu.models import DeepseekV3Attention
    arch = dict(ARCH, hidden_size=64, num_attention_heads=2, kv_lora_rank=32,
                qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    cfg = DeepseekV3Config(hidden_size=64, num_attention_heads=2,
                           kv_lora_rank=32)
    layer = DeepseekV3Attention(cfg)
    rng = np.random.default_rng(3)
    p = {"q_w": (64, 384), "kva_w": (64, 96), "kvb_w": (32, 512),
         "o_w": (256, 64)}
    p = {n: jnp.asarray(0.1 * rng.standard_normal(s), jnp.float32)
         for n, s in p.items()}
    p["kv_norm_g"] = jnp.asarray(1 + 0.1 * rng.standard_normal(32),
                                 jnp.float32)
    for name, leaf in (("q_proj", "q_w"), ("kv_a_proj", "kva_w"),
                       ("kv_a_norm", "kv_norm_g"), ("kv_b_proj", "kvb_w"),
                       ("o_proj", "o_w")):
        getattr(layer, name).weight.value = p[leaf]
    x = _rand(2, 128, 64, seed=9)
    xt = paddle.to_tensor(np.asarray(x))
    xt.stop_gradient = False
    out = layer(xt)
    rec = F.last_attention_dispatch()
    assert rec["backend"] == "pallas" and rec["layout"] == "head_major"
    assert (rec["head_dim_qk"], rec["head_dim_v"]) == (192, 128)
    ref = lambda x_, p_: R.attention_forward(p_, x_, R.settings(arch), R._dot)
    np.testing.assert_allclose(np.asarray(out.value), np.asarray(ref(x, p)),
                               rtol=1e-5, atol=2e-5)
    (out * out).sum().backward()
    gx, gp = jax.grad(lambda x_, p_: (ref(x_, p_) ** 2).sum(), (0, 1))(x, p)
    np.testing.assert_allclose(np.asarray(xt.grad.value), np.asarray(gx),
                               rtol=1e-4, atol=5e-5)
    np.testing.assert_allclose(np.asarray(layer.kv_b_proj.weight.grad.value),
                               np.asarray(gp["kvb_w"]), rtol=1e-4, atol=5e-5)


def test_every_new_layer_registers_its_scope():
    """``TrainStep.op_scopes()`` maps device operations by these names."""
    model, _, _ = _program(True)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = TrainStep(model, model.make_loss_fn(), opt)
    ids = np.random.default_rng(0).integers(0, 64, (2, 16))
    step(paddle.to_tensor(ids), paddle.to_tensor(ids))
    paths = " ".join(set(step.op_scopes().values()))
    for scope in ("attn", "q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj",
                  "o_proj", "router", "experts", "shared_expert",
                  "input_layernorm", "post_attention_layernorm", "head_loss",
                  "optimizer"):
        assert f"/{scope}/" in paths or f"({scope})" in paths, scope


def test_eight_shares_add_up_to_the_uncut_layer():
    """The published router (8 here) in 4 shares of 2 at this family's
    settings (top-3 of 8, normalised, times 2.448, two shared experts as
    one SwiGLU of twice the width): the shares' routed parts plus the
    shared experts counted once equal the uncut reference's layer."""
    from paddle_tpu.models.deepseek_v3 import _swiglu
    rng = np.random.default_rng(0)
    g = lambda *s, sc: jnp.asarray(rng.standard_normal(s).astype("f4") * sc)
    p = {"router_w": g(32, 8, sc=0.5), "exp_w1": g(8, 32, 12, sc=0.2),
         "exp_w3": g(8, 32, 12, sc=0.2), "exp_w2": g(8, 12, 32, sc=0.2),
         "sh_w1": g(32, 24, sc=0.2), "sh_w3": g(32, 24, sc=0.2),
         "sh_w2": g(24, 32, sc=0.2)}
    x, bias = _rand(3, 20, 32, seed=5), _rand(8, seed=6, scale=0.1)
    uncut = dict(R.settings(dict(ARCH, n_routed_experts=8, expert_offset=0)))
    want, counts = R.moe_forward(p, x, bias, uncut)
    total = 0
    for share in range(4):
        sh = None
        if share == 0:
            sh = _swiglu(32, 24, _config())
            sh.gate_proj.weight.value = p["sh_w1"]
            sh.up_proj.weight.value = p["sh_w3"]
            sh.down_proj.weight.value = p["sh_w2"]
        m = TokenChoiceMoE(32, 12, 8, 3, experts_held=2,
                           expert_offset=2 * share, shared_expert=sh,
                           route_norm=True, route_scale=2.448)
        m.router.weight.value, m.expert_bias.value = p["router_w"], bias
        sl = slice(2 * share, 2 * share + 2)
        m.experts.w1.value, m.experts.w3.value, m.experts.w2.value = (
            p["exp_w1"][sl], p["exp_w3"][sl], p["exp_w2"][sl])
        y, c = m(paddle.to_tensor(np.asarray(x)))
        total = total + np.asarray(y.value)
        assert np.array_equal(np.asarray(c.value), np.asarray(counts))
    np.testing.assert_allclose(total, np.asarray(want), atol=5e-6)
