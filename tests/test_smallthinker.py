"""The SmallThinker family (PowerInfer/SmallThinker-21BA3B-Instruct) for
training: ``head_major_attention`` with a window and grouped heads (groups
of 7: no power of two) on both paths of the attention functional, the expert
layer's router apart from its experts' input, the softmax-over-chosen score
rule and the ReLU gate, and ``SmallThinkerForCausalLM`` through
``jit.TrainStep``, each against the plain float32 reference that the
benchmark keeps (``benchmark/reference/smallthinker.py``, which imports
nothing of paddle_tpu).

Tolerances: everything here runs in float32 at ``highest`` matmul precision
(tests/conftest.py), so the program and the reference differ by the order of
float32 sums alone: 1e-5 relative on outputs, losses and gradients, with
2e-5 absolute beside it for gradients that are sums of either sign (the
leaves are drawn at 0.02). The splash kernel keeps float32 scores and
accumulators in interpret mode: 2e-5 on its sums, 5e-5 on its gradients, as
``tests/test_afmoe.py``.
"""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn.functional as F
from benchmark.reference import smallthinker as R
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import moe
from paddle_tpu.distributed.moe import TokenChoiceMoE
from paddle_tpu.jit import TrainStep
from paddle_tpu.jit.functional import load_state
from paddle_tpu.models import (SmallThinkerAttention, SmallThinkerBlock,
                               SmallThinkerConfig, SmallThinkerForCausalLM)

fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")


@pytest.fixture(autouse=True)
def fresh_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


def _rand(*shape, seed=0, scale=1.0):
    return jnp.asarray(scale * np.random.RandomState(seed).randn(*shape)
                       .astype("float32"))


def _interpreted(real):
    """``_splash_kernel`` built to interpret whatever ``_on_tpu`` says."""
    def make(heads, s_q, s_k, causal, interpret, window=None, grouped=False):
        return real(heads, s_q, s_k, causal, True, window, grouped)
    return make


# ------------------------------------------------------------- attention

def _softmax_attention(q, k, v, window):
    """Plain causal f32 attention at scale 1: q [b, h, s, d] on k, v
    [b, kv, s, d], query head h on key/value head h // group; query i sees
    keys j with 0 <= i - j < window."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    i, j = jnp.arange(q.shape[2])[:, None], jnp.arange(k.shape[2])[None, :]
    mask = j <= i
    if window is not None:
        mask = mask & (i - j < window)
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1), v)


def _operands(B=2, H=7, KV=1, S=256, d=64):
    return (_rand(B, H, S, d, seed=1, scale=0.3 * d ** -0.5 * 8),
            _rand(B, KV, S, d, seed=2, scale=0.3), _rand(B, KV, S, d, seed=3),
            _rand(B, H, S, d, seed=4))


@pytest.mark.parametrize("heads,kv", [(7, 1), (14, 2), (4, 4)])
@pytest.mark.parametrize("window", [None, 128, 100])
@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_head_major_window_and_groups_match_plain_softmax(path, window, heads,
                                                          kv, monkeypatch):
    """``head_major_attention(window=, grouped)``: the XLA path and the
    Pallas path (the kernel interprets here), values and all three
    gradients, at groups of 7 and a window shorter than the sequence (one
    that is no multiple of a tile too); equal to ``F.flash_attention`` on
    the transposed operands."""
    q, k, v, co = _operands(H=heads, KV=kv)
    if path == "pallas":
        monkeypatch.setattr(fa, "_on_tpu", lambda: True)
        monkeypatch.setattr(fa, "_splash_kernel",
                            _interpreted(fa._splash_kernel))

    def entry(q, k, v):
        return fa.head_major_attention(Tensor(q), Tensor(k), Tensor(v),
                                       causal=True, window=window).value

    def run(f):
        return jax.value_and_grad(lambda *a: (f(*a) * co).sum(),
                                  argnums=(0, 1, 2))(q, k, v)
    out, grads = run(entry)
    rec = F.last_attention_dispatch()
    assert rec["backend"] == path and rec["window"] == window
    assert rec["kv_heads"] == kv
    assert rec["layout"] == ("head_major" if path == "pallas"
                             else "seq_major")
    ro, rg = run(lambda q, k, v: _softmax_attention(q, k, v, window))
    np.testing.assert_allclose(float(out), float(ro), rtol=2e-5)
    for g, r in zip(grads, rg):
        assert g.shape == r.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-5)
    # the public seq-major functional on the same attention (it puts the
    # scale on itself: hand it q without one)
    sw = lambda t: jnp.swapaxes(t, 1, 2)
    d = q.shape[-1]
    pub = sw(F.flash_attention(Tensor(sw(q) * d ** 0.5), Tensor(sw(k)),
                               Tensor(sw(v)), causal=True,
                               window=window)[0].value)
    np.testing.assert_allclose(np.asarray(pub), np.asarray(entry(q, k, v)),
                               atol=2e-5)


def test_head_major_refuses_what_no_path_computes():
    q, k, v, _ = _operands(H=7, KV=2)
    with pytest.raises(ValueError, match="do not divide"):
        fa.head_major_attention(Tensor(q), Tensor(k), Tensor(v))
    q, k, v, _ = _operands()
    with pytest.raises(ValueError, match="causal window"):
        fa.head_major_attention(Tensor(q), Tensor(k), Tensor(v),
                                causal=False, window=8)


def test_grouped_window_call_touches_nothing_but_the_kernel(monkeypatch):
    """On the Pallas path the head-major entry reshapes q into its groups
    and calls the kernel: no transpose, no multiply, no copy of k or v out
    to the query heads, whatever the window."""
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    shapes = [(1, 28, 512, 128), (1, 4, 512, 128), (1, 4, 512, 128)]
    text = str(jax.make_jaxpr(lambda q, k, v: fa.head_major_attention(
        Tensor(q), Tensor(k), Tensor(v), window=256).value)(
        *(jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes)))
    before = text.split("pallas_call")[0]
    for op in ("transpose", "mul ", "concatenate", "gather"):
        assert f" {op}" not in before, op
    assert "pallas_call" in text and "bf16[1,4,7,512,128]" in text
    fa._splash_kernel.cache_clear()


# ------------------------------------------------------------ expert layer

def _layer_leaves(rng, H=32, E=8, Fe=12, held=8):
    g = lambda *s, sc: jnp.asarray(rng.standard_normal(s).astype("f4") * sc)
    return {"router_w": g(H, E, sc=0.5), "exp_w1": g(held, H, Fe, sc=0.2),
            "exp_w3": g(held, H, Fe, sc=0.2), "exp_w2": g(held, Fe, H, sc=0.2)}


MOE_ARCH = dict(hidden_size=32, num_attention_heads=7, num_key_value_heads=1,
                head_dim=8, moe_ffn_hidden_size=12,
                moe_num_primary_experts=8, moe_num_active_primary_experts=3,
                moe_primary_router_apply_softmax=True, vocab_size=64,
                num_hidden_layers=1, rope_layout=[0],
                sliding_window_layout=[0], sliding_window_size=8,
                rms_norm_eps=1e-6, rope_theta=1500000)


def _share(p, offset, held):
    m = TokenChoiceMoE(32, 12, 8, 3, experts_held=held, expert_offset=offset,
                       score="softmax_of_chosen", activation="relu",
                       bias_update_rate=0.0)
    m.router.weight.value = p["router_w"]
    sl = slice(offset, offset + held)
    m.experts.w1.value, m.experts.w3.value, m.experts.w2.value = (
        p["exp_w1"][sl], p["exp_w3"][sl], p["exp_w2"][sl])
    return m


def test_four_shares_add_up_to_the_uncut_layer():
    """The published router (8 here) in 4 shares of 2 at this family's
    settings (top-3 of 8 by the logits, softmax over the chosen, ReLU
    gate, routed on ANOTHER tensor than the experts read): there is no
    shared expert, so nothing is counted once, and the four shares' sums
    equal the uncut reference's whole layer."""
    p = _layer_leaves(np.random.default_rng(0))
    x, xr = _rand(3, 20, 32, seed=5), _rand(3, 20, 32, seed=6)
    want, counts = R.moe_forward(p, x, xr, R.settings(MOE_ARCH))
    total = 0
    for share in range(4):
        m = _share(p, 2 * share, 2)
        y, c = m(paddle.to_tensor(np.asarray(x)),
                 routing=m.route(paddle.to_tensor(np.asarray(xr))))
        total = total + np.asarray(y.value)
        assert np.array_equal(np.asarray(c.value), np.asarray(counts))
        rec = moe.last_moe_dispatch()
        assert (rec["activation"], rec["score"], rec["router_input"]) == (
            "relu", "softmax_of_chosen", "given")
        # 60 tokens' top-3 with 2 of 8 held: 1.25 and (every row the
        # share can land) 2.67 even shares of 45, in whole tiles
        assert rec["rows_ladder"] == (64, 128)
        assert rec["rows_bound"] == 128
    np.testing.assert_allclose(total, np.asarray(want), atol=5e-6)
    assert float(np.abs(np.asarray(want)).max()) > 1e-3


def test_softmax_of_chosen_weights_and_their_gradient():
    """w = softmax(logits[top-k]): sums to 1 over the chosen, and the
    router's gradient is the reference's (through the softmax over the
    chosen alone)."""
    p = _layer_leaves(np.random.default_rng(1))
    x = _rand(40, 32, seed=7)
    m = _share(p, 0, 8)
    sel, w, counts = m.route(paddle.to_tensor(np.asarray(x)))
    rs, rw, rc = R.route(p, x, R.settings(MOE_ARCH), R._dot)
    assert np.array_equal(np.asarray(sel.value), np.asarray(rs))
    np.testing.assert_allclose(np.asarray(w.value), np.asarray(rw),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w.value).sum(-1), 1.0, rtol=1e-6)
    assert float(np.asarray(counts.value).sum()) == 40 * 3
    xt = paddle.to_tensor(np.asarray(x))
    y, _ = m(xt)
    (y * y).sum().backward()
    want = jax.grad(lambda p_: (R.moe_forward(
        p_, x, x, R.settings(MOE_ARCH))[0] ** 2).sum())(p)
    for leaf, got in (("router_w", m.router.weight),
                      ("exp_w1", m.experts.w1), ("exp_w2", m.experts.w2)):
        np.testing.assert_allclose(np.asarray(got.grad.value),
                                   np.asarray(want[leaf]), rtol=1e-4,
                                   atol=2e-5, err_msg=leaf)
    assert moe.last_moe_dispatch()["router_input"] == "expert_input"


def test_defaults_are_the_sigmoid_silu_layer_and_say_so():
    """Trinity's and kanana's layer: the constructor's defaults, the same
    record keys beside the new ones, and the jaxpr of the default layer
    holds a logistic and no softmax's exp over the chosen."""
    m = TokenChoiceMoE(64, 48, 8, 2, experts_held=4)
    assert (m.router.score, m.experts.activation) == ("sigmoid", "silu")
    m(paddle.to_tensor(np.zeros((2, 16, 64), "float32")))
    rec = moe.last_moe_dispatch()
    assert (rec["activation"], rec["score"], rec["router_input"]) == (
        "silu", "sigmoid", "expert_input")
    assert {"kernel", "experts_held", "experts_published", "top_k",
            "rows_bound", "tiling"} <= set(rec)
    for bad in (dict(score="softmax"), dict(activation="gelu")):
        with pytest.raises(ValueError):
            TokenChoiceMoE(64, 48, 8, 2, **bad)


# ----------------------------------------------------------------- model

ARCH = dict(hidden_size=32, num_attention_heads=7, num_key_value_heads=1,
            head_dim=8, moe_ffn_hidden_size=12, moe_num_primary_experts=4,
            moe_num_primary_experts_published=8, expert_offset=2,
            moe_num_active_primary_experts=3,
            moe_primary_router_apply_softmax=True, norm_topk_prob=True,
            vocab_size=64, num_hidden_layers=4,
            rope_layout=[0, 1, 1, 1, 0, 1], layers_kept=[0, 1, 2, 3],
            sliding_window_layout=[0, 1, 1, 1, 0, 1], sliding_window_size=8,
            rms_norm_eps=1e-6, rope_theta=1500000, initializer_range=0.02)
JOB = dict(compute_dtype="float32", master_weights=True, learning_rate=1e-3,
           beta1=0.9, beta2=0.999, epsilon=1e-8, weight_decay=0.01)


def _config(**kw):
    base = dict(
        vocab_size=64, hidden_size=32, num_hidden_layers=4,
        num_attention_heads=7, num_key_value_heads=1, head_dim=8,
        sliding_window_size=8, moe_ffn_hidden_size=12,
        moe_num_primary_experts=8, experts_held=4, expert_offset=2,
        moe_num_active_primary_experts=3, max_position_embeddings=64)
    base.update(kw)
    return SmallThinkerConfig(**base)


def _program(recompute, fused_loss_chunk=8, seed=5):
    from benchmark.drivers.train_steps_smallthinker import program_layout
    model = SmallThinkerForCausalLM(_config(
        recompute=recompute, fused_loss_chunk=fused_loss_chunk))
    leaves = R.init_params(ARCH, seed, jnp.float32)
    layout = program_layout(ARCH)
    assert set(layout) == {n for n, _ in model.named_parameters()}
    load_state(model, {prog: leaves[leaf] if at is None else leaves[leaf][at]
                       for prog, (leaf, at) in layout.items()})
    return model, leaves, layout


def test_defaults_are_the_published_config():
    cfg = SmallThinkerConfig()
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.vocab_size) == (
        2560, 52, 28, 4, 128, 151936)
    assert (cfg.moe_num_primary_experts, cfg.moe_num_active_primary_experts,
            cfg.moe_ffn_hidden_size, cfg.sliding_window_size, cfg.rope_theta,
            cfg.max_position_embeddings) == (64, 6, 768, 4096, 1.5e6, 16384)
    layouts = cfg.layouts()
    assert len(layouts) == 52 and layouts[:5] == (
        (0, 0), (1, 1), (1, 1), (1, 1), (0, 0))
    with pytest.raises(ValueError):
        SmallThinkerConfig(num_hidden_layers=4, rope_layout=[0, 1]).layouts()


def test_logits_match_the_reference():
    model, leaves, _ = _program(False)
    model.eval()
    ids = np.random.default_rng(1).integers(0, 64, (2, 16))
    logits = model(paddle.to_tensor(ids))
    assert tuple(logits.shape) == (2, 16, 64)
    cfg = R.settings(ARCH)
    w = {n: v.astype(jnp.float32) for n, v in leaves.items()}
    x = w["wte"][jnp.asarray(ids)]
    for i in range(4):
        x, _ = R.layer_forward({n: w[n][i] for n in R.LAYER_NAMES}, x,
                               cfg["layouts"][i], cfg, R._dot)
    want = R._dot(R._rms(x, w["lnf_g"], 1e-6), w["head_w"])
    np.testing.assert_allclose(np.asarray(logits.value), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    loss, _ = R.loss_whole(leaves, jnp.asarray(ids), ARCH)
    got = model.make_loss_fn()(logits, paddle.to_tensor(ids))
    np.testing.assert_allclose(float(got), float(loss), rtol=1e-5)


@pytest.mark.parametrize("recompute", [False, True])
def test_model_two_train_steps_match_the_reference(recompute):
    """``SmallThinkerForCausalLM`` + ``make_loss_fn()`` + ``AdamW`` +
    ``TrainStep``: both losses, every leaf's first gradient (Adam's first
    moment over 1 - beta1), the counts of tokens by expert of the first
    step's routing and every leaf's change after both steps, against the
    reference's two steps; full and window layers, groups of 7, a window
    (8) shorter than the sequence (16); with and without per-block
    recomputation."""
    model, leaves, layout = _program(recompute)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, beta1=0.9, beta2=0.999, epsilon=1e-8,
        weight_decay=0.01, parameters=model.parameters())
    step = TrainStep(model, model.make_loss_fn(), opt)
    ids = np.random.default_rng(0).integers(0, 64, (2, 16))
    (_, counts1), grads = jax.value_and_grad(
        lambda p: R.loss_whole(p, jnp.asarray(ids), ARCH),
        has_aux=True)(leaves)
    ref = R.train_readings(ARCH, JOB, 5, [ids, ids])

    loss1 = float(step(paddle.to_tensor(ids), paddle.to_tensor(ids)))
    load1 = np.stack([np.asarray(step.buffers[f"model.block_{i}.mlp."
                                              "expert_load"])
                      for i in range(4)])
    for prog, (leaf, at) in layout.items():
        got = np.asarray(step.opt_state[prog]["moment1"]) / (1 - 0.9)
        want = np.asarray(grads[leaf] if at is None else grads[leaf][at])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-5,
                                   err_msg=prog)
    loss2 = float(step(paddle.to_tensor(ids), paddle.to_tensor(ids)))
    np.testing.assert_allclose([loss1, loss2], ref["losses"], rtol=1e-5)
    assert np.array_equal(load1, np.asarray(counts1))
    assert np.array_equal(load1, ref["expert_load"])
    for i in range(4):          # the model has no balancing bias
        assert not np.asarray(
            step.buffers[f"model.block_{i}.mlp.expert_bias"]).any()
    from benchmark.drivers.train_steps_afmoe import _by_leaf
    start = {n: p.value for n, p in model.named_parameters()}
    change = _by_leaf({
        n: np.asarray(R.leaf_norms(step.params[n] - start[n], layout[n][0],
                                   held=(2, 4))) for n in layout}, layout)
    for leaf, want in ref["change_norms"].items():
        np.testing.assert_allclose(change[leaf], want, rtol=2e-4,
                                   err_msg=leaf)


def test_the_reference_step_is_the_gradient_of_its_whole_loss():
    """The layer-by-layer step (a row at a time under ``jax.vjp``) gives
    the norms of ``jax.grad(loss_whole)``."""
    leaves = R.init_params(ARCH, 5, jnp.float32)
    ids = np.random.default_rng(0).integers(0, 64, (2, 16))
    (loss, _), grads = jax.value_and_grad(
        lambda p: R.loss_whole(p, jnp.asarray(ids), ARCH),
        has_aux=True)(leaves)
    ref = R.train_readings(ARCH, JOB, 5, [ids])
    np.testing.assert_allclose(ref["losses"][0], float(loss), rtol=1e-6)
    for leaf, want in ref["grad_norms"].items():
        got = R.leaf_norms(grads[leaf], leaf, leaf in R.LAYER_NAMES,
                           held=(2, 4))
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                                   err_msg=leaf)


def _block_pair(seed=11):
    """One block and its leaves, at weights large enough that the two
    candidate router inputs choose different experts."""
    rng = np.random.default_rng(seed)
    g = lambda *s, sc: jnp.asarray(rng.standard_normal(s).astype("f4") * sc)
    p = {"ln1_g": 1 + g(32, sc=0.1), "ln2_g": 1 + g(32, sc=0.1),
         "q_w": g(32, 56, sc=0.2), "k_w": g(32, 8, sc=0.2),
         "v_w": g(32, 8, sc=0.2), "o_w": g(56, 32, sc=0.2),
         "router_w": g(32, 8, sc=0.5), "exp_w1": g(8, 32, 12, sc=0.2),
         "exp_w3": g(8, 32, 12, sc=0.2), "exp_w2": g(8, 12, 32, sc=0.2)}
    blk = SmallThinkerBlock(_config(num_hidden_layers=1, rope_layout=[1],
                                    sliding_window_layout=[1],
                                    experts_held=8, expert_offset=0), 0)
    from benchmark.drivers.train_steps_smallthinker import _BLOCK
    load_state(blk, {prog: p[leaf] for leaf, prog in _BLOCK.items()})
    return blk, p


def test_the_router_reads_the_blocks_input():
    """The block against the reference, and against the reference with the
    router moved behind the attention (the fault ``correct`` plants): the
    program equals the first and NOT the second, in the output and in the
    counts. Fails if the router reads ``n2(h)``."""
    blk, p = _block_pair()
    arch = dict(MOE_ARCH, rope_layout=[1], sliding_window_layout=[1])
    x = _rand(2, 16, 32, seed=3)
    y, counts = blk(paddle.to_tensor(np.asarray(x)))
    want, wc = R.layer_forward(p, x, (1, 1), R.settings(arch), R._dot)
    np.testing.assert_allclose(np.asarray(y.value), np.asarray(want),
                               rtol=1e-5, atol=2e-6)
    assert np.array_equal(np.asarray(counts.value), np.asarray(wc))
    other, oc = R.layer_forward(
        p, x, (1, 1), R.settings(arch, "router_after_attention"), R._dot)
    assert not np.array_equal(np.asarray(oc), np.asarray(wc))
    assert float(np.abs(np.asarray(other) - np.asarray(want)).max()) > 1e-3
    assert moe.last_moe_dispatch()["router_input"] == "given"


@pytest.mark.parametrize("fault", R.FAULTS + ("half_batch",))
def test_every_planted_fault_moves_the_reference(fault):
    """Each fault ``correct``'s readings plant changes what the reference
    computes at this size (so a limit can tell it)."""
    ids = np.random.default_rng(0).integers(0, 64, (1, 32))
    good = R.train_readings(ARCH, JOB, 5, [ids])
    kw = dict(half_batch=True) if fault == "half_batch" else dict(fault=fault)
    bad = R.train_readings(ARCH, JOB, 5, [ids], **kw)
    gap = max(float(np.max(np.abs(bad["grad_norms"][n] - v)
                           / np.maximum(v, 1e-12)))
              for n, v in good["grad_norms"].items())
    assert gap > 1e-3, gap


@pytest.mark.parametrize("rope,window", [(True, True), (False, False)])
def test_attention_layer_on_the_kernel_path_matches_the_reference(
        rope, window, monkeypatch):
    """The layer at groups of 7 on heads of 128 with the gate open (the
    kernel interprets): q, k, v reach the kernel head-major with the
    window, and the output and gradients are the reference's; the window
    layer with RoPE and the full layer without."""
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setattr(fa, "_splash_kernel", _interpreted(fa._splash_kernel))
    arch = dict(MOE_ARCH, hidden_size=64, head_dim=128,
                sliding_window_size=128)
    cfg = SmallThinkerConfig(hidden_size=64, num_attention_heads=7,
                             num_key_value_heads=1, head_dim=128,
                             sliding_window_size=128)
    layer = SmallThinkerAttention(cfg, rope, window)
    rng = np.random.default_rng(3)
    p = {"q_w": (64, 896), "k_w": (64, 128), "v_w": (64, 128),
         "o_w": (896, 64)}
    p = {n: jnp.asarray(0.1 * rng.standard_normal(s), jnp.float32)
         for n, s in p.items()}
    for name, leaf in (("q_proj", "q_w"), ("k_proj", "k_w"),
                       ("v_proj", "v_w"), ("o_proj", "o_w")):
        getattr(layer, name).weight.value = p[leaf]
    x = _rand(2, 256, 64, seed=9)
    xt = paddle.to_tensor(np.asarray(x))
    xt.stop_gradient = False
    out = layer(xt)
    rec = F.last_attention_dispatch()
    assert rec["backend"] == "pallas" and rec["layout"] == "head_major"
    assert rec["window"] == (128 if window else None)
    assert rec["kv_heads"] == 1
    layout = (int(rope), int(window))
    ref = lambda x_, p_: R.attention_forward(p_, x_, layout,
                                             R.settings(arch), R._dot)
    np.testing.assert_allclose(np.asarray(out.value), np.asarray(ref(x, p)),
                               rtol=1e-5, atol=2e-5)
    (out * out).sum().backward()
    gx, gp = jax.grad(lambda x_, p_: (ref(x_, p_) ** 2).sum(), (0, 1))(x, p)
    np.testing.assert_allclose(np.asarray(xt.grad.value), np.asarray(gx),
                               rtol=1e-4, atol=5e-5)
    np.testing.assert_allclose(np.asarray(layer.k_proj.weight.grad.value),
                               np.asarray(gp["k_w"]), rtol=1e-4, atol=5e-5)


def test_every_new_layer_registers_its_scope():
    """``TrainStep.op_scopes()`` maps device operations by these names, and
    ``router`` is a child of the block (not of ``mlp``): the block asks for
    the routing itself, ahead of ``attn``."""
    model, _, _ = _program(True)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = TrainStep(model, model.make_loss_fn(), opt)
    ids = np.random.default_rng(0).integers(0, 64, (2, 16))
    step(paddle.to_tensor(ids), paddle.to_tensor(ids))
    every = set(step.op_scopes().values())
    paths = " ".join(every)
    for scope in ("attn", "q_proj", "k_proj", "v_proj", "o_proj", "router",
                  "experts", "input_layernorm", "post_attention_layernorm",
                  "head_loss", "optimizer"):
        assert f"/{scope}/" in paths or f"({scope})" in paths, scope
    assert any("block_0/router" in p for p in every)
    assert not any("mlp/router" in p for p in every)
