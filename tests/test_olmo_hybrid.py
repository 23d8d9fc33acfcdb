"""The Olmo Hybrid family (allenai/Olmo-Hybrid-7B) for training: the gated
delta rule's chunked kernel (``kernels/gated_delta.py``) on both paths of
``F.gated_delta_rule``, against the recurrence token by token, and
``OlmoHybridForCausalLM`` through ``jit.TrainStep`` against the plain
float32 reference that the benchmark keeps
(``benchmark/reference/olmo_hybrid.py``, which imports nothing of
paddle_tpu).

Tolerances: everything here runs in float32 at ``highest`` matmul precision
(tests/conftest.py). The chunked rule and the recurrence sum in different
orders: 2e-5 of the output's largest entry, 5e-5 of each gradient's. The
model and the reference then differ by float32 sums alone: 1e-5 relative on
logits and losses, 1e-4 on gradients (the recurrence's sums), and every
planted fault moves a gradient's norm by more than 100 times that.
"""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn.functional as F
from benchmark.reference import olmo_hybrid as R
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit import TrainStep
from paddle_tpu.jit.functional import load_state
from paddle_tpu.kernels import gated_delta as G
from paddle_tpu.models import OlmoHybridConfig, OlmoHybridForCausalLM

la = importlib.import_module("paddle_tpu.nn.functional.linear_attention")


@pytest.fixture(autouse=True)
def fresh_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


# ------------------------------------------------------------- the rule

def _recurrence(q, k, v, g, b):
    """The rule token by token in float32: o [B, H, S, dv]."""
    def head(q, k, v, g, b):
        def step(s, x):
            qt, kt, vt, gt, bt = x
            s = jnp.exp(gt) * s
            s = s + jnp.outer(bt * (vt - s @ kt), kt)
            return s, s @ qt
        s0 = jnp.zeros((v.shape[-1], q.shape[-1]), jnp.float32)
        _, o = jax.lax.scan(step, s0, tuple(x.astype(jnp.float32)
                                            for x in (q, k, v, g, b)))
        return o
    return jax.vmap(jax.vmap(head))(q, k, v, g, b)


def _operands(B=1, H=2, S=128, dk=16, dv=32, beta=(0.0, 2.0), seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, H, S, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, H, S, dk)))
    v = jax.random.normal(ks[2], (B, H, S, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, H, S)))
    b = jax.random.uniform(ks[4], (B, H, S), minval=beta[0], maxval=beta[1])
    return (q, k, v, g, b), jax.random.normal(ks[5], (B, H, S, dv))


def _value_and_grads(f, args, co):
    return jax.value_and_grad(
        lambda *a: jnp.sum(f(*a).astype(jnp.float32) * co),
        argnums=(0, 1, 2, 3, 4))(*args), f(*args)


@pytest.mark.parametrize("chunk", [8, 16, 64, 128])
@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("beta", [(0.0, 2.0), (1.0, 2.0)])
def test_chunked_rule_matches_the_recurrence(path, chunk, beta):
    """Output and the gradients of q, k, v, g and beta, at chunks of 8 to
    64, with beta over (0, 2) and over (1, 2), where the transition's
    eigenvalue 1 - beta is negative (``allow_neg_eigval``)."""
    args, co = _operands(beta=beta)
    rule = (lambda *a: G.gated_delta_xla(*a, chunk=chunk)) if path == "xla" \
        else (lambda *a: G.gated_delta_pallas(*a, chunk=chunk,
                                              interpret=True))
    (_, grads), out = _value_and_grads(rule, args, co)
    (_, want_g), want = _value_and_grads(_recurrence, args, co)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5 * scale)
    for name, a, b in zip("qkvgb", grads, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5 * float(jnp.max(jnp.abs(b))),
                                   err_msg=name)


def test_strong_decay_stays_finite():
    """A decay of e^-40 a position (the chunk's cumulative log-decay far
    below float32's range) computes without an overflow."""
    (q, k, v, g, b), co = _operands()
    g = jnp.full_like(g, -40.0)
    out = G.gated_delta_xla(q, k, v, g, b, chunk=64)
    want = _recurrence(q, k, v, g, b)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("on_tpu", [False, True])
def test_functional_dispatches_and_pads(on_tpu, monkeypatch):
    """``F.gated_delta_rule``: Pallas where ``_on_tpu`` (interpreted here),
    else the XLA path with a reason; a sequence that is no multiple of the
    chunk is padded and cut back; the record names chunk, heads, dk, dv."""
    monkeypatch.setattr(la, "_on_tpu", lambda: on_tpu)
    (q, k, v, g, b), _ = _operands(S=100)
    out = F.gated_delta_rule(*(Tensor(x) for x in (q, k, v, g, b)),
                             chunk=32)
    rec = F.last_linear_attention_dispatch()
    assert rec == {"backend": "pallas" if on_tpu else "xla",
                   "reason": "ok" if on_tpu else "not on TPU", "chunk": 32,
                   "heads": 2, "dk": 16, "dv": 32}
    np.testing.assert_allclose(
        np.asarray(out.value),
        np.asarray(_recurrence(q, k, v, g, b)), atol=2e-5)


def test_rule_refuses_a_chunk_that_is_no_power_of_two():
    (q, k, v, g, b), _ = _operands()
    with pytest.raises(ValueError, match="power of two"):
        G.gated_delta_xla(q, k, v, g, b, chunk=24)


def test_short_convolution_and_l2_norm():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 10, 4))
    w = jax.random.normal(jax.random.PRNGKey(2), (12, 4))
    y = F.short_convolution(Tensor(x), Tensor(w)).value
    wh = w.reshape(3, 4, 4)
    for t in range(10):
        acc = sum(x[:, :, t - 3 + j] * wh[:, :, j] for j in range(4)
                  if t - 3 + j >= 0)
        np.testing.assert_allclose(np.asarray(y[:, :, t]),
                                   np.asarray(jax.nn.silu(acc)), rtol=1e-5,
                                   atol=1e-6)
    n = F.l2_normalize(Tensor(x), 1e-6, 0.5).value
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(n, axis=-1)), 0.5,
                               rtol=1e-5)


# ----------------------------------------------------------------- model

ARCH = dict(hidden_size=64, intermediate_size=96, num_attention_heads=2,
            num_key_value_heads=2, linear_num_key_heads=2,
            linear_num_value_heads=2, linear_key_head_dim=16,
            linear_value_head_dim=32, linear_conv_kernel_dim=4,
            linear_allow_neg_eigval=True, vocab_size=256,
            num_hidden_layers=4,
            layer_types=["linear_attention"] * 3 + ["full_attention"],
            layers_kept=[0, 1, 2, 3], rms_norm_eps=1e-6,
            rope_parameters={"rope_theta": None}, initializer_range=0.02)
JOB = dict(compute_dtype="float32", master_weights=True, learning_rate=1e-3,
           beta1=0.9, beta2=0.999, epsilon=1e-8, weight_decay=0.01)
SEQ = 128


def _config(**kw):
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "initializer_range")
    base = {k: ARCH[k] for k in keys}
    base.update(max_position_embeddings=SEQ)
    base.update(kw)
    return OlmoHybridConfig(**base)


def _program(recompute=False, fused_loss_chunk=32, seed=5):
    from benchmark.drivers.train_steps_olmo_hybrid import program_layout
    model = OlmoHybridForCausalLM(_config(
        recompute=recompute, fused_loss_chunk=fused_loss_chunk))
    leaves = R.init_params(ARCH, seed, jnp.float32)
    layout = program_layout(R.layer_kinds(ARCH))
    assert set(layout) == {n for n, _ in model.named_parameters()}
    load_state(model, {prog: leaves[leaf] if at is None else leaves[leaf][at]
                       for prog, (leaf, at) in layout.items()})
    return model, leaves, layout


def test_defaults_are_the_published_config_and_its_count():
    cfg = OlmoHybridConfig()
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.num_attention_heads,
            cfg.intermediate_size, cfg.vocab_size) == (
        3840, 32, 30, 11008, 100352)
    assert (cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim) == (96, 192, 4)
    assert cfg.kinds()[:8] == (("linear_attention",) * 3
                               + ("full_attention",)) * 2
    with paddle.LazyGuard():
        model = OlmoHybridForCausalLM(OlmoHybridConfig(num_hidden_layers=4,
                                                       vocab_size=12544))
    assert sum(int(np.prod(p.shape)) for p in model.parameters()) \
        == 928_862_196
    with pytest.raises(ValueError):
        OlmoHybridConfig(num_hidden_layers=4, layer_types=["x"] * 4).kinds()


@pytest.mark.parametrize("on_tpu", [False, True])
def test_logits_match_the_reference(on_tpu, monkeypatch):
    """Both paths of the delta rule (the kernel interprets here)."""
    monkeypatch.setattr(la, "_on_tpu", lambda: on_tpu)
    model, leaves, _ = _program()
    model.eval()
    ids = np.random.default_rng(1).integers(0, 256, (2, SEQ))
    logits = model(paddle.to_tensor(ids))
    assert F.last_linear_attention_dispatch()["backend"] == (
        "pallas" if on_tpu else "xla")
    cfg = R.settings(ARCH)

    @jax.jit
    def reference(w, ids):
        x = w["wte"][ids]
        for i, kind in enumerate(cfg["kinds"]):
            x = R.layer_forward(R.layer_params(w, i, cfg), x, kind, cfg,
                                R._dot)
        return R._dot(R._rms(x, w["lnf_g"], 1e-6), w["head_w"])
    want = reference(leaves, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(logits.value), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_model_two_train_steps_match_the_reference():
    """``OlmoHybridForCausalLM`` + ``make_loss_fn()`` + ``AdamW`` +
    ``TrainStep`` with per-block recomputation, as the cell trains: both
    losses, every leaf's first gradient (Adam's first moment over 1 -
    beta1) and every leaf's change after both steps, against the
    reference's two steps."""
    model, leaves, layout = _program(recompute=True)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, beta1=0.9, beta2=0.999, epsilon=1e-8,
        weight_decay=0.01, parameters=model.parameters())
    step = TrainStep(model, model.make_loss_fn(), opt)
    ids = np.random.default_rng(0).integers(0, 256, (2, SEQ))
    grads = jax.jit(jax.grad(
        lambda p: R.loss_whole(p, jnp.asarray(ids), ARCH)))(leaves)
    ref = R.train_readings(ARCH, JOB, 5, [ids, ids])
    loss1 = float(step(paddle.to_tensor(ids), paddle.to_tensor(ids)))
    for prog, (leaf, at) in layout.items():
        got = np.asarray(step.opt_state[prog]["moment1"]) / (1 - 0.9)
        want = np.asarray(grads[leaf] if at is None else grads[leaf][at])
        np.testing.assert_allclose(got, want, rtol=1e-4,
                                   atol=1e-4 * np.abs(want).max(),
                                   err_msg=prog)
    loss2 = float(step(paddle.to_tensor(ids), paddle.to_tensor(ids)))
    np.testing.assert_allclose([loss1, loss2], ref["losses"], rtol=1e-5)
    from benchmark.drivers.train_steps_afmoe import _by_leaf
    start = {n: p.value for n, p in model.named_parameters()}
    change = _by_leaf({n: np.asarray(R.leaf_norms(step.params[n] - start[n],
                                                  layout[n][0]))
                       for n in layout}, layout)
    for leaf, want in ref["change_norms"].items():
        np.testing.assert_allclose(change[leaf], want, rtol=2e-3,
                                   err_msg=leaf)


@pytest.mark.parametrize("fault", ["beta_single", "no_decay", "no_l2norm",
                                   "no_conv", "no_gate"])
def test_planted_faults_fall_outside_the_tolerance(fault):
    """The reference with each fault against the reference: some leaf's
    gradient moves by more than 100 times the 1e-4 that holds the program
    to the reference above."""
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (1, SEQ)))
    leaves = R.init_params(ARCH, 5, jnp.float32)
    norms = jax.jit(lambda p, f: {
        n: jnp.linalg.norm(g) for n, g in jax.grad(
            lambda p: R.loss_whole(p, ids, ARCH, fault=f))(p).items()},
        static_argnums=1)
    good, bad = norms(leaves, None), norms(leaves, fault)
    gap = max(float(abs(bad[n] - v) / v) for n, v in good.items())
    assert gap > 1e-2, gap


def test_every_new_layer_registers_its_scope():
    """The compiled step's scope table holds the linear mixer's scope and
    its three children in the forward pass and the backward pass, and the
    full layer's ``attn``."""
    model, _, _ = _program(recompute=True)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = TrainStep(model, model.make_loss_fn(), opt)
    ids = paddle.to_tensor(np.random.default_rng(0).integers(0, 256,
                                                             (1, SEQ)))
    step(ids, ids)
    paths = set(step.op_scopes().values())
    for child in ("short_conv", "delta_rule", "gated_norm"):
        for pass_ in ("jvp(", "transpose("):
            assert any(f"linear_attn/{child}" in p and p.count(pass_)
                       for p in paths), (child, pass_)
    assert any("block_3/attn/" in p for p in paths)
