"""The Olmo Hybrid family (allenai/Olmo-Hybrid-7B) for training: the gated
delta rule's chunked kernel (``kernels/gated_delta.py``) on both paths of
``F.gated_delta_rule``, against the recurrence token by token; the short
convolution's kernels (``kernels/short_conv.py``) against its XLA path; and
``OlmoHybridForCausalLM`` through ``jit.TrainStep`` against the plain
float32 reference that the benchmark keeps
(``benchmark/reference/olmo_hybrid.py``, which imports nothing of
paddle_tpu).

Tolerances: everything here runs in float32 at ``highest`` matmul precision
(tests/conftest.py). The chunked rule and the recurrence sum in different
orders: 2e-5 of the output's largest entry, 5e-5 of each gradient's; on
bfloat16 operands the output and the gradients of q, k and v come back in
bfloat16, whose rounding (2^-8 of an entry) adds to that. Fed the same
values as bfloat16 (a float32 operand split into three bfloat16 parts
against them) and as float32 (full precision everywhere), the chunk bodies
agree to float32 rounding: 1e-6 of each array's largest entry (they read
4.4e-7 at most; a split that dropped its low part reads 3e-6 - 7e-6). The
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


def _as_bf16(args, co):
    """q, k, v and the output's cotangent rounded to bfloat16, g and beta
    as they are: the values both dtypes of a comparison are fed."""
    q, k, v, g, b = args
    r = lambda x: x.astype(jnp.bfloat16)
    return (r(q), r(k), r(v), g, b), r(co).astype(jnp.float32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunk", [8, 16, 64, 128])
@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("beta", [(0.0, 2.0), (1.0, 2.0)])
def test_chunked_rule_matches_the_recurrence(path, chunk, beta, dtype):
    """Output and the gradients of q, k, v, g and beta, at chunks of 8 to
    64, with beta over (0, 2) and over (1, 2), where the transition's
    eigenvalue 1 - beta is negative (``allow_neg_eigval``); q, k, v in
    float32 (every product at full precision) or in bfloat16 (the split
    products), the recurrence fed the same values in float32."""
    args, co = _operands(beta=beta)
    rounding = 0.0              # of the results that come back in q's dtype
    if dtype == "bfloat16":
        args, co = _as_bf16(args, co)
        rounding = 2.0 ** -8
    rule = (lambda *a: G.gated_delta_xla(*a, chunk=chunk)) if path == "xla" \
        else (lambda *a: G.gated_delta_pallas(*a, chunk=chunk,
                                              interpret=True))
    (_, grads), out = _value_and_grads(rule, args, co)
    wide = tuple(x.astype(jnp.float32) for x in args)
    (_, want_g), want = _value_and_grads(_recurrence, wide, co)
    assert out.dtype == args[2].dtype
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want),
                               atol=(2e-5 + rounding) * scale)
    for name, a, b in zip("qkvgb", grads, want_g):
        ulp = rounding if name in "qkv" else 0.0
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b),
            atol=(5e-5 + ulp) * float(jnp.max(jnp.abs(b))), err_msg=name)


def _bodies(args, co, chunk):
    """The chunk bodies over the sequence as the XLA path runs them, with
    their float32 results kept: (o, dq, dk, dv, dg, db)."""
    q, k, v, g, b = args
    fwd, bwd = (jax.vmap(jax.vmap(f)) for f in (G._chunk_fwd, G._chunk_bwd))

    def forward(h, xs):
        qc, kc, vc, gc, bc = xs
        o, h_next = fwd(qc, kc, vc, gc[:, :, None], bc[:, :, None], h)
        return h_next, (o, h)

    def backward(dh, xs):
        qc, kc, vc, gc, bc, hc, doc = xs
        *grads, dh_prev = bwd(qc, kc, vc, gc[:, :, None], bc[:, :, None],
                              hc, doc, dh)
        return dh_prev, tuple(grads)

    xs = tuple(G._chunks(x, chunk) for x in args)
    h0 = jnp.zeros(q.shape[:2] + (q.shape[-1], v.shape[-1]), jnp.float32)
    _, (o, states) = jax.lax.scan(forward, h0, xs)
    _, grads = jax.lax.scan(backward, h0, xs + (
        states, G._chunks(co.astype(v.dtype), chunk)), reverse=True)
    return tuple(G._unchunks(x) for x in (o,) + grads)


def _dots(jaxpr):
    """Every ``dot_general`` of a jaxpr, through its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dots(sub)


@pytest.mark.parametrize("chunk", [16, 128])
def test_split_products_are_full_precision_on_bf16_operands(chunk):
    """The same values fed once as bfloat16 (``_mm`` splits the float32
    side of 4 products forward and 14 backward) and once upcast to float32
    (every product at ``HIGHEST``, as the jaxpr shows): the output and
    all five gradients of the chunk bodies agree to float32 rounding."""
    args, co = _as_bf16(*_operands(S=256))
    wide = tuple(x.astype(jnp.float32) for x in args)
    narrow = _bodies(args, co, chunk)
    full = _bodies(wide, co, chunk)
    assert G.split_products(*(x.dtype for x in args[:3])) == {
        "forward": 4, "backward": 14}
    assert G.split_products(*(x.dtype for x in wide[:3])) == {
        "forward": 0, "backward": 0}
    precisions = {e.params["precision"] for e in _dots(
        jax.make_jaxpr(lambda *a: _bodies(a[:5], a[5], chunk))(*wide, co))}
    assert precisions == {(jax.lax.Precision.HIGHEST,) * 2}, precisions
    for name, a, b in zip(["o", "dq", "dk", "dv", "dg", "db"], narrow, full):
        assert a.dtype == b.dtype == jnp.float32, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6 * float(jnp.max(jnp.abs(b))),
                                   rtol=0, err_msg=name)


@pytest.mark.parametrize("c", [8, 16, 64, 128])
def test_unit_lower_inverse(c):
    """``_unit_lower_inverse`` of A as ``_prepare`` builds it from
    unit-norm keys, beta in (0, 2) and the decays ``_operands`` draws:
    (I + A) T = I, and T is numpy's inverse in float64 (the half-row merges
    from block 8 on leave the other rows as they are)."""
    (_, k, _, g, b), _ = _operands(S=c, seed=3)
    p = G._prepare(k[0, 0], g[0, 0][None], b[0, 0][None])
    a = np.where(np.asarray(p["strict"]),
                 np.asarray(p["b"] * p["kk"] * p["e"], np.float64), 0.0)
    t = np.asarray(p["tinv"], np.float64)
    eye = np.eye(c)
    np.testing.assert_allclose((eye + a) @ t, eye, atol=1e-5, rtol=0)
    want = np.linalg.inv(eye + a)
    np.testing.assert_allclose(t, want, atol=1e-5 * np.abs(want).max(),
                               rtol=0)


def test_strong_decay_stays_finite():
    """A decay of e^-40 a position (the chunk's cumulative log-decay far
    below float32's range) computes without an overflow."""
    (q, k, v, g, b), co = _operands()
    g = jnp.full_like(g, -40.0)
    out = G.gated_delta_xla(q, k, v, g, b, chunk=64)
    want = _recurrence(q, k, v, g, b)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("on_tpu", [False, True])
def test_functional_dispatches_and_pads(on_tpu, dtype, monkeypatch):
    """``F.gated_delta_rule``: Pallas where ``_on_tpu`` (interpreted here),
    else the XLA path with a reason; a sequence that is no multiple of the
    chunk is padded and cut back; the record names chunk, heads, dk, dv
    and, once the gradient is traced too, the products each body split:
    none on float32 operands, 4 and 14 on bfloat16 ones; and the name under
    which recomputation keeps the rule's output and chunk states."""
    monkeypatch.setattr(la, "_on_tpu", lambda: on_tpu)
    (q, k, v, g, b), co = _operands(S=100)
    split, rounding = {"forward": 0, "backward": 0}, 0.0
    if dtype == "bfloat16":
        (q, k, v, g, b), co = _as_bf16((q, k, v, g, b), co)
        split, rounding = {"forward": 4, "backward": 14}, 2.0 ** -8
    ts = [paddle.to_tensor(np.asarray(x)) for x in (q, k, v, g, b)]
    for t in ts:
        t.stop_gradient = False
    out = F.gated_delta_rule(*ts, chunk=32)
    (out.astype("float32") * paddle.to_tensor(np.asarray(co))).sum() \
        .backward()
    rec = F.last_linear_attention_dispatch()
    assert rec == {"backend": "pallas" if on_tpu else "xla",
                   "reason": "ok" if on_tpu else "not on TPU", "chunk": 32,
                   "heads": 2, "dk": 16, "dv": 32, "split_products": split,
                   "residual": G.DELTA_RULE_RESIDUAL}
    assert ts[0].grad is not None
    want = _recurrence(*(x.astype(jnp.float32) for x in (q, k, v, g, b)))
    np.testing.assert_allclose(
        np.asarray(out.value, np.float32), np.asarray(want),
        atol=2e-5 + rounding * float(jnp.max(jnp.abs(want))))


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
    n = F.short_convolution(Tensor(x), Tensor(w), True, 1e-6, 0.5).value
    np.testing.assert_allclose(np.asarray(n), np.asarray(
        0.5 * y / jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)),
        rtol=1e-5, atol=1e-6)


def _conv_chain(x, w, co, normalize, scale, recompute):
    """``F.short_convolution`` as the mixer calls it (optionally inside a
    checkpoint under the "full" policy): output, dx and the taps' gradient
    of a weighted sum of the output."""
    from paddle_tpu.distributed.recompute import resolve_checkpoint_policy

    def f(x, w):
        return F.short_convolution(Tensor(x), Tensor(w), normalize, 1e-6,
                                   scale).value
    if recompute:
        f = jax.checkpoint(f, policy=resolve_checkpoint_policy("full"))
    y, vjp = jax.vjp(f, x, w)
    return (y,) + vjp(co.astype(y.dtype))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("width,normalize,scale,batch,seq,recompute", [
    (96, True, 96 ** -0.5, 1, 80, False),   # q: the L2 epilogue and scale
    (96, True, 1.0, 2, 64, False),          # k
    (192, False, 1.0, 2, 80, False),        # v
    (96, True, 96 ** -0.5, 2, 80, True),
    (192, False, 1.0, 1, 64, True),
])
def test_short_convolution_kernels_match_the_xla_path(
        width, normalize, scale, batch, seq, recompute, dtype, monkeypatch):
    """The Pallas path (interpreted here, tiles of 32 rows, so that 80
    positions are padded to three tiles and the rows the taps reach cross
    tile edges) against the XLA path: the output, dx and the taps'
    gradient, at the mixer's two widths, with and without the L2 epilogue
    and q's scale, at batch 1 and 2, and under per-block recomputation.
    Both compute the chain in float32: they differ by the order of float32
    sums (1e-6 of each array's largest entry), and on bfloat16 operands by
    one rounding of the output and of dx (2^-8)."""
    ks = jax.random.split(jax.random.PRNGKey(width + seq), 3)
    x = jax.random.normal(ks[0], (batch, 2, seq, width)).astype(dtype)
    w = jax.random.uniform(ks[1], (2 * width, 4), minval=-0.5, maxval=0.5)
    co = jax.random.normal(ks[2], (batch, 2, seq, width))
    monkeypatch.setattr(la, "CONV_TILE", 32)
    got = {}
    for on_tpu in (False, True):
        monkeypatch.setattr(la, "_on_tpu", lambda: on_tpu)
        monkeypatch.setattr(la, "_last_conv", {})
        got[on_tpu] = _conv_chain(x, w, co, normalize, scale, recompute)
        assert F.last_linear_attention_dispatch()["conv"] == {
            "backend": "pallas" if on_tpu else "xla",
            "reason": "ok" if on_tpu else "not on TPU",
            "tile": 32 if on_tpu else None,
            "l2": {width: normalize}}
    rounding = 2.0 ** -8 if dtype == "bfloat16" else 0.0
    for name, a, b, ulp in zip(("y", "dx", "dw"), got[True], got[False],
                               (rounding, rounding, 0.0)):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        b = np.asarray(b, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                   atol=(1e-6 + ulp) * np.abs(b).max(),
                                   err_msg=name)


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


def _program(recompute=False, fused_loss_chunk=32, seed=5, policy="full"):
    from benchmark.drivers.train_steps_olmo_hybrid import program_layout
    model = OlmoHybridForCausalLM(_config(
        recompute=recompute, recompute_policy=policy,
        fused_loss_chunk=fused_loss_chunk))
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
    """Both paths of the delta rule and of the short convolution (the
    kernels interpret here); the record names the convolutions' widths, q's
    and k's with the L2 epilogue and v's without."""
    monkeypatch.setattr(la, "_on_tpu", lambda: on_tpu)
    monkeypatch.setattr(la, "_last_conv", {})
    model, leaves, _ = _program()
    model.eval()
    ids = np.random.default_rng(1).integers(0, 256, (2, SEQ))
    logits = model(paddle.to_tensor(ids))
    rec = F.last_linear_attention_dispatch()
    assert rec["backend"] == rec["conv"]["backend"] == (
        "pallas" if on_tpu else "xla")
    assert rec["conv"]["l2"] == {16: True, 32: False}
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


def _step_gradient(policy):
    """A recomputed step's gradient (the model under ``policy``, a name of
    ``distributed.recompute`` or a ``jax.checkpoint_policies`` callable,
    with its chunked loss), the parameters to take it at, and the
    reference's gradients by program name."""
    from paddle_tpu.jit.functional import functional_call, raw_state
    model, leaves, layout = _program(recompute=True, policy=policy)
    params, buffers = raw_state(model)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (1, SEQ)))
    loss_fn = model.make_loss_fn()

    def loss(params):
        out, _ = functional_call(model, params, buffers, ids, training=True)
        return loss_fn(tuple(Tensor(x) for x in out), Tensor(ids)).value

    def reference():
        grads = jax.jit(jax.grad(lambda p: R.loss_whole(p, ids, ARCH)))(
            leaves)
        return {prog: grads[leaf] if at is None else grads[leaf][at]
                for prog, (leaf, at) in layout.items()}
    return jax.grad(loss), params, reference


def _kernels(jaxpr, found=None):
    """Pallas kernels of a jaxpr by name, through its sub-jaxprs."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = found.get(eqn.params["name"], 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernels(sub, found)
    return found


@pytest.mark.parametrize("policy", ["full", "dots"])
def test_recomputed_step_names_the_delta_rules_residual(policy):
    """Both named policies see the rule's output and chunk states under
    ``DELTA_RULE_RESIDUAL`` in the gradient of a recomputed step."""
    grad, params, _ = _step_gradient(policy)
    assert G.DELTA_RULE_RESIDUAL in str(jax.make_jaxpr(grad)(params))


@pytest.mark.parametrize("policy,forwards",
                         [("full", 1), ("dots", 1), ("nothing", 2)])
def test_recomputed_step_runs_the_forward_kernel_once_a_layer(
        policy, forwards, monkeypatch):
    """Under the named policies the gradient of a recomputed step holds the
    forward kernel once for each of the three linear layers (its output
    and chunk states are kept for the backward kernel), under
    ``nothing_saveable`` twice; the backward kernel once either way. The
    short convolution's forward kernel runs in the forward and the
    recomputed pass under every policy (q, k and v of three layers), its
    backward kernel once a call."""
    monkeypatch.setattr(la, "_on_tpu", lambda: True)
    if policy == "nothing":
        policy = jax.checkpoint_policies.nothing_saveable
    grad, params, _ = _step_gradient(policy)
    assert _kernels(jax.make_jaxpr(grad)(params).jaxpr) == {
        "gated_delta_fwd": 3 * forwards, "gated_delta_bwd": 3,
        "short_conv_fwd": 2 * 9, "short_conv_bwd": 9}


@pytest.mark.parametrize("policy", ["full", "dots"])
@pytest.mark.parametrize("on_tpu", [False, True])
def test_kept_residual_gives_the_references_gradients(on_tpu, policy,
                                                      monkeypatch):
    """With the rule's output and chunk states kept, not recomputed, the
    step's gradients on both paths and under both named policies are the
    reference's to the 1e-4 that holds the train steps to it above."""
    monkeypatch.setattr(la, "_on_tpu", lambda: on_tpu)
    grad, params, reference = _step_gradient(policy)
    got, want = jax.jit(grad)(params), reference()
    assert set(got) == set(want)
    for n, w in want.items():
        w = np.asarray(w)
        np.testing.assert_allclose(np.asarray(got[n]), w, rtol=1e-4,
                                   atol=1e-4 * np.abs(w).max(), err_msg=n)


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
