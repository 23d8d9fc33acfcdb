"""GPTAttention's training path writes and reads the attention kernel's
[b, h, s, d] layout (three products of x with the thirds of ``qkv.weight``,
the softmax scale in q's; ``out_proj`` as one contraction over heads): held
here, in float32 at ``highest``, to a plain fused-[B, S, 3H] attention from
the same leaves, to the serving path, to itself under tensor parallelism,
and to a state dict in the layout every checkpoint has.
"""
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.jit.functional import functional_call, raw_state
from paddle_tpu.models.gpt import (GPTAttention, GPTConfig, GPTForCausalLM,
                                   gpt_tiny)

fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")

LEAVES = ("qkv.weight", "qkv.bias", "out_proj.weight", "out_proj.bias")


@pytest.fixture(autouse=True)
def fresh_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


def _layer(nh, hd, seed=0):
    """A GPTAttention and random leaves for it (biases too: they start at
    zero, which would hide a bias added to the wrong head)."""
    paddle.seed(seed)
    layer = GPTAttention(GPTConfig(hidden_size=nh * hd, num_heads=nh))
    rng = np.random.default_rng(seed)
    params = {n: jnp.asarray(0.05 * rng.standard_normal(v.shape), jnp.float32)
              for n, v in raw_state(layer)[0].items()}
    assert tuple(params) == LEAVES
    assert params["qkv.weight"].shape == (nh * hd, 3 * nh * hd)
    return layer, params


def _x(b, s, h, seed=1):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (b, s, h)), jnp.float32)


def _plain(params, x, nh):
    """One fused product to [B, S, 3H], split [q; k; v], heads of
    H // nh, causal softmax attention, ``out_proj``."""
    b, s, h = x.shape
    qkv = x @ params["qkv.weight"] + params["qkv.bias"]
    q, k, v = (t.reshape(b, s, nh, h // nh) for t in jnp.split(qkv, 3, -1))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(h // nh)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return ctx.reshape(b, s, h) @ params["out_proj.weight"] \
        + params["out_proj.bias"]


def _value_and_grads(fn, params, x, co):
    return jax.value_and_grad(
        lambda p, x: (fn(p, x) * co).sum(), argnums=(0, 1))(params, x)


@pytest.mark.parametrize("path", ["xla", "splash"])
@pytest.mark.parametrize("hd", [64, 128])
def test_forward_and_every_gradient_match_plain_attention(hd, path,
                                                          monkeypatch):
    """Both paths under the head-major entry: XLA attention at scale 1,
    and the library kernel (interpreted here) with no scale of its own."""
    nh, b, s = 2, 2, 128
    layer, params = _layer(nh, hd)
    x, co = _x(b, s, nh * hd), _x(b, s, nh * hd, seed=2)
    if path == "splash":
        # the gate reads the backend; the kernel below it interprets
        monkeypatch.setattr(fa, "_pallas_ok", lambda *a, **kw: True)

    def program(p, x):
        return functional_call(layer, p, {}, x)[0]

    out = program(params, x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_plain(params, x, nh)), atol=2e-6)
    (_, (gp, gx)), (_, (rp, rx)) = (
        _value_and_grads(program, params, x, co),
        _value_and_grads(lambda p, x: _plain(p, x, nh), params, x, co))
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), atol=2e-5)
    for name in LEAVES:
        np.testing.assert_allclose(np.asarray(gp[name]),
                                   np.asarray(rp[name]), atol=2e-5,
                                   err_msg=name)


@pytest.mark.parametrize("hd", [64, 128])
def test_training_forward_matches_a_prefill_through_the_cache(hd):
    """``forward(x)`` against ``forward(x, cache, pos=0)``: head-major
    products with the scale in q against the fused product, [B, S, nh, hd]
    rows and ``cached_attention``'s own scale."""
    nh, b, s = 2, 2, 32
    layer, params = _layer(nh, hd)
    x = _x(b, s, nh * hd)
    cache = tuple(jnp.zeros((b, s, nh, hd), jnp.float32) for _ in range(2))
    train = functional_call(layer, params, {}, x)[0]
    (serve, (kc, _)), _ = functional_call(layer, params, {}, x, cache, 0)
    np.testing.assert_allclose(np.asarray(train), np.asarray(serve),
                               atol=2e-6)
    # the rows the cache got are the fused product's k third, unscaled
    k = (x @ params["qkv.weight"] + params["qkv.bias"])[
        ..., nh * hd:2 * nh * hd].reshape(b, s, nh, hd)
    np.testing.assert_allclose(np.asarray(kc), np.asarray(k), atol=2e-6)


def test_eager_tape_reaches_every_leaf():
    """Outside jit the layer records on the tape: ``backward()`` leaves a
    gradient on each of the four leaves, equal to the plain one."""
    nh, hd = 2, 64
    layer, params = _layer(nh, hd)
    for (name, p) in layer.named_parameters():
        p.value = params[name]
    x, co = _x(1, 16, nh * hd), _x(1, 16, nh * hd, seed=2)
    (layer(paddle.to_tensor(x)) * paddle.to_tensor(co)).sum().backward()
    _, (rp, _) = _value_and_grads(lambda p, x: _plain(p, x, nh), params, x,
                                  co)
    for name, p in layer.named_parameters():
        np.testing.assert_allclose(np.asarray(p.grad.value),
                                   np.asarray(rp[name]), atol=2e-5,
                                   err_msg=name)


def test_mp2_matches_one_device():
    """The same layer under ``mp=2`` on virtual devices: columns of
    ``qkv`` and rows of ``out_proj`` sharded as their annotations say,
    heads over "mp" between them, the partial products reduced and the
    bias added once."""
    nh, hd, b, s = 4, 64, 2, 32
    layer, params = _layer(nh, hd)
    x, co = _x(b, s, nh * hd), _x(b, s, nh * hd, seed=2)

    def program(p, x):
        return functional_call(layer, p, {}, x)[0]

    want_out = program(params, x)
    _, (want_p, want_x) = _value_and_grads(program, params, x, co)

    mesh = dist.init_mesh({"mp": 2}, devices=jax.devices()[:2])
    shardings = dist.param_sharding(layer, mesh)
    assert shardings["qkv.weight"].spec == jax.sharding.PartitionSpec(
        None, "mp")
    sharded = {n: jax.device_put(v, shardings[n]) for n, v in params.items()}
    out = jax.jit(program)(sharded, x)
    _, (got_p, got_x) = jax.jit(
        lambda p, x: _value_and_grads(program, p, x, co))(sharded, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(got_x), np.asarray(want_x),
                               atol=2e-5)
    for name in LEAVES:
        np.testing.assert_allclose(np.asarray(got_p[name]),
                                   np.asarray(want_p[name]), atol=2e-5,
                                   err_msg=name)
    # the reduction is there: row-parallel out_proj sums over "mp"
    assert "all-reduce" in jax.jit(program).lower(sharded, x).compile(
        ).as_text()


def test_state_dict_of_the_fused_layout_loads_and_gives_the_same_logits(
        tmp_path):
    """A checkpoint holds ``qkv.weight`` [H, 3H] laid out [q; k; v],
    ``qkv.bias`` [3H] and ``out_proj.weight`` [H, H]: saved, loaded into a
    fresh model, its logits are those of the benchmark's plain reference
    (one fused product, ``jnp.split`` in three) on the same arrays."""
    from benchmark.drivers.train_steps import _BLOCK as names
    from benchmark.reference import gpt as R
    cfg = gpt_tiny()
    paddle.seed(3)
    model = GPTForCausalLM(cfg)
    rng = np.random.default_rng(3)
    for name, p in model.named_parameters():     # biases off zero
        if name.endswith(".bias"):
            p.value = jnp.asarray(0.05 * rng.standard_normal(p.shape),
                                  jnp.float32)
    state = model.state_dict()
    h = cfg.hidden_size
    assert {n: tuple(v.shape) for n, v in state.items()
            if ".block_0.attn." in n} == {
        "gpt.block_0.attn.qkv.weight": (h, 3 * h),
        "gpt.block_0.attn.qkv.bias": (3 * h,),
        "gpt.block_0.attn.out_proj.weight": (h, h),
        "gpt.block_0.attn.out_proj.bias": (h,)}
    paddle.save(state, str(tmp_path / "gpt.pdparams"))

    paddle.seed(4)
    fresh = GPTForCausalLM(cfg)
    fresh.set_state_dict(paddle.load(str(tmp_path / "gpt.pdparams")))
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, (2, 24))
    model.eval(), fresh.eval()
    logits = fresh(paddle.to_tensor(ids)).value
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(model(paddle.to_tensor(ids)).value),
        atol=1e-6)

    w = {n: jnp.asarray(v.value, jnp.float32) for n, v in state.items()}
    wte = w["gpt.embeddings.word_embeddings.weight"]
    x = wte[ids] + w["gpt.embeddings.position_embeddings.weight"][
        :ids.shape[1]]
    for i in range(cfg.num_layers):
        x = R.block_forward({k: w[f"gpt.block_{i}.{v}"]
                             for k, v in names.items()}, x, cfg.num_heads,
                            R._dot)
    want = R._dot(R._layer_norm(x, w["gpt.ln_f.weight"],
                                w["gpt.ln_f.bias"]), wte.T)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               atol=2e-5)


def test_dispatch_record_says_which_layout_reached_the_kernel(monkeypatch):
    """Traced as on the chip: the model's call hands the kernel its own
    layout, the public functional a transpose of paddle's; off the chip
    (XLA attention) the record reads ``seq_major``."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.core.tensor import Tensor
    layer, params = _layer(2, 64)
    x = jax.ShapeDtypeStruct((2, 128, 128), jnp.bfloat16)

    def program():      # a new function a trace: jax keeps traces by it
        return lambda p, x: functional_call(layer, p, {}, x)[0]

    jax.eval_shape(program(), params, x)
    assert fa.last_attention_dispatch()["layout"] == "seq_major"
    assert fa.last_attention_dispatch()["backend"] == "xla"

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    jax.eval_shape(program(), params, x)
    rec = fa.last_attention_dispatch()
    assert rec["backend"] == "pallas" and rec["layout"] == "head_major"
    assert rec["kernel"] == "splash_fused" and rec["kv_heads"] == 2

    q = jax.ShapeDtypeStruct((2, 128, 2, 64), jnp.bfloat16)
    jax.eval_shape(lambda q: F.flash_attention(
        Tensor(q), Tensor(q), Tensor(q), causal=True)[0].value, q)
    rec = fa.last_attention_dispatch()
    assert rec["backend"] == "pallas" and rec["layout"] == "seq_major"
    fa._splash_kernel.cache_clear()


def test_jit_save_exports_the_forward_with_its_layout_pin(tmp_path):
    """The q, k, v products pin their weight's layout with
    ``with_layout_constraint``, a custom call ``jax.export`` refuses
    unless told it is safe: ``jit.save`` admits that one target
    (``jit.functional.EXPORT_DISABLED_CHECKS``), for the CPU and the TPU,
    and the loaded program gives the model's logits."""
    cfg = gpt_tiny()
    paddle.seed(6)
    model = GPTForCausalLM(cfg)
    model.eval()
    ids = np.random.default_rng(7).integers(0, cfg.vocab_size, (2, 16))
    want = model(paddle.to_tensor(ids)).numpy()
    path = str(tmp_path / "gpt")
    paddle.jit.save(model, path,
                    input_spec=[paddle.jit.InputSpec([2, 16], "int64")])
    with open(path + ".pdmodel", "rb") as f:
        exported = jax.export.deserialize(f.read())
    assert set(exported.platforms) == {"cpu", "tpu"}
    assert "LayoutConstraint" in exported.mlir_module()
    got = paddle.jit.load(path)(paddle.to_tensor(ids)).numpy()
    np.testing.assert_allclose(got, want, atol=1e-5)
