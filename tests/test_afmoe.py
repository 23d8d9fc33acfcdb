"""The afmoe family (Trinity-Mini) for training: the dropless token-choice
expert layer, window and grouped-head attention on both paths, and
``AfmoeForCausalLM`` through ``jit.TrainStep``, each against the plain
float32 reference that the benchmark keeps (``benchmark/reference/afmoe.py``,
which imports nothing of paddle_tpu).

Tolerances: everything here runs in float32 at ``highest`` matmul precision
(tests/conftest.py), so the program and the reference differ by the order
of float32 sums alone: 1e-5 relative on outputs, losses and gradients, with
2e-4 absolute beside it for gradients that are sums over a few hundred terms
of either sign. The splash kernel
keeps float32 scores and accumulators in interpret mode: 2e-5.
"""
import contextlib
import importlib
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn.functional as F
from benchmark.reference import afmoe as R
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.moe import TokenChoiceMoE, last_moe_dispatch
from paddle_tpu.jit import TrainStep
from paddle_tpu.jit.functional import load_state
from paddle_tpu.models import AfmoeConfig, AfmoeForCausalLM

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

def _masked_attention(q, k, v, causal, scale, window=None):
    """Plain f32 attention on [b, s, h, d] with an explicit mask; k and v
    may hold fewer heads: query head h reads key/value head h // group."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    i = jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    mask = (j <= i) if causal else jnp.ones((q.shape[1], k.shape[1]), bool)
    if window is not None:
        mask = mask & (i - j < window)
    s = jnp.where(mask, s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _xla(q, k, v, causal, scale, window=None):
    return fa._xla_attention(q, k, v, None, None, causal, scale,
                             window=window)


@pytest.mark.parametrize("window", [None, 1, 64, 100, 256, 1000])
@pytest.mark.parametrize("kv_heads", [4, 2, 1])
@pytest.mark.parametrize("path", ["xla", "splash"])
def test_window_and_grouped_heads_match_explicit_mask(path, kv_heads,
                                                      window):
    """Both paths of the attention functional, forward and all three
    gradients, against an explicit mask: a causal window narrower than,
    equal to and wider than the sequence, heads grouped 1, 2 and 4 to a
    key/value head."""
    B, S, H, D = 2, 256, 4, 64
    q = _rand(B, S, H, D, seed=1)
    k, v = _rand(B, S, kv_heads, D, seed=2), _rand(B, S, kv_heads, D, seed=3)
    co = _rand(B, S, H, D, seed=4)
    scale = 1.0 / math.sqrt(D)
    attn = _xla if path == "xla" else fa._pallas_flash_local

    def run(f):
        return jax.value_and_grad(
            lambda q, k, v: (f(q, k, v, True, scale, window) * co).sum(),
            argnums=(0, 1, 2))(q, k, v)

    (out, grads), (ro, rg) = run(attn), run(_masked_attention)
    np.testing.assert_allclose(float(out), float(ro), rtol=2e-5)
    for g, r in zip(grads, rg):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-5)


@pytest.mark.parametrize("path", ["xla", "splash"])
def test_window_past_the_sequence_is_plain_causal(path):
    """Bitwise: a window that reaches every key builds the causal call."""
    q, k, v = (_rand(1, 128, 4, 64, seed=i) for i in range(3))
    attn = _xla if path == "xla" else fa._pallas_flash_local
    wide = attn(q, k[:, :, :2], v[:, :, :2], True, 0.125, 128)
    none = attn(q, k[:, :, :2], v[:, :, :2], True, 0.125, None)
    if path == "splash":
        assert np.array_equal(np.asarray(wide), np.asarray(none))
    else:
        np.testing.assert_allclose(np.asarray(wide), np.asarray(none),
                                   atol=1e-6)


def test_functional_takes_window_and_grouped_heads_and_says_so():
    q = paddle.to_tensor(np.asarray(_rand(2, 128, 4, 32, seed=1)))
    k = paddle.to_tensor(np.asarray(_rand(2, 128, 2, 32, seed=2)))
    v = paddle.to_tensor(np.asarray(_rand(2, 128, 2, 32, seed=3)))
    want = _masked_attention(q.value, k.value, v.value, True,
                             1 / math.sqrt(32), 48)
    out, _ = F.flash_attention(q, k, v, causal=True, window=48)
    rec = F.last_attention_dispatch()
    assert rec["window"] == 48 and rec["kv_heads"] == 2
    np.testing.assert_allclose(np.asarray(out.value), np.asarray(want),
                               atol=2e-6)
    out2 = F.scaled_dot_product_attention(q, k, v, is_causal=True, window=48)
    np.testing.assert_allclose(np.asarray(out2.value), np.asarray(want),
                               atol=2e-6)
    F.flash_attention(q, q, q, causal=True)
    rec = F.last_attention_dispatch()
    assert rec["window"] is None and rec["kv_heads"] == 4


@pytest.mark.parametrize("bad", ["heads", "not_causal", "zero"])
def test_functional_refuses_what_no_path_computes(bad):
    q = paddle.to_tensor(np.zeros((1, 128, 6, 32), "float32"))
    kv = paddle.to_tensor(np.zeros((1, 128, 4 if bad == "heads" else 3, 32),
                                   "float32"))
    kw = {"heads": dict(causal=True), "not_causal": dict(window=8),
          "zero": dict(causal=True, window=0)}[bad]
    with pytest.raises(ValueError):
        F.flash_attention(q, kv, kv, **kw)


def test_gpt_call_builds_the_same_kernel_and_blocks(monkeypatch):
    """The MHA causal call of the GPT cells: the library's MHA maker, a
    ``CausalMask`` a head, PR 29's blocks; window and grouping key other
    kernel objects and leave this one alone."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    made = []
    for name in ("make_splash_mha_single_device",
                 "make_splash_mqa_single_device"):
        real = getattr(sk, name)
        monkeypatch.setattr(sk, name, lambda mask, _n=name, _r=real, **kw: (
            made.append((_n, mask, kw)), _r(mask, **kw))[1])
    fa._splash_kernel.cache_clear()
    for s, heads in ((1024, 12), (2048, 16)):
        kernel = fa._splash_kernel(heads, s, s, True, True, None, False)
        name, mask, kw = made[-1]
        assert name == "make_splash_mha_single_device"
        assert len(mask.masks) == heads and all(
            type(m) is sm.CausalMask for m in mask.masks)
        assert kw["block_sizes"] == sk.BlockSizes(
            block_q=1024, block_kv=1024, block_kv_compute=512,
            block_q_dkv=1024, block_kv_dkv=1024, block_kv_dkv_compute=512,
            use_fused_bwd_kernel=True)
        q = jax.ShapeDtypeStruct((2, s, heads, 64), jnp.float32)
        n = len(made)
        jax.eval_shape(lambda q: fa._pallas_flash_local(q, q, q, True, 1.0),
                       q)       # the call asks for that very object
        assert len(made) == n and fa._splash_kernel(
            heads, s, s, True, True, None, False) is kernel
    n = len(made)
    windowed = fa._splash_kernel(8, 2048, 2048, True, True, 512, True)
    assert made[-1][0] == "make_splash_mqa_single_device" and len(made) == n + 1
    assert all(type(m) is sm.LocalMask for m in made[-1][1].masks)
    assert windowed is not fa._splash_kernel(16, 2048, 2048, True, True,
                                             None, False)
    assert fa._splash_blocks(8192, 8192)["block_q"] == 1024
    fa._splash_kernel.cache_clear()


def test_grouped_call_copies_no_keys(monkeypatch):
    """Traced for the chip: the kernels' key/value operands keep the
    key/value heads ([b, kv, s, d]); nothing of [b, heads, s, d] is made
    from them."""
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    q = jax.ShapeDtypeStruct((2, 256, 8, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 256, 2, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q, k, v: F.flash_attention(
        Tensor(q), Tensor(k), Tensor(v), causal=True,
        window=128)[0].value)(q, kv, kv)
    rec = fa.last_attention_dispatch()
    assert rec["backend"] == "pallas" and rec["kernel"] == "splash_fused"
    assert rec["window"] == 128 and rec["kv_heads"] == 2
    text = str(jaxpr)
    assert "bf16[2,2,4,256,64]" in text          # q by group
    assert "bf16[2,2,256,64]" in text            # k, v by key/value head
    assert "concatenate" not in text and "gather" not in text
    fa._splash_kernel.cache_clear()


# ---------------------------------------------------------- expert layer

E, K, D_MODEL, D_EXP = 8, 2, 32, 16


def _moe_leaves(seed=0):
    rng = np.random.default_rng(seed)
    g = lambda *s, sc: jnp.asarray(rng.standard_normal(s).astype("f4") * sc)
    return {"router_w": g(D_MODEL, E, sc=0.5),
            "exp_w1": g(E, D_MODEL, D_EXP, sc=0.2),
            "exp_w3": g(E, D_MODEL, D_EXP, sc=0.2),
            "exp_w2": g(E, D_EXP, D_MODEL, sc=0.2),
            "sh_w1": g(D_MODEL, D_EXP, sc=0.2),
            "sh_w3": g(D_MODEL, D_EXP, sc=0.2),
            "sh_w2": g(D_EXP, D_MODEL, sc=0.2)}


def _ref_cfg(offset=0, top_k=K):
    return dict(E=E, top_k=top_k, route_norm=True, route_scale=2.826,
                offset=offset)


def _share(p, bias, held, offset, shared=False):
    """The program's layer holding experts offset .. offset + held."""
    from paddle_tpu.models.afmoe import _swiglu
    sh = None
    if shared:
        sh = _swiglu(D_MODEL, D_EXP, AfmoeConfig())
        sh.gate_proj.weight.value = p["sh_w1"]
        sh.up_proj.weight.value = p["sh_w3"]
        sh.down_proj.weight.value = p["sh_w2"]
    m = TokenChoiceMoE(D_MODEL, D_EXP, E, K, experts_held=held,
                       expert_offset=offset, shared_expert=sh,
                       route_scale=2.826)
    m.router.weight.value = p["router_w"]
    m.expert_bias.value = bias
    sl = slice(offset, offset + held)
    m.experts.w1.value, m.experts.w3.value, m.experts.w2.value = (
        p["exp_w1"][sl], p["exp_w3"][sl], p["exp_w2"][sl])
    return m


def test_shares_add_up_to_the_uncut_layer():
    """8 experts in 4 shares of 2: the shares' routed parts plus the
    shared expert counted once equal the uncut reference's layer output,
    and every share counts the same tokens by expert."""
    p, x = _moe_leaves(), _rand(3, 20, D_MODEL, seed=5)
    bias = _rand(E, seed=6, scale=0.1)
    want, counts = R.moe_forward(p, x, bias, _ref_cfg())
    total = 0
    for share in range(4):
        y, c = _share(p, bias, 2, 2 * share, shared=share == 0)(
            paddle.to_tensor(np.asarray(x)))
        total = total + np.asarray(y.value)
        assert np.array_equal(np.asarray(c.value), np.asarray(counts))
    np.testing.assert_allclose(total, np.asarray(want), atol=5e-6)
    assert last_moe_dispatch() == {
        "kernel": "xla_ragged_dot", "experts_held": 2,
        "experts_published": 8, "top_k": 2, "rows_ladder": (48, 96),
        "rows_bound": 96, "tiling": {"w1_w3": (96, 32, 16), "w2": (96, 16, 32)},
        "combine": {"kernel": "xla_ragged_dot_by_token_tile",
                    "token_tile": 64},
        "activation": "silu", "score": "sigmoid",
        "router_input": "expert_input"}


@pytest.mark.parametrize("tokens,top_k,held,published,ladder", [
    (16384, 6, 16, 64, (30720, 73728)),         # train-smallthinker-16k
    (16384, 6, 16, 128, (15360, 36864)),        # train-kanana-2-8k
    (16384, 8, 16, 128, (20480, 49152)),        # train-trinity-mini-8k
    (60, 2, 2, 8, (48, 96)),
    (60, 2, 4, 8, (80, 128)),       # 2 shares are every row it can land
    (60, 2, 8, 8, (128,)),          # an uncut layer: one share is all
    (4, 2, 2, 8, (16,))])
def test_rows_ladder_from_shapes(tokens, top_k, held, published, ladder):
    """1.25 and 3 even shares of the assignments in whole tiles, at most
    what any routing lands; equal rungs are one, and the last is
    ``rows_bound``."""
    e = TokenChoiceMoE(8, 4, published, top_k, experts_held=held).experts
    assert e.rows_ladder(tokens) == ladder
    assert e.rows_bound(tokens) == ladder[-1]


@pytest.mark.parametrize("held,offset,bias,rung", [
    (8, 0, (10., 9.), 0), (4, 2, (10., 9.), 0), (2, 2, (10., 0.), 0),
    (2, 1, (10., 9.), 1), (2, 0, (10., 0.), 1), (2, 0, (10., 9.), 2)])
def test_every_token_to_one_expert_drops_nothing(held, offset, bias, rung):
    """A bias that sends every token to expert 0 (and to expert 1): on a
    share of 2 that lands a part of an even routing's rows (experts 2 and
    3: the first rung), twice them or somewhat more (experts 1 and 2;
    experts 0 and 1 without the second bias: the last rung) or four
    times, which the dense path takes; on the uncut layer every row the
    layer has. The call runs at the first rung that holds what landed,
    as ``rows_rung_total`` says.
    Output and every gradient equal the reference's: nothing is
    dropped."""
    p, x = _moe_leaves(1), _rand(2, 30, D_MODEL, seed=7)
    bias = jnp.asarray(bias + (0.,) * 6, jnp.float32)
    m = _share(p, bias, held, offset)
    xt = paddle.to_tensor(np.asarray(x))
    xt.stop_gradient = False
    y, counts = m(xt)
    m.note_load(counts)
    counts = np.asarray(counts.value)
    assert counts[0] == 60 and counts.sum() == 120
    landed = counts[offset:offset + held].sum()
    ladder = m.experts.rows_ladder(60)
    assert last_moe_dispatch()["rows_ladder"] == ladder
    took = sum(landed > r for r in ladder)
    assert (rung == 2) == (took == len(ladder)) == (
        landed > m.experts.rows_bound(60))
    assert rung == 2 or rung == took
    assert np.array_equal(np.asarray(m.rows_rung_total.value),
                          np.eye(3, dtype="f4")[rung])
    sl = slice(offset, offset + held)

    def ref(xv, rw, w1, w3, w2):
        return R.moe_forward({"router_w": rw, "exp_w1": w1, "exp_w3": w3,
                              "exp_w2": w2}, xv, bias, _ref_cfg(offset),
                             shared=False)[0]
    args = (x, p["router_w"], p["exp_w1"][sl], p["exp_w3"][sl],
            p["exp_w2"][sl])
    np.testing.assert_allclose(np.asarray(y.value), np.asarray(ref(*args)),
                               atol=5e-6)
    (y * y).sum().backward()
    want = jax.grad(lambda *a: (ref(*a) ** 2).sum(), range(5))(*args)
    got = (xt.grad, m.router.weight.grad, m.experts.w1.grad,
           m.experts.w3.grad, m.experts.w2.grad)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g.value), np.asarray(r),
                                   rtol=1e-5, atol=2e-4)


@pytest.mark.parametrize("held,offset", [(8, 0), (4, 2), (2, 2)])
def test_expert_layer_under_checkpoint_gives_the_same_gradients(held,
                                                                offset):
    """The layer is a function of values (the counts come out, nothing
    goes through a side channel): under ``jax.checkpoint`` it traces and
    gives the gradients it gives without; with one rung and with two
    beside the dense path in one conditional."""
    from paddle_tpu.jit.functional import functional_call, raw_state
    p, x = _moe_leaves(2), _rand(2, 24, D_MODEL, seed=8)
    m = _share(p, _rand(E, seed=9, scale=0.1), held, offset, shared=True)
    assert len(m.experts.rows_ladder(48)) == {8: 1, 4: 2, 2: 2}[held]
    params, buffers = raw_state(m)

    def loss(params, x):
        (y, counts), _ = functional_call(m, params, buffers, x)
        return (y ** 2).sum() + 0.0 * counts.sum(), counts

    plain = jax.grad(loss, (0, 1), has_aux=True)(params, x)
    remat = jax.jit(jax.grad(jax.checkpoint(loss), (0, 1), has_aux=True))(
        params, x)
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(remat)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


def _routing(tokens, top_k, published, held, seed, keep=None):
    """A random choice of ``top_k`` distinct experts of ``published`` a
    token, the first ``held`` held here; ``keep(token, expert)`` False
    sends an assignment elsewhere."""
    rng = np.random.default_rng(seed)
    sel = np.stack([rng.permutation(published)[:top_k]
                    for _ in range(tokens)]).astype("int32")
    if keep is not None:
        stays = np.array([[bool(keep(t, e)) for e in row]
                          for t, row in enumerate(sel)])
        sel = np.where(~stays & (sel < held), sel + held, sel)
    return jnp.asarray(sel)


def _rung_index(moe, sel, held, rows):
    """(tok, back, landed) of a rung of ``rows`` sorted rows, as
    ``moe._routed_sorted`` takes them from ``moe._sorted_index``."""
    tokens, k = sel.shape
    order, _, _, perm, col, tile_sizes, landed = moe._sorted_index(
        sel, sel < held, held)
    first = lambda a: jnp.pad(a, (0, max(0, rows - tokens * k)))[:rows]
    return first(order) // k, (first(perm), first(col), tile_sizes,
                               landed), int(landed)


@contextlib.contextmanager
def _chip_product(moe, monkeypatch, backend):
    """``backend`` "tgmm": the chip's path of the layer off the chip, the
    library's megablox kernels interpreted; "xla": this backend's own."""
    if backend == "tgmm":
        import functools
        import types
        lib = moe._megablox()
        monkeypatch.setattr(moe, "_on_tpu", lambda: True)
        monkeypatch.setattr(moe, "_megablox", lambda: types.SimpleNamespace(
            gmm=functools.partial(lib.gmm, interpret=True),
            tgmm=functools.partial(lib.tgmm, interpret=True)))
    yield
    monkeypatch.undo()


# tokens, top_k, published, held, which assignments stay, rows
_ROWS_TO_TOKENS = {
    # the ladder's two rungs at one routing, and a rung past every row
    "first_rung": (60, 2, 8, 2, None, 48),
    "last_rung": (60, 2, 8, 2, None, 96),
    "rows_past_every_assignment": (4, 2, 8, 2, None, 16),
    # tokens 10 .. 19 land nothing here
    "tokens_with_no_local_assignment": (
        60, 2, 8, 4, lambda t, e: not 10 <= t < 20, 80),
    # two tiles of 256 tokens, the second owns no row
    "an_empty_tile_of_tokens": (512, 2, 8, 4, lambda t, e: t < 256, 512),
    "every_token_on_one_held_expert": (60, 2, 8, 4, lambda t, e: e == 1, 80),
    # 300 tokens: a tile of 256 and 44 of the next
    "tokens_the_tile_does_not_divide": (300, 2, 8, 4, None, 384),
    "three_tiles_two_of_them_ragged": (700, 3, 8, 2, None, 1024),
}


@pytest.mark.parametrize("backend", ["xla", "tgmm"])
@pytest.mark.parametrize("case", sorted(_ROWS_TO_TOKENS))
def test_rows_go_back_to_their_tokens(case, backend, monkeypatch):
    """``moe._rows_to_tokens`` (the rows in token order, then one grouped
    product over tiles of tokens) against the sum it replaces,
    ``zeros.at[tok].add(y)`` over the rows that landed, to the last bit in
    float32 (products with 1.0, sums of at most ``top_k`` terms a token in
    the rows' own order); rows past what landed hold NaN and must not
    enter it. By this backend's ragged dot and by the chip's kernel,
    interpreted."""
    moe = importlib.import_module("paddle_tpu.distributed.moe")
    tokens, top_k, published, held, keep, rows = _ROWS_TO_TOKENS[case]
    sel = _routing(tokens, top_k, published, held, 3, keep)
    tok, back, landed = _rung_index(moe, sel, held, rows)
    assert 0 < landed <= rows
    tile = moe._token_tile(tokens)
    assert back[2].shape == (-(-tokens // tile),) and int(back[2].sum()) \
        == landed
    if case == "an_empty_tile_of_tokens":
        assert np.asarray(back[2]).tolist() == [landed, 0]
    y = _rand(rows, 24, seed=4)
    live = (jnp.arange(rows) < landed)[:, None]
    want = jnp.zeros((tokens, 24)).at[tok].add(jnp.where(live, y, 0))
    with _chip_product(moe, monkeypatch, backend):
        got = jax.jit(moe._rows_to_tokens, static_argnums=2)(
            jnp.where(live, y, jnp.nan), back, tokens)
    assert got.shape == want.shape
    assert np.array_equal(np.asarray(got), np.asarray(want))
    if keep is not None:
        assert (np.abs(np.asarray(got)).max(axis=1) == 0).any()


def _parent_sorted(x, w1, w3, w2, wgt, here, index, rows, act):
    """``moe._routed_sorted`` as it stood before the rows went back by a
    grouped product: the gather differentiated as it stands and the
    combine a scatter-add."""
    moe = importlib.import_module("paddle_tpu.distributed.moe")
    order, pos, sizes, *_, landed = index
    T, k = here.shape
    pos = jnp.where(here & (pos < rows), pos, rows)
    slot = jnp.pad(order, (0, max(0, rows - T * k)))[:rows]
    tok = slot // k
    live = (jnp.arange(rows) < landed)[:, None]
    dot = lambda a, b: jax.lax.ragged_dot(a, b, sizes)
    y = moe._glu(jnp.where(live, x[tok], 0), w1, w3, w2, dot, act)
    y = jnp.where(live, y, 0) * moe._sorted_weights(wgt, slot, pos)[:, None]
    return jnp.zeros_like(x).at[tok].add(y)


@pytest.mark.parametrize("backend", ["xla", "tgmm"])
@pytest.mark.parametrize("act", ["silu", "relu"])
@pytest.mark.parametrize("rows", [48, 96])
def test_sorted_path_against_the_scatter_add_formulation(rows, act, backend,
                                                         monkeypatch):
    """A rung's output and its gradients by x, the three stacks and the
    weights equal the parent formulation's (``x[tok]`` differentiated as it
    stands, the combine ``.at[tok].add``) to float32 round-off, at both
    rungs of the ladder, SwiGLU and ReGLU, by this backend's products and
    by the chip's kernels interpreted."""
    moe = importlib.import_module("paddle_tpu.distributed.moe")
    p, x = _moe_leaves(4), _rand(60, D_MODEL, seed=9)
    sel = _routing(60, K, E, 2, 11)
    here = sel < 2
    wgt = jnp.abs(_rand(60, K, seed=12)) + 0.1
    index = moe._sorted_index(sel, here, 2)
    assert int(index[-1]) <= 48
    data = (x, p["exp_w1"][:2], p["exp_w3"][:2], p["exp_w2"][:2], wgt)
    cot = _rand(60, D_MODEL, seed=13)

    def grads(path):
        out, pull = jax.vjp(lambda *d: path(*d, here, index, rows,
                                            moe._GATES[act]), *data)
        return (out, *pull(cot))
    want = grads(_parent_sorted)
    with _chip_product(moe, monkeypatch, backend):
        got = grads(moe._routed_sorted)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-5)


def test_the_transposes_of_both_uses_are_gathers():
    """The combine's transpose is ``d_out[tok]`` and the dispatch's is the
    combine's sum: differentiated, neither use holds a scatter, and the
    gradients are the scatter-add formulation's."""
    moe = importlib.import_module("paddle_tpu.distributed.moe")
    sel = _routing(60, 2, 8, 2, 5)
    tok, back, landed = _rung_index(moe, sel, 2, 48)
    live = (jnp.arange(48) < landed)[:, None]
    y, x = _rand(48, 16, seed=6), _rand(60, 16, seed=7)
    uses = {
        "combine": (lambda y: moe._combine(60, jnp.where(live, y, 0), tok,
                                           back),
                    lambda y: jnp.zeros((60, 16)).at[tok].add(
                        jnp.where(live, y, 0)), y),
        "dispatch": (lambda x: jnp.where(
                         live, moe._tokens_to_rows(60, x, tok, back), 0),
                     lambda x: jnp.where(live, x[tok], 0), x)}
    for name, (new, old, arg) in uses.items():
        loss = lambda f: lambda a: (f(a) ** 2).sum()
        assert "scatter" in str(jax.make_jaxpr(jax.grad(loss(old)))(arg))
        assert "scatter" not in str(jax.make_jaxpr(jax.grad(loss(new)))(arg)), \
            name
        np.testing.assert_allclose(
            np.asarray(jax.grad(loss(new))(arg)),
            np.asarray(jax.grad(loss(old))(arg)), rtol=1e-6, atol=1e-6,
            err_msg=name)


def _conds(jaxpr):
    """Every ``cond`` equation of a jaxpr, those inside others too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _conds(sub)


def test_nothing_sized_by_a_rung_crosses_the_conditional():
    """Differentiated as it stands, the ladder's conditional would hand
    its backward pass every rung's residuals (each branch writes noughts
    for the others': on the chip, 14 ms a step and 1.7 GiB in the
    SmallThinker cell). The layer's conditional keeps its inputs alone:
    in the gradient of a recomputed layer every conditional (the
    forward's, its recomputed copy, which has no reader and which the
    compiler drops, and the backward's) has rungs + 1 branches and none
    hands on an array as long as a rung."""
    from paddle_tpu.jit.functional import functional_call, raw_state
    m = _share(_moe_leaves(2), jnp.zeros((E,)), 2, 0)
    params, buffers = raw_state(m)
    x = _rand(2, 30, D_MODEL, seed=8)
    ladder = m.experts.rows_ladder(60)
    assert len(ladder) == 2 and not set(ladder) & {60, 120, D_MODEL, D_EXP}

    def loss(params, x):
        (y, _), _ = functional_call(m, params, buffers, x)
        return (y ** 2).sum()
    conds = list(_conds(jax.make_jaxpr(jax.grad(jax.checkpoint(loss),
                                                (0, 1)))(params, x).jaxpr))
    assert [len(c.params["branches"]) for c in conds] == [3, 3, 3]
    for c in conds:
        assert not any(set(v.aval.shape) & set(ladder) for v in c.outvars)


@pytest.mark.parametrize("policy,conditionals", [
    ("full", 2), ("dots", 2), ("nothing_saveable", 3)])
def test_a_block_that_reads_the_result_keeps_it(policy, conditionals):
    """The layer's conditional runs its path again inside its backward
    branch. A block whose backward pass reads the layer's result (a norm
    behind it, as in ``models/afmoe.py``) would run it a third time in
    the recomputed forward; the named policies keep the result
    (``moe.EXPERTS_RESULT``), so the compiled gradient holds the forward's
    conditional and the backward's and no other."""
    from paddle_tpu.distributed.moe import EXPERTS_RESULT
    from paddle_tpu.distributed.recompute import resolve_checkpoint_policy
    from paddle_tpu.jit.functional import functional_call, raw_state
    m = _share(_moe_leaves(2), jnp.zeros((E,)), 2, 0)
    params, buffers = raw_state(m)
    x = _rand(2, 30, D_MODEL, seed=8)
    policy = getattr(jax.checkpoint_policies, policy, None) \
        or resolve_checkpoint_policy(policy)

    def block(params, x):
        (y, _), _ = functional_call(m, params, buffers, x)
        return x + y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + 1.0)

    def loss(params, x):
        return (jax.checkpoint(block, policy=policy)(params, x) ** 2).sum()
    grad = jax.jit(jax.grad(loss, (0, 1)))
    assert EXPERTS_RESULT in str(jax.make_jaxpr(grad)(params, x))
    text = grad.lower(params, x).compile().as_text()
    assert text.count(" conditional(") == conditionals


def test_note_load_keeps_counts_and_moves_the_bias():
    m = _share(_moe_leaves(), jnp.zeros((E,)), 8, 0)
    c = jnp.asarray([5., 1., 3., 3., 0., 9., 3., 0.])
    m.note_load(c)
    m.note_load(2 * c)
    assert np.array_equal(np.asarray(m.expert_load.value), 2 * np.asarray(c))
    assert np.array_equal(np.asarray(m.expert_load_total.value),
                          3 * np.asarray(c))
    np.testing.assert_allclose(
        np.asarray(m.expert_bias.value),
        2e-3 * np.asarray([-1, 1, 0, 0, 1, -1, 0, 1], "f4"))


def test_note_load_counts_one_rung_a_call():
    """Every call adds one to one entry of ``rows_rung_total``, the rung
    the forward took by the same rule (the dense path last), so the
    entries sum to the steps; before any call there is no ladder to count
    by and the buffer stands."""
    p = _moe_leaves(3)
    m = _share(p, jnp.zeros((E,)), 2, 0)
    m.note_load(jnp.ones((E,)))
    assert not np.asarray(m.rows_rung_total.value).any()
    want = np.zeros(3, "f4")
    for step, bias in enumerate([(0., 0.), (10., 0.), (10., 9.), (0., 0.),
                                 (-10., -10.)]):
        m.expert_bias.value = jnp.asarray(bias + (0.,) * 6, jnp.float32)
        y, counts = m(paddle.to_tensor(np.asarray(
            _rand(2, 30, D_MODEL, seed=20 + step))))
        m.note_load(counts)
        landed = float(np.asarray(counts.value)[:2].sum())
        took = sum(landed > r for r in (48, 96))
        want[took] += 1
        got = np.asarray(m.rows_rung_total.value)
        assert np.array_equal(got, want) and got.sum() == step + 1
    assert want.all()           # the tight rung, the last, the dense path


def test_moe_layer_refuses_experts_not_published():
    with pytest.raises(ValueError):
        TokenChoiceMoE(8, 4, 8, 2, experts_held=4, expert_offset=6)


# ----------------------------------------------------------------- model

ARCH = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
            head_dim=8, intermediate_size=48, moe_intermediate_size=16,
            num_shared_experts=1, num_experts=4, num_experts_published=8,
            expert_offset=2, num_experts_per_tok=3, vocab_size=64,
            num_hidden_layers=3, num_dense_layers=1,
            layer_types=["sliding_attention", "sliding_attention",
                         "full_attention"],
            sliding_window=4, rms_norm_eps=1e-5, rope_theta=10000,
            route_norm=True, route_scale=2.826, load_balance_coeff=0.001,
            initializer_range=0.02)
JOB = dict(compute_dtype="float32", master_weights=True, learning_rate=1e-3,
           beta1=0.9, beta2=0.999, epsilon=1e-8, weight_decay=0.01)


def _program(recompute, fused_loss_chunk=8, seed=5):
    from benchmark.drivers.train_steps_afmoe import program_layout
    cfg = AfmoeConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=3, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        layer_types=ARCH["layer_types"], sliding_window=4, num_experts=8,
        experts_held=4, expert_offset=2, num_experts_per_tok=3,
        max_seq_len=64, recompute=recompute,
        fused_loss_chunk=fused_loss_chunk)
    model = AfmoeForCausalLM(cfg)
    leaves = R.init_params(ARCH, seed, jnp.float32)
    layout = program_layout(ARCH)
    assert set(layout) == {n for n, _ in model.named_parameters()}
    load_state(model, {prog: leaves[leaf] if at is None else leaves[leaf][at]
                       for prog, (leaf, at) in layout.items()})
    return model, leaves, layout


@pytest.mark.parametrize("recompute", [False, True])
def test_model_two_train_steps_match_the_reference(recompute):
    """``AfmoeForCausalLM`` + ``make_loss_fn()`` + ``AdamW`` +
    ``TrainStep``: both losses, every leaf's first gradient (Adam's first
    moment over 1 - beta1), the counts of tokens by expert of both steps'
    routing and the expert bias after them, against the reference's two
    steps; with and without per-block recomputation."""
    model, leaves, layout = _program(recompute)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, beta1=0.9, beta2=0.999, epsilon=1e-8,
        weight_decay=0.01, parameters=model.parameters())
    step = TrainStep(model, model.make_loss_fn(), opt)
    ids = np.random.default_rng(0).integers(0, 64, (2, 16))
    bias0 = jnp.zeros((2, 8))
    (_, counts1), grads = jax.value_and_grad(
        lambda p: R.loss_whole(p, bias0, jnp.asarray(ids), ARCH),
        has_aux=True)(leaves)
    ref = R.train_readings(ARCH, JOB, 5, [ids, ids])

    loss1 = float(step(paddle.to_tensor(ids), paddle.to_tensor(ids)))
    load1 = np.stack([np.asarray(step.buffers[f"model.block_{i}.mlp."
                                              "expert_load"]) for i in (1, 2)])
    for prog, (leaf, at) in layout.items():
        got = np.asarray(step.opt_state[prog]["moment1"]) / (1 - 0.9)
        want = np.asarray(grads[leaf] if at is None else grads[leaf][at])
        np.testing.assert_allclose(got, want, atol=2e-5, err_msg=prog)
    loss2 = float(step(paddle.to_tensor(ids), paddle.to_tensor(ids)))
    np.testing.assert_allclose([loss1, loss2], ref["losses"], rtol=1e-5)
    assert np.array_equal(load1, np.asarray(counts1))
    assert np.array_equal(load1, ref["expert_load"])
    bias = np.stack([np.asarray(step.buffers[f"model.block_{i}.mlp."
                                             "expert_bias"]) for i in (1, 2)])
    np.testing.assert_allclose(bias, ref["expert_bias"], atol=1e-7)
    assert np.abs(bias).max() > 0          # the step moved it
    # the running counts hold both steps'
    for i in (1, 2):
        total = np.asarray(step.buffers[f"model.block_{i}.mlp."
                                        "expert_load_total"])
        last = np.asarray(step.buffers[f"model.block_{i}.mlp.expert_load"])
        assert np.array_equal(total, load1[i - 1] + last)


def test_model_eval_returns_logits_and_leaves_buffers():
    model, leaves, _ = _program(False)
    model.eval()
    ids = np.random.default_rng(1).integers(0, 64, (2, 16))
    logits = model(paddle.to_tensor(ids))
    assert tuple(logits.shape) == (2, 16, 64)
    loss, _ = R.loss_whole(leaves, jnp.zeros((2, 8)), jnp.asarray(ids), ARCH)
    got = model.make_loss_fn()(logits, paddle.to_tensor(ids))
    np.testing.assert_allclose(float(got), float(loss), rtol=1e-5)
    assert all(float(np.abs(np.asarray(b.value)).max()) == 0.0
               for n, b in model.named_buffers())


def test_every_new_layer_registers_its_scope():
    """``TrainStep.op_scopes()`` maps device operations by these names."""
    model, _, _ = _program(True)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = TrainStep(model, model.make_loss_fn(), opt)
    ids = np.random.default_rng(0).integers(0, 64, (2, 16))
    step(paddle.to_tensor(ids), paddle.to_tensor(ids))
    paths = " ".join(set(step.op_scopes().values()))
    for scope in ("router", "experts", "shared_expert", "attn", "q_norm",
                  "k_norm", "gate_proj", "input_layernorm",
                  "post_attention_layernorm", "pre_mlp_layernorm",
                  "post_mlp_layernorm", "head_loss", "optimizer"):
        assert f"/{scope}/" in paths or f"({scope})" in paths, scope


def _compiled_step(recompute=True):
    """(the table, the opcodes of every instruction) of a tiny
    ``AfmoeForCausalLM`` step compiled on this backend."""
    import collections
    from paddle_tpu.analysis.hlo_cost import parse_hlo_module
    moe = importlib.import_module("paddle_tpu.distributed.moe")
    moe._traced_once.cache_clear()      # a path is traced once a process
    model, _, _ = _program(recompute)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = TrainStep(model, model.make_loss_fn(), opt)
    ids = np.random.default_rng(0).integers(0, 64, (2, 16))
    step(paddle.to_tensor(ids), paddle.to_tensor(ids))
    rec = step._step_program
    module = parse_hlo_module(rec.hlo_text())
    return rec.op_scopes(), collections.Counter(
        (i.opcode, i.attrs.get("custom_call_target", ""))
        for c in module.computations.values() for i in c.instrs)


def test_expert_layer_names_its_sort_dispatch_products_and_combine(
        monkeypatch):
    """The four scopes inside ``experts`` reach the compiled step's table,
    forward and transposed, through the conditional's branches and the
    paths' shared ``jax.jit``; and they are names alone: without them the
    step compiles to the same instructions."""
    from paddle_tpu.analysis import runtime_profile as rp
    table, opcodes = _compiled_step()
    read = [rp.read_scope(p, n) for n, p in table.items()
            if "/experts/" in p]
    inside = {}
    for r in read:
        names = r["scope"].split("/")
        for name in ("sort", "dispatch", "products", "combine"):
            if name in names:
                assert "experts" in names[:names.index(name)]
                inside.setdefault(name, set()).add(r["pass"])
    # the recomputed block keeps the layer's result, so its forward
    # conditional has no reader and is gone: the sort alone is recomputed
    assert inside["sort"] == {"forward", "recompute"}
    # (the dense path recomputes each expert's products: its own
    # ``jax.checkpoint``)
    assert inside["dispatch"] == inside["combine"] == {"forward", "backward"}
    assert inside["products"] == {"forward", "backward", "recompute"}
    # the rows go back to their tokens by a gather and a grouped product,
    # in the combine (forward) and in the transpose of the dispatch's
    # gather (backward): the gather of the rows into token order and the
    # product's one-hot operand read those two scopes, so the three shares
    # still add up to `experts` (the chip's kernel by its own name:
    # tests/test_tpu_lowering.py; this backend inlines its ragged dot
    # from a function that carries no path), and no scatter is left in the
    # sorted path at all (as scatter-adds of model-width rows they were
    # 14% of the SmallThinker cell's step)
    def leaves_under(name, pass_):
        return {p.rsplit("/", 1)[-1] for p in table.values()
                if f"/{name}/" in p and "routed_sorted" in p
                and rp.read_scope(p)["pass"] == pass_}
    for name, pass_ in (("combine", "forward"), ("dispatch", "backward")):
        assert {"gather", "eq"} <= leaves_under(name, pass_), (name, pass_)
    assert not any("scatter" in n or "scatter" in p
                   for n, p in table.items() if "routed_sorted" in p)
    assert any("transpose(jvp(jit(routed_sorted)))" in p
               for p in table.values())
    # every operation of the sorted path is under one of the three: what
    # is left to `experts` itself is the conditional and its plumbing
    inner = {m.group(1) for p in table.values() for m in [re.search(
        r"jit\(routed_sorted\)\)*/([\w\-]+)/", p)] if m}
    assert inner == {"dispatch", "products", "combine"}, inner

    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope", lambda name: (
        contextlib.nullcontext() if name in (
            "sort", "dispatch", "products", "combine") else real(name)))
    bare_table, bare_opcodes = _compiled_step()
    monkeypatch.undo()
    importlib.import_module(
        "paddle_tpu.distributed.moe")._traced_once.cache_clear()
    assert not any(re.search(r"/(dispatch|products|combine)/", p)
                   for p in bare_table.values())
    assert bare_opcodes == opcodes and len(bare_table) == len(table)


def test_default_layer_kinds_follow_the_published_pattern():
    cfg = AfmoeConfig()
    kinds = cfg.kinds()
    assert len(kinds) == 32 and kinds[:4] == (
        "sliding_attention",) * 3 + ("full_attention",)
    assert kinds.count("full_attention") == 8
    with pytest.raises(ValueError):
        AfmoeConfig(num_hidden_layers=2, layer_types=["full_attention"]
                    ).kinds()


def test_use_moe_recompute_points_at_the_new_layer():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    model = GPTForCausalLM(GPTConfig(
        vocab_size=32, hidden_size=16, num_layers=1, num_heads=2,
        max_seq_len=8, use_moe=True, moe_experts=2, recompute=True))
    with pytest.raises(NotImplementedError, match="TokenChoiceMoE"):
        model(paddle.to_tensor(np.zeros((1, 8), "int64")))
