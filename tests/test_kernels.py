"""Fused Pallas kernel library (ISSUE 19): interpret-mode unit tests.

kernels/fused_ce.py runs its gridded bodies through the interpreter;
kernels/cache_write.py and kernels/mega_decode.py share ONE math body
between a grid-free wrapper (the CPU dispatch) and the blocked wrapper
the chip compiles (tests/test_tpu_lowering.py), both covered here. Identity targets are the UNFUSED chains they replace: jax.nn
softmax/logsumexp for cross-entropy, flash_attention.py's one-hot write
+ read + masked-softmax chain for the decode paths. The dispatch knobs
(PADDLE_TPU_FUSED_CE / _FUSED_CACHE_WRITE / _MEGA_DECODE) are exercised
through the real functionals, not by monkeypatching internals.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import (ce_bwd, ce_fwd, fused_paged_write,
                                fused_slot_write, mega_decode_step,
                                online_lse)
from importlib import import_module

# the functional package re-exports a *function* named flash_attention,
# shadowing the submodule on attribute access — import the module itself
fa = import_module("paddle_tpu.nn.functional.flash_attention")
loss_mod = import_module("paddle_tpu.nn.functional.loss")


def _rand(*shape, seed=0, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype("float32") * scale)


# ---------------------------------------------------------------- fused CE

class TestFusedCE:
    N, V = 24, 384

    def _fixture(self, dtype=jnp.float32, seed=3):
        rs = np.random.RandomState(seed)
        lg = jnp.asarray(rs.randn(self.N, self.V) * 3).astype(dtype)
        labels = jnp.asarray(rs.randint(0, self.V, self.N), jnp.int32)
        return lg, labels

    def test_online_lse_matches_logsumexp(self):
        lg, _ = self._fixture()
        ref = jax.scipy.special.logsumexp(lg, axis=-1)
        np.testing.assert_allclose(online_lse(lg), ref, atol=1e-5)

    def test_online_lse_padded_tail_excluded(self):
        lg, _ = self._fixture()
        vv = self.V - 96
        junk = lg.at[:, vv:].set(1e4)   # tail junk must contribute 0
        ref = jax.scipy.special.logsumexp(lg[:, :vv], axis=-1)
        np.testing.assert_allclose(online_lse(junk, valid_vocab=vv),
                                   ref, atol=1e-5)

    def test_online_lse_inf_pairing_no_nan(self):
        # reduce order is unspecified: a tree reduction can combine two
        # -inf lanes even when the row has valid columns. Leading -inf
        # entries force the sequential CPU fold through the same
        # (-inf, -inf) monoid combine — must yield 0 weight, not nan.
        lg, _ = self._fixture()
        lg = lg.at[:, :2].set(-jnp.inf)
        ref = jax.scipy.special.logsumexp(lg, axis=-1)
        out = online_lse(lg)
        assert bool(jnp.all(jnp.isfinite(out)))
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_online_lse_all_masked_row_is_neg_inf(self):
        # a fully -inf row is an empty sum: LSE is -inf, never nan
        out = online_lse(jnp.full((3, 16), -jnp.inf, jnp.float32))
        assert bool(jnp.all(out == -jnp.inf))

    def test_ce_fwd_matches_reference(self):
        lg, labels = self._fixture()
        per, lse = ce_fwd(lg, labels, interpret=True)
        ref_lse = jax.scipy.special.logsumexp(lg, axis=-1)
        ref_per = ref_lse - jnp.take_along_axis(
            lg, labels[:, None], 1)[:, 0]
        assert per.dtype == jnp.float32
        np.testing.assert_allclose(per, ref_per, atol=1e-5)
        np.testing.assert_allclose(lse, ref_lse, atol=1e-5)

    def test_ce_bwd_matches_reference(self):
        lg, labels = self._fixture()
        _, lse = ce_fwd(lg, labels, interpret=True)
        g = _rand(self.N, seed=7)
        dlg = ce_bwd(lg, labels, lse, g, interpret=True)
        ref = ((jax.nn.softmax(lg, axis=-1)
                - jax.nn.one_hot(labels, self.V)) * g[:, None])
        np.testing.assert_allclose(dlg, ref, atol=1e-5)

    def test_ce_bf16_computes_f32(self):
        lg, labels = self._fixture(dtype=jnp.bfloat16)
        per, lse = ce_fwd(lg, labels, interpret=True)
        assert per.dtype == jnp.float32
        ref = (jax.scipy.special.logsumexp(
                   lg.astype(jnp.float32), axis=-1)
               - jnp.take_along_axis(lg.astype(jnp.float32),
                                     labels[:, None], 1)[:, 0])
        # bf16 inputs, f32 accumulation: tolerance is the input grid
        np.testing.assert_allclose(per, ref, atol=5e-2)
        dlg = ce_bwd(lg, labels, lse, _rand(self.N, seed=9),
                     interpret=True)
        assert dlg.dtype == jnp.bfloat16

    def test_ce_padded_vocab_bwd_zeros_tail(self):
        lg, _ = self._fixture()
        vv = self.V - 128
        labels = jnp.asarray(
            np.random.RandomState(0).randint(0, vv, self.N), jnp.int32)
        junk = lg.at[:, vv:].set(1e4)
        per, lse = ce_fwd(junk, labels, valid_vocab=vv, interpret=True)
        ref_lse = jax.scipy.special.logsumexp(lg[:, :vv], axis=-1)
        ref_per = ref_lse - jnp.take_along_axis(
            lg, labels[:, None], 1)[:, 0]
        np.testing.assert_allclose(per, ref_per, atol=1e-5)
        dlg = ce_bwd(junk, labels, lse, _rand(self.N, seed=1),
                     valid_vocab=vv, interpret=True)
        assert bool(jnp.all(dlg[:, vv:] == 0))

    def test_ce_gridded_path_n_above_block(self):
        # the TPU kernel body: N > block_n and V > block_v, neither a
        # multiple of its block, so labels must be consumed per
        # row-block (a whole-[N] compare fails to trace here)
        N, V, bn, bv = 37, 200, 8, 64
        rs = np.random.RandomState(5)
        lg = jnp.asarray(rs.randn(N, V).astype("float32") * 3)
        labels = jnp.asarray(rs.randint(0, V, N), jnp.int32)
        per, lse = ce_fwd(lg, labels, block_n=bn, block_v=bv,
                          interpret=True)
        ref_lse = jax.scipy.special.logsumexp(lg, axis=-1)
        ref_per = ref_lse - jnp.take_along_axis(
            lg, labels[:, None], 1)[:, 0]
        np.testing.assert_allclose(per, ref_per, atol=1e-5)
        np.testing.assert_allclose(lse, ref_lse, atol=1e-5)
        g = _rand(N, seed=6)
        dlg = ce_bwd(lg, labels, lse, g, block_n=bn, block_v=bv,
                     interpret=True)
        ref = ((jax.nn.softmax(lg, axis=-1)
                - jax.nn.one_hot(labels, V)) * g[:, None])
        np.testing.assert_allclose(dlg, ref, atol=1e-5)

    def test_ce_gridded_path_padded_vocab(self):
        # gridded + valid_vocab: whole trailing vocab blocks are fully
        # masked, exercising the in-kernel -inf monoid guards
        N, V, vv, bn, bv = 20, 256, 100, 8, 64
        rs = np.random.RandomState(7)
        lg = jnp.asarray(rs.randn(N, V).astype("float32") * 3)
        junk = lg.at[:, vv:].set(1e4)
        labels = jnp.asarray(rs.randint(0, vv, N), jnp.int32)
        per, lse = ce_fwd(junk, labels, valid_vocab=vv, block_n=bn,
                          block_v=bv, interpret=True)
        ref_lse = jax.scipy.special.logsumexp(lg[:, :vv], axis=-1)
        ref_per = ref_lse - jnp.take_along_axis(
            lg, labels[:, None], 1)[:, 0]
        np.testing.assert_allclose(per, ref_per, atol=1e-5)
        dlg = ce_bwd(junk, labels, lse, _rand(N, seed=8),
                     valid_vocab=vv, block_n=bn, block_v=bv,
                     interpret=True)
        assert bool(jnp.all(dlg[:, vv:] == 0))
        assert bool(jnp.all(jnp.isfinite(dlg)))

    def test_dispatch_value_and_grad_match_unfused(self, monkeypatch):
        lg, labels = self._fixture()

        def loss_of(ce):
            return lambda x: jnp.sum(ce(x, labels) * _rand(
                self.N, seed=11))

        v0, g0 = jax.value_and_grad(
            loss_of(loss_mod._fused_softmax_ce))(lg)
        v1, g1 = jax.value_and_grad(
            loss_of(loss_mod._pallas_softmax_ce))(lg)
        np.testing.assert_allclose(v0, v1, rtol=1e-6)
        np.testing.assert_allclose(g0, g1, atol=1e-5)

    def test_cross_entropy_knob(self, monkeypatch):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        lg, labels = self._fixture()
        x = paddle.to_tensor(np.asarray(lg))
        y = paddle.to_tensor(np.asarray(labels).astype("int64"))
        base = np.asarray(F.cross_entropy(x, y).value)
        monkeypatch.setenv("PADDLE_TPU_FUSED_CE", "1")
        fused = np.asarray(F.cross_entropy(x, y).value)
        np.testing.assert_allclose(base, fused, rtol=1e-6)


# ------------------------------------------------------------ cache writes

class TestFusedSlotWrite:
    def test_identity_with_unfused(self, monkeypatch):
        cache = _rand(3, 16, 2, 8, seed=0)
        rows = _rand(3, 1, 2, 8, seed=1)
        pos = jnp.asarray([0, 7, 15], jnp.int32)
        base = fa._cache_write(cache, rows, pos)
        monkeypatch.setenv("PADDLE_TPU_FUSED_CACHE_WRITE", "1")
        fused = fa._cache_write(cache, rows, pos)
        assert bool(jnp.array_equal(base, fused))

    def test_int8_dict_identity(self, monkeypatch):
        cache = {"data": jnp.zeros((2, 8, 2, 4), jnp.int8),
                 "scale": jnp.zeros((2, 8, 2), jnp.float32)}
        rows = _rand(2, 1, 2, 4, seed=2)
        pos = jnp.asarray([3, 5], jnp.int32)
        base = fa._cache_write(cache, rows, pos)
        monkeypatch.setenv("PADDLE_TPU_FUSED_CACHE_WRITE", "1")
        fused = fa._cache_write(cache, rows, pos)
        assert bool(jnp.array_equal(base["data"], fused["data"]))
        assert bool(jnp.array_equal(base["scale"], fused["scale"]))

    def test_kernel_direct(self):
        cache = _rand(2, 6, 1, 4, seed=4)
        rows = _rand(2, 1, 1, 4, seed=5)
        pos = jnp.asarray([2, 5], jnp.int32)
        out = fused_slot_write(cache, rows, pos, interpret=True)
        ref = cache
        for b in range(2):
            ref = ref.at[b, int(pos[b])].set(rows[b, 0])
        assert bool(jnp.array_equal(out, ref))


class TestFusedPagedWrite:
    def _cache(self, dtype="float32"):
        pool = fa.paged_kv_cache(6, 4, 2, 8, dtype=dtype)
        bt = jnp.asarray([[2, 0], [5, 1], [3, 4]], jnp.int32)
        return {**pool, "bt": bt}

    def test_identity_with_unfused(self, monkeypatch):
        cache = self._cache()
        rows = _rand(3, 1, 2, 8, seed=6)
        pos = jnp.asarray([1, 6, 3], jnp.int32)
        base = fa._paged_cache_write(cache, rows, pos)
        monkeypatch.setenv("PADDLE_TPU_FUSED_CACHE_WRITE", "1")
        fused = fa._paged_cache_write(cache, rows, pos)
        assert bool(jnp.array_equal(base["pages"], fused["pages"]))

    def test_live_and_wlen_gating_identity(self, monkeypatch):
        cache = {**self._cache(),
                 "live": jnp.asarray([True, False, True]),
                 "wlen": jnp.asarray(2, jnp.int32)}
        rows = _rand(3, 3, 2, 8, seed=8)      # S=3, only first 2 land
        pos = jnp.asarray([0, 4, 2], jnp.int32)
        base = fa._paged_cache_write(cache, rows, pos)
        monkeypatch.setenv("PADDLE_TPU_FUSED_CACHE_WRITE", "1")
        fused = fa._paged_cache_write(cache, rows, pos)
        assert bool(jnp.array_equal(base["pages"], fused["pages"]))

    def test_int8_pool_identity(self, monkeypatch):
        cache = self._cache(dtype="int8")
        rows = _rand(3, 1, 2, 8, seed=9)
        pos = jnp.asarray([1, 6, 3], jnp.int32)
        base = fa._paged_cache_write(cache, rows, pos)
        monkeypatch.setenv("PADDLE_TPU_FUSED_CACHE_WRITE", "1")
        fused = fa._paged_cache_write(cache, rows, pos)
        assert bool(jnp.array_equal(base["pages"], fused["pages"]))
        assert bool(jnp.array_equal(base["scale"], fused["scale"]))

    def test_kernel_direct(self):
        pages = _rand(5, 3, 1, 2, seed=10)
        rows = _rand(4, 1, 2, seed=11)
        phys = jnp.asarray([4, 0, 2, 1], jnp.int32)
        off = jnp.asarray([0, 2, 1, 2], jnp.int32)
        valid = jnp.asarray([1, 0, 1, 1], jnp.int32)
        out = fused_paged_write(pages, rows, phys, off, valid,
                                interpret=True)
        ref = pages
        for i in range(4):
            if int(valid[i]):
                ref = ref.at[int(phys[i]), int(off[i])].set(rows[i])
        assert bool(jnp.array_equal(out, ref))


class TestGriddedKernelPaths:
    """The CPU dispatch runs the grid-free wrappers; these run the
    BLOCKED wrappers the chip compiles (``gridded=True``) through the
    interpreter, so their grid index maps, L-block offsets and scratch
    carry are covered on CPU. (fused-CE has one gridded form only.)"""

    def test_slot_write_gridded(self, monkeypatch):
        from paddle_tpu.kernels import cache_write
        monkeypatch.setattr(cache_write, "_L_BLOCK", 8)   # 2 L-blocks
        cache = _rand(3, 16, 2, 8, seed=20)
        rows = _rand(3, 1, 2, 8, seed=21)
        pos = jnp.asarray([0, 7, 15], jnp.int32)
        out = fused_slot_write(cache, rows, pos, interpret=True,
                               gridded=True)
        ref = cache
        for b in range(3):
            ref = ref.at[b, int(pos[b])].set(rows[b, 0])
        assert bool(jnp.array_equal(out, ref))

    def test_paged_write_gridded(self):
        pages = _rand(5, 3, 1, 2, seed=22)
        rows = _rand(4, 1, 2, seed=23)
        phys = jnp.asarray([4, 0, 2, 1], jnp.int32)
        off = jnp.asarray([0, 2, 1, 2], jnp.int32)
        valid = jnp.asarray([1, 0, 1, 1], jnp.int32)
        out = fused_paged_write(pages, rows, phys, off, valid,
                                interpret=True, gridded=True)
        ref = pages
        for i in range(4):
            if int(valid[i]):
                ref = ref.at[int(phys[i]), int(off[i])].set(rows[i])
        assert bool(jnp.array_equal(out, ref))

    @pytest.mark.parametrize("nh,nkv", [(4, 4), (4, 2)])
    def test_mega_decode_gridded(self, monkeypatch, nh, nkv):
        from paddle_tpu.kernels import mega_decode
        monkeypatch.setattr(mega_decode, "_L_BLOCK", 4)   # 4 L-blocks
        q, k, v, kc, vc, pos = _decode_fixture(nh=nh, nkv=nkv, L=16)
        ctx_g, kc_g, vc_g = mega_decode_step(q, k, v, kc, vc, pos,
                                             interpret=True,
                                             gridded=True)
        ctx_w, kc_w, vc_w = mega_decode_step(q, k, v, kc, vc, pos,
                                             interpret=True)
        np.testing.assert_allclose(np.asarray(ctx_g), np.asarray(ctx_w),
                                   atol=1e-6)
        assert bool(jnp.array_equal(kc_g, kc_w))
        assert bool(jnp.array_equal(vc_g, vc_w))


# ------------------------------------------------- fused decode attention

def _decode_fixture(nh=4, nkv=2, B=3, L=16, hd=8, int8=False, seed=0):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, 1, nh, hd), jnp.float32)
    k = jnp.asarray(rs.randn(B, 1, nkv, hd), jnp.float32)
    v = jnp.asarray(rs.randn(B, 1, nkv, hd), jnp.float32)
    if int8:
        kc = {"data": jnp.asarray(rs.randint(-90, 90, (B, L, nkv, hd)),
                                  jnp.int8),
              "scale": jnp.asarray(np.abs(rs.randn(B, L, nkv)) * 0.02,
                                   jnp.float32)}
        vc = {"data": jnp.asarray(rs.randint(-90, 90, (B, L, nkv, hd)),
                                  jnp.int8),
              "scale": jnp.asarray(np.abs(rs.randn(B, L, nkv)) * 0.02,
                                   jnp.float32)}
    else:
        kc = jnp.asarray(rs.randn(B, L, nkv, hd), jnp.float32)
        vc = jnp.asarray(rs.randn(B, L, nkv, hd), jnp.float32)
    # corners: empty cache (pos 0), last slot (L-1), duplicate pos —
    # the states dead/eos slots park the decode loop in
    pos = jnp.asarray([0, L - 1, 5], jnp.int32)
    return q, k, v, kc, vc, pos


def _run_cached_attention(q, k, v, kc, vc, pos):
    ctx, kc2, vc2 = fa.cached_attention(q, k, v, kc, vc, pos)
    arr = getattr(ctx, "value", ctx)
    return np.asarray(arr), kc2, vc2


class TestFusedDecodeAttention:
    @pytest.mark.parametrize("nh,nkv", [(4, 4), (4, 2)])
    def test_identity_with_unfused(self, monkeypatch, nh, nkv):
        args = _decode_fixture(nh=nh, nkv=nkv)
        ctx0, kc0, vc0 = _run_cached_attention(*args)
        monkeypatch.setenv("PADDLE_TPU_FUSED_CACHE_WRITE", "1")
        ctx1, kc1, vc1 = _run_cached_attention(*args)
        # caches: bit-exact (same rows blended at the same slots);
        # ctx: softmax reassociation only (PERF.md PR 19 bound)
        assert bool(jnp.array_equal(kc0, kc1))
        assert bool(jnp.array_equal(vc0, vc1))
        np.testing.assert_allclose(ctx0, ctx1, atol=1e-5)
        assert np.argmax(ctx0[..., -1]) == np.argmax(ctx1[..., -1])

    def test_int8_dict_identity(self, monkeypatch):
        args = _decode_fixture(int8=True)
        ctx0, kc0, vc0 = _run_cached_attention(*args)
        monkeypatch.setenv("PADDLE_TPU_FUSED_CACHE_WRITE", "1")
        ctx1, kc1, vc1 = _run_cached_attention(*args)
        assert bool(jnp.array_equal(kc0["data"], kc1["data"]))
        assert bool(jnp.array_equal(kc0["scale"], kc1["scale"]))
        assert bool(jnp.array_equal(vc0["data"], vc1["data"]))
        np.testing.assert_allclose(ctx0, ctx1, atol=1e-5)

    def test_multi_token_path_unaffected(self, monkeypatch):
        # S>1 (verify block) must keep the unfused chain bit-exactly:
        # the fused path is S=1-only by dispatch condition
        q, k, v, kc, vc, _ = _decode_fixture()
        q = _rand(3, 4, 4, 8, seed=13)
        k = _rand(3, 4, 2, 8, seed=14)
        v = _rand(3, 4, 2, 8, seed=15)
        pos = jnp.asarray([0, 3, 5], jnp.int32)
        ctx0, kc0, vc0 = _run_cached_attention(q, k, v, kc, vc, pos)
        monkeypatch.setenv("PADDLE_TPU_FUSED_CACHE_WRITE", "1")
        ctx1, kc1, vc1 = _run_cached_attention(q, k, v, kc, vc, pos)
        assert bool(jnp.array_equal(ctx0, ctx1))
        assert bool(jnp.array_equal(kc0, kc1))


class TestMegaDecode:
    def test_identity_with_unfused(self, monkeypatch):
        args = _decode_fixture(nh=4, nkv=2)
        ctx0, kc0, vc0 = _run_cached_attention(*args)
        monkeypatch.setenv("PADDLE_TPU_MEGA_DECODE", "1")
        ctx1, kc1, vc1 = _run_cached_attention(*args)
        assert bool(jnp.array_equal(kc0, kc1))
        assert bool(jnp.array_equal(vc0, vc1))
        np.testing.assert_allclose(ctx0, ctx1, atol=1e-5)

    def test_kernel_direct_empty_and_full(self):
        q, k, v, kc, vc, pos = _decode_fixture(nh=2, nkv=2, L=8)
        ctx, kc2, vc2 = mega_decode_step(q, k, v, kc, vc, pos,
                                         interpret=True)
        # write landed at pos[b] exactly, everything else untouched
        for b, p in enumerate(np.asarray(pos)):
            np.testing.assert_array_equal(
                np.asarray(kc2[b, p]), np.asarray(k[b, 0]))
            rest = np.delete(np.asarray(kc2[b]), p, axis=0)
            ref = np.delete(np.asarray(kc[b]), p, axis=0)
            np.testing.assert_array_equal(rest, ref)
        # pos=0 row (empty cache): attention is ONLY the new row ->
        # ctx equals v exactly (softmax of a single logit is 1)
        np.testing.assert_allclose(np.asarray(ctx[0, 0]),
                                   np.asarray(v[0, 0]), atol=1e-6)

    def test_mega_skips_int8_and_paged(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_MEGA_DECODE", "1")
        args = _decode_fixture(int8=True)
        base = _decode_fixture(int8=True)
        ctx0, kc0, _ = _run_cached_attention(*base)
        ctx1, kc1, _ = _run_cached_attention(*args)
        # dict caches fall back to the unfused chain, bit-exactly
        assert bool(jnp.array_equal(ctx0, ctx1))
        assert bool(jnp.array_equal(kc0["data"], kc1["data"]))
