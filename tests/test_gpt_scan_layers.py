"""GPTScannedBlocks (cfg.scan_layers): the depth-independent-compile
decoder stack.

Reference role: no analog — the reference's executor dispatches per-op
per-layer at runtime (SURVEY.md §3.3), so its "compile time" doesn't
grow with depth; under XLA the unrolled stack does, and scan-over-layers
is the TPU-native answer (flax nn.scan idiom). Parity obligations here
are internal: identical math to the unrolled stack, trainable under the
donated TrainStep, loud errors for the unsupported combinations.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny


def _ids(batch=2, seq=64, vocab=256):
    rng = np.random.RandomState(0)
    return paddle.to_tensor(
        rng.randint(0, vocab, (batch, seq)).astype("int64"))


def _scanned_pair(**scan_cfg_kw):
    """(unrolled, scanned) GPT models with identical parameters;
    scan_cfg_kw adds config fields to the scanned model only."""
    paddle.seed(0)
    m_u = GPTForCausalLM(gpt_tiny())
    paddle.seed(1)  # different init seed: copy must erase the difference
    m_s = GPTForCausalLM(gpt_tiny(scan_layers=True, **scan_cfg_kw))
    m_s.gpt.blocks.load_from_blocks(m_u.gpt.blocks)
    sd_u = dict(m_u.named_parameters())
    for n, p in m_s.named_parameters():
        if not n.startswith("gpt.blocks."):
            p.value = sd_u[n].value
    return m_u, m_s


class TestScanLayersParity:
    def test_forward_matches_unrolled(self):
        m_u, m_s = _scanned_pair()
        ids = _ids()
        out_u, out_s = m_u(ids), m_s(ids)
        np.testing.assert_allclose(np.asarray(out_u.value),
                                   np.asarray(out_s.value),
                                   rtol=0, atol=1e-5)

    def test_eager_backward_matches_unrolled(self):
        # the scan is one tape op (tape.apply over jax.vjp) — per-layer
        # grads must equal the unrolled model's
        m_u, m_s = _scanned_pair()
        ids = _ids()
        GPTForCausalLM.loss_fn(m_u(ids), ids).backward()
        GPTForCausalLM.loss_fn(m_s(ids), ids).backward()
        sd_u = dict(m_u.named_parameters())
        sd_s = dict(m_s.named_parameters())
        g_stack = sd_s["gpt.blocks.attn__qkv__weight"].grad
        assert g_stack is not None
        for i in range(m_u.cfg.num_layers):
            g_i = sd_u[f"gpt.block_{i}.attn.qkv.weight"].grad
            np.testing.assert_allclose(np.asarray(g_i),
                                       np.asarray(g_stack[i]),
                                       rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(sd_u["gpt.embeddings.word_embeddings.weight"].grad),
            np.asarray(sd_s["gpt.embeddings.word_embeddings.weight"].grad),
            rtol=1e-4, atol=1e-6)

    def test_recompute_policy_dots_matches_full(self):
        # "dots" saves matmul outputs instead of recomputing everything —
        # gradients must be identical either way
        ids = _ids(seq=32)
        gs = {}
        for pol in ("full", "dots"):
            paddle.seed(0)
            m = GPTForCausalLM(gpt_tiny(scan_layers=True, recompute=True,
                                        recompute_policy=pol))
            m.train()
            GPTForCausalLM.loss_fn(m(ids), ids).backward()
            gs[pol] = np.asarray(dict(m.named_parameters())
                                 ["gpt.blocks.attn__qkv__weight"].grad)
        np.testing.assert_allclose(gs["full"], gs["dots"], atol=1e-6)

    def test_bad_recompute_policy_raises(self):
        with pytest.raises(ValueError, match="recompute policy"):
            GPTForCausalLM(gpt_tiny(scan_layers=True,
                                    recompute_policy="bogus"))

    def test_callable_policy_passes_through_and_names_are_checked(self):
        # a jax.checkpoint_policies callable is the caller's own choice
        # (nothing_saveable: keep nothing, least memory) and reaches
        # jax.checkpoint as it is; the named ones keep the attention
        # kernel's result; any other name is refused
        import jax
        from paddle_tpu.distributed.recompute import (
            resolve_checkpoint_policy)
        from paddle_tpu.models.scanned import ScannedStack
        nothing = jax.checkpoint_policies.nothing_saveable
        assert resolve_checkpoint_policy(nothing) is nothing
        assert resolve_checkpoint_policy(None) is None
        stack = ScannedStack(lambda: paddle.nn.Linear(4, 4), 2, 0.02,
                             recompute=True, recompute_policy=nothing)
        assert stack._ckpt_policy is nothing
        for name in ("full", "dots"):
            assert callable(resolve_checkpoint_policy(name))
        with pytest.raises(ValueError, match="recompute policy"):
            resolve_checkpoint_policy("nothing_saveable")

    def test_jit_save_load_roundtrip(self, tmp_path):
        # scanned models must export (lax.scan -> StableHLO) and serve
        import paddle_tpu.jit as jit
        paddle.seed(0)
        m = GPTForCausalLM(gpt_tiny(scan_layers=True))
        m.eval()
        ids = _ids(seq=16)
        ref = np.asarray(m(ids).value)
        prefix = str(tmp_path / "gpt_scan")
        jit.save(m, prefix, input_spec=[ids])
        loaded = jit.load(prefix)
        out = loaded(ids)
        out = out[0] if isinstance(out, (list, tuple)) else out
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)

    def test_recompute_matches(self):
        paddle.seed(0)
        m_plain = GPTForCausalLM(gpt_tiny(scan_layers=True))
        paddle.seed(0)
        m_rc = GPTForCausalLM(gpt_tiny(scan_layers=True, recompute=True))
        ids = _ids()
        m_rc.train(), m_plain.train()
        GPTForCausalLM.loss_fn(m_plain(ids), ids).backward()
        GPTForCausalLM.loss_fn(m_rc(ids), ids).backward()
        for (n, p), (_, q) in zip(m_plain.named_parameters(),
                                  m_rc.named_parameters()):
            if p.grad is not None:
                np.testing.assert_allclose(np.asarray(p.grad),
                                           np.asarray(q.grad),
                                           rtol=1e-5, atol=1e-6,
                                           err_msg=n)


class TestScanLayersTraining:
    def test_trainstep_bf16_converges(self):
        # the exact 1.3B bench recipe at tiny scale: bf16 params, plain
        # Adam, per-block remat, scanned stack, donated whole-step program
        paddle.seed(0)
        m = GPTForCausalLM(gpt_tiny(scan_layers=True, recompute=True))
        m.bfloat16()
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     multi_precision=False,
                                     parameters=m.parameters())
        step = TrainStep(m, GPTForCausalLM.loss_fn, opt)
        ids = _ids()
        losses = [float(step(ids, ids)) for _ in range(6)]
        assert losses[-1] < losses[0] - 0.2, losses

    def test_param_count_matches_unrolled(self):
        m_u, m_s = _scanned_pair()
        n_u = sum(int(np.prod(p.shape)) for _, p in m_u.named_parameters())
        n_s = sum(int(np.prod(p.shape)) for _, p in m_s.named_parameters())
        assert n_u == n_s


class TestFusedLoss:
    def test_trajectory_matches_plain(self):
        # cfg.fused_loss_chunk changes only the loss composition, not
        # param creation — same seed must give the IDENTICAL trajectory
        import functools
        ids = _ids()
        traj = {}
        for tag, kw, lf in (
            ("plain", {}, GPTForCausalLM.loss_fn),
            ("fused", {"fused_loss_chunk": 32},
             functools.partial(GPTForCausalLM.fused_loss_fn,
                               chunk_size=32)),
        ):
            paddle.seed(0)
            m = GPTForCausalLM(gpt_tiny(**kw))
            opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                         parameters=m.parameters())
            step = TrainStep(m, lf, opt)
            traj[tag] = [float(step(ids, ids)) for _ in range(4)]
        np.testing.assert_allclose(traj["plain"], traj["fused"],
                                   rtol=1e-5)

    @pytest.mark.parametrize("scale", [1.0, 3.0])
    @pytest.mark.parametrize("labels", ["some_ignored", "chunk_ignored",
                                        "all_ignored"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("layout", ["vh", "hv"])
    def test_functional_parity_with_ignore_index(self, layout, dtype,
                                                 labels, scale):
        # loss and both gradients (taken in the forward pass of each
        # chunk, scaled by the incoming cotangent) against the plain
        # matmul + cross_entropy on float32 copies of the same values
        import paddle_tpu.nn.functional as F
        import paddle_tpu.tensor as T
        rng = np.random.RandomState(0)
        N, H, V, C = 70, 16, 37, 16  # non-multiple of chunk -> padding
        lbl = rng.randint(0, V, (N,))
        if labels == "some_ignored":
            lbl[::7] = -100
        elif labels == "chunk_ignored":
            lbl[C:2 * C] = -100
        else:
            lbl[:] = -100
        lt = paddle.to_tensor(lbl.astype("int64"))
        x = paddle.to_tensor(rng.randn(N, H).astype("float32")).astype(dtype)
        w = paddle.to_tensor(rng.randn(*((V, H) if layout == "vh" else
                                         (H, V))).astype("float32")
                             ).astype(dtype)
        xr, wr = x.astype("float32"), w.astype("float32")
        for t in (x, w, xr, wr):
            t.stop_gradient = False
        loss_f = F.fused_linear_cross_entropy(x, w, lt, chunk_size=C)
        (scale * loss_f).backward()
        logits = paddle.matmul(
            xr, T.transpose(wr, [1, 0]) if layout == "vh" else wr)
        loss_r = F.cross_entropy(logits, lt, ignore_index=-100)
        (scale * loss_r).backward()
        assert abs(float(loss_f) - float(loss_r)) < 1e-5
        for got, ref in ((x.grad, xr.grad), (w.grad, wr.grad)):
            assert str(got.dtype).endswith(dtype)
            got = np.asarray(got.astype("float32"))
            ref = np.asarray(ref)
            assert np.isfinite(got).all()
            tol = 1e-6 if dtype == "float32" else \
                2.0 ** -7 * max(np.abs(ref).max(), 1e-30)
            np.testing.assert_allclose(got, ref, atol=tol, rtol=0)
        if labels == "all_ignored":
            assert float(loss_f) == 0.0
            assert not np.asarray(x.grad.astype("float32")).any()
            assert not np.asarray(w.grad.astype("float32")).any()

    def test_logits_are_computed_once(self):
        # under differentiation the chunk loop holds three
        # vocabulary-sized products (logits, dx, dW) and nothing is
        # rematerialized; the primal alone holds one
        import jax
        import paddle_tpu.nn.functional as F
        N, H, V = 64, 16, 41

        def loss(x, w, lbl):
            return F.fused_linear_cross_entropy(
                paddle.Tensor(x), paddle.Tensor(w), paddle.Tensor(lbl),
                chunk_size=16).value

        def primitives(jaxpr, out):
            for eqn in jaxpr.eqns:
                out.append(eqn)
                for v in eqn.params.values():
                    for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            primitives(sub, out)
            return out

        def vocab_products(fn):
            eqns = primitives(jax.make_jaxpr(fn)(
                np.zeros((N, H), "float32"), np.zeros((V, H), "float32"),
                np.zeros((N,), "int32")).jaxpr, [])
            names = {e.primitive.name for e in eqns}
            assert not names & {"checkpoint", "remat", "remat2"}, names
            return sum(e.primitive.name == "dot_general" and any(
                V in v.aval.shape for v in (*e.invars, *e.outvars))
                for e in eqns)

        assert vocab_products(loss) == 1
        assert vocab_products(jax.grad(loss, argnums=(0, 1))) == 3

    def test_square_weight_raises(self):
        import paddle_tpu.nn.functional as F
        x = paddle.to_tensor(np.zeros((4, 8), "float32"))
        w = paddle.to_tensor(np.eye(8, dtype="float32"))
        lbl = paddle.to_tensor(np.zeros((4,), "int64"))
        with pytest.raises(ValueError, match="ambiguous"):
            F.fused_linear_cross_entropy(x, w, lbl)


class TestScanLayersDistributed:
    @pytest.mark.parametrize("fused_loss_chunk", [0, 32])
    def test_dp_mp_step_matches_unrolled(self, fused_loss_chunk):
        # the stacked leaves carry (None,)+inner sharding annotations —
        # prove they are correct by training the scanned model under the
        # hybrid engine on the virtual mesh and matching the unrolled
        # model's loss trajectory exactly; with fused_loss_chunk the
        # head's dW rides the chunk loop under the mp-sharded vocabulary
        import paddle_tpu.distributed as dist
        dist.init_mesh({"dp": 2, "mp": 2})
        try:
            m_u, m_s = _scanned_pair(fused_loss_chunk=fused_loss_chunk)
            sd = dict(m_s.named_parameters())
            assert sd["gpt.blocks.attn__qkv__weight"].sharding_axes == \
                (None, None, "mp")
            assert sd["gpt.blocks.mlp__fc_out__weight"].sharding_axes == \
                (None, "mp", None)
            ids = _ids(batch=4)
            losses = {}
            for tag, m in (("unrolled", m_u), ("scanned", m_s)):
                opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                             parameters=m.parameters())
                step = dist.ParallelTrainStep(m, m.make_loss_fn(), opt)
                losses[tag] = [float(step(ids, ids)) for _ in range(3)]
            np.testing.assert_allclose(losses["unrolled"],
                                       losses["scanned"],
                                       rtol=2e-4)
            assert losses["scanned"][-1] < losses["scanned"][0]
        finally:
            dist.set_mesh(None)


class TestLlamaScanLayers:
    """ScannedStack generalizes: GQA + RoPE blocks (LlamaBlock) through
    the same scan, incl. stacked-cache decode."""

    def test_train_and_decode(self):
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        paddle.seed(0)
        m = LlamaForCausalLM(llama_tiny(scan_layers=True, recompute=True,
                                        fused_loss_chunk=32))
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        step = TrainStep(m, m.make_loss_fn(), opt)
        ids = _ids(seq=48)
        losses = [float(step(ids, ids)) for _ in range(4)]
        assert losses[-1] < losses[0], losses

    def test_decode_matches_unrolled(self):
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
        paddle.seed(0)
        m_u = LlamaForCausalLM(llama_tiny())
        m_s = LlamaForCausalLM(llama_tiny(scan_layers=True))
        m_s.llama.blocks.load_from_blocks(m_u.llama.blocks)
        sd_u = dict(m_u.named_parameters())
        for n, p in m_s.named_parameters():
            if not n.startswith("llama.blocks."):
                p.value = sd_u[n].value
        prompt = paddle.to_tensor(
            np.random.RandomState(2).randint(0, 256, (2, 9)).astype(
                "int64"))
        out_u = m_u.generate(prompt, max_new_tokens=6, do_sample=False,
                             cache_dtype="float32")
        out_s = m_s.generate(prompt, max_new_tokens=6, do_sample=False,
                             cache_dtype="float32")
        np.testing.assert_array_equal(np.asarray(out_u),
                                      np.asarray(out_s))


class TestScanSequenceParallel:
    def test_scan_with_ring_attention_trains(self):
        # ring attention's shard_map runs INSIDE the scan body under the
        # sp axis — the full long-context composition. Ring attention is
        # exact, so the trajectory must MATCH the same scanned model
        # trained without sp, and the ring dispatch must actually fire.
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed.sequence_parallel import \
            last_ring_dispatch
        ids = _ids(batch=4)
        traj = {}
        try:
            for tag, degrees in (("no_sp", {"dp": 8}),
                                 ("sp", {"sp": 2, "mp": 2, "dp": 2})):
                dist.set_mesh(None)
                dist.init_mesh(degrees)
                paddle.seed(0)
                m = GPTForCausalLM(gpt_tiny(scan_layers=True))
                opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                             parameters=m.parameters())
                step = dist.ParallelTrainStep(
                    m, GPTForCausalLM.loss_fn, opt)
                traj[tag] = [float(step(ids, ids)) for _ in range(3)]
            assert last_ring_dispatch(), \
                "ring attention never dispatched under the sp mesh"
            np.testing.assert_allclose(traj["no_sp"], traj["sp"],
                                       rtol=2e-4)
        finally:
            dist.set_mesh(None)


class TestFusedScanDistributed:
    def test_dp_mp_fused_scan_matches_plain(self):
        # the full composition: scanned TP blocks + fused CE over the
        # vocab-sharded tied weight, under the hybrid engine — GSPMD must
        # insert the cross-shard collectives for the chunked logsumexp
        import paddle_tpu.distributed as dist
        dist.init_mesh({"dp": 2, "mp": 2})
        try:
            ids = _ids(batch=4, seq=48)
            m_plain, m_fused = _scanned_pair(fused_loss_chunk=32)
            traj = {}
            for tag, m in (("plain", m_plain), ("fused+scan", m_fused)):
                opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                             parameters=m.parameters())
                step = dist.ParallelTrainStep(m, m.make_loss_fn(), opt)
                traj[tag] = [float(step(ids, ids)) for _ in range(3)]
            np.testing.assert_allclose(traj["plain"], traj["fused+scan"],
                                       rtol=2e-4)
        finally:
            dist.set_mesh(None)


class TestBertScanLayers:
    """ScannedStack with a layer-invariant extra arg (the additive
    attention mask) — the encoder-family wiring."""

    def test_masked_forward_matches_unrolled(self):
        from paddle_tpu.models.bert import BertModel, bert_tiny
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(rng.randint(0, 512, (3, 24)).astype(
            "int64"))
        mask_np = np.ones((3, 24), "int64")
        mask_np[:, 18:] = 0
        mask = paddle.to_tensor(mask_np)
        paddle.seed(0)
        m_u = BertModel(bert_tiny())
        m_s = BertModel(bert_tiny(scan_layers=True))
        m_s.layers.load_from_blocks(m_u.layers)
        sd = dict(m_u.named_parameters())
        for n, p in m_s.named_parameters():
            if not n.startswith("layers."):
                p.value = sd[n].value
        seq_u, pool_u = m_u(ids, attention_mask=mask)
        seq_s, pool_s = m_s(ids, attention_mask=mask)
        np.testing.assert_allclose(np.asarray(seq_u.value),
                                   np.asarray(seq_s.value), atol=1e-5)
        np.testing.assert_allclose(np.asarray(pool_u.value),
                                   np.asarray(pool_s.value), atol=1e-5)

    def test_finetune_trains_through_mask(self):
        import paddle_tpu.nn.functional as F
        from paddle_tpu.models.bert import (BertForSequenceClassification,
                                            bert_tiny)
        rng = np.random.RandomState(1)
        ids = paddle.to_tensor(rng.randint(0, 512, (3, 24)).astype(
            "int64"))
        mask_np = np.ones((3, 24), "int64")
        mask_np[:, 20:] = 0  # real padding: grads flow past -1e30 masks
        mask = paddle.to_tensor(mask_np)
        y = paddle.to_tensor(rng.randint(0, 3, (3,)).astype("int64"))
        paddle.seed(1)
        clf = BertForSequenceClassification(bert_tiny(scan_layers=True),
                                            num_classes=3)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=clf.parameters())
        losses = []
        for _ in range(4):
            loss = F.cross_entropy(clf(ids, attention_mask=mask), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0], losses

    def test_dropout_raises(self):
        # bert_base keeps the real default dropout=0.1
        from paddle_tpu.models.bert import BertModel, bert_base
        with pytest.raises(NotImplementedError, match="dropout"):
            BertModel(bert_base(scan_layers=True))


class TestMoEScan:
    """MoE blocks through the scan: per-layer aux losses ride the scan
    outputs and are re-reported once to the outer scope."""

    def test_aux_loss_matches_unrolled(self):
        from paddle_tpu.framework.aux_loss import aux_loss_scope, total
        paddle.seed(0)
        m_u = GPTForCausalLM(gpt_tiny(use_moe=True, moe_experts=4))
        m_s = GPTForCausalLM(gpt_tiny(use_moe=True, moe_experts=4,
                                      scan_layers=True))
        m_s.gpt.blocks.load_from_blocks(m_u.gpt.blocks)
        sd = dict(m_u.named_parameters())
        for n, p in m_s.named_parameters():
            if not n.startswith("gpt.blocks."):
                p.value = sd[n].value
        ids = _ids(seq=32)
        with aux_loss_scope() as b_u:
            out_u = m_u(ids)
        with aux_loss_scope() as b_s:
            out_s = m_s(ids)
        np.testing.assert_allclose(np.asarray(out_u.value),
                                   np.asarray(out_s.value), atol=1e-5)
        assert float(total(b_u)) > 0
        np.testing.assert_allclose(float(total(b_u)), float(total(b_s)),
                                   rtol=1e-6)

    def test_moe_scan_remat_trains(self):
        paddle.seed(0)
        m = GPTForCausalLM(gpt_tiny(use_moe=True, moe_experts=4,
                                    scan_layers=True, recompute=True))
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        step = TrainStep(m, GPTForCausalLM.loss_fn, opt)
        ids = _ids(seq=32)
        losses = [float(step(ids, ids)) for _ in range(4)]
        assert losses[-1] < losses[0], losses


class TestScanLayersGuards:

    def test_dropout_raises(self):
        with pytest.raises(NotImplementedError, match="dropout"):
            GPTForCausalLM(gpt_tiny(scan_layers=True, dropout=0.1))

    def test_greedy_decode_matches_unrolled(self):
        # stacked-cache decode: same params -> same greedy continuation
        m_u, m_s = _scanned_pair()
        prompt = paddle.to_tensor(
            np.random.RandomState(3).randint(0, 256, (2, 12)).astype(
                "int64"))
        out_u = m_u.generate(prompt, max_new_tokens=8, do_sample=False,
                             cache_dtype="float32")
        out_s = m_s.generate(prompt, max_new_tokens=8, do_sample=False,
                             cache_dtype="float32")
        np.testing.assert_array_equal(np.asarray(out_u),
                                      np.asarray(out_s))
