"""Chip-compile checks for the Pallas kernels and the programs around
them — no hardware.

Two depths of check live here, in ONE file (the topology fixture below
loads the TPU compiler library, which one process may hold at a time —
a second file could land on another xdist worker and skip in silence):

- ``jax.export(platforms=["tpu"])`` cross-lowering (the older tests):
  runs the Pallas->Mosaic MLIR pipeline and embeds the payload as a
  ``tpu_custom_call``, but stops short of the chip's compiler — Mosaic
  layout inference, the XLA/Mosaic operand-layout check and the VMEM
  and HBM limits are NOT exercised (PR 19's three fusion kernels passed
  this depth and were all refused by the compiler).
- ``.lower(...).compile()`` against a DESCRIBED v5e (``topo`` fixture):
  what the chip's compiler would say, at the real widths — every kernel
  in paddle_tpu/kernels/, the library splash call at both GPT cells'
  geometries, the engine's decode ticks at 1.3B widths, and (marked
  slow) the whole train steps of the benchmark's two GPT cells, their
  sizes and job read from BENCHMARK.json's files. A compile that passes
  is not a chip run.
"""
import functools
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import paddle_tpu.distributed as dist
from paddle_tpu.jit.functional import EXPORT_DISABLED_CHECKS
from paddle_tpu.kernels.flash_block import flash_block_attention


def _cell(workload):
    """One GPT cell of the benchmark, read only, by the benchmark's own
    lookup: the GPTConfig arguments as benchmark/drivers/train_steps.py
    passes them, the job, and the step's batch and sequence — the one
    copy of the cells' sizes."""
    from benchmark.run import find_cell, load_json
    cell = find_cell(load_json("BENCHMARK.json"), workload)
    arch, traffic = cell["config"], cell["traffic"]
    job = arch["job"]
    cfg_kw = dict(
        {k: arch[k] for k in ("vocab_size", "hidden_size", "num_layers",
                              "num_heads", "max_seq_len", "ffn_mult",
                              "initializer_range")},
        dropout=0.0, tie_embeddings=True,
        **{k: job[k] for k in ("recompute", "recompute_policy",
                               "scan_layers", "fused_loss_chunk")})
    return cfg_kw, job, traffic["batch"], traffic["seq"]


def _cell_optimizer(paddle, job, model):
    return paddle.optimizer.AdamW(
        learning_rate=job["learning_rate"], beta1=job["beta1"],
        beta2=job["beta2"], epsilon=job["epsilon"],
        weight_decay=job["weight_decay"],
        multi_precision=job["master_weights"],
        parameters=model.parameters())


@pytest.fixture(autouse=True)
def fresh_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


@pytest.fixture(scope="module")
def topo():
    """The described chip. Called only once a test of THIS file runs —
    never at import, in a skipif or in parametrize (every xdist worker
    imports every test file; only the one given this file may load the
    TPU library)."""
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_like_config():
    """Compile as a process on the chip would. (1) A compile for a
    described device is written to the persistent cache but cannot be
    read back without a chip (the next one warns and recompiles) — keep
    these compiles out of it. (2) tests/conftest.py asks for "highest"
    matmul precision for the CPU numerics; a program on the chip runs
    jax's default, and the library attention kernel's bf16 dots are REFUSED
    at fp32 contract precision ("Bad lhs type") — a fault of the test
    environment, not of the kernel."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_default_matmul_precision)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev[0])
    jax.config.update("jax_default_matmul_precision", prev[1])
    compilation_cache.reset_cache()


def _tpu_mlir(fn, *args):
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(
        *args).mlir_module()


def test_flash_fwd_lowers_for_tpu():
    q = jnp.zeros((1, 4, 256, 64), jnp.bfloat16)

    def f(q, k, v):
        return flash_block_attention(q, k, v, 0, 0, causal=True,
                                     sm_scale=0.125)

    mlir = _tpu_mlir(f, q, q, q)
    assert mlir.count("tpu_custom_call") == 1


def test_flash_bwd_lowers_for_tpu():
    q = jnp.zeros((1, 4, 256, 64), jnp.bfloat16)

    def loss(q, k, v):
        o, lse = flash_block_attention(q, k, v, 0, 0, True, 0.125,
                                       128, 128, False)
        return o.astype(jnp.float32).sum() + lse.sum()

    mlir = _tpu_mlir(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    # fwd + dkv + dq kernels
    assert mlir.count("tpu_custom_call") == 3


def test_fused_ring_lowers_for_tpu():
    import paddle_tpu.distributed.sequence_parallel as sp
    dist.init_mesh({"sp": 8})
    mesh = dist.get_mesh()
    q = jnp.zeros((1, 1024, 8, 64), jnp.bfloat16)
    # the exact program the TPU dispatch builds: fused=True,
    # interpret=False (what backend == "tpu" selects)
    prog = sp._ring_program(mesh, 8, 0.125, True, 128, True, False)
    mlir = _tpu_mlir(prog, q, q, q)
    assert mlir.count("tpu_custom_call") >= 1      # Pallas kernel fires
    assert mlir.count("collective_permute") >= 2   # the k/v rotation ring


def _export_train_step_for_tpu(step, batch=(2, 256)):
    """Cross-lower a built TrainStep's whole donated program for the TPU
    target (the one export recipe both cell-shaped gates share)."""
    import paddle_tpu.framework.random as _rng
    step._build()
    aval = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    key = jax.eval_shape(lambda: _rng.default_generator().fold_in(1))
    ids = jax.ShapeDtypeStruct(batch, jnp.int64)
    return jax.export.export(step._jitted, platforms=["tpu"],
                             disabled_checks=EXPORT_DISABLED_CHECKS)(
        aval(step.params), aval(step.buffers), aval(step.opt_state),
        scalar, scalar, key, ids, ids)


def test_gpt_train_step_with_pallas_attention_lowers_for_tpu(monkeypatch):
    """train-gpt-125m's job at tiny geometry: full donated GPT train step
    with the library splash attention (dispatch forced as on a real TPU
    backend), cross-lowered for the TPU target — the forward and the
    fused backward Mosaic payloads."""
    import importlib
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)

    cell_kw, job, _, _ = _cell("train-gpt-125m")
    cfg = GPTConfig(**dict(cell_kw, vocab_size=512, hidden_size=256,
                           num_layers=2, num_heads=4, max_seq_len=256,
                           fused_loss_chunk=64))
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.bfloat16()
    step = TrainStep(model, model.make_loss_fn(),
                     _cell_optimizer(paddle, job, model))
    exp = _export_train_step_for_tpu(step)
    assert exp.mlir_module().count("tpu_custom_call") == 2
    assert fa.last_attention_dispatch()["backend"] == "pallas"


@pytest.mark.parametrize("policy", ["full", "dots"])
def test_gpt_1p3b_shaped_step_lowers_for_tpu(monkeypatch, policy):
    """train-gpt-1.3b's job at tiny geometry: scan-over-layers +
    per-block remat (both recompute_policy values) + chunked head/loss +
    pure-bf16 Adam, with pallas attention dispatch forced — cross-lowered
    for the TPU target so a Mosaic/lowering blocker is caught HERE, not
    on the chip's clock."""
    import importlib
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)

    cell_kw, job, _, _ = _cell("train-gpt-1.3b")
    assert cell_kw["scan_layers"] and cell_kw["recompute"]
    assert not job["master_weights"]
    cfg = GPTConfig(**dict(cell_kw, vocab_size=512, hidden_size=256,
                           num_layers=3, num_heads=4, max_seq_len=256,
                           recompute_policy=policy, fused_loss_chunk=64))
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.bfloat16()
    step = TrainStep(model, model.make_loss_fn(),
                     _cell_optimizer(paddle, job, model))
    exp = _export_train_step_for_tpu(step)
    # scan body compiles ONCE (depth-independent): fwd + fused bwd = 2
    # Mosaic payloads; the remat'd bwd replays no fwd kernel, because
    # both policies keep the kernel's result and logsumexp
    assert exp.mlir_module().count("tpu_custom_call") == 2
    assert fa.last_attention_dispatch()["backend"] == "pallas"


# ---------------------------------------------------------------------------
# compiles against the described chip (what the chip's compiler says)
# ---------------------------------------------------------------------------

BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32
_sd = jax.ShapeDtypeStruct
_B, _L, _H, _D = 8, 2048, 16, 128          # 1.3B serving cache geometry
_NP, _PS = 1024, 16                        # paged pool at the same bytes
_N, _V = 8192, 50304                       # 1.3B train tokens x vocab


def _flash_block(shape, bwd):
    scale = 1.0 / shape[-1] ** 0.5

    def fwd(q, k, v):
        return flash_block_attention(q, k, v, 0, 0, True, scale,
                                     128, 128, False)

    def loss(q, k, v):
        o, lse = fwd(q, k, v)
        return o.astype(jnp.float32).sum() + lse.sum()
    fn = jax.grad(loss, argnums=(0, 1, 2)) if bwd else fwd
    return fn, [_sd(shape, BF16)] * 3, 3 if bwd else 1


def _library_flash(shape, kv_heads=None, window=None):
    """flash_attention._pallas_flash: the library's splash kernel with
    this repo's block rule, the forward and the one fused backward;
    optionally with fewer key/value heads and a causal window."""
    import importlib
    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")
    scale = 1.0 / shape[-1] ** 0.5
    kv_shape = shape[:2] + (kv_heads or shape[2],) + shape[3:]

    def loss(q, k, v):
        # traced as on the chip: off it the kernel is built to interpret
        with mock.patch.object(fa, "_on_tpu", lambda: True):
            return fa._pallas_flash(q, k, v, True, scale, window).astype(
                jnp.float32).sum()
    return (jax.grad(loss, argnums=(0, 1, 2)),
            [_sd(shape, BF16), _sd(kv_shape, BF16), _sd(kv_shape, BF16)], 2)


def _library_flash_mla():
    """The head-major entry at the latent attention cell's operands: q and
    k heads 192 wide, v heads 128, [2, 32, 8192, d], forward and the one
    fused backward."""
    import importlib
    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")

    def loss(q, k, v):
        with mock.patch.object(fa, "_on_tpu", lambda: True):
            assert fa._pallas_ok(q, 192, 0.0, 32, None, head_axis=1, d_v=128)
            return fa._pallas_flash(q, k, v, True, None,
                                    head_axis=1).astype(jnp.float32).sum()
    return (jax.grad(loss, argnums=(0, 1, 2)),
            [_sd((2, 32, 8192, 192), BF16), _sd((2, 32, 8192, 192), BF16),
             _sd((2, 32, 8192, 128), BF16)], 2)


def _library_flash_head_major_grouped(window):
    """The head-major entry at the SmallThinker cell's operands: 28 query
    heads in groups of 7 on 4 key/value heads of 128 at 16,384 positions,
    a causal window of 4096 or none, forward and the one fused backward."""
    import importlib
    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")

    def loss(q, k, v):
        with mock.patch.object(fa, "_on_tpu", lambda: True):
            assert fa._pallas_ok(q, 128, 0.0, 4, window, head_axis=1)
            return fa._pallas_flash(q, k, v, True, None, window,
                                    head_axis=1).astype(jnp.float32).sum()
    return (jax.grad(loss, argnums=(0, 1, 2)),
            [_sd((1, 28, 16384, 128), BF16), _sd((1, 4, 16384, 128), BF16),
             _sd((1, 4, 16384, 128), BF16)], 2)


def _grouped_experts(width=1024, top_k=8, rows=20480, hidden=2048,
                     gate="silu"):
    """distributed/moe.py's sorted path at one chip's share of the
    published experts: 16,384 tokens, ``top_k`` experts a token, 16 held
    of ``width``, through the library's megablox kernels, forward and
    backward, at ``rows`` sorted rows: the ladder's rung that the cell's
    even routing takes, 1.25 even shares. Trinity-Mini's share by default;
    the deepseek_v3 cell's at width 768, top-6, 15,360 rows; the
    SmallThinker cell's at hidden 2560, width 768, top-6, 30,720 rows,
    ReLU gate."""
    import importlib
    moe = importlib.import_module("paddle_tpu.distributed.moe")

    def loss(x, w1, w3, w2, wgt, sel):
        with mock.patch.object(moe, "_on_tpu", lambda: True):
            here = sel < 16
            return moe._routed_sorted(
                x, w1, w3, w2, wgt, here, moe._sorted_index(sel, here, 16),
                rows, moe._GATES[gate]).astype(jnp.float32).sum()
    return (jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
            _expert_share_args(width, top_k, hidden), 9)


def _expert_share_args(width, top_k, hidden):
    return [_sd((16384, hidden), BF16), _sd((16, hidden, width), BF16),
            _sd((16, hidden, width), BF16), _sd((16, width, hidden), BF16),
            _sd((16384, top_k), F32), _sd((16384, top_k), I32)]


def _slot_write(dtype, tail):
    from paddle_tpu.kernels import fused_slot_write
    return (fused_slot_write,
            [_sd((_B, _L) + tail, dtype), _sd((_B, 1) + tail, dtype),
             _sd((_B,), I32)], 1)


def _paged_write():
    from paddle_tpu.kernels import fused_paged_write
    return (fused_paged_write,
            [_sd((_NP, _PS, _H, _D), BF16), _sd((_B, _H, _D), BF16)]
            + [_sd((_B,), I32)] * 3, 1)


def _ce(bwd):
    from paddle_tpu.kernels import ce_bwd, ce_fwd
    args = [_sd((_N, _V), BF16), _sd((_N,), I32)]
    if bwd:
        return ce_bwd, args + [_sd((_N,), F32)] * 2, 1
    return ce_fwd, args, 1


def _mega(L, nh, hd):
    from paddle_tpu.kernels import mega_decode_step
    return (mega_decode_step,
            [_sd((_B, 1, nh, hd), BF16)] * 3
            + [_sd((_B, L, nh, hd), BF16)] * 2 + [_sd((_B,), I32)], 1)


def _engine_decode(paged):
    """The ContinuousBatchingEngine decode tick at GPT-1.3B widths
    (depth cut to two layers), slots 8 x max_len 2048, bf16 cache — the
    program chip_smoke.py's replica warms. No Pallas kernel in it with
    the fusion knobs off; the compile proves XLA takes the masked-write
    / page-gather tick at real cache sizes."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=50304, hidden_size=2048, num_layers=2, num_heads=16,
        max_seq_len=2048))
    eng = ContinuousBatchingEngine(model, slots=8, max_len=2048,
                                   cache_dtype="bfloat16", paged=paged)
    try:
        prog = eng._get_decode_prog()
        args = jax.tree_util.tree_map(
            lambda x: _sd(np.shape(x), jax.dtypes.canonicalize_dtype(
                x.dtype if hasattr(x, "dtype") else np.asarray(x).dtype)),
            eng._decode_example_args())
    finally:
        eng.stop()
    return prog, args, 0


CHIP_COMPILE_CASES = {
    "flash_block_fwd_hd64": lambda: _flash_block((1, 12, 1024, 64), False),
    "flash_block_bwd_hd64": lambda: _flash_block((1, 12, 1024, 64), True),
    "flash_block_fwd_hd128": lambda: _flash_block((1, 16, 2048, 128),
                                                  False),
    "flash_block_bwd_hd128": lambda: _flash_block((1, 16, 2048, 128),
                                                  True),
    "library_flash_gpt125m": lambda: _library_flash((8, 1024, 12, 64)),
    "library_flash_gpt1p3b": lambda: _library_flash((4, 2048, 16, 128)),
    "library_flash_gqa_window_8k": lambda: _library_flash(
        (2, 8192, 32, 128), kv_heads=4, window=2048),
    "library_flash_gqa_full_8k": lambda: _library_flash(
        (2, 8192, 32, 128), kv_heads=4),
    "library_flash_mla_192_128_8k": _library_flash_mla,
    "grouped_experts_trinity_share": _grouped_experts,
    "grouped_experts_kanana_share": lambda: _grouped_experts(768, 6, 15360),
    "library_flash_head_major_gqa7_window_16k":
        lambda: _library_flash_head_major_grouped(4096),
    "library_flash_head_major_gqa7_full_16k":
        lambda: _library_flash_head_major_grouped(None),
    "grouped_experts_smallthinker_share": lambda: _grouped_experts(
        768, 6, 30720, hidden=2560, gate="relu"),
    "fused_slot_write_bf16": lambda: _slot_write(BF16, (_H, _D)),
    "fused_slot_write_int8": lambda: _slot_write(I8, (_H, _D)),
    "fused_slot_write_scale": lambda: _slot_write(F32, (_H,)),
    "fused_paged_write": _paged_write,
    "ce_fwd": lambda: _ce(False),
    "ce_bwd": lambda: _ce(True),
    "mega_decode_L2048_hd128": lambda: _mega(2048, 16, 128),
    "mega_decode_L1024_hd64": lambda: _mega(1024, 12, 64),
    "engine_decode_tick_slot_1p3b": lambda: _engine_decode(False),
    "engine_decode_tick_paged_1p3b": lambda: _engine_decode(True),
}


def _abstract(tree, sharding):
    """Place shape/dtype leaves on the described chip (no array can be
    put on a device that is not attached)."""
    return jax.tree_util.tree_map(
        lambda x: _sd(x.shape, x.dtype, sharding=sharding), tree)


@pytest.mark.parametrize("case", sorted(CHIP_COMPILE_CASES))
def test_compiles_for_v5e(case, one_chip, chip_like_config):
    fn, args, n_kernels = CHIP_COMPILE_CASES[case]()
    if not hasattr(fn, "lower"):
        fn = jax.jit(fn)
    compiled = fn.lower(*_abstract(args, one_chip)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= n_kernels


def _conditionals(text):
    """A compiled program's conditionals, each as its branches' names, and
    ``reached(name)``: a computation and whatever it calls, as
    {name: body}."""
    blocks = dict(re.findall(r"^(?:ENTRY )?%([\w.\-]+) [^\n]*\{\n(.*?)^\}",
                             text, re.M | re.S))

    def reached(name, seen=None):
        seen = {} if seen is None else seen
        if name not in seen and name in blocks:
            seen[name] = blocks[name]
            for callee in re.findall(
                    r"(?:calls|to_apply|body|condition)=%([\w.\-]+)",
                    blocks[name]):
                reached(callee, seen)
        return seen
    conds = [[b.strip().lstrip("%") for b in c.split(",")] for c in re.findall(
        r"conditional\([^\n]*branch_computations=\{([^}]*)\}", text)]
    return conds, reached, blocks


def test_expert_ladder_is_one_conditional_for_v5e(one_chip,
                                                  chip_like_config):
    """The SmallThinker cell's expert share as the layer calls it.
    Forward: the compiled program holds ONE conditional, of the ladder's
    two rungs and the dense path, three grouped products a rung and the
    combine's product by tiles of tokens, and the index work (the stable
    sorts by expert and by token) outside its branches, where it waits
    for the routing alone: no sort and no scatter is left inside, since
    the rows go back to their tokens by a gather and a grouped product
    (as scatter-adds the compiler sorted each one's token indices). The
    gradient of the recomputed share: TWO, the forward's and the
    backward's, whose rung runs its three products again, their six
    transposes and the product that transposes the dispatch's gather (the
    second run's combine has no reader and is gone, as is the recomputed
    forward's conditional)."""
    import importlib
    moe = importlib.import_module("paddle_tpu.distributed.moe")
    ladder = (30720, 73728)
    args = _abstract(_expert_share_args(768, 6, 2560), one_chip)

    def part(*args):
        return moe._routed(*args, offset=0, ladder=ladder,
                           act=moe._GATES["relu"])

    def compiled(fn):
        # round the whole trace: the backward conditional is traced when
        # the gradient is, after ``part`` has returned
        with mock.patch.object(moe, "_on_tpu", lambda: True):
            return jax.jit(fn).lower(*args).compile().as_text()

    def kernels(branch):
        return sum(body.count('custom_call_target="tpu_custom_call"')
                   for body in reached(branch).values())

    def kernel_scopes(branch):
        # (the scope, the library kernel) of every kernel a branch runs,
        # by the path JAX left on it: ``benchmark/step_scopes.py``'s
        # shares of the expert layer read the same paths
        return sorted(re.findall(
            r'custom_call_target="tpu_custom_call"[^\n]*op_name="[^"]*?'
            r'(\w+\(jvp\(|jvp\(|)jit\(routed_sorted\)\)*/(\w+)/jit\((\w+)\)',
            "\n".join(reached(branch).values())))

    def wide_scatters(bodies):
        # the scatters left are the library kernels' own bookkeeping, a
        # vector of tile counts each: none updates rows of a matrix
        return [line for body in bodies for line in body.splitlines()
                if " scatter(" in line
                and not re.search(r"= \w+\[\d+\]\{", line)]
    conds, reached, blocks = _conditionals(compiled(part))
    assert [len(c) for c in conds] == [len(ladder) + 1]
    inside = set().union(*(reached(b) for b in conds[0]))
    sorts = {name for name, body in blocks.items()
             if re.search(r" sort\(", body)}
    assert sorts and not sorts & inside
    assert not wide_scatters(blocks[name] for name in inside)
    assert [kernels(b) for b in conds[0]] == [4, 4, 0]
    assert kernel_scopes(conds[0][0]) == [("", "combine", "tgmm")] + [
        ("", "products", "gmm")] * 3

    grad = jax.grad(jax.checkpoint(
        lambda *a: (part(*a).astype(jnp.float32) ** 2).sum()),
        argnums=(0, 1, 2, 3, 4))
    conds, reached, _ = _conditionals(compiled(grad))
    assert sorted([kernels(b) for b in c] for c in conds) == [
        [4, 4, 0], [10, 10, 0]]
    backward = max(conds, key=lambda c: kernels(c[0]))[0]
    assert kernel_scopes(backward) == [
        ("jvp(", "products", "gmm")] * 3 + [
        ("transpose(jvp(", "dispatch", "tgmm")] + [
        ("transpose(jvp(", "products", "gmm")] * 3 + [
        ("transpose(jvp(", "products", "tgmm")] * 3
    for c in conds:
        bodies = [body for b in c for body in reached(b).values()]
        assert not wide_scatters(bodies)
        assert not any(" sort(" in body for body in bodies)


# -- whole train steps (slow: minutes of compile each, and tier-1 has a
# clock) ---------------------------------------------------------------------

def _compile_train_step_for_v5e(workload, one_chip, monkeypatch,
                                **cfg_override):
    """The cell's own step program (benchmark/drivers/train_steps.py's
    composition, sizes and job from the cell's files), compiled for the
    described chip; returns (compiled, per-device memory analysis)."""
    import importlib
    import paddle_tpu as paddle
    import paddle_tpu.framework.random as _rng
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    cfg_kw, job, batch, seq = _cell(workload)
    assert job["attention_backend"] == "pallas"
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(**dict(cfg_kw, **cfg_override)))
    model.bfloat16()
    step = TrainStep(model, model.make_loss_fn(),
                     _cell_optimizer(paddle, job, model))
    step._build()
    aval = functools.partial(jax.tree_util.tree_map, lambda x:
                             jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                  sharding=one_chip))
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    key = aval(jax.eval_shape(
        lambda: _rng.default_generator().fold_in(1)))
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
    compiled = step._jitted.lower(
        aval(step.params), aval(step.buffers), aval(step.opt_state),
        scalar, scalar, key, ids, ids).compile()
    assert fa.last_attention_dispatch()["backend"] == "pallas"
    return compiled, compiled.memory_analysis()


@pytest.mark.slow
@pytest.mark.timeout(1800)
def test_gpt125m_train_step_compiles_for_v5e(one_chip, chip_like_config,
                                             monkeypatch):
    """train-gpt-125m's step: unrolled, f32 master weights, chunked
    head/loss."""
    compiled, mem = _compile_train_step_for_v5e(
        "train-gpt-125m", one_chip, monkeypatch)
    assert compiled.as_text().count("tpu_custom_call") >= 2
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


@pytest.mark.slow
@pytest.mark.timeout(1800)
def test_gpt1p3b_train_step_compiles_for_v5e(one_chip, chip_like_config,
                                             monkeypatch):
    """train-gpt-1.3b's step: scanned, per-block remat, chunked
    head/loss, bf16 weights without master. The published 24 layers are
    refused for memory (16.23 G of 15.75 G); the cell's cut depth must
    fit."""
    compiled, mem = _compile_train_step_for_v5e(
        "train-gpt-1.3b", one_chip, monkeypatch)
    # the forward kernel in the forward scan and the fused backward
    # kernel in the backward scan: the recomputed block keeps the
    # kernel's result, so no second forward kernel
    assert compiled.as_text().count("tpu_custom_call") == 2
    print("GPT-1.3B one-chip step:", mem)


def test_gpt1p3b_width_step_carries_its_scopes_for_v5e(one_chip,
                                                       chip_like_config,
                                                       monkeypatch):
    """Two layers at GPT-1.3B widths, batch 4 x seq 2048, scanned and
    recomputed as the benchmark's cell runs them: compiled for the
    described chip, the program's scopes are on its fusions and on its
    two attention kernels (the forward once: the recomputed block keeps
    its result; one fused backward), so a device trace's operations can
    be summed by them."""
    from paddle_tpu.analysis import runtime_profile as rp
    compiled, _ = _compile_train_step_for_v5e(
        "train-gpt-1.3b", one_chip, monkeypatch, num_layers=2)
    text = compiled.as_text()
    table = rp.hlo_op_scopes(text)
    kernels = [rp.read_scope(table[n], n) for n in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)]
    assert sorted((k["region"], k["pass"]) for k in kernels) == [
        ("attn", "backward"), ("attn", "forward")]
    assert {k["scope"] for k in kernels} == {
        "gptforcausallm/gpt/blocks/block/attn"}
    fusions = [rp.read_scope(table[n]) for n in re.findall(
        r"%([\w.\-]+) = [^\n]* fusion\(", text) if table[n]]
    assert len(fusions) > 100
    assert sum(f["region"] == "unscoped" for f in fusions) \
        < 0.05 * len(fusions)
    found = {(f["region"], f["pass"]) for f in fusions}
    assert {("fc_in", "forward"), ("fc_in", "recompute"),
            ("fc_in", "backward"), ("qkv", "recompute"),
            ("ln", "backward"), ("head_loss", "forward"),
            ("head_loss", "backward"), ("optimizer", "update"),
            ("scan_carry", "backward"),
            ("word_embeddings", "forward")} <= found
    # the head's logits are computed once: of a chunk's three products
    # the logits read forward and the two gradient products backward
    # (transposes taken inside the forward rule), and nothing of the
    # head is recomputed
    assert ("head_loss", "recompute") not in found
    products = [rp.read_scope(table[n]) for n in re.findall(
        r"%([\w.\-]+) = [^\n]* convolution\(", text)]
    assert sorted(p["pass"] for p in products
                  if p["region"] == "head_loss") == [
        "backward", "backward", "forward"]


@pytest.mark.parametrize("workload", ["train-gpt-125m", "train-gpt-1.3b"])
def test_gpt_attention_has_no_activation_sized_copy_for_v5e(
        workload, one_chip, chip_like_config, monkeypatch):
    """Two GPT blocks forward + backward at each GPT cell's sizes, in the
    cell's own composition (125M unrolled; 1.3B scanned and recomputed):
    the projections write and read the kernel's [b, h, s, d], so the
    compiled step holds no ``copy`` in the ``attn`` scope as large as q
    (the fused [B, S, 3H] path had ten a layer: a slice and a transpose
    of each of q, k, v, the output's transpose, and their cotangents').
    What is left there, if anything, copies a third of the qkv weight."""
    import importlib
    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")
    cfg_kw, _, batch, seq = _cell(workload)
    compiled, _ = _compile_train_step_for_v5e(
        workload, one_chip, monkeypatch, num_layers=2)
    assert fa.last_attention_dispatch()["layout"] == "head_major"
    q_elements = batch * seq * cfg_kw["hidden_size"]
    copies = re.findall(
        r"= \w+\[([\d,]+)\]\S* copy\([^\n]*op_name=\"([^\"]*)\"",
        compiled.as_text())
    assert copies         # the regex reads this compiler's text
    assert [(shape, scope) for shape, scope in copies if "/attn/" in scope
            and np.prod([int(n) for n in shape.split(",")]) >= q_elements
            ] == []


# -- the library kernel under a multi-device mesh ---------------------------

def test_mesh_wrap_decides_from_the_trace_time_mesh():
    """flash_attention._mesh_wrap: no mesh / one device / inside a
    shard_map -> no wrap; data x mp meshes -> a shard_map spec; axes it
    has no wrap for (sp) or shapes that do not divide -> a reason."""
    import importlib
    from jax.sharding import PartitionSpec as P
    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")
    shape = (8, 256, 4, 64)
    assert fa._mesh_wrap(shape) == (None, None, None)
    dist.init_mesh({"dp": 2, "sharding": 2, "mp": 2})
    mesh, spec, why_not = fa._mesh_wrap(shape)
    assert mesh is dist.get_mesh() and why_not is None
    assert spec == P(("dp", "sharding"), None, "mp", None)
    assert "do not divide" in fa._mesh_wrap((6, 256, 4, 64))[2]
    seen = []
    jax.shard_map(lambda x: (seen.append(fa._mesh_wrap(shape)), x)[1],
                  mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))(
        jnp.zeros((8,)))
    assert seen == [(None, None, None)]
    dist.set_mesh(None)
    dist.init_mesh({"sp": 8})
    assert "sp" in fa._mesh_wrap(shape)[2]


@pytest.mark.parametrize("head_axis", [2, 1])
def test_kernel_inside_the_shard_map_is_built_for_the_shard(
        head_axis, topo, chip_like_config, monkeypatch):
    """tp=4 on the described chips: _pallas_flash wraps the call in a
    shard_map over batch and heads, and the kernel (its mask tables are a
    row a head) is built inside it from the shard's own head count; the
    spec names heads on whichever axis the caller's layout has them
    ([b, s, h, d], or the kernel's own [b, h, s, d])."""
    import importlib
    from jax.sharding import NamedSharding, PartitionSpec as P
    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    dist.init_mesh({"dp": 2, "mp": 2}, devices=list(topo.devices))
    shape = (4, 1024, 8, 64) if head_axis == 2 else (4, 8, 1024, 64)
    mesh, spec, _ = fa._mesh_wrap(shape, head_axis=head_axis)
    assert spec == (P("dp", None, "mp", None) if head_axis == 2
                    else P("dp", "mp", None, None))
    built = []
    real = fa._splash_kernel.__wrapped__
    monkeypatch.setattr(fa, "_splash_kernel", lambda heads, *a: (
        built.append(heads), real(heads, *a))[1])
    x = _sd(shape, BF16, sharding=NamedSharding(mesh, spec))
    compiled = jax.jit(jax.grad(
        lambda q, k, v: fa._pallas_flash(
            q, k, v, True, 0.125, head_axis=head_axis).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))).lower(x, x, x).compile()
    assert set(built) == {4}                       # 8 heads over mp=2
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "bf16[2,4,1024,64]" in text             # the shard's operands


def test_fewer_kv_heads_than_mp_still_take_the_kernel(topo,
                                                      chip_like_config,
                                                      monkeypatch):
    """mp=4 on the described chips, 8 query heads on 2 key/value heads
    (a Llama block's GQA call): "mp" does not divide the key/value heads,
    so the functional copies each out twice, the least that divides, and
    the kernel is built inside the shard_map for the shard's 2 query
    heads on 1 key/value head. Nothing falls to the XLA path."""
    import importlib
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_REQUIRE_PALLAS", "1")
    dist.init_mesh({"mp": 4}, devices=list(topo.devices))
    built = []
    real = fa._splash_kernel.__wrapped__
    monkeypatch.setattr(fa, "_splash_kernel", lambda heads, *a: (
        built.append((heads, a[-1])), real(heads, *a))[1])
    k4 = fa._kv_for_mesh(jnp.zeros((2, 256, 8, 64)),
                         *[jnp.arange(2.).reshape(1, 1, 2, 1)] * 2)[0]
    assert k4.ravel().tolist() == [0., 0., 1., 1.]

    def loss(q, k, v):
        out, _ = F.flash_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                                   paddle.to_tensor(v), causal=True)
        return out.value.astype(jnp.float32).sum()
    q, kv = _sd((2, 1024, 8, 64), BF16), _sd((2, 1024, 2, 64), BF16)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    assert set(built) == {(2, True)}        # 2 query heads, grouped
    d = fa.last_attention_dispatch()
    assert d["backend"] == "pallas" and d["kv_heads"] == 4
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_zero3_train_step_compiles_for_four_v5e_chips(topo,
                                                      chip_like_config,
                                                      monkeypatch):
    """dist.ParallelTrainStep(zero_stage=3) on dp2 x sharding2 of the
    described chips, Pallas attention dispatch forced as on a TPU: GSPMD
    refuses a bare Mosaic kernel ("cannot be automatically partitioned"),
    which eight virtual CPU devices never showed — the kernel must
    arrive wrapped in a shard_map."""
    import importlib
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    dist.init_mesh({"dp": 2, "sharding": 2}, devices=list(topo.devices))
    with paddle.LazyGuard():
        model = GPTForCausalLM(GPTConfig(
            vocab_size=512, hidden_size=256, num_layers=2, num_heads=4,
            max_seq_len=256))
    model.bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, multi_precision=True,
                                 parameters=model.parameters())
    step = dist.ParallelTrainStep(model, GPTForCausalLM.loss_fn, opt,
                                  zero_stage=3)
    ids = jax.ShapeDtypeStruct((8, 256), jnp.int32)
    compiled = step.aot_compile(ids, ids)
    assert "shard_map" in fa.last_attention_dispatch()["reason"]
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "all-gather" in text            # the stage-3 weight gathers
