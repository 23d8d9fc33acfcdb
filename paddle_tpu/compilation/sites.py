"""Canonical program sites — the registry's default population.

These builders were born in `analysis/manifest.py` (PR 3) as tpulint's
private "rebuild the real programs" list; they now live here so ONE
table serves every consumer: tpulint lints them, `compilation.warmup`
prebuilds them and `tools/warmup.py` stores them. Each builds the
tiny-config variant of a production program exactly as its owner
builds it:

- gpt_decode:      the continuous-batching engine's batched decode tick
- gpt_admit:       the engine's bucketed prefill/admission program
- llama_prefill:   generate()'s prefill program over LLaMA-tiny
- llama_decode:    generate()'s whole-decode-scan program (newly
                   lint-covered by landing in the registry)
- train_step:      TrainStep's fused whole-step program
- train_step_scan: the K=4 fused training window
- parallel_train_step: ParallelTrainStep on a fake 4-device
                   dp2 x sharding2 ZeRO-2 mesh (compiled for the
                   collective inventory)

Everything is tiny-config and CPU-safe; no program is executed. Live
sites (a real serving engine, a real fit loop) don't go through these
fixtures — they warm THEIR OWN programs via `engine.warmup()` /
`TrainStep.warm()`; the fixtures' value is priming the persistent
caches for CI/tier-1 (the same programs tpulint and the quick tests
compile) and giving lint/warmup a hardware-free stand-in.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from .registry import BuildResult, register

__all__ = ["ensure_registered"]


def _tree_nbytes(tree) -> int:
    """Total leaf bytes of a pytree (params/caches) — the geometry
    inputs the tpucost decode anchor computes its analytic bound from.
    ONE implementation, shared with the live engine gauges
    (obs/efficiency.py): the modeled bytes the anchors price and the
    bytes the ptpu_engine_tick_model_eff gauge divides by must never
    drift apart."""
    from ..obs.efficiency import tree_nbytes
    return tree_nbytes(tree)


def _gpt_tiny_model():
    from ..models.gpt import GPTConfig, GPTForCausalLM
    from ..framework import random as _rng
    _rng.seed(0)
    return GPTForCausalLM(GPTConfig(vocab_size=256, hidden_size=64,
                                    num_layers=2, num_heads=4,
                                    max_seq_len=128))


def _tiny_engine():
    from ..inference.engine import ContinuousBatchingEngine
    model = _gpt_tiny_model()
    return ContinuousBatchingEngine(model, slots=4, max_len=64,
                                    cache_dtype="float32", tick_tokens=4)


def _tiny_paged_engine():
    """Paged variant of the tiny engine, with a pool SMALLER than
    slots * pages_per_slot (9 pages vs 16) — the fixture mirrors the
    production claim that the pool, not the slot count, bounds cache
    bytes, and the geometry below is what the tpucost
    decode_hbm_paged anchor prices."""
    from ..inference.engine import ContinuousBatchingEngine
    model = _gpt_tiny_model()
    return ContinuousBatchingEngine(model, slots=4, max_len=64,
                                    cache_dtype="float32", tick_tokens=4,
                                    paged=True, page_size=16,
                                    num_pages=9)


def build_gpt_decode() -> BuildResult:
    import jax
    eng = _tiny_engine()
    prog = eng._get_decode_prog()
    N = eng.slots
    args = (eng._params, eng._buffers, eng._caches,
            np.zeros(N, np.int32), np.zeros(N, np.int32),
            np.ones(N, bool), np.full(N, -1, np.int32),
            np.zeros((N, 2), np.uint32))
    geometry = {
        "kind": "decode", "slots": N, "max_len": eng.max_len,
        "tick_tokens": eng.tick_tokens,
        "tokens_per_exec": N * eng.tick_tokens,
        "param_bytes": _tree_nbytes((eng._params, eng._buffers)),
        "kv_cache_bytes": _tree_nbytes(eng._caches),
    }
    return BuildResult(prog, args, cleanup=eng.stop, geometry=geometry)


def build_gpt_admit() -> BuildResult:
    eng = _tiny_engine()
    bucket = eng.prefill_buckets[0]
    prog = eng._get_admit_prog(bucket)
    args = eng._admit_example_args(bucket)
    geometry = {
        "kind": "prefill", "batch": 1, "seq": bucket,
        "tokens_per_exec": bucket,
        "param_bytes": _tree_nbytes((eng._params, eng._buffers)),
        "kv_cache_bytes": _tree_nbytes(eng._caches),
    }
    return BuildResult(prog, args, cleanup=eng.stop, geometry=geometry)


def build_gpt_decode_paged() -> BuildResult:
    eng = _tiny_paged_engine()
    prog = eng._get_decode_prog()
    args = eng._decode_example_args()
    # kv_cache_bytes is the page POOL (what HBM actually holds);
    # kv_view_bytes is the gathered [N, pages_per_slot * page] view one
    # micro-step materializes — the paged analytic anchor prices both
    # (the engine's own gauge geometry computes the same number)
    view_bytes = eng._kv_view_nbytes()
    geometry = {
        "kind": "decode_paged", "slots": eng.slots,
        "max_len": eng.max_len, "page_size": eng.page_size,
        "num_pages": eng.num_pages,
        "pages_per_slot": eng.pages_per_slot,
        "tick_tokens": eng.tick_tokens,
        "tokens_per_exec": eng.slots * eng.tick_tokens,
        "param_bytes": _tree_nbytes((eng._params, eng._buffers)),
        "kv_cache_bytes": _tree_nbytes(eng._caches),
        "kv_view_bytes": view_bytes,
    }
    return BuildResult(prog, args, cleanup=eng.stop, geometry=geometry)


def build_gpt_admit_paged() -> BuildResult:
    eng = _tiny_paged_engine()
    bucket = eng.prefill_buckets[0]
    prog = eng._get_admit_prog(bucket)
    args = eng._admit_example_args(bucket)
    geometry = {
        "kind": "prefill_paged", "batch": 1, "seq": bucket,
        "page_size": eng.page_size, "num_pages": eng.num_pages,
        "tokens_per_exec": bucket,
        "param_bytes": _tree_nbytes((eng._params, eng._buffers)),
        "kv_cache_bytes": _tree_nbytes(eng._caches),
    }
    return BuildResult(prog, args, cleanup=eng.stop, geometry=geometry)


def _tiny_spec_engine():
    """Speculative (n-gram) variant of the tiny engine — the fixture
    behind the gpt_verify_k registry site. Slot cache: the verify
    block's cache traffic, not paging, is what the verify anchor
    prices."""
    from ..inference.engine import ContinuousBatchingEngine
    model = _gpt_tiny_model()
    return ContinuousBatchingEngine(model, slots=4, max_len=64,
                                    cache_dtype="float32", tick_tokens=4,
                                    speculative="ngram", spec_k=4)


def build_gpt_verify_k() -> BuildResult:
    """The speculative engine's batched verify-k program: ONE target
    forward scores k+1 positions for every slot (proposals, draft
    lengths, positions and live mask all ride as arguments — the
    zero-recompile contract tpulint pins)."""
    eng = _tiny_spec_engine()
    prog = eng._get_verify_prog()
    args = eng._verify_example_args()
    K = eng._spec.k
    geometry = {
        "kind": "verify", "slots": eng.slots, "max_len": eng.max_len,
        "spec_k": K, "block_tokens": K + 1,
        "tokens_per_exec": eng.slots * (K + 1),
        "param_bytes": _tree_nbytes((eng._params, eng._buffers)),
        "kv_cache_bytes": _tree_nbytes(eng._caches),
    }
    return BuildResult(prog, args, cleanup=eng.stop, geometry=geometry)


def build_gpt_draft_decode() -> BuildResult:
    """The draft-model proposer's batched decode program: the 2-token
    sync block + a k-step greedy draft scan over the draft's own slot
    cache — [N, k] proposals per dispatch."""
    from ..inference.speculative import DraftModelProposer
    model = _gpt_tiny_model()
    prop = DraftModelProposer(model, slots=4, max_len=64, k=4,
                              cache_dtype="float32")
    prog = prop._get_decode_prog()
    args = prop._decode_example_args()
    geometry = {
        "kind": "draft_decode", "slots": prop.slots,
        "max_len": prop.max_len, "spec_k": prop.k,
        "tokens_per_exec": prop.slots * prop.k,
        "param_bytes": _tree_nbytes((prop._params, prop._buffers)),
        "kv_cache_bytes": _tree_nbytes(prop._caches),
    }
    return BuildResult(prog, args, geometry=geometry)


def _llama_tiny_programs():
    import jax
    from ..models.llama import LlamaConfig, LlamaForCausalLM
    from ..models.generation import build_generate_programs
    from ..jit.functional import raw_state
    from ..framework import random as _rng
    _rng.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=176,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128))
    model.eval()
    P, new = 16, 8
    prefill, decode = build_generate_programs(
        model, P, new, eos=None, do_sample=False, temperature=1.0,
        top_k=0, top_p=1.0)
    params, buffers = raw_state(model)
    caches = model.new_cache(1, P + new, "float32")
    return prefill, decode, params, buffers, caches, P


def build_llama_prefill() -> BuildResult:
    import jax
    prefill, _, params, buffers, caches, P = _llama_tiny_programs()
    args = (params, buffers, np.zeros((1, P), np.int64), caches,
            jax.random.PRNGKey(0))
    geometry = {
        "kind": "prefill", "batch": 1, "seq": P, "tokens_per_exec": P,
        "param_bytes": _tree_nbytes((params, buffers)),
        "kv_cache_bytes": _tree_nbytes(caches),
    }
    return BuildResult(prefill, args, geometry=geometry)


def build_llama_decode() -> BuildResult:
    import jax
    _, decode, params, buffers, caches, _ = _llama_tiny_programs()
    tok0 = np.zeros((1,), np.int32)
    args = (params, buffers, tok0, caches, jax.random.PRNGKey(0))
    geometry = {
        "kind": "decode", "batch": 1, "new_tokens": 8,
        "tokens_per_exec": 8,
        "param_bytes": _tree_nbytes((params, buffers)),
        "kv_cache_bytes": _tree_nbytes(caches),
    }
    return BuildResult(decode, args, geometry=geometry)


def _train_step_parts(model):
    from ..optimizer import AdamW
    from ..models.gpt import GPTForCausalLM
    from ..framework import random as _rng
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
    return GPTForCausalLM.loss_fn, opt, _rng


def build_train_step() -> BuildResult:
    import jax.numpy as jnp
    from ..jit.training import TrainStep
    model = _gpt_tiny_model()
    loss_fn, opt, _rng = _train_step_parts(model)
    step = TrainStep(model, loss_fn, opt)
    step._build()
    ids = np.zeros((2, 32), np.int64)
    args = (step.params, step.buffers, step.opt_state,
            jnp.asarray(1e-3, jnp.float32), jnp.asarray(1, jnp.float32),
            _rng.default_generator().fold_in(1), ids, ids)
    geometry = {
        "kind": "train", "batch": 2, "seq": 32, "tokens_per_exec": 64,
        "param_bytes": _tree_nbytes((step.params, step.buffers)),
    }
    return BuildResult(step._jitted, args, geometry=geometry)


def build_train_step_scan() -> BuildResult:
    """The fused K-step window exactly as Model.fit dispatches it:
    TrainStep.scan_steps' jitted program at K=4 — super-batch + state
    donated, the PRNG base key an ARGUMENT (per-step keys fold in-
    program), no host callback anywhere in the window."""
    from ..jit.training import TrainStep
    from ..framework import random as _rng
    model = _gpt_tiny_model()
    loss_fn, opt, _rng2 = _train_step_parts(model)
    step = TrainStep(model, loss_fn, opt)
    K = 4
    prog = step._get_scan_prog(K, 2)
    ids = np.zeros((K, 2, 32), np.int64)
    args = (step.params, step.buffers, step.opt_state,
            _rng.get_rng_state(),
            np.full((K,), 1e-3, np.float32),
            np.arange(1, K + 1, dtype=np.float32),
            np.arange(1, K + 1, dtype=np.int32), ids, ids)
    geometry = {
        "kind": "train", "scan_steps": K, "batch": 2, "seq": 32,
        "tokens_per_exec": K * 2 * 32,
        "param_bytes": _tree_nbytes((step.params, step.buffers)),
    }
    return BuildResult(prog, args, geometry=geometry)


def build_parallel_train_step() -> BuildResult:
    import jax
    import jax.numpy as jnp
    from ..distributed import mesh as mesh_mod
    from ..distributed.parallel_step import ParallelTrainStep
    prev = mesh_mod.get_mesh(create_default=False)
    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(
            f"parallel_train_step needs >= 4 devices, have {len(devs)} "
            "(run under XLA_FLAGS=--xla_force_host_platform_device_"
            "count=8; tools/tpulint.py and tools/warmup.py set this up "
            "themselves)")

    def cleanup():
        mesh_mod.set_mesh(prev)

    try:
        mesh_mod.init_mesh({"dp": 2, "sharding": 2}, devices=devs[:4])
        model = _gpt_tiny_model()
        loss_fn, opt, _rng = _train_step_parts(model)
        step = ParallelTrainStep(model, loss_fn, opt, zero_stage=2)
        ids = np.zeros((4, 32), np.int64)
        raw_batch = (ids, ids)
        step._build(raw_batch)
        args = (step.params, step.buffers, step.opt_state,
                jnp.asarray(1e-3, jnp.float32),
                jnp.asarray(1, jnp.float32),
                _rng.default_generator().fold_in(1)) + raw_batch
        geometry = {
            "kind": "train", "batch": 4, "seq": 32,
            "tokens_per_exec": 128,
            "param_bytes": _tree_nbytes((step.params, step.buffers)),
        }
    except BaseException:
        # build raised after the global mesh was swapped: restore it
        # here — consumers never receive the cleanup on this path
        cleanup()
        raise
    return BuildResult(step._jitted, args, cleanup=cleanup,
                       geometry=geometry)


def _build_parallel_train_step_stage3(comm_precision: str,
                                      kind: str) -> BuildResult:
    """ZeRO-3 ParallelTrainStep at dp2 x sharding2 — the fp32/quantized
    A/B pair behind the tpucost comm_bytes anchor: identical model,
    mesh and batch, the ONLY difference is the collective wire
    precision, so the per-chip byte ratio between the two inventories
    is exactly the quantization saving (ISSUE 17 acceptance gate)."""
    import jax
    import jax.numpy as jnp
    from ..distributed import mesh as mesh_mod
    from ..distributed.parallel_step import ParallelTrainStep
    prev = mesh_mod.get_mesh(create_default=False)
    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(
            f"{kind} needs >= 4 devices, have {len(devs)} (run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)")

    def cleanup():
        mesh_mod.set_mesh(prev)

    try:
        mesh_mod.init_mesh({"dp": 2, "sharding": 2}, devices=devs[:4])
        model = _gpt_tiny_model()
        loss_fn, opt, _rng = _train_step_parts(model)
        step = ParallelTrainStep(model, loss_fn, opt, zero_stage=3,
                                 comm_precision=comm_precision)
        ids = np.zeros((4, 32), np.int64)
        raw_batch = (ids, ids)
        step._build(raw_batch)
        args = (step.params, step.buffers, step.opt_state,
                jnp.asarray(1e-3, jnp.float32),
                jnp.asarray(1, jnp.float32),
                _rng.default_generator().fold_in(1)) + raw_batch
        geometry = {
            "kind": "train", "batch": 4, "seq": 32,
            "tokens_per_exec": 128, "zero_stage": 3,
            "comm_precision": comm_precision,
            "param_bytes": _tree_nbytes((step.params, step.buffers)),
        }
    except BaseException:
        cleanup()
        raise
    return BuildResult(step._jitted, args, cleanup=cleanup,
                       geometry=geometry)


def build_parallel_train_step_z3() -> BuildResult:
    return _build_parallel_train_step_stage3("fp32",
                                             "parallel_train_step_z3")


def build_parallel_train_step_q() -> BuildResult:
    return _build_parallel_train_step_stage3("int8",
                                             "parallel_train_step_q")


def _knob_variant(knob: str, base_builder, geom_key: str) -> BuildResult:
    """A fusion-knob twin of an existing site: build the SAME program
    with the env knob on for the whole build->lower->measure window
    (the knobs are trace-time reads), restore the prior value in
    cleanup. The twin gets its own registry name so tpucost budgets the
    fused inventory separately and the fusion_hbm anchor can price it
    against the unfused baseline_program."""
    import os
    prev = os.environ.get(knob)
    os.environ[knob] = "1"
    br = base_builder()

    def cleanup(_prev=prev, _inner=br.cleanup):
        if _prev is None:
            os.environ.pop(knob, None)
        else:
            os.environ[knob] = _prev
        if _inner:
            _inner()

    geometry = dict(br.geometry or {})
    geometry[geom_key] = True
    return BuildResult(br.fn, br.args, cleanup=cleanup,
                       geometry=geometry)


def build_gpt_decode_fused() -> BuildResult:
    """gpt_decode with PADDLE_TPU_FUSED_CACHE_WRITE on: the S=1 slot
    decode runs the fused write+attend chain (kernels/cache_write.py +
    the restructured old-cache attention in flash_attention.py).
    Greedy-token-identical to gpt_decode; the fusion_hbm anchor pins
    the modeled HBM drop."""
    return _knob_variant("PADDLE_TPU_FUSED_CACHE_WRITE",
                         build_gpt_decode, "fused_cache_write")


def build_gpt_decode_mega() -> BuildResult:
    """gpt_decode with PADDLE_TPU_MEGA_DECODE on: each layer's decode
    inner step (cache read -> attention -> cache write) is ONE Pallas
    dispatch (kernels/mega_decode.py). Prototype site — budgets pin
    whatever the mega kernel measures at, so regressions in its
    CPU-modeled form stay visible."""
    return _knob_variant("PADDLE_TPU_MEGA_DECODE",
                         build_gpt_decode, "mega_decode")


def _per_chip_nbytes(tree) -> int:
    """One chip's bytes for a (possibly sharded) pytree: a sharded
    leaf contributes its LOCAL shard, a replicated leaf its full size.
    This is the geometry convention for the TP sites — the compiled
    SPMD module tpucost measures is the per-chip partition, so the
    decode_hbm analytic bound must be priced in per-chip bytes too
    (÷tp for the sharded weights/caches, full for the replicated
    remainder)."""
    total = 0
    for leaf in _jax_tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards:
            total += shards[0].data.nbytes
        else:
            total += leaf.nbytes
    return total


def _jax_tree_leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


def _tp_engine(model, comm_precision: Optional[str] = None, tp: int = 2):
    """A tp-sliced engine with its TP scope HELD ACTIVE past builder
    return (the z3 lifetime pattern: consumers trace/lower AFTER the
    builder returns, and the thread-local mesh + comm-precision must
    still be live then). The returned cleanup closes the scope, then
    stops the engine."""
    import contextlib
    from ..inference.engine import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(model, slots=4, max_len=64,
                                   cache_dtype="float32", tick_tokens=4,
                                   tp=tp, comm_precision=comm_precision)
    stack = contextlib.ExitStack()
    stack.callback(eng.stop)
    stack.enter_context(eng._tp_scope())
    return eng, stack.close


def build_gpt_decode_tp() -> BuildResult:
    """The tp=2 sharded engine decode tick (ISSUE 20): same program
    shape as gpt_decode, params/KV head-sharded over the "mp" slice,
    one all-reduce pair per block. Geometry is PER-CHIP (what the SPMD
    partition tpucost measures), so the decode_hbm anchor pins
    per-chip tick HBM at ~1/tp of the single-chip pin; the exact-fp32
    wire makes this the comm_bytes A/B reference for _tp_q."""
    eng, cleanup = _tp_engine(_gpt_tiny_model())
    prog = eng._get_decode_prog()
    args = eng._decode_example_args()
    geometry = {
        "kind": "decode", "slots": eng.slots, "max_len": eng.max_len,
        "tick_tokens": eng.tick_tokens, "tp": eng.tp,
        "tokens_per_exec": eng.slots * eng.tick_tokens,
        "param_bytes": _per_chip_nbytes((eng._params, eng._buffers)),
        "kv_cache_bytes": _per_chip_nbytes(eng._caches),
        "modeled_tick_comm_bytes": eng.tp_tick_comm_bytes,
    }
    return BuildResult(prog, args, cleanup=cleanup, geometry=geometry)


def build_gpt_decode_tp_q() -> BuildResult:
    """gpt_decode_tp with comm_precision="int8": the per-block TP
    all-reduce routed through the PR 17 EQuARX wire bodies. Same
    geometry as the fp32 twin; the comm_bytes anchor pins the per-chip
    collective-byte reduction ratio so the quantized wire can't
    silently revert to f32 payloads."""
    eng, cleanup = _tp_engine(_gpt_tiny_model(), comm_precision="int8")
    prog = eng._get_decode_prog()
    args = eng._decode_example_args()
    geometry = {
        "kind": "decode", "slots": eng.slots, "max_len": eng.max_len,
        "tick_tokens": eng.tick_tokens, "tp": eng.tp,
        "comm_precision": "int8",
        "tokens_per_exec": eng.slots * eng.tick_tokens,
        "param_bytes": _per_chip_nbytes((eng._params, eng._buffers)),
        "kv_cache_bytes": _per_chip_nbytes(eng._caches),
        "modeled_tick_comm_bytes": eng.tp_tick_comm_bytes,
    }
    return BuildResult(prog, args, cleanup=cleanup, geometry=geometry)


def build_gpt_admit_tp() -> BuildResult:
    """The tp=2 engine's bucketed admission program — prefill over the
    sharded weights writing head-sharded cache rows. In the registry so
    the WHOLE sharded lifecycle (admit -> decode) is lint/cost covered,
    not just the steady-state tick."""
    eng, cleanup = _tp_engine(_gpt_tiny_model())
    bucket = eng.prefill_buckets[0]
    prog = eng._get_admit_prog(bucket)
    args = eng._admit_example_args(bucket)
    geometry = {
        "kind": "prefill", "batch": 1, "seq": bucket, "tp": eng.tp,
        "tokens_per_exec": bucket,
        "param_bytes": _per_chip_nbytes((eng._params, eng._buffers)),
        "kv_cache_bytes": _per_chip_nbytes(eng._caches),
    }
    return BuildResult(prog, args, cleanup=cleanup, geometry=geometry)


def _llama_tiny_model():
    from ..models.llama import LlamaConfig, LlamaForCausalLM
    from ..framework import random as _rng
    _rng.seed(0)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=176,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128))


def build_llama_decode_tp() -> BuildResult:
    """The tp=2 engine decode tick over LLaMA-tiny — GQA coverage: the
    num_kv_heads=2 pools shard one KV head per chip while the 4 query
    heads shard 2-per-chip, exercising the uneven head-group split the
    GPT site can't."""
    eng, cleanup = _tp_engine(_llama_tiny_model())
    prog = eng._get_decode_prog()
    args = eng._decode_example_args()
    geometry = {
        "kind": "decode", "slots": eng.slots, "max_len": eng.max_len,
        "tick_tokens": eng.tick_tokens, "tp": eng.tp,
        "tokens_per_exec": eng.slots * eng.tick_tokens,
        "param_bytes": _per_chip_nbytes((eng._params, eng._buffers)),
        "kv_cache_bytes": _per_chip_nbytes(eng._caches),
        "modeled_tick_comm_bytes": eng.tp_tick_comm_bytes,
    }
    return BuildResult(prog, args, cleanup=cleanup, geometry=geometry)


def build_train_step_fused_ce() -> BuildResult:
    """train_step with PADDLE_TPU_FUSED_CE on: the loss functional
    dispatches the online-LSE fused cross-entropy
    (kernels/fused_ce.py). The fusion_hbm anchor pins the forward
    LSE-chain collapse (kernel count AND bytes) against train_step."""
    return _knob_variant("PADDLE_TPU_FUSED_CE",
                         build_train_step, "fused_ce")


_registered = False


def ensure_registered() -> None:
    """Populate the registry with the canonical sites (idempotent —
    registry.py calls this lazily on first lookup)."""
    global _registered
    if _registered:
        return
    # ORDER MATTERS for tpulint: the first five names reproduce the
    # pre-registry MANIFEST_PROGRAMS order so baseline keys and
    # reports stay stable; newly covered programs append after.
    register("gpt_decode", build_gpt_decode,
             tags=("manifest", "serving"),
             description="engine batched decode tick (GPT-tiny)")
    register("llama_prefill", build_llama_prefill,
             tags=("manifest", "serving"),
             description="generate() prefill program (LLaMA-tiny)")
    register("train_step", build_train_step,
             tags=("manifest", "training"),
             description="TrainStep fused whole-step program")
    register("train_step_scan", build_train_step_scan,
             tags=("manifest", "training"),
             description="fused K=4 training window")
    register("parallel_train_step", build_parallel_train_step,
             tags=("manifest", "training", "collectives"),
             compile_collectives=True, min_devices=4,
             description="ParallelTrainStep on dp2 x sharding2 ZeRO-2")
    register("gpt_admit", build_gpt_admit,
             tags=("manifest", "serving"),
             description="engine bucketed prefill/admission program")
    register("llama_decode", build_llama_decode,
             tags=("manifest", "serving"),
             description="generate() whole-decode scan (LLaMA-tiny)")
    register("gpt_decode_paged", build_gpt_decode_paged,
             tags=("manifest", "serving"),
             description="paged-engine batched decode tick "
                         "(gather-based block-table reads)")
    register("gpt_admit_paged", build_gpt_admit_paged,
             tags=("manifest", "serving"),
             description="paged-engine suffix admission program "
                         "(page-masked prefill append)")
    register("gpt_verify_k", build_gpt_verify_k,
             tags=("manifest", "serving"),
             description="speculative batched verify-k program "
                         "(one forward scores k+1 positions per slot)")
    register("gpt_draft_decode", build_gpt_draft_decode,
             tags=("manifest", "serving"),
             description="draft-model proposer decode program "
                         "(sync block + k-step greedy draft scan)")
    register("parallel_train_step_z3", build_parallel_train_step_z3,
             tags=("manifest", "training", "collectives"),
             compile_collectives=True, min_devices=4,
             description="ParallelTrainStep ZeRO-3 fp32 baseline "
                         "(dp2 x sharding2; comm_bytes A/B reference)")
    register("parallel_train_step_q", build_parallel_train_step_q,
             tags=("manifest", "training", "collectives"),
             compile_collectives=True, min_devices=4,
             description="ParallelTrainStep ZeRO-3 int8 quantized "
                         "collectives (same geometry as _z3)")
    register("gpt_decode_fused", build_gpt_decode_fused,
             tags=("manifest", "serving"),
             description="engine decode tick with fused cache-write + "
                         "write+attend chain (fusion_hbm A/B twin of "
                         "gpt_decode)")
    register("gpt_decode_mega", build_gpt_decode_mega,
             tags=("manifest", "serving"),
             description="engine decode tick with the mega-kernel "
                         "per-layer inner step (Pallas prototype)")
    register("train_step_fused_ce", build_train_step_fused_ce,
             tags=("manifest", "training"),
             description="TrainStep with the fused online-LSE "
                         "cross-entropy (fusion_hbm A/B twin of "
                         "train_step)")
    register("gpt_decode_tp", build_gpt_decode_tp,
             tags=("manifest", "serving", "collectives"),
             compile_collectives=True, min_devices=2,
             description="TP-sharded engine decode tick (tp=2 slice; "
                         "per-chip decode_hbm pin + comm_bytes fp32 "
                         "reference)")
    register("gpt_decode_tp_q", build_gpt_decode_tp_q,
             tags=("manifest", "serving", "collectives"),
             compile_collectives=True, min_devices=2,
             description="TP decode tick with int8 quantized per-block "
                         "all-reduce wire (comm_bytes A/B twin of "
                         "gpt_decode_tp)")
    register("gpt_admit_tp", build_gpt_admit_tp,
             tags=("manifest", "serving", "collectives"),
             compile_collectives=True, min_devices=2,
             description="TP-sharded engine admission program (bucketed "
                         "prefill writing head-sharded cache rows)")
    register("llama_decode_tp", build_llama_decode_tp,
             tags=("manifest", "serving", "collectives"),
             compile_collectives=True, min_devices=2,
             description="TP-sharded engine decode tick over LLaMA-tiny "
                         "(GQA: one KV head per chip)")
    # only now: a failure above (e.g. a consumer squatting a canonical
    # name) must stay loud on every retry, not flip the flag and leave
    # the registry silently half-populated for the rest of the process
    _registered = True


# registry.py imports this module lazily and expects registration as a
# side effect of that import
ensure_registered()
