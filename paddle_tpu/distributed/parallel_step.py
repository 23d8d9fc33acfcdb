"""ParallelTrainStep: the hybrid-parallel training engine.

This one class is the TPU-native replacement for the reference's whole
hybrid stack: HybridParallelOptimizer (fleet/meta_optimizers/
dygraph_optimizer/hybrid_parallel_optimizer.py:226), the EagerReducer DP
path, GroupSharded ZeRO stages 1-2 (group_sharded_optimizer_stage2.py:53),
and the per-axis broadcast/allreduce utils (hybrid_parallel_util.py). One
jitted program over the global Mesh carries every axis:

- dp:        batch dim sharded; gradient psum emitted by XLA where the
             batch-mean demands it.
- mp:        parameters annotated by the TP layers (Parameter.sharding_axes)
             are laid out sharded; GSPMD inserts the per-layer collectives
             (reference: mpu/mp_ops.py identity/allreduce/split ops).
- sharding:  ZeRO — optimizer slots (and master weights) sharded over the
             axis; gradients constrained to the same layout so XLA lowers
             grad psum into reduce-scatter + sharded update + param
             all-gather (the "Automatic Cross-Replica Sharding of Weight
             Update" recipe, PAPERS.md arxiv 2004.13336).
- sp:        sequence dim of the batch sharded (exceeds reference, §5.7).

Buffers are donated: params/slots update in place in HBM.
"""
from __future__ import annotations

import os
import re
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..autograd.tape import no_grad
from ..core.tensor import Tensor
from ..framework import random as _rng
from ..jit.functional import (EXPORT_DISABLED_CHECKS, functional_call,
                              load_state, raw_state, _wrap)
from ..jit.training import (TrainStep, _raw_tuple, op_scopes_of,
                            publish_step_program)
from ..obs.trace import span as _span
from . import mesh as mesh_mod

__all__ = ["ParallelTrainStep", "param_sharding", "shard_params"]


def _spec_from_axes(shape, axes, mesh) -> P:
    """Parameter.sharding_axes (tuple of axis-name-or-None per dim, or
    None) -> PartitionSpec valid on `mesh` (unknown/size-1 axes elided)."""
    if axes is None:
        return P()
    spec = []
    for d, ax in enumerate(axes):
        if ax is not None and ax in mesh.shape and mesh.shape[ax] > 1 \
                and shape[d] % mesh.shape[ax] == 0:
            spec.append(ax)
        else:
            spec.append(None)
    return P(*spec)


def param_sharding(model, mesh=None) -> Dict[str, NamedSharding]:
    """NamedSharding per named parameter from its sharding_axes annotation
    (role of the reference's dist_attr, auto_parallel/dist_attr.cc)."""
    mesh = mesh or mesh_mod.get_mesh()
    out = {}
    for name, p in model.named_parameters():
        axes = getattr(p, "sharding_axes", None)
        out[name] = NamedSharding(mesh, _spec_from_axes(p.shape, axes, mesh))
    return out


def shard_params(model, mesh=None):
    """Physically lay out the model's parameters on the mesh according to
    their annotations (reference: Partitioner, auto_parallel/partitioner.py)."""
    mesh = mesh or mesh_mod.get_mesh()
    shardings = param_sharding(model, mesh)
    for name, p in model.named_parameters():
        p.value = jax.device_put(p.value, shardings[name])
    return model


def _zero_spec(shape, mesh, axis: str, base: Optional[P] = None) -> P:
    """ZeRO layout for one leaf: add `axis` on the LAST dim that is
    divisible by the axis size and not already sharded by `base` (the
    parameter's mp layout). Last-dim placement composes with typical mp
    layouts without forcing GSPMD replicate-then-repartition resharding
    (first-dim placement triggered "involuntary full rematerialization"
    on pipeline-stacked embedding grads). Composing instead of overriding matters: a
    zero spec that conflicts with the mp layout forces GSPMD into a
    replicate-then-repartition ("involuntary full rematerialization")
    on every grad reduce. Scalars/indivisible leaves stay at `base`."""
    n = mesh.shape.get(axis, 1)
    base_spec = list(base) if base is not None else []
    base_spec += [None] * (len(shape) - len(base_spec))
    if n <= 1:
        return P(*base_spec)
    for d in reversed(range(len(shape))):
        size = shape[d]
        if base_spec[d] is None and size % n == 0 and size >= n:
            spec = list(base_spec)
            spec[d] = axis
            return P(*spec)
    return P(*base_spec)


_COMM_PRECISIONS = ("fp32", "bf16", "int8")


def _layer_groups(names):
    """Order parameter names into gather groups for the stage-3 chunked
    overlap schedule: the first ``.<int>.`` path segment is the layer
    index; indexless params (embeddings, final norms, heads) form the
    leading group. Returns a list of name-lists in gather order."""
    groups: Dict[int, list] = {}
    for n in names:
        m = re.search(r"\.(\d+)\.", n)
        key = int(m.group(1)) if m else -1
        groups.setdefault(key, []).append(n)
    return [groups[k] for k in sorted(groups)]


class ParallelTrainStep:
    """Hybrid-parallel fused train step over the global mesh.

    loss_fn contract matches jit.TrainStep: loss_fn(outputs, *labels).
    `batch_specs`: optional PartitionSpec per batch arg (default: dim 0
    over every data axis — ("dp", "sharding") jointly when both exist
    and divide the batch, ZeRO groups being sub-groups of data
    parallelism — and, if the arg is rank>=2 and "sp" exists, dim 1
    over "sp" for sequence parallelism).
    """

    def __init__(self, model, loss_fn, optimizer, n_inputs: int = 1,
                 zero_stage: int = 0, batch_specs=None, mesh=None,
                 remat: bool = False, accumulate_steps: int = 1,
                 remat_policy: str = "full",
                 comm_precision: Optional[str] = None,
                 comm_block: int = 256):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.n_inputs = n_inputs
        if zero_stage == 0:
            # sharding.group_sharded_parallel records the requested ZeRO
            # level on the optimizer (reference GroupSharded entry point)
            zero_stage = getattr(optimizer, "_group_sharded_level", 0)
        self.zero_stage = zero_stage
        self.remat = remat
        # resolve eagerly: a typo'd policy fails at construction (same
        # contract as models/scanned.py)
        from .recompute import resolve_checkpoint_policy
        self._remat_policy = resolve_checkpoint_policy(remat_policy)
        self.mesh = mesh or mesh_mod.get_mesh()
        self.batch_specs = batch_specs
        if accumulate_steps < 1:
            raise ValueError("accumulate_steps must be >= 1")
        self.accumulate_steps = accumulate_steps
        self.step_count = 0
        self.update_count = 0
        self._jitted = None
        self._jitted_acc = None
        # flush_accumulation programs keyed by remainder r (tpulint
        # jit-in-call: a fresh jax.jit per flush re-traced every time)
        self._flush_progs = {}
        # scanned K-step fused programs keyed by (k_steps, batch avals)
        self._scan_progs = {}
        # trace-time program counter (same contract as jit.TrainStep)
        self._trace_count = 0
        # `_trace_count` at the last record of its program, and that
        # record (jit.training.publish_step_program / op_scopes_of)
        self._published_at = 0
        self._step_program = None
        # LR-scheduler ownership knob, honored by BOTH __call__ and
        # scan_steps (same contract as jit.TrainStep.auto_lr_step):
        # False = an external owner steps the schedule between calls
        self.auto_lr_step = True

        # ZeRO collective wire precision (ISSUE 17): "fp32" keeps the
        # implicit GSPMD collectives bitwise; "bf16"/"int8" replace the
        # stage>=2 gradient reduction and stage-3 weight gather with
        # EXPLICIT quantized collectives (distributed/quantized.py) via
        # a shard_map over the data axes. Programs are cached per
        # precision, so flipping the knob across steps never recompiles
        # an already-built program.
        if comm_precision is None:
            comm_precision = os.environ.get(
                "PADDLE_TPU_COMM_PRECISION", "fp32")
        comm_precision = str(comm_precision).lower()
        if comm_precision not in _COMM_PRECISIONS:
            raise ValueError(
                f"comm_precision must be one of {_COMM_PRECISIONS}; "
                f"got {comm_precision!r}")
        self.comm_precision = comm_precision
        self.comm_block = int(comm_block)
        self._prec_progs = {}

        shardings = param_sharding(model, self.mesh)
        params, buffers = raw_state(model)
        base_specs = {n: shardings[n].spec for n in params}
        ax = "sharding" if self.mesh.shape.get("sharding", 1) > 1 else "dp"
        self._zero_axis = ax if zero_stage >= 1 else None
        self._comm_axes = tuple(
            a for a in ("dp", "sharding")
            if self.mesh.shape.get(a, 1) > 1)
        self._comm_group = 1
        for a in self._comm_axes:
            self._comm_group *= self.mesh.shape[a]
        if comm_precision != "fp32" and self._comm_group > 1:
            hybrid = [a for a in ("mp", "sp", "pp", "ep")
                      if self.mesh.shape.get(a, 1) > 1]
            if hybrid:
                raise ValueError(
                    f"comm_precision={comm_precision!r} needs a "
                    f"data-only mesh (dp/sharding); mesh also has "
                    f"{hybrid} — the quantized fwd/bwd runs the model "
                    "per-shard and cannot carry tensor/sequence/"
                    "pipeline collectives")
            if zero_stage < 2:
                raise ValueError(
                    f"comm_precision={comm_precision!r} requires ZeRO "
                    f"stage >= 2 (stage {zero_stage} has no gradient "
                    "reduce-scatter to quantize)")

        # ZeRO stages (reference: GroupSharded stage1/2/3,
        # group_sharded_optimizer_stage2.py:53, group_sharded_stage3.py:59):
        #   1: optimizer slots (incl. master weights) sharded over `ax`
        #   2: + gradients reduce-scattered into the same layout
        #   3: + parameters themselves sharded (param memory / N); GSPMD
        #      all-gathers each weight at its use site in forward — the
        #      in-program equivalent of stage3's forward all-gather hooks
        #      (group_sharded_stage3.py:194) — and keeps the updated param
        #      sharded on output.
        if zero_stage >= 3:
            self.param_shardings = {
                n: NamedSharding(self.mesh,
                                 _zero_spec(v.shape, self.mesh, ax,
                                            base_specs[n]))
                for n, v in params.items()}
            # stage-3 FSDP contract, made explicit: weights are
            # all-gathered back to their mp layout ONCE per fwd (and
            # re-gathered in the remat'd bwd), not resolved ad-hoc at
            # every matmul. Without this use-site constraint the SPMD
            # partitioner sees the zero axis on BOTH matmul operands
            # (batch rows of x, contraction dim of W) and can resolve
            # the conflict by un-sharding the ACTIVATIONS — counted
            # from the 6.7B step's shapes: ~2.7 TiB/step of activation
            # all-gathers vs ~40 GiB/step of weight gathers with the
            # constraint (not checked by any test). Reference semantics:
            # group_sharded_stage3.py:194 forward all-gather hooks.
            self._use_shardings = {n: NamedSharding(self.mesh,
                                                    base_specs[n])
                                   for n in params}
        else:
            self.param_shardings = {n: shardings[n] for n in params}
            self._use_shardings = None
        # Abstract mode (framework/lazy_init.LazyGuard): params are
        # ShapeDtypeStruct avals — nothing is materialized; the step can
        # only be aot_compile()d (north-star-scale validation without the
        # memory, reference role: the fleet hybrid suites at real scale).
        self._abstract = any(isinstance(v, jax.ShapeDtypeStruct)
                             for v in params.values())
        if self._abstract:
            self.params = dict(params)
            self.buffers = {n: (v if isinstance(v, jax.ShapeDtypeStruct)
                                else jax.ShapeDtypeStruct(v.shape, v.dtype))
                            for n, v in buffers.items()}
            opt_state = jax.eval_shape(optimizer.init, self.params)
        else:
            # params live sharded (mp; + zero axis at stage 3).
            # jnp.copy first: device_put with an already-matching sharding
            # returns the SAME buffer, and step() donates these — without
            # the copy the model's own arrays would be deleted
            self.params = {n: jax.device_put(jnp.copy(v),
                                             self.param_shardings[n])
                           for n, v in params.items()}
            self.buffers = {n: jnp.copy(v) for n, v in buffers.items()}
            opt_state = optimizer.init(self.params)
        if zero_stage >= 1:
            def slot_spec(pname, leaf):
                # slots follow their parameter's mp+zero layout when shapes
                # line up (momentum/variance/master copies); scalar slots
                # stay replicated
                base = (base_specs[pname]
                        if leaf.shape == params[pname].shape else None)
                return NamedSharding(
                    self.mesh, _zero_spec(leaf.shape, self.mesh, ax, base))
            self.opt_shardings = {
                n: jax.tree_util.tree_map(
                    lambda leaf, n=n: slot_spec(n, leaf), slots)
                for n, slots in opt_state.items()}
            self.grad_shardings = {
                n: NamedSharding(self.mesh,
                                 _zero_spec(v.shape, self.mesh, ax,
                                            base_specs[n]))
                for n, v in params.items()}
        else:
            self.opt_shardings = jax.tree_util.tree_map(
                lambda leaf: NamedSharding(self.mesh, P()), opt_state)
        if self._abstract:
            self.opt_state = opt_state
        else:
            self.opt_state = jax.tree_util.tree_map(
                lambda v, s: jax.device_put(v, s), opt_state,
                self.opt_shardings)
        self.acc_grads = None
        if accumulate_steps > 1:
            acc_sh = (self.grad_shardings if zero_stage >= 2
                      else self.param_shardings)
            self.acc_grad_shardings = acc_sh
            if self._abstract:
                self.acc_grads = {
                    n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                    for n, v in self.params.items()}
            else:
                self.acc_grads = {
                    n: jax.device_put(jnp.zeros_like(v), acc_sh[n])
                    for n, v in self.params.items()}

    # ------------------------------------------------------------------
    def _batch_sharding(self, raw_batch):
        mesh = self.mesh
        out = []
        for i, b in enumerate(raw_batch):
            if self.batch_specs is not None:
                out.append(NamedSharding(mesh, self.batch_specs[i]))
                continue
            spec = [None] * b.ndim
            if b.ndim >= 1:
                # The batch axis splits over EVERY data axis: dp AND
                # sharding. ZeRO's sharding groups live INSIDE data
                # parallelism (reference GroupSharded: world = dp x
                # shard group, every rank holds a DIFFERENT batch
                # shard) — replicating the batch across "sharding"
                # would redundantly compute identical microbatches on
                # every group member (caught by the r5 north-star
                # analytic model: 8x wasted FLOPs at dp8 x sharding8).
                axes = []
                width = 1
                for ax in ("dp", "sharding"):
                    n = mesh.shape.get(ax, 1)
                    if n > 1 and b.shape[0] % (width * n) == 0:
                        axes.append(ax)
                        width *= n
                if axes:
                    spec[0] = tuple(axes) if len(axes) > 1 else axes[0]
            if b.ndim >= 2 and mesh.shape.get("sp", 1) > 1 \
                    and b.shape[1] % mesh.shape["sp"] == 0:
                spec[1] = "sp"
            out.append(NamedSharding(mesh, P(*spec)))
        return tuple(out)

    def _comm_active(self) -> bool:
        """True when the explicit quantized-collective fwd/bwd is in
        force (a non-fp32 knob on a trivial 1-device data group is a
        no-op — there is no wire to quantize)."""
        return self.comm_precision != "fp32" and self._comm_group > 1

    def set_comm_precision(self, precision: str):
        """Flip the collective wire precision between steps. Programs
        are cached per precision: the first step at a new precision
        compiles once, flipping back reuses the cached executable with
        ZERO recompiles (asserted via `_trace_count` in the tests)."""
        precision = str(precision).lower()
        if precision not in _COMM_PRECISIONS:
            raise ValueError(
                f"comm_precision must be one of {_COMM_PRECISIONS}; "
                f"got {precision!r}")
        if precision == self.comm_precision:
            return
        if precision != "fp32" and self._comm_group > 1:
            if self.zero_stage < 2:
                raise ValueError(
                    f"comm_precision={precision!r} requires ZeRO "
                    "stage >= 2")
        self._prec_progs[self.comm_precision] = (self._jitted,
                                                 self._jitted_acc)
        self.comm_precision = precision
        self._jitted, self._jitted_acc = self._prec_progs.get(
            precision, (None, None))

    def _make_fwd_bwd(self):
        """fwd+loss+bwd closure shared by the per-step and scanned
        programs (same graph -> bitwise-equal trajectories). Dispatches
        to the explicit quantized-collective variant when a non-fp32
        comm_precision is active."""
        if self._comm_active():
            return self._make_fwd_bwd_q()
        model, loss_fn = self.model, self.loss_fn
        n_in = self.n_inputs
        # stage >= 2: gradients reduce-scattered into the ZeRO layout
        # (stage 1 shards only the optimizer state, reference stage1/2 split)
        zero_grads = self.zero_stage >= 2
        grad_shardings = self.grad_shardings if self.zero_stage >= 1 else None
        remat = self.remat

        use_shardings = self._use_shardings

        def fwd_bwd(params, buffers, lr, step_no, rng_key, *batch):
            inputs, labels = batch[:n_in], batch[n_in:]

            def loss_of(p):
                from ..framework.aux_loss import aux_loss_scope, total
                if use_shardings is not None:
                    # inside the checkpoint boundary: the gathered
                    # weights are recomputed (re-gathered) in bwd, not
                    # saved — stage-3 memory stays sharded between uses
                    p = {n: lax.with_sharding_constraint(
                        v, use_shardings[n]) for n, v in p.items()}
                with _rng.rng_guard(rng_key), aux_loss_scope() as auxes:
                    out, new_bufs = functional_call(model, p, buffers,
                                                    *inputs, training=True)
                    with no_grad(), jax.named_scope("head_loss"):
                        loss_t = loss_fn(_wrap(out),
                                         *[_wrap(l) for l in labels])
                loss_v = loss_t.value if isinstance(loss_t, Tensor) else loss_t
                if auxes:  # MoE load-balancing etc., already weighted
                    loss_v = loss_v + total(auxes)
                return loss_v, new_bufs

            if remat:
                loss_of = jax.checkpoint(loss_of,
                                         policy=self._remat_policy)
            (loss, new_bufs), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)
            if zero_grads:
                # constrain grads to the ZeRO layout: XLA fuses the grad
                # psum into a reduce-scatter feeding the sharded update
                grads = {n: lax.with_sharding_constraint(
                    g, grad_shardings[n]) for n, g in grads.items()}
            return loss, new_bufs, grads

        return fwd_bwd

    # ------------------------------------------------------------------
    # quantized-collective fwd/bwd (ISSUE 17 tentpole)
    # ------------------------------------------------------------------
    def _q_gather_fn(self, dim: Optional[int], shard_aval):
        """custom_vjp gather for ONE stage-3 parameter leaf: forward is
        the quantized all-gather of the local zero-shard along `dim`
        (identity for indivisible leaves, dim=None); backward is the
        quantized reduce-scatter of the full-weight cotangent back into
        the zero layout, plus the data-parallel all-reduce. The `tok`
        operand is a scalar scheduling token: an optimization_barrier
        chains this gather after the PREVIOUS layer group's gathered
        output, so the SPMD scheduler cannot combine/front-load the
        per-layer gathers — gather i+1 overlaps layer i's matmuls
        instead (the 2112.01075 chunked redistribution schedule)."""
        from . import quantized as q
        zax = self._zero_axis
        nz = self.mesh.shape.get(zax, 1)
        precision = self.comm_precision
        block = self.comm_block
        other_axes = tuple(a for a in self._comm_axes if a != zax)
        mesh_shape = dict(self.mesh.shape)
        # int8 pays a per-block f32 scale and pads to the block size —
        # on a sub-block leaf that SHIP MORE bytes than plain f32.
        # bf16 has neither cost, so it quantizes every leaf.
        small = precision == "int8" and shard_aval.size < block

        def _reduce_ct(ct):
            """full-weight cotangent -> zero-sharded, summed over the
            whole data group (scaling by 1/G happens in the caller)."""
            if small:
                # sub-block leaves: plain f32 psum + local slice (the
                # scale vector would outweigh the int8 payload)
                g = lax.psum(ct, (zax,) + other_axes)
                if dim is not None:
                    idx = lax.axis_index(zax)
                    size = g.shape[dim] // nz
                    g = lax.dynamic_slice_in_dim(g, idx * size, size,
                                                 dim)
                return g
            g = ct
            if dim is not None:
                g = q.body_reduce_scatter(g, zax, nz, dim, precision,
                                          block)
            else:
                g = q.body_all_reduce(g, zax, nz, precision, block)
            for ax in other_axes:
                g = q.body_all_reduce(g, ax, mesh_shape[ax], precision,
                                      block)
            return g

        @jax.custom_vjp
        def gather(shard, tok):
            shard = lax.optimization_barrier((shard, tok))[0]
            if dim is None:
                return shard
            if small:
                # sub-block leaves gather in plain f32: 256 padded int8
                # bytes + scales would exceed the raw payload
                return lax.all_gather(shard, zax, axis=dim, tiled=True)
            return q.body_all_gather(shard, zax, nz, dim, precision,
                                     block)

        def gather_fwd(shard, tok):
            return gather(shard, tok), None

        def gather_bwd(_, ct):
            return _reduce_ct(ct), jnp.zeros((), jnp.float32)

        gather.defvjp(gather_fwd, gather_bwd)
        return gather

    def _make_fwd_bwd_q(self):
        """The explicit-collective twin of `_make_fwd_bwd`: the whole
        fwd+loss+bwd runs inside ONE `jax.shard_map` over the data axes
        (dp, sharding), so the gradient reduction and the stage-3
        weight gather are explicit in-program collectives carrying
        int8/bf16 wire payloads (distributed/quantized.py body
        helpers) instead of GSPMD's implicit fp32 ones.

        Semantics: each shard computes the loss of ITS batch shard;
        the reported loss is the group mean (pmean) and gradients are
        summed across the group then scaled by 1/G — identical math to
        the fp32 path up to the documented quantization drift. Float
        buffers are group-averaged. The per-step rng_key is shared by
        every shard (stateless dropout draws the same mask per shard)."""
        model, loss_fn = self.model, self.loss_fn
        n_in = self.n_inputs
        remat = self.remat
        mesh = self.mesh
        precision = self.comm_precision
        block = self.comm_block
        stage3 = self.zero_stage >= 3
        zax = self._zero_axis
        nz = mesh.shape.get(zax, 1)
        red_axes = self._comm_axes
        other_axes = tuple(a for a in red_axes if a != zax)
        G = self._comm_group
        grad_specs = {n: s.spec for n, s in self.grad_shardings.items()}
        param_specs = ({n: s.spec for n, s in
                        self.param_shardings.items()} if stage3
                       else jax.tree_util.tree_map(
                           lambda _: P(), dict(self.param_shardings)))
        from . import quantized as q

        def _zero_dim(spec):
            for d, entry in enumerate(spec):
                if entry == zax:
                    return d
            return None

        if stage3:
            groups = _layer_groups(list(self.params))
            gather_fns = {
                n: self._q_gather_fn(_zero_dim(grad_specs[n]),
                                     self.params[n])
                for n in self.params}

            def gather_chained(p):
                """Walk layer groups in order, chaining each group's
                gathers after the previous group's gathered outputs via
                the custom_vjp token — (gather layer i+1 || compute
                layer i) is the schedule this dependency shape admits."""
                out = {}
                tok = jnp.zeros((), jnp.float32)
                for group in groups:
                    for n in group:
                        out[n] = gather_fns[n](p[n], tok)
                    probe = [out[n][(0,) * out[n].ndim].astype(
                        jnp.float32) for n in group]
                    tok = probe[0]
                    for extra in probe[1:]:
                        tok = tok + extra
                return out

        def _reduce_grad(g, spec):
            """stage-2 gradient: local partial (full shape) -> summed
            over the data group in the ZeRO layout."""
            d = _zero_dim(spec)
            if precision == "int8" and g.size < block:
                # sub-block leaves: the scale vector would outweigh the
                # payload — plain f32 psum (negligible bytes)
                g = lax.psum(g, red_axes)
                if d is not None:
                    idx = lax.axis_index(zax)
                    size = g.shape[d] // nz
                    g = lax.dynamic_slice_in_dim(g, idx * size, size, d)
                return g
            if d is not None:
                g = q.body_reduce_scatter(g, zax, nz, d, precision,
                                          block)
            else:
                g = q.body_all_reduce(g, zax, nz, precision, block)
            for ax in other_axes:
                g = q.body_all_reduce(g, ax, mesh.shape[ax], precision,
                                      block)
            return g

        def fwd_bwd(params, buffers, lr, step_no, rng_key, *batch):
            batch_specs = tuple(s.spec
                                for s in self._batch_sharding(batch))

            def body(params_l, buffers_l, rng_key_l, *batch_l):
                inputs = batch_l[:n_in]
                labels = batch_l[n_in:]

                def loss_of(p):
                    from ..framework.aux_loss import (aux_loss_scope,
                                                      total)
                    if stage3:
                        p = gather_chained(p)
                    with _rng.rng_guard(rng_key_l), \
                            aux_loss_scope() as auxes:
                        out, new_bufs = functional_call(
                            model, p, buffers_l, *inputs,
                            training=True)
                        with no_grad(), jax.named_scope("head_loss"):
                            loss_t = loss_fn(_wrap(out),
                                             *[_wrap(l) for l in labels])
                    loss_v = (loss_t.value
                              if isinstance(loss_t, Tensor) else loss_t)
                    if auxes:
                        loss_v = loss_v + total(auxes)
                    return loss_v, new_bufs

                if remat:
                    loss_of = jax.checkpoint(loss_of,
                                             policy=self._remat_policy)
                (loss, new_bufs), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(params_l)
                if not stage3:
                    grads = {n: _reduce_grad(g, grad_specs[n])
                             for n, g in grads.items()}
                # the group loss is the mean over shards; each shard's
                # grads were of its LOCAL mean, so the summed grads
                # scale by 1/G to match
                grads = {n: g / G for n, g in grads.items()}
                loss = lax.pmean(loss, red_axes)
                new_bufs = jax.tree_util.tree_map(
                    lambda v: (lax.pmean(v, red_axes)
                               if jnp.issubdtype(v.dtype, jnp.floating)
                               else v), new_bufs)
                return loss, new_bufs, grads

            mapped = jax.shard_map(
                body, mesh=mesh,
                in_specs=(param_specs, P(), P()) + batch_specs,
                out_specs=(P(), P(), grad_specs),
                check_vma=False)
            return mapped(params, buffers, rng_key, *batch)

        return fwd_bwd

    def _post_update_fn(self):
        """The 2004.13336 cross-replica weight-update analysis, applied:
        in the quantized stage-2 program gradients arrive zero-sharded
        but params are replicated — left alone, GSPMD may all-gather
        the optimizer DELTA and run the update math replicated on every
        device. Constraining the updated params to the zero layout
        keeps every optimizer op on 1/N shards; the one all-gather back
        to the replicated param layout happens at the program output
        (sharded-update-then-gather, exactly the paper's recipe).
        Stage 3 params stay sharded end-to-end and fp32 mode returns
        None so that program is bitwise-unchanged."""
        if not (self._comm_active() and self.zero_stage == 2):
            return None
        upd_sh = self.grad_shardings

        def post_update(new_params):
            return {n: lax.with_sharding_constraint(v, upd_sh[n])
                    for n, v in new_params.items()}

        return post_update

    def _build(self, raw_batch):
        optimizer = self.optimizer
        fwd_bwd = self._make_fwd_bwd()
        post_update = self._post_update_fn()
        step_self = self

        in_batch = self._batch_sharding(raw_batch)
        buf_shardings = {n: NamedSharding(self.mesh, P())
                         for n in self.buffers}
        scalar_sh = NamedSharding(self.mesh, P())
        k = self.accumulate_steps

        if k == 1:
            def full_step(params, buffers, opt_state, lr, step_no, rng_key,
                          *batch):
                step_self._count_trace()
                loss, new_bufs, grads = fwd_bwd(params, buffers, lr, step_no,
                                                rng_key, *batch)
                with jax.named_scope("optimizer"):
                    new_params, new_opt = optimizer.apply_gradients(
                        params, grads, opt_state, lr=lr, step=step_no)
                if post_update is not None:
                    new_params = post_update(new_params)
                return loss, new_params, new_bufs, new_opt

            self._jitted = jax.jit(
                full_step,
                in_shardings=(self.param_shardings, buf_shardings,
                              self.opt_shardings, None, None, None)
                + in_batch,
                out_shardings=(scalar_sh, self.param_shardings,
                               buf_shardings, self.opt_shardings),
                donate_argnums=(0, 1, 2))
            self._prec_progs[self.comm_precision] = (self._jitted,
                                                     self._jitted_acc)
            return

        # gradient merge (reference: gradient_merge_optimizer.py): the host
        # knows the cadence, so two programs — accumulate-only and apply
        acc_sh = self.acc_grad_shardings

        def acc_step(params, buffers, opt_state, acc, lr, step_no, rng_key,
                     *batch):
            step_self._count_trace()
            loss, new_bufs, grads = fwd_bwd(params, buffers, lr, step_no,
                                            rng_key, *batch)
            with jax.named_scope("grad_accumulate"):
                new_acc = {n: acc[n] + grads[n] for n in acc}
            return loss, new_bufs, new_acc

        def apply_step(params, buffers, opt_state, acc, lr, step_no, rng_key,
                       *batch):
            step_self._count_trace()
            loss, new_bufs, grads = fwd_bwd(params, buffers, lr, step_no,
                                            rng_key, *batch)
            with jax.named_scope("grad_accumulate"):
                mean = {n: (acc[n] + grads[n]) / k for n in acc}
            with jax.named_scope("optimizer"):
                new_params, new_opt = optimizer.apply_gradients(
                    params, mean, opt_state, lr=lr, step=step_no)
            if post_update is not None:
                new_params = post_update(new_params)
            zeros = {n: jnp.zeros_like(v) for n, v in acc.items()}
            return loss, new_params, new_bufs, new_opt, zeros

        self._jitted_acc = jax.jit(
            acc_step,
            in_shardings=(self.param_shardings, buf_shardings,
                          self.opt_shardings, acc_sh, None, None, None)
            + in_batch,
            out_shardings=(scalar_sh, buf_shardings, acc_sh),
            donate_argnums=(1, 3))
        self._jitted = jax.jit(
            apply_step,
            in_shardings=(self.param_shardings, buf_shardings,
                          self.opt_shardings, acc_sh, None, None, None)
            + in_batch,
            out_shardings=(scalar_sh, self.param_shardings, buf_shardings,
                           self.opt_shardings, acc_sh),
            donate_argnums=(0, 1, 2, 3))
        self._prec_progs[self.comm_precision] = (self._jitted,
                                                 self._jitted_acc)

    # ------------------------------------------------------------------
    def aot_compile(self, *batch_avals, platform: str = None):
        """Lower + compile the full hybrid-parallel training step with
        abstract inputs — no parameter bytes are ever allocated. Use with
        a LazyGuard-constructed model to validate north-star-scale
        configs (GPT-6.7B, LLaMA-13B) on a virtual mesh:

            with paddle.LazyGuard():
                model = LlamaForCausalLM(llama_13b())
            step = ParallelTrainStep(model, loss_fn, opt, ...)
            compiled = step.aot_compile(
                jax.ShapeDtypeStruct((B, S), jnp.int32), ...)
            compiled.memory_analysis()   # per-device HBM requirements

        Returns the jax Compiled object (cost_analysis/memory_analysis).
        With `platform` (e.g. "tpu") the step is instead CROSS-LOWERED
        for that backend via jax.export and the Exported is returned —
        this validates the program's TPU lowering (dtype/collective
        patterns the CPU backend cannot compile, e.g. bf16 through the
        pipeline ppermute ring) on a host with no TPU attached; backend
        code generation still happens at load time on the real target.
        Reference-scale counterpart: the fleet hybrid suites
        (unittests/collective/fleet/hybrid_parallel_pp_transformer.py),
        which need real GPUs; this validates the same compositions
        compiler-side.
        """
        if self.accumulate_steps != 1:
            raise NotImplementedError(
                "aot_compile validates the accumulate_steps=1 program")
        raw_batch = tuple(
            b if isinstance(b, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(tuple(b.shape), b.dtype)
            for b in batch_avals)
        if self._jitted is None:
            self._build(raw_batch)
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        key = jax.eval_shape(
            lambda: _rng.default_generator().fold_in(1))
        args = (self.params, self.buffers, self.opt_state, scalar, scalar,
                key) + raw_batch
        if platform is not None:
            return jax.export.export(
                self._jitted, platforms=[platform],
                disabled_checks=EXPORT_DISABLED_CHECKS)(*args)
        lowered = self._jitted.lower(*args)
        return lowered.compile()

    def __call__(self, *batch) -> Tensor:
        if self._abstract:
            raise RuntimeError(
                "this ParallelTrainStep was built from a LazyGuard "
                "(abstract) model — only aot_compile() is available; "
                "construct the model outside LazyGuard to train")
        raw_batch = _raw_tuple(batch)
        if self._jitted is None:
            self._build(raw_batch)
        self.step_count += 1
        n = self.step_count
        k = self.accumulate_steps
        micro = k > 1 and n % k != 0    # accumulate grads, no update
        # the spans of jit.TrainStep.__call__, name for name
        with _span("train.step", cat="train", step=n,
                   program="accumulate" if micro else "step"):
            with _span("train.step.prep", cat="train", step=n):
                lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
                rng_key = _rng.default_generator().fold_in(n)
                if not micro:
                    self.update_count += 1
                step_no = jnp.asarray(
                    self.update_count + (1 if micro else 0), jnp.float32)
            with _span("train.step.enqueue", cat="train", step=n):
                prog = self._jitted_acc if micro else self._jitted
                args = (self.params, self.buffers, self.opt_state,
                        *((self.acc_grads,) if k > 1 else ()),
                        lr, step_no, rng_key, *raw_batch)
                if micro:
                    loss, self.buffers, self.acc_grads = prog(*args)
                elif k > 1:
                    (loss, self.params, self.buffers, self.opt_state,
                     self.acc_grads) = prog(*args)
                else:
                    (loss, self.params, self.buffers,
                     self.opt_state) = prog(*args)
            if self._trace_count != self._published_at:     # it compiled
                publish_step_program(
                    self, "accumulate" if micro else "step", prog, args)
            del args        # the donated arrays
            with _span("train.step.post", cat="train", step=n):
                lr_sched = getattr(self.optimizer, "_learning_rate", None)
                if not micro and self.auto_lr_step \
                        and hasattr(lr_sched, "step"):
                    lr_sched.step()
        if micro:
            return Tensor(loss)
        # FLAGS_check_nan_inf wiring (framework/nan_inf.py): scan the
        # step loss — the one concrete value the fused program yields —
        # so a divergence aborts (level 0) or warns (level>=1) at the
        # step boundary instead of poisoning the next N steps. Costs a
        # device sync, so it only runs when the flag is armed.
        from ..framework import flags as _flags
        if _flags.flag_value("check_nan_inf"):
            from ..framework.nan_inf import check_numerics
            check_numerics(loss, "ParallelTrainStep.step")
        return Tensor(loss)

    # ------------------------------------------------------------------
    # fused K-step window (lax.scan under the mesh)
    # ------------------------------------------------------------------
    def _scan_batch_sharding(self, raw_batch):
        """Stacked super-batch shardings: the single-batch spec shifted
        one dim right (the leading K window dim is never sharded — the
        scan walks it)."""
        singles = self._batch_sharding(tuple(
            jax.ShapeDtypeStruct(b.shape[1:], b.dtype) for b in raw_batch))
        return tuple(NamedSharding(self.mesh, P(None, *s.spec))
                     for s in singles)

    def _get_scan_prog(self, k_steps: int, raw_batch):
        """The jitted K-step fused program over the mesh — same
        signature/semantics as jit.TrainStep._get_scan_prog, with the
        per-step batch sharded exactly as the per-step program shards
        it (the window dim replicated, scan slices it locally)."""
        key_sig = (int(k_steps), self.comm_precision,
                   tuple((tuple(b.shape), str(b.dtype)) for b in raw_batch))
        prog = self._scan_progs.get(key_sig)
        if prog is not None:
            return prog
        from ..jit.training import make_scan_window
        fwd_bwd = self._make_fwd_bwd()

        def fwd(params, buffers, opt_state, lr, step_no, rng_key, *batch):
            # adapt to the shared window builder's fwd contract —
            # fwd_bwd doesn't consume opt_state
            return fwd_bwd(params, buffers, lr, step_no, rng_key, *batch)

        k = self.accumulate_steps
        n_batch = len(raw_batch)
        scan_window = make_scan_window(fwd, self.optimizer, k,
                                       self._count_trace,
                                       post_update=self._post_update_fn())

        in_batch = self._scan_batch_sharding(raw_batch)
        buf_shardings = {n: NamedSharding(self.mesh, P())
                         for n in self.buffers}
        scalar_sh = NamedSharding(self.mesh, P())

        if k == 1:
            prog = jax.jit(
                scan_window,
                in_shardings=(self.param_shardings, buf_shardings,
                              self.opt_shardings, None, None, None, None)
                + in_batch,
                out_shardings=(scalar_sh, self.param_shardings,
                               buf_shardings, self.opt_shardings),
                donate_argnums=(0, 1, 2) + tuple(range(7, 7 + n_batch)))
        else:
            acc_sh = self.acc_grad_shardings
            prog = jax.jit(
                scan_window,
                in_shardings=(self.param_shardings, buf_shardings,
                              self.opt_shardings, acc_sh, None, None,
                              None, None, None) + in_batch,
                out_shardings=(scalar_sh, self.param_shardings,
                               buf_shardings, self.opt_shardings, acc_sh),
                donate_argnums=(0, 1, 2, 3) + tuple(
                    range(9, 9 + n_batch)))
        self._scan_progs[key_sig] = prog
        return prog

    def _count_trace(self):
        self._trace_count += 1    # fires at trace time only

    def op_scopes(self) -> Dict[str, str]:
        return op_scopes_of(self)

    op_scopes.__doc__ = op_scopes_of.__doc__

    def scan_steps(self, k_steps: int, *batch) -> Tensor:
        """K fused (micro-)steps in ONE compiled program over the mesh —
        see jit.TrainStep.scan_steps for the full contract (stacked
        ``[k_steps, ...]`` leaves, donated super-batch, device-resident
        stacked losses, bitwise sequential-equivalence)."""
        if self._abstract:
            raise RuntimeError(
                "this ParallelTrainStep was built from a LazyGuard "
                "(abstract) model — only aot_compile() is available; "
                "construct the model outside LazyGuard to train")
        if k_steps < 1:
            raise ValueError("k_steps must be >= 1")
        raw_batch = _raw_tuple(batch)
        for b in raw_batch:
            if b.ndim < 1 or b.shape[0] != k_steps:
                raise ValueError(
                    f"scan_steps batch leaves must be stacked "
                    f"[{k_steps}, ...]; got shape {b.shape}")
        from ..jit.training import (_quiet_unused_donation,
                                    window_rollback, window_schedule)
        n = self.step_count + 1         # the window's first step
        with _span("train.window", cat="train", step=n, k=k_steps), \
                window_rollback(self):
            with _span("train.step.prep", cat="train", step=n):
                prog = self._get_scan_prog(k_steps, raw_batch)
                base_key = _rng.get_rng_state()
                lrs, step_nos, counts, upd = window_schedule(self, k_steps)
            with _span("train.step.enqueue", cat="train", step=n), \
                    _quiet_unused_donation():
                if self.accumulate_steps > 1:
                    args = (self.params, self.buffers, self.opt_state,
                            self.acc_grads, base_key, lrs, step_nos, counts,
                            upd, *raw_batch)
                    (losses, self.params, self.buffers, self.opt_state,
                     self.acc_grads) = prog(*args)
                else:
                    args = (self.params, self.buffers, self.opt_state,
                            base_key, lrs, step_nos, counts, *raw_batch)
                    (losses, self.params, self.buffers,
                     self.opt_state) = prog(*args)
                if self._trace_count != self._published_at:
                    publish_step_program(self, "scan", prog, args)
                del args
        # one stacked-loss scan per WINDOW when the nan flag is armed —
        # the fused loop's supervision cost is 1 sync / K steps
        # (check_numerics takes the raw jax array, same as __call__)
        from ..framework import flags as _flags
        if _flags.flag_value("check_nan_inf"):
            from ..framework.nan_inf import check_numerics
            check_numerics(losses, "ParallelTrainStep.scan_steps")
        return Tensor(losses)

    # ------------------------------------------------------------------
    def skip_step(self):
        """Advance the step/update counters — and with them the
        per-step RNG fold position and (``auto_lr_step``) the LR
        schedule — WITHOUT executing the program (the supervisor's
        poison-window skip; contract identical to
        ``jit.TrainStep.skip_step``, so ``Model.fit(skip_windows=)``
        works unchanged on the hybrid-parallel path)."""
        self.step_count += 1
        k = self.accumulate_steps
        if k > 1 and self.step_count % k != 0:
            return
        self.update_count += 1
        if self.auto_lr_step:
            lr_sched = getattr(self.optimizer, "_learning_rate", None)
            if hasattr(lr_sched, "step"):
                lr_sched.step()

    # ------------------------------------------------------------------
    def flush_accumulation(self):
        """Apply a pending partial accumulation window (see
        jit.TrainStep.flush_accumulation). Shardings ride on the arrays."""
        k = self.accumulate_steps
        r = self.step_count % k
        if k == 1 or r == 0 or self.acc_grads is None:
            return
        self.update_count += 1
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        step_no = jnp.asarray(self.update_count, jnp.float32)
        optimizer = self.optimizer

        prog = self._flush_progs.get(r)
        if prog is None:
            def apply_only(params, opt_state, acc, lr, step_no):
                mean = jax.tree_util.tree_map(lambda a: a / r, acc)
                new_p, new_o = optimizer.apply_gradients(
                    params, mean, opt_state, lr=lr, step=step_no)
                zeros = jax.tree_util.tree_map(jnp.zeros_like, acc)
                return new_p, new_o, zeros

            prog = jax.jit(apply_only, donate_argnums=(0, 1, 2))
            self._flush_progs[r] = prog

        self.params, self.opt_state, self.acc_grads = prog(
            self.params, self.opt_state, self.acc_grads, lr, step_no)
        self.step_count += k - r

    def sync_to_model(self):
        load_state(self.model,
                   jax.tree_util.tree_map(jnp.copy, self.params),
                   jax.tree_util.tree_map(jnp.copy, self.buffers))
        return self.model

    def eval_fn(self):
        model = self.model

        @jax.jit
        def infer(params, buffers, *inputs):
            out, _ = functional_call(model, params, buffers, *inputs,
                                     training=False)
            return out

        def run(*inputs):
            out = infer(self.params, self.buffers, *_raw_tuple(inputs))
            return _wrap(out)

        return run
