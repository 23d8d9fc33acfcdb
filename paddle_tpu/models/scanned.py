"""Scan-over-layers: a homogeneous decoder stack as ONE set of stacked
parameters applied with `jax.lax.scan`.

TPU-first compile-time scaling. An unrolled block list emits
O(num_layers) copies of identical HLO, so XLA compile time grows
linearly with depth — the unrolled 12-layer GPT-125M whole-step program
takes 292 s to compile for v5e on 8 host cores where the scanned
24-layer GPT-1.3B one takes 30 s, and the 6.7B ZeRO-3 AOT compile took
209s. Scanned, the block body is compiled
ONCE regardless of depth (6.7B: 7.4s, identical per-device memory).
This is the idiom flax calls scan-over-layers; the reference has no
analog — its executor re-dispatches per-op per-layer at runtime
(SURVEY.md §3.3), which is why its "compile time" doesn't grow but its
dispatch overhead does.

Semantics are identical to the unrolled stack: the scan body swaps the
i-th parameter slice into a template block (built abstract under
LazyGuard — zero resident bytes) and runs its ordinary ``forward``.
Per-block rematerialisation becomes ``jax.checkpoint`` on the scan
body. Eager autograd works — the scan is recorded on the tape as one op
via ``tape.apply`` — and under TrainStep/ParallelTrainStep the stacked
leaves are ordinary donated parameters whose sharding annotations keep
the block's TP axes with the layer axis unsharded. KV-cache decode
rotates stacked `[L, B, M, heads, hd]` caches through the same scan
(``forward_cached``).

Used by `GPTConfig.scan_layers` and `LlamaConfig.scan_layers`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..jit.functional import functional_call
from ..nn import initializer as I
from ..nn.layer_base import Layer

__all__ = ["ScannedStack"]


class ScannedStack(Layer):
    """num_layers copies of block_factory() as stacked-leaf parameters.

    Initialization rule (matches the transformer blocks this serves):
    rank>=2 leaves draw Normal(0, initializer_range) — L independent
    draws == one draw of the stacked shape; rank-1 ``*.weight`` leaves
    are norm scales (ones); everything else is a bias (zeros).

    Blocks that report auxiliary losses (MoE) are supported — see
    ``forward``. Restrictions (loud): blocks with buffers are rejected
    (buffers are not stacked, same rule as PipelineLayer body blocks).
    Stochastic blocks (dropout>0) must be rejected by the CALLER — the
    scan body is traced once, so every layer would reuse one RNG draw.

    Initializer restriction: the rule above REPLACES the template
    block's own initializers (a LazyGuard template holds no values to
    stack). A block with a custom ``weight_attr`` (scaled residual
    init, non-Normal draws) or a rank-1 parameter not named
    ``*.weight``/bias would initialize differently from its unrolled
    counterpart — such blocks must either use ``load_from_blocks`` to
    import real values, or extend the rule here. Today's GPT/LLaMA/BERT
    blocks all follow the rule exactly.
    """

    def __init__(self, block_factory, num_layers: int,
                 initializer_range: float, recompute: bool = False,
                 recompute_policy: str = "full"):
        super().__init__()
        from ..distributed.recompute import resolve_checkpoint_policy
        self.num_layers = num_layers
        self.recompute = recompute
        # resolve eagerly: a typo'd policy fails at construction
        self._ckpt_policy = resolve_checkpoint_policy(recompute_policy)
        # plain-list attribute: provides structure + forward only — built
        # abstract (LazyGuard) so its parameters are ShapeDtypeStructs,
        # not resident arrays that compute never touches
        from ..framework.lazy_init import LazyGuard
        with LazyGuard():
            self._template = [block_factory()]
        tmpl = self._template[0]
        # the template stands for every layer: its scope in a traced
        # program is what an unrolled stack names one, less the index
        tmpl._scope_name = "block"
        if list(tmpl.named_buffers()):
            raise NotImplementedError(
                "scan_layers with buffered blocks: buffers are not "
                "stacked across layers (same restriction as "
                "PipelineLayer body blocks)")
        # static: does any sublayer report aux losses (MoE gates)?
        # decided here so aux-free stacks keep the single-output path
        self._has_aux = any(hasattr(l, "aux_loss_weight")
                            for l in tmpl.sublayers(include_self=True))
        w_init = I.Normal(0.0, initializer_range)
        self._names = []
        for name, p in tmpl.named_parameters():
            shape = [num_layers] + list(p.shape)
            if len(p.shape) >= 2:
                value = w_init(shape, "float32")
            elif name.endswith(".weight"):  # norm scales
                value = I.Constant(1.0)(shape, "float32")
            else:  # biases
                value = I.Constant(0.0)(shape, "float32")
            sp = type(p)(value)
            # stacked leaf keeps the block's TP annotation with the layer
            # axis unsharded (same pattern as PipelineLayer._stack_params,
            # which prepends "pp"); scan runs every layer on every chip
            inner = p.sharding_axes
            if inner is not None:
                sp.sharding_axes = (None,) + tuple(inner)
            sp.is_distributed = p.is_distributed
            self.add_parameter(self._mangle(name), sp)
            self._names.append(name)

    @staticmethod
    def reject_dropout(p: float) -> None:
        """Caller-side guard: stochastic blocks cannot scan — the body is
        traced once, so every layer would reuse one RNG draw."""
        if p:
            raise NotImplementedError(
                "scan_layers requires dropout=0.0: the scan body is "
                "traced once, so every layer would reuse the same "
                "dropout mask")

    @staticmethod
    def _mangle(name: str) -> str:
        # parameter-dict keys must not contain "." (named_parameters
        # joins hierarchy with "."); keep a reversible encoding
        return name.replace(".", "__")

    def _scan_leaves(self):
        """(template, names, stacked leaves) — the ONE definition of the
        leaf ordering fed to lax.scan; train and decode must agree."""
        return (self._template[0], self._names,
                [self._parameters[self._mangle(n)] for n in self._names])

    def load_from_blocks(self, blocks) -> None:
        """Stack per-layer params from an unrolled block list (checkpoint
        interop: unrolled state_dicts convert mechanically)."""
        blocks = list(blocks)
        if len(blocks) != self.num_layers:
            raise ValueError(
                f"load_from_blocks: got {len(blocks)} blocks for a "
                f"num_layers={self.num_layers} model")
        per_layer = [dict(b.named_parameters()) for b in blocks]
        for name in self._names:
            vals = [d[name].value for d in per_layer]
            if any(isinstance(v, jax.ShapeDtypeStruct) for v in vals):
                raise ValueError(
                    "load_from_blocks: source blocks hold abstract "
                    "(LazyGuard) parameters — materialize them first")
            target = self._parameters[self._mangle(name)]
            # keep the scanned model's precision (e.g. after .bfloat16())
            target.value = jnp.stack(vals).astype(target.value.dtype)

    def forward(self, x, *extra):
        """Apply the stack to x. ``extra`` are layer-INVARIANT positional
        args handed to every block unchanged (e.g. an attention mask for
        encoder blocks) — they ride along as differentiable inputs.

        Blocks that report auxiliary losses (MoE load balancing) work:
        each scan iteration collects its block's aux losses in a private
        scope and returns their sum as a scan output; the per-layer sums
        are re-reported ONCE to the active outer scope after the tape op
        (the report-after-apply pattern MoELayer itself uses), so the
        training engines add them to the objective and gate gradients
        flow through the scan."""
        from ..autograd import tape as _tape
        from ..framework.aux_loss import (add_aux_loss, aux_loss_scope,
                                          total)
        tmpl, names, leaves = self._scan_leaves()
        training = self.training
        recompute = self.recompute and training
        n_extra = len(extra)
        has_aux = self._has_aux  # static (decided at construction)

        def run(h, *rest):
            ex, stacked = rest[:n_extra], rest[n_extra:]

            def body(h, psl):
                # private scope even when has_aux is False: an aux report
                # from inside the scan trace must never reach an outer
                # bucket (tracer leak)
                with aux_loss_scope() as bucket:
                    out, _ = functional_call(tmpl, dict(zip(names, psl)),
                                             {}, h, *ex,
                                             training=training)
                if not has_aux:
                    return out
                return out, jnp.asarray(total(bucket), jnp.float32)
            if recompute:
                body = jax.checkpoint(body, policy=self._ckpt_policy)

            if not has_aux:
                def scan_body(h, psl):
                    return body(h, psl), None
                out, _ = jax.lax.scan(scan_body, h, list(stacked))
                return out
            out, auxs = jax.lax.scan(body, h, list(stacked))
            return out, jnp.sum(auxs)

        if not has_aux:
            return _tape.apply(run, x, *extra, *leaves,
                               _op_name="scanned_stack")
        out, aux_sum = _tape.apply(run, x, *extra, *leaves,
                                   _op_name="scanned_stack")
        add_aux_loss(aux_sum.value if hasattr(aux_sum, "value")
                     else aux_sum)
        return out

    def forward_cached(self, x, caches, pos):
        """Decode step: caches is (k_stack, v_stack), each [L, B, M,
        heads, hd]; every layer's slice rotates through the scan body."""
        from ..autograd import tape as _tape
        from ..framework.aux_loss import aux_loss_scope
        tmpl, names, leaves = self._scan_leaves()
        k_stack, v_stack = caches
        pos_raw = pos.value if isinstance(pos, Tensor) else pos

        def run(h, kst, vst, *stacked):
            def body(carry, xs):
                psl_leaves, kc, vc = xs
                psl = dict(zip(names, psl_leaves))
                # private scope: a decode-time aux report (MoE gates fire
                # regardless of training mode) must not leak scan-trace
                # tracers into an outer bucket; decode discards aux
                with aux_loss_scope():
                    out, _ = functional_call(tmpl, psl, {}, carry,
                                             (kc, vc), pos_raw,
                                             training=False)
                h2, (kc2, vc2) = out
                return h2, (kc2, vc2)

            h2, (knew, vnew) = jax.lax.scan(
                body, h, (list(stacked), kst, vst))
            return h2, knew, vnew

        if isinstance(k_stack, dict) or isinstance(v_stack, dict):
            # int8 (dict-pytree) caches: the tape cannot wrap dicts and
            # quantized writes are not differentiable — run raw
            from ..core.tensor import as_raw
            h2, k2, v2 = run(as_raw(x), k_stack, v_stack,
                             *[l.value for l in leaves])
            return Tensor(h2, stop_gradient=True), (k2, v2)
        h_t, k_t, v_t = _tape.apply(run, x, k_stack, v_stack, *leaves,
                                    _op_name="scanned_stack_decode")
        return h_t, (k_t, v_t)
