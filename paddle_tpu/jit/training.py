"""Fused whole-step training engine.

In the reference a training iteration is hundreds of separately-dispatched
kernels: per-op eager calls (paddle/fluid/eager/), backward queue traversal
(backward.cc:380), then per-param optimizer kernels. Here the ENTIRE step —
forward, loss, backward, gradient clip, optimizer update, buffer (BN stats)
update — is one XLA program with donated buffers: parameters and optimizer
slots update in place in HBM, the compiler overlaps and fuses everything.
This is the single-chip engine; the distributed engine
(paddle_tpu.distributed.parallel_step) builds the same program under pjit
over a Mesh.
"""
from __future__ import annotations

import contextlib
import time
import warnings
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..autograd.tape import no_grad
from ..core.tensor import Tensor
from ..framework import random as _rng
from ..obs.trace import span as _span
from .functional import functional_call, load_state, raw_state, _wrap

__all__ = ["TrainStep", "StepProgram", "last_step_program"]


def _as_tuple(x):
    if x is None:
        return ()
    if isinstance(x, (tuple, list)):
        return tuple(x)
    return (x,)


def _raw_tuple(xs):
    return tuple(x.value if isinstance(x, Tensor) else jnp.asarray(x)
                 for x in _as_tuple(xs))


@contextlib.contextmanager
def _quiet_unused_donation():
    """The scanned window donates its super-batch: the buffers are
    consumed, but scan xs can never alias an output so jax warns the
    donation was "not usable" on every compile. The donation is still
    wanted (the input super-batch dies with the call instead of pinning
    HBM until GC) and tpulint's undonated-buffer anchors guard the
    donations that DO alias — silence just this message, just here."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


@contextlib.contextmanager
def window_rollback(step):
    """Undo ``window_schedule``'s K steps of host schedule state if the
    fused window fails to DISPATCH. The schedule (counters + LR
    scheduler) is precomputed before the program call, so a trace or
    compile error — e.g. a K-wide program that OOMs where the per-step
    one fits — would otherwise leave the schedule up to K steps ahead
    of the params, poisoning emergency checkpoints and any per-step
    fallback (the sequential path only ever skews by the 1 in-flight
    step). A post-dispatch device hang is out of scope: dispatch
    succeeded, and the sequential loop has the same in-flight skew."""
    lr_sched = getattr(step.optimizer, "_learning_rate", None)
    sched_state = (lr_sched.state_dict()
                   if hasattr(lr_sched, "state_dict") else None)
    prev_step, prev_update = step.step_count, step.update_count
    try:
        yield
    except BaseException:
        step.step_count, step.update_count = prev_step, prev_update
        if sched_state is not None:
            lr_sched.set_state_dict(sched_state)
        raise


def window_schedule(step, k_steps: int):
    """Host-side precompute of a fused window's per-step lr / step_no /
    fold-in count vectors (+ update mask), advancing ``step``'s
    counters and the LR scheduler in EXACTLY the order the sequential
    path would: get_lr() is read before each step, the scheduler steps
    after each optimizer update.

    Shared by :class:`TrainStep` and ``distributed.ParallelTrainStep``
    — ``step`` is either one; the contract is the attributes both
    expose: ``accumulate_steps``, ``optimizer``, ``step_count``,
    ``update_count``, ``auto_lr_step``."""
    k = step.accumulate_steps
    lr_sched = getattr(step.optimizer, "_learning_rate", None)
    lrs, step_nos, counts, upd = [], [], [], []
    for _ in range(k_steps):
        step.step_count += 1
        counts.append(step.step_count)
        lrs.append(step.optimizer.get_lr())
        is_upd = k == 1 or step.step_count % k == 0
        upd.append(is_upd)
        if is_upd:
            step.update_count += 1
            step_nos.append(step.update_count)
            if step.auto_lr_step and hasattr(lr_sched, "step"):
                lr_sched.step()
        else:
            step_nos.append(step.update_count + 1)
    return (np.asarray(lrs, np.float32),
            np.asarray(step_nos, np.float32),
            np.asarray(counts, np.int32),
            np.asarray(upd, bool))


def make_scan_window(fwd, optimizer, k, on_trace, post_update=None):
    """Build the (un-jitted) K-step fused window function shared by
    :class:`TrainStep` and ``distributed.ParallelTrainStep`` — the ONE
    place the scanned-window contract lives (per-step key
    ``fold_in(base_key, count)``, the ``(acc+grads)/k`` gradient-merge
    mean, zero reset, carry ordering). Callers wrap the result in
    ``jax.jit`` with their own donation/sharding.

    ``fwd(params, buffers, opt_state, lr, step_no, rng_key, *batch) ->
    (loss, new_buffers, grads)`` is the per-step fwd+loss+bwd closure
    (ParallelTrainStep's opt_state-free fwd_bwd is adapted by its
    caller); ``k`` is accumulate_steps; ``on_trace`` fires inside the
    traced body, so it ticks once per actual XLA (re)trace.
    ``post_update`` (optional) maps the freshly-updated params pytree
    right after ``optimizer.apply_gradients`` — ParallelTrainStep's
    quantized stage-2 path uses it to constrain the weight update into
    the ZeRO layout (sharded update, one gather at the end); ``None``
    leaves the traced graph byte-identical to before the hook existed.

    Signature of the returned function:
      k == 1:  (params, buffers, opt, key, lrs, steps, counts, *sb)
               -> (losses[K], params, buffers, opt)
      k > 1:   (params, buffers, opt, acc, key, lrs, steps, counts,
                upd_mask, *sb)
               -> (losses[K], params, buffers, opt, acc)
    """
    if k == 1:
        def scan_window(params, buffers, opt_state, base_key, lrs,
                        step_nos, counts, *superbatch):
            on_trace()

            def body(carry, xs):
                params, buffers, opt_state = carry
                lr, step_no, count = xs[0], xs[1], xs[2]
                batch = xs[3:]
                rng_key = jax.random.fold_in(base_key, count)
                loss, new_bufs, grads = fwd(
                    params, buffers, opt_state, lr, step_no, rng_key,
                    *batch)
                with jax.named_scope("optimizer"):
                    new_params, new_opt = optimizer.apply_gradients(
                        params, grads, opt_state, lr=lr, step=step_no)
                if post_update is not None:
                    new_params = post_update(new_params)
                return (new_params, new_bufs, new_opt), loss

            (params, buffers, opt_state), losses = lax.scan(
                body, (params, buffers, opt_state),
                (lrs, step_nos, counts) + superbatch)
            return losses, params, buffers, opt_state

        return scan_window

    def scan_window(params, buffers, opt_state, acc, base_key,
                    lrs, step_nos, counts, upd_mask, *superbatch):
        on_trace()

        def body(carry, xs):
            params, buffers, opt_state, acc = carry
            lr, step_no, count, is_upd = xs[0], xs[1], xs[2], xs[3]
            batch = xs[4:]
            rng_key = jax.random.fold_in(base_key, count)
            loss, new_bufs, grads = fwd(
                params, buffers, opt_state, lr, step_no, rng_key,
                *batch)

            def apply_br(_):
                with jax.named_scope("grad_accumulate"):
                    mean = jax.tree_util.tree_map(
                        lambda a, g: (a + g) / k, acc, grads)
                with jax.named_scope("optimizer"):
                    new_p, new_o = optimizer.apply_gradients(
                        params, mean, opt_state, lr=lr, step=step_no)
                if post_update is not None:
                    new_p = post_update(new_p)
                zeros = jax.tree_util.tree_map(jnp.zeros_like, acc)
                return new_p, new_o, zeros

            def acc_br(_):
                with jax.named_scope("grad_accumulate"):
                    new_acc = jax.tree_util.tree_map(jnp.add, acc, grads)
                return params, opt_state, new_acc

            new_p, new_o, new_acc = lax.cond(
                is_upd, apply_br, acc_br, None)
            return (new_p, new_bufs, new_o, new_acc), loss

        (params, buffers, opt_state, acc), losses = lax.scan(
            body, (params, buffers, opt_state, acc),
            (lrs, step_nos, counts, upd_mask) + superbatch)
        return losses, params, buffers, opt_state, acc

    return scan_window


class StepProgram:
    """What a trainer knows of the per-step program it compiled last,
    as host data: ``program`` ("step" | "accumulate" | "scan"),
    ``trainer`` (the class's name), ``traces`` (the trainer's
    ``_trace_count`` when the program had traced), ``publish_s`` (host
    seconds the compiling call spent making this record, nearly all of
    it the runtime's copy of the module) and the optimized HLO module,
    from which `hlo_text` and `op_scopes` are made when first asked and
    kept.

    It holds no trainer, model, optimizer, parameter, buffer, device
    array or executable: dropping the trainer frees what it held, and
    the record goes on answering."""

    __slots__ = ("program", "trainer", "traces", "publish_s", "_modules",
                 "_text", "_table")

    def __init__(self, program: str, trainer: str, traces: int, modules,
                 publish_s: float = 0.0):
        self.program, self.trainer, self.traces = program, trainer, int(traces)
        self.publish_s = publish_s
        self._modules, self._text, self._table = modules, None, None

    def hlo_text(self) -> str:
        """The optimized module as text, ``op_name`` metadata and all."""
        if self._text is None:
            self._text = "\n\n".join(m.to_string() for m in self._modules)
            self._modules = None
        return self._text

    def op_scopes(self) -> Dict[str, str]:
        """{HLO instruction name: ``op_name`` path}
        (``analysis.runtime_profile.hlo_op_scopes`` of `hlo_text`):
        the table that maps a device operation in a profiler's trace to
        the scope of the program it came from (`read_scope` reads a
        path, `by_scope` sums a trace by it). Parsed on the first call
        (1.2 - 1.9 s for the 32,000 - 49,000 instructions of the
        benchmark's unrolled sparse steps), kept for the next."""
        if self._table is None:
            from ..analysis.runtime_profile import hlo_op_scopes
            self._table = hlo_op_scopes(self.hlo_text())
        return dict(self._table)

    def __repr__(self):
        return (f"StepProgram(program={self.program!r}, "
                f"trainer={self.trainer!r}, traces={self.traces})")


# the record of the per-step program that compiled last, in any trainer
# of this process (as ``F.last_attention_dispatch()`` for attention and
# ``last_moe_dispatch()`` for the expert layer)
_last_program: Optional[StepProgram] = None


def last_step_program() -> Optional[StepProgram]:
    """The `StepProgram` of the per-step program (of a `TrainStep`, a
    ``distributed.ParallelTrainStep`` or either's ``scan_steps``) that
    compiled last in this process; ``None`` before any has. It outlives
    its trainer: a reader that holds a profiler's trace of the step and
    no trainer (the benchmark's readers after ``Session.release()``, an
    operator with a capture of ``/admin/trace?profile=1``) breaks the
    trace down by the program's own scopes through it. A second program
    replaces the first; no history is kept."""
    return _last_program


def _aval_of(x):
    """The abstract value a jit call keyed its caches on for ``x``: a
    committed array's sharding is part of the key, an uncommitted
    one's is not. Read from a donated (deleted) array as from a live
    one: shape, type and sharding outlive the buffer."""
    if not isinstance(x, jax.Array):            # numpy, a Python scalar
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    # (a typed PRNG key array has neither attribute of its own)
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, weak_type=getattr(x, "weak_type", False),
        sharding=x.sharding if getattr(x, "committed", False) else None)


def _executable_that_ran(step, prog, args):
    """The ``jax.stages.Compiled`` that a call of ``prog`` with ``args``
    has just run, found without compiling or loading anything; raises
    where it cannot be.

    A program `TrainStep.warm` installed (an ``AotProgram`` that has not
    fallen back to its jit wrapper) holds it. A jit wrapper keeps it in
    its own caches: after a call, ``prog.lower(avals)`` at that call's
    abstract values re-runs no Python of the step and returns the
    lowering the call compiled, executable and all (one sub-millisecond
    ``jaxpr_trace`` event fires for the cache lookup). Where the lookup
    misses instead (the trace hook ticked, or the lowering holds no
    executable: an argument this rule reads otherwise than jit did),
    nothing is compiled here for the record's sake."""
    from ..compilation.store import AotProgram
    if isinstance(prog, AotProgram):
        if not prog._use_fallback:
            return prog.compiled
        prog = prog.fallback
    count = step._trace_count
    lowered = prog.lower(*jax.tree_util.tree_map(_aval_of, tuple(args)))
    if step._trace_count != count:
        raise LookupError("the step traced again at the call's abstract "
                          "values: jit's caches were keyed otherwise")
    if getattr(getattr(lowered, "_lowering", None), "_executable",
               None) is None:
        raise LookupError("jit holds no executable for this lowering")
    return lowered.compile()


def publish_step_program(step, program: str, prog, args=()) -> None:
    """Make ``step``'s `StepProgram` of the program ``prog`` that has
    just compiled (in a call with ``args``, or in `TrainStep.warm`), and
    put it where `last_step_program` finds it. Called by the trainers
    after the enqueue of the one call on which ``_trace_count`` moved;
    every other call pays one integer comparison.

    No trace, lowering, compile or load (`_executable_that_ran`); what
    costs is the copy of the executable's ``hlo_modules()`` out of the
    runtime, made here, once: 0.04 - 0.63 s on the chip for the
    benchmark's steps (PERF.md section 6, PR 38). A failure here never
    fails the training step, and is not tried again."""
    global _last_program
    t0 = time.perf_counter()
    try:
        modules = _executable_that_ran(
            step, prog, args).runtime_executable().hlo_modules()
    except Exception as e:      # noqa: BLE001 — observability only
        warnings.warn(f"{type(step).__name__}: the step program's record "
                      f"was not made ({type(e).__name__}: {e})")
        return
    finally:
        step._published_at = step._trace_count      # asked once
    step._step_program = _last_program = StepProgram(
        program, type(step).__name__, step._trace_count, modules,
        time.perf_counter() - t0)


def op_scopes_of(step) -> Dict[str, str]:
    """{HLO instruction name: ``op_name`` path} of the per-step program
    of ``step`` that compiled last, at the shapes it compiled at:
    `StepProgram.op_scopes` of the record the trainer published on that
    call, which is the one source of the table.

    Costs nothing until called, and then no trace, lowering or compile:
    the first call parses the module's text (1.9 s for the 49,000
    instructions of a six-layer unrolled sparse step), later calls copy
    the kept table. No step, update or trace counter, learning rate or
    RNG state moves, and nothing runs on the device."""
    record = step._step_program
    if record is None:
        raise RuntimeError(
            "op_scopes(): no per-step program has traced yet; run one "
            "step first")
    return record.op_scopes()


class TrainStep:
    """Compile model+loss+optimizer into one donated XLA training step.

    loss_fn contract: ``loss_fn(outputs, *labels) -> scalar Tensor`` where
    `outputs` is whatever the model forward returns (Tensors).

    Usage::

        step = TrainStep(model, loss_fn, opt)
        for x, y in loader:
            loss = step(x, y)          # one fused XLA program
        step.sync_to_model()           # write params back into the Layer
    """

    def __init__(self, model, loss_fn: Callable, optimizer,
                 n_inputs: int = 1, accumulate_steps: int = 1):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.n_inputs = n_inputs
        if accumulate_steps < 1:
            raise ValueError("accumulate_steps must be >= 1")
        self.accumulate_steps = accumulate_steps
        params, buffers = raw_state(model)
        # copy: step() donates these buffers; the model's own tensors must
        # stay valid for eager use (same aliasing rule as Optimizer.set_state)
        self.params = jax.tree_util.tree_map(jnp.copy, params)
        self.buffers = jax.tree_util.tree_map(jnp.copy, buffers)
        self.opt_state = optimizer.init(params)
        self.step_count = 0
        self.update_count = 0
        # gradient-merge accumulator (reference:
        # meta_optimizers/gradient_merge_optimizer.py — k micro-steps of
        # summed grads, averaged at the update). Device state so the whole
        # cadence stays inside donated XLA programs.
        self.acc_grads = None
        if accumulate_steps > 1:
            self.acc_grads = jax.tree_util.tree_map(
                lambda p: jnp.zeros_like(p), params)
        # set False when an external driver (hapi LRScheduler callback)
        # owns scheduler stepping
        self.auto_lr_step = True
        self._jitted = None
        self._jitted_acc = None
        # flush_accumulation programs keyed by remainder r (tpulint
        # jit-in-call: a fresh jax.jit per flush re-traced every time)
        self._flush_progs = {}
        # scanned K-step fused programs keyed by (k_steps, n_batch_args)
        self._scan_progs = {}
        # engine-style compiled-program accounting: ticks inside the
        # TRACED bodies, so it moves only when XLA actually (re)traces —
        # tests assert a drifting-length fused epoch compiles exactly 2
        # programs (scanned window + trailing per-step)
        self._trace_count = 0
        # `_trace_count` when this step last published its program's
        # record, and that record (`publish_step_program`)
        self._published_at = 0
        self._step_program = None

    # ------------------------------------------------------------------
    def _make_step_fn(self):
        """fwd+loss+bwd closure shared VERBATIM by the per-step programs
        and the scanned K-step program — same graph, same training
        semantics, and bitwise-equal trajectories at the tier-1 tested
        geometries (identical jaxprs don't force identical machine
        code: XLA may vectorize a reduction differently inside a scan
        body, which can drift the last ulp at other shapes)."""
        model, loss_fn = self.model, self.loss_fn
        n_in = self.n_inputs

        def step_fn(params, buffers, opt_state, lr, step_no, rng_key, *batch):
            inputs, labels = batch[:n_in], batch[n_in:]

            def loss_of(p):
                # thread the per-step key functionally: dropout etc. draw
                # fresh randomness each step instead of a baked trace-time
                # constant (framework.random rng_guard contract)
                from ..framework.aux_loss import aux_loss_scope, total
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

            (loss, new_bufs), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)
            return loss, new_bufs, grads

        return step_fn

    def _build(self):
        optimizer = self.optimizer
        step_fn = self._make_step_fn()
        step_self = self

        k = self.accumulate_steps

        if k == 1:
            def full_step(params, buffers, opt_state, lr, step_no, rng_key,
                          *batch):
                step_self._count_trace()
                loss, new_bufs, grads = step_fn(params, buffers, opt_state,
                                                lr, step_no, rng_key, *batch)
                with jax.named_scope("optimizer"):
                    new_params, new_opt = optimizer.apply_gradients(
                        params, grads, opt_state, lr=lr, step=step_no)
                return loss, new_params, new_bufs, new_opt

            # donate params/buffers/opt-state: they update in place in HBM
            self._jitted = jax.jit(full_step, donate_argnums=(0, 1, 2))
            return

        # gradient merge: two programs — the host knows the cadence
        # (call_count % k), so no in-program branch is needed
        def acc_step(params, buffers, opt_state, acc, lr, step_no, rng_key,
                     *batch):
            step_self._count_trace()
            loss, new_bufs, grads = step_fn(params, buffers, opt_state,
                                            lr, step_no, rng_key, *batch)
            with jax.named_scope("grad_accumulate"):
                new_acc = jax.tree_util.tree_map(jnp.add, acc, grads)
            return loss, new_bufs, new_acc

        def apply_step(params, buffers, opt_state, acc, lr, step_no, rng_key,
                       *batch):
            step_self._count_trace()
            loss, new_bufs, grads = step_fn(params, buffers, opt_state,
                                            lr, step_no, rng_key, *batch)
            with jax.named_scope("grad_accumulate"):
                mean = jax.tree_util.tree_map(
                    lambda a, g: (a + g) / k, acc, grads)
            with jax.named_scope("optimizer"):
                new_params, new_opt = optimizer.apply_gradients(
                    params, mean, opt_state, lr=lr, step=step_no)
            zeros = jax.tree_util.tree_map(jnp.zeros_like, acc)
            return loss, new_params, new_bufs, new_opt, zeros

        self._jitted_acc = jax.jit(acc_step, donate_argnums=(1, 3))
        self._jitted = jax.jit(apply_step, donate_argnums=(0, 1, 2, 3))

    # ------------------------------------------------------------------
    def __call__(self, *batch) -> Tensor:
        """One (micro-)step. Spans, in the ring and in a running
        profiler session: ``train.step`` with children by containment
        ``.prep`` (learning rate, step number, key fold-in, batch),
        ``.enqueue`` (the call of the jitted program: the runtime takes
        it, or makes the host wait) and ``.post`` (scheduler, wrap)."""
        if self._jitted is None:
            self._build()
        self.step_count += 1
        n = self.step_count
        k = self.accumulate_steps
        micro = k > 1 and n % k != 0    # accumulate grads, no update
        with _span("train.step", cat="train", step=n,
                   program="accumulate" if micro else "step"):
            with _span("train.step.prep", cat="train", step=n):
                lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
                rng_key = _rng.default_generator().fold_in(n)
                raw_batch = _raw_tuple(batch)
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
                if not micro and self.auto_lr_step:
                    lr_sched = getattr(self.optimizer, "_learning_rate",
                                       None)
                    if hasattr(lr_sched, "step"):
                        lr_sched.step()
                out = Tensor(loss)
        return out

    def op_scopes(self) -> Dict[str, str]:
        return op_scopes_of(self)

    op_scopes.__doc__ = op_scopes_of.__doc__

    # ------------------------------------------------------------------
    # fused K-step window (lax.scan over a stacked super-batch)
    # ------------------------------------------------------------------
    def _get_scan_prog(self, k_steps: int, n_batch: int):
        """The jitted K-step fused program: `k_steps` consecutive
        (micro-)steps as ONE donated XLA program — `lax.scan` over the
        stacked super-batch, per-step lr/step_no/fold-in count vectors
        as scan xs, the PRNG base key as a program argument (fold_in
        happens IN-program, so the per-step keys match the eager
        `default_generator().fold_in(step_count)` exactly). With
        gradient merge (accumulate_steps k>1) the update cadence rides
        in as a boolean mask and a `lax.cond` applies/accumulates —
        both branches the same arithmetic as the sequential two-program
        split, so the update cadence and training semantics match the
        sequential loop exactly (and the bits do too at the tier-1
        tested geometries; see `_make_step_fn`).

        Signature (k == accumulate_steps):
          k == 1:  (params, buffers, opt, key, lrs, steps, counts, *sb)
                   -> (losses[K], params, buffers, opt)
          k > 1:   (params, buffers, opt, acc, key, lrs, steps, counts,
                    upd_mask, *sb)
                   -> (losses[K], params, buffers, opt, acc)

        The super-batch buffers are donated (consumed) along with the
        state — no host callback, no mid-window sync.
        """
        key_sig = (int(k_steps), int(n_batch))
        prog = self._scan_progs.get(key_sig)
        if prog is not None:
            return prog
        k = self.accumulate_steps
        scan_window = make_scan_window(
            self._make_step_fn(), self.optimizer, k, self._count_trace)
        if k == 1:
            prog = jax.jit(
                scan_window,
                donate_argnums=(0, 1, 2) + tuple(
                    range(7, 7 + n_batch)))
        else:
            prog = jax.jit(
                scan_window,
                donate_argnums=(0, 1, 2, 3) + tuple(
                    range(9, 9 + n_batch)))
        self._scan_progs[key_sig] = prog
        return prog

    def _count_trace(self):
        self._trace_count += 1    # fires at trace time only

    def scan_steps(self, k_steps: int, *batch) -> Tensor:
        """Run ``k_steps`` consecutive (micro-)steps inside ONE donated
        compiled program. Every leaf of ``batch`` is stacked
        ``[k_steps, ...]`` (io.dataloader.prefetch_to_device builds
        these). Returns the stacked per-step losses as a ``[k_steps]``
        Tensor that stays ON DEVICE — reading it (float()/numpy()) is
        the only host sync, so drivers fetch at log/epoch boundaries
        instead of every step. The super-batch buffers are donated
        (consumed by the program).

        Counter/LR/RNG semantics are bitwise those of ``k_steps``
        sequential ``__call__``s, including the gradient-accumulation
        cadence at any window phase; trailing partial windows should
        use ``__call__`` per step (Model.fit does). With
        ``auto_lr_step=False`` the LR is frozen across the window — an
        external scheduler owner must step between windows, so
        Model.fit keeps the per-step path when an LRScheduler callback
        is active.
        """
        if k_steps < 1:
            raise ValueError("k_steps must be >= 1")
        raw_batch = _raw_tuple(batch)
        for b in raw_batch:
            if b.ndim < 1 or b.shape[0] != k_steps:
                raise ValueError(
                    f"scan_steps batch leaves must be stacked "
                    f"[{k_steps}, ...]; got shape {b.shape}")
        n = self.step_count + 1         # the window's first step
        with _span("train.window", cat="train", step=n, k=k_steps), \
                window_rollback(self):
            with _span("train.step.prep", cat="train", step=n):
                prog = self._get_scan_prog(k_steps, len(raw_batch))
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
            with _span("train.step.post", cat="train", step=n):
                out = Tensor(losses)
        return out

    # ------------------------------------------------------------------
    # AOT warmup (paddle_tpu.compilation)
    # ------------------------------------------------------------------
    def _static_key(self, extra: str = "") -> str:
        """Trace-time constants of this step's programs that never
        appear in an argument aval: the loss/optimizer code baked into
        the graph (betas, eps, weight decay are trace constants — the
        LR is the only hyperparameter passed as an argument) and the
        accumulation cadence. Part of the executable-store key so two
        models with identical parameter geometry but different baked
        config cannot collide. ``extra`` lets the owner add what it
        alone can see (hapi passes its loss object's type — TrainStep
        only sees an anonymous closure)."""
        opt = self.optimizer
        hypers = sorted((k, v) for k, v in vars(opt).items()
                        if isinstance(v, (bool, int, float, str)))
        return repr((type(self.model).__name__, type(opt).__name__,
                     hypers, getattr(self.loss_fn, "__qualname__",
                                     repr(self.loss_fn)),
                     self.accumulate_steps, self.n_inputs, extra))

    def warm(self, *example_batch, scan_k: Optional[int] = None,
             store=None, static_extra: str = "") -> list:
        """Compile-or-load this step's programs through the persistent
        executable store (paddle_tpu.compilation) BEFORE the first
        step: the per-step program(s) — both cadence programs with
        gradient merge — and, with ``scan_k``, the fused K-step window.
        ``example_batch`` is one real (or shape-identical) batch; it is
        only lowered, never executed, and no counter/LR/RNG state
        moves. On a store-warm machine the first `fit` step then
        dispatches a deserialized executable with ZERO XLA compiles
        (tests/test_compilation.py::TestFitWarmStart asserts exactly
        this; tests/test_train_tracing.py that the step program's
        record, made here of each program, costs none either). Returns
        the compile-log records."""
        from ..compilation import log as _clog
        from ..compilation import prime_helper_ops
        from ..compilation.store import AotProgram, aot_compile
        prime_helper_ops()
        static = self._static_key(static_extra)
        if self._jitted is None:
            self._build()
        raw_batch = _raw_tuple(example_batch)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        step_no = jnp.asarray(1, jnp.float32)
        key = _rng.default_generator().fold_in(1)
        recs = []
        k = self.accumulate_steps

        def _warm_site(name, prog, args, program="step"):
            rec = {"site": name}
            aot = aot_compile(name, prog, args, store=store,
                              log_record=rec, static_key=static)
            recs.append(_clog.record(rec))
            # the record of what was compiled or loaded, made from it
            # here: the first step then has nothing to publish
            publish_step_program(self, program, aot)
            return aot

        if not isinstance(self._jitted, AotProgram):
            if k == 1:
                args = (self.params, self.buffers, self.opt_state,
                        lr, step_no, key) + raw_batch
                self._jitted = _warm_site("train_step", self._jitted,
                                          args)
            else:
                acc_args = (self.params, self.buffers, self.opt_state,
                            self.acc_grads, lr, step_no, key) + raw_batch
                self._jitted_acc = _warm_site(
                    "train_step_acc", self._jitted_acc, acc_args,
                    "accumulate")
                self._jitted = _warm_site(
                    "train_step_apply", self._jitted, acc_args)
        if scan_k is not None and scan_k > 1:
            prog = self._get_scan_prog(scan_k, len(raw_batch))
            if not isinstance(prog, AotProgram):
                sb = tuple(np.stack([b] * scan_k) for b in raw_batch)
                lrs = np.full((scan_k,), self.optimizer.get_lr(),
                              np.float32)
                step_nos = np.arange(1, scan_k + 1, dtype=np.float32)
                counts = np.arange(1, scan_k + 1, dtype=np.int32)
                base_key = _rng.get_rng_state()
                if k > 1:
                    upd = (counts % k) == 0
                    args = (self.params, self.buffers, self.opt_state,
                            self.acc_grads, base_key, lrs, step_nos,
                            counts, upd) + sb
                else:
                    args = (self.params, self.buffers, self.opt_state,
                            base_key, lrs, step_nos, counts) + sb
                with _quiet_unused_donation():
                    aot = _warm_site(f"train_step_scan_k{scan_k}",
                                     prog, args, "scan")
                self._scan_progs[(int(scan_k), len(raw_batch))] = aot
        return recs

    # ------------------------------------------------------------------
    def skip_step(self):
        """Advance the step/update counters — and with them the
        per-step RNG fold position and (``auto_lr_step``) the LR
        schedule — WITHOUT executing the program. The supervisor's
        poison-window skip: the batch is consumed from the loader but
        never trained on, and every step AFTER the window draws the
        same fold-in key and schedule position an unfaulted run would
        have at that step count. Parameters and optimizer slots are
        untouched (the in-program step number they carry lags by the
        skipped updates — the documented bounded-drift of a skipped
        window). A skipped micro-step under gradient merge leaves the
        accumulator as-is."""
        self.step_count += 1
        k = self.accumulate_steps
        if k > 1 and self.step_count % k != 0:
            return
        self.update_count += 1
        if self.auto_lr_step:
            lr_sched = getattr(self.optimizer, "_learning_rate", None)
            if hasattr(lr_sched, "step"):
                lr_sched.step()

    def flush_accumulation(self):
        """Apply any pending partial accumulation (mean over the
        micro-steps seen so far). No-op when the cadence is aligned.
        Reference: gradient_merge applies on the k-th step; a trailing
        partial window at the end of an epoch must not leak into the
        next run."""
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
        # realign the cadence so the next call starts a fresh window
        self.step_count += k - r

    def sync_to_model(self):
        """Copy the device-resident state back into the Layer's tensors
        (do this before state_dict/save/eval)."""
        load_state(self.model,
                   jax.tree_util.tree_map(jnp.copy, self.params),
                   jax.tree_util.tree_map(jnp.copy, self.buffers))
        return self.model

    def eval_fn(self):
        """A jitted inference function over the current training state."""
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
