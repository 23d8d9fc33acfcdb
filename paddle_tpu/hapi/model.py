"""Model: the high-level train/eval/predict API.

Parity: paddle.Model (python/paddle/hapi/model.py — fit :1045, evaluate
:1740, predict :1991, prepare, save/load, summary). The reference keeps two
adapters (dygraph :771 / static graph :285); here there is one path: every
train step runs through the fused jit TrainStep (forward+loss+backward+
update in one XLA program), eval/predict through a jitted inference
function — the static-graph speed with the dygraph API.
"""
from __future__ import annotations

import os
import time
from typing import List, Optional, Sequence

import numpy as np

from ..core.tensor import Tensor
from ..framework.env import bool_env, int_env
from ..io.state import load as _load, save as _save
from ..jit.training import TrainStep
from ..metric import Metric
from ..nn.layer_base import Layer
from .callbacks import EarlyStopping, config_callbacks
from .lazy import LazyLoss, LossWindow

__all__ = ["Model"]


def _as_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _obs_hist(name, help_):
    """Registry histogram when ambient obs is on, else None — the
    training loop's instrumentation collapses to one ``is not None``
    branch per site when disabled (paddle_tpu.obs)."""
    from .. import obs
    if not obs.enabled():
        return None
    return obs.metrics.registry.histogram(name, help_)


def _obs_gauge(name, help_):
    """Registry gauge under the same obs gate as _obs_hist."""
    from .. import obs
    if not obs.enabled():
        return None
    return obs.metrics.registry.gauge(name, help_)


class Model:
    """Parity: paddle.Model(network, inputs=None, labels=None)."""

    def __init__(self, network: Layer, inputs=None, labels=None):
        self.network = network
        # declared specs (parity: paddle.Model(inputs=..., labels=...));
        # when given, their lengths drive the batch split instead of the
        # last-element-is-label heuristic
        self._input_specs = _as_list(inputs) or None
        self._label_specs = _as_list(labels) or None
        self._optimizer = None
        self._loss = None
        self._metrics: List[Metric] = []
        self._train_step: Optional[TrainStep] = None
        self._parallel = None
        self._auto_lr_step = True
        self._accumulate = 1
        self._carried_opt = None
        self.stop_training = False
        # resume/skip hooks (distributed/supervisor.py drives these via
        # fit(resume_step=, skip_windows=)): batches left to fast-forward
        # and step-index windows to skip without training
        self._ff_remaining = 0
        self._skip_windows: tuple = ()

    # -- setup -----------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None, parallel=None):
        """Parity: Model.prepare. ``parallel`` opts the training loop
        into the hybrid-parallel engine: with a truthy value every fit
        step runs through ``distributed.ParallelTrainStep`` over the
        global mesh instead of the single-chip ``TrainStep`` — pass
        ``True`` (ZeRO stage picked up from
        ``sharding.group_sharded_parallel``'s mark on the optimizer) or
        a kwargs dict forwarded verbatim (``{"zero_stage": 3,
        "remat": True, ...}``). The supervisor/fit self-healing hooks
        (resume fast-forward, skip windows, topology-elastic
        checkpoint restore) work identically on both engines."""
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = _as_list(metrics)
        self._parallel = parallel
        self._train_step = None
        return self

    # -- helpers ---------------------------------------------------------
    def _split_batch(self, data):
        """DataLoader yields (x.., y..) / (x,) / dict; normalize to lists.
        Declared inputs/labels specs override the default split (last
        element = single label)."""
        if isinstance(data, dict):
            data = tuple(data.values())
        if isinstance(data, (list, tuple)):
            data = list(data)
            if self._input_specs is not None:
                n_in = len(self._input_specs)
                n_lb = len(self._label_specs) if self._label_specs else \
                    len(data) - n_in
                return data[:n_in], data[n_in:n_in + n_lb]
            if len(data) >= 2:
                return data[:-1], [data[-1]]
            return data, []
        return [data], []

    def _loss_value(self, outputs, labels):
        loss = self._loss(outputs, *labels) if labels else \
            self._loss(outputs)
        return loss

    def _ensure_train_step(self, n_inputs):
        if self._train_step is None:
            if self._optimizer is None or self._loss is None:
                raise RuntimeError("call prepare(optimizer, loss) first")
            if self._parallel:
                from ..distributed.parallel_step import ParallelTrainStep
                pkw = dict(self._parallel) \
                    if isinstance(self._parallel, dict) else {}
                self._train_step = ParallelTrainStep(
                    self.network,
                    lambda out, *ys: self._loss_value(out, ys),
                    self._optimizer, n_inputs=n_inputs,
                    accumulate_steps=self._accumulate, **pkw)
            else:
                self._train_step = TrainStep(
                    self.network,
                    lambda out, *ys: self._loss_value(out, ys),
                    self._optimizer, n_inputs=n_inputs,
                    accumulate_steps=self._accumulate)
            self._train_step.auto_lr_step = self._auto_lr_step
            if self._carried_opt is not None:
                import jax as _jax
                import jax.numpy as _jnp
                state, updates = self._carried_opt
                self._train_step.opt_state = _jax.tree_util.tree_map(
                    _jnp.copy, state)
                self._train_step.update_count = updates
                self._carried_opt = None
        return self._train_step

    # -- train -----------------------------------------------------------
    def train_batch(self, inputs, labels=None):
        """Parity: Model.train_batch. The returned loss is a LAZY float
        (hapi.lazy.LazyLoss): the compiled step is dispatched but the
        device->host sync happens only when the caller actually reads
        the value — the hot loop never blocks on `float(loss)`."""
        inputs = _as_list(inputs)
        labels = _as_list(labels)
        step = self._ensure_train_step(len(inputs))
        loss = step(*inputs, *labels)
        from ..distributed import resilience as _resil
        if _resil.should_fire("train_step_nan"):
            # fault site: the step's REPORTED loss is non-finite while
            # the real program ran and advanced state — the transient
            # divergence the watchdog's storm counter and the
            # supervisor's rollback absorb (N firings under nan_limit=N
            # make one full storm)
            return [LazyLoss(LossWindow(float("nan")))]
        # fault site: the step wedges AFTER dispatch — the loss fetch
        # hangs (wedged device); under a StepWatchdog deadline
        # this surfaces as StepTimeout, state already advanced
        _resil.maybe_inject("step_hang")
        return [LazyLoss(LossWindow(loss.value))]

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1,
            verbose=1, drop_last=False, shuffle=True, num_workers=0,
            callbacks=None, accumulate_grad_batches=1, num_iters=None,
            scan_steps=None, warm_start=None, resume_step=None,
            skip_windows=None, watchdog=None):
        """Parity: Model.fit (hapi/model.py:1045). train_data may be a
        DataLoader or a Dataset (a loader is built with batch_size).

        ``scan_steps`` (default: PADDLE_TPU_SCAN_STEPS env, else 1):
        with K>1 the loop runs K optimizer steps per dispatch inside ONE
        donated compiled program (TrainStep.scan_steps) fed by a
        double-buffered host->device super-batch pipeline
        (io.dataloader.prefetch_to_device) — no host sync inside the
        window; losses reach callbacks as lazy objects that materialize
        at log_freq/epoch boundaries. Because the K steps execute as
        one uninterruptible program, per-step callbacks fire POST-HOC:
        each window's K on_train_batch_begin/end pairs are emitted
        after the window completes (step indices and losses are exact;
        wall-clock between begin and end is not, and a begin-callback
        cannot veto a step inside the window). Trailing partial windows
        fall back to the per-step program, so step counts, LR schedule,
        and gradient-accumulation cadence are bitwise those of the
        per-step loop. When an LRScheduler callback owns schedule
        stepping the loop stays per-step (the callback steps between
        batches).

        Self-healing hooks (distributed/supervisor.py drives these):
        ``resume_step=N`` fast-forwards the first N batches — consumed
        from the loader, never trained, no callbacks — so a run
        restored from a step-N checkpoint lines its (deterministic)
        data stream back up with its counters. ``skip_windows`` is a
        sequence of ``(lo, hi)`` step-index ranges to SKIP: each
        batch is consumed and the step counters/RNG-fold/LR schedule
        advance (``TrainStep.skip_step``) but the program never runs —
        the poison-data escape hatch, with documented bounded drift.
        ``watchdog`` accepts a pre-armed ``StepWatchdog`` (the
        supervisor's, so NaN-storm limits and deadlines follow its
        policy); None keeps the env-gated arming."""
        from ..io.dataloader import DataLoader, Dataset
        if accumulate_grad_batches != self._accumulate:
            # gradient merge happens inside the compiled step
            # (jit.TrainStep accumulate_steps); changing it needs a rebuild
            # — sync trained params back and carry the optimizer state over
            # so Adam moments / step numbering survive the rebuild
            if self._train_step is not None:
                self._train_step.flush_accumulation()
                self._sync()
                self._carried_opt = (self._train_step.opt_state,
                                     self._train_step.update_count)
            self._accumulate = accumulate_grad_batches
            self._train_step = None
        loader = train_data
        if isinstance(train_data, Dataset):
            loader = DataLoader(train_data, batch_size=batch_size,
                                shuffle=shuffle, drop_last=drop_last,
                                num_workers=num_workers)
        self._save_dir = save_dir
        cbs = config_callbacks(callbacks, self, verbose,
                               log_freq=log_freq, save_dir=save_dir,
                               save_freq=save_freq)
        # a user-supplied LRScheduler callback takes over schedule
        # stepping; recomputed each fit() so dropping the callback later
        # hands stepping back to TrainStep
        from .callbacks import LRScheduler as _LRCb
        self._auto_lr_step = not any(isinstance(c, _LRCb) for c in cbs)
        if self._train_step is not None:
            self._train_step.auto_lr_step = self._auto_lr_step
        self.stop_training = False
        # Resilience (distributed/resilience.py): with
        # PADDLE_TPU_STEP_TIMEOUT set (or FLAGS_check_nan_inf armed)
        # every train step runs under a StepWatchdog — a wedged step
        # raises StepTimeout instead of hanging fit() forever, a NaN
        # storm raises NanInfStorm, and both write an atomic
        # checkpoint-on-failure into save_dir first.
        from ..distributed.resilience import StepWatchdog
        if watchdog is None and StepWatchdog.enabled_by_env():
            watchdog = StepWatchdog(
                on_failure=lambda kind, exc: self._emergency_save(kind))
        self._ff_remaining = max(0, int(resume_step or 0))
        self._skip_windows = tuple(sorted(
            (int(lo), int(hi)) for lo, hi in (skip_windows or ())
            if int(hi) > int(lo)))
        if scan_steps is None:
            scan_steps = int_env("PADDLE_TPU_SCAN_STEPS", 1, minimum=1)
        scan_steps = max(1, int(scan_steps))
        # AOT warmup (paddle_tpu.compilation): compile-or-load the
        # training program(s) through the persistent executable store
        # BEFORE the first step — a store-warm fresh process reaches
        # its first train step with zero XLA compiles. Default from
        # PADDLE_TPU_WARM_START (off: warming peeks one batch from a
        # fresh loader iterator, which assumes a re-iterable loader).
        if warm_start is None:
            warm_start = bool_env("PADDLE_TPU_WARM_START", False)
        if warm_start:
            self._warm_start(loader, scan_steps)
        for cb in cbs:
            cb.on_train_begin()
        try:
            self._fit_epochs(loader, eval_data, batch_size, epochs,
                             eval_freq, num_workers, num_iters, cbs,
                             watchdog, scan_steps)
        finally:
            if watchdog is not None:
                watchdog.close()
        if self._train_step is not None:
            # apply a trailing partial accumulation window so its grads
            # are not silently carried into a later fit/evaluate
            self._train_step.flush_accumulation()
        for cb in cbs:
            cb.on_train_end()
        return self

    def _warm_start(self, loader, scan_steps):
        """fit(warm_start=True): peek ONE batch from a fresh loader
        iterator for shapes only and compile-or-load the training
        program(s) through the persistent executable store
        (TrainStep.warm) — including the fused K-step window when the
        fused path will run — so time-to-first-step stops paying the
        compile. The peeked batch is never trained on here: epoch
        iteration restarts from its own iterator."""
        try:
            batch = next(iter(loader))
        except (StopIteration, TypeError):
            return
        inputs, labels = self._split_batch(batch)
        step = self._ensure_train_step(len(inputs))
        if not hasattr(step, "warm"):
            return      # hybrid-parallel step: no AOT warmup site yet
        fused = scan_steps > 1 and self._auto_lr_step
        step.warm(*inputs, *labels,
                  scan_k=scan_steps if fused else None,
                  static_extra=type(self._loss).__name__)

    def _fit_epochs(self, loader, eval_data, batch_size, epochs,
                    eval_freq, num_workers, num_iters, cbs, watchdog,
                    scan_steps=1):
        # The fused path needs the step to own LR stepping: an external
        # LRScheduler callback steps BETWEEN batches, which a K-step
        # window cannot replay mid-program.
        fused = scan_steps > 1 and self._auto_lr_step
        it_count = 0
        for epoch in range(epochs):
            try:
                steps = len(loader)
            except TypeError:
                steps = None
            for cb in cbs:
                cb.on_epoch_begin(epoch, {"steps": steps})
            if fused:
                logs, it_count = self._run_epoch_fused(
                    loader, scan_steps, cbs, watchdog, it_count,
                    num_iters)
            else:
                logs, it_count, _ = self._run_epoch_steps(
                    loader, cbs, watchdog, it_count, num_iters)
            # epoch boundary: materialize lazy losses (ONE window fetch)
            # so epoch-end consumers (VisualDL scalars, checkpoints
            # keyed on loss) see plain floats
            logs = {k: float(v) if isinstance(v, LazyLoss) else v
                    for k, v in logs.items()}
            if eval_data is not None and (epoch + 1) % eval_freq == 0:
                eval_logs = self.evaluate(eval_data, batch_size=batch_size,
                                          verbose=0,
                                          num_workers=num_workers)
                logs.update({f"eval_{k}": v for k, v in eval_logs.items()})
                for cb in cbs:
                    cb.on_eval_end(eval_logs)
            for cb in cbs:
                cb.on_epoch_end(epoch, logs)
            if any(getattr(cb, "stop_training", False) for cb in cbs) or \
                    self.stop_training:
                break
            if num_iters is not None and it_count >= num_iters:
                break

    def _run_epoch_steps(self, loader, cbs, watchdog, it_count, num_iters,
                         step_i=0, batches=None):
        """The per-step dispatch loop (also the fused loop's trailing-
        window fallback, via `batches`/`step_i`). Returns
        ``(logs, it_count, step_i)``."""
        logs = {}
        h_step = _obs_hist("ptpu_train_step_ms",
                           "per-step dispatch wall time")
        g_mfu = _obs_gauge("ptpu_train_mfu",
                           "model-FLOPs-utilization of the last train "
                           "dispatch (obs.efficiency, chip-relative)")
        g_step_s = _obs_gauge("ptpu_train_step_seconds",
                              "measured wall seconds per optimizer "
                              "step (last dispatch)")
        for data in (batches if batches is not None else loader):
            if self._ff_remaining > 0:
                # resume fast-forward: this batch was already trained
                # before the restart; consume it (no callbacks, no
                # counters) so the stream lines back up
                self._ff_remaining -= 1
                continue
            x, y = self._split_batch(data)
            if self._skip_windows:
                step = self._ensure_train_step(len(x))
                if self._skip_hit(step.step_count):
                    # poison-window skip: batch consumed, counters/RNG/
                    # LR advance, program never runs, no callbacks
                    step.skip_step()
                    continue
            t_step = time.perf_counter() if h_step is not None else 0.0
            for cb in cbs:
                cb.on_train_batch_begin(step_i)
            if watchdog is not None:
                (loss,) = watchdog.run(self.train_batch, x, y)
            else:
                (loss,) = self.train_batch(x, y)
            logs = {"loss": loss}
            for cb in cbs:
                cb.on_train_batch_end(step_i, logs)
            if h_step is not None:
                dt_step = time.perf_counter() - t_step
                h_step.observe(dt_step * 1e3)
                self._observe_train_eff(g_mfu, g_step_s, dt_step, 1,
                                        x[0] if x else None)
            step_i += 1
            it_count += 1
            if num_iters is not None and it_count >= num_iters:
                break
        return logs, it_count, step_i

    def _run_epoch_fused(self, loader, k, cbs, watchdog, it_count,
                         num_iters):
        """One epoch as K-step fused windows: scan_steps programs over
        prefetched super-batches; callbacks fire per step with LAZY
        losses (one device fetch per window, at most — and only when
        something reads them). The window executes BEFORE its K
        begin/end callback pairs are emitted (see fit's docstring).
        Trailing partial windows and num_iters caps run the per-step
        program so step semantics are identical to the sequential
        loop."""
        from .. import obs as _obs
        from ..io.dataloader import prefetch_to_device
        depth = int_env("PADDLE_TPU_PREFETCH_DEPTH", 2, minimum=1)
        # per-window training telemetry (paddle_tpu.obs): prefetch-wait
        # (the host starved waiting for the super-batch pipeline),
        # dispatch (handing the window to the device), and the window's
        # wall time — the measured step-phase times the MFU campaign
        # pairs with tpucost's static model. The fetch span lives where
        # the fetch does (hapi.lazy.LossWindow).
        obs_on = _obs.enabled()
        h_wait = _obs_hist("ptpu_train_prefetch_wait_ms",
                           "host wait for the next super-batch") \
            if obs_on else None
        h_window = _obs_hist("ptpu_train_window_ms",
                             "fused K-step window wall time") \
            if obs_on else None
        g_mfu = _obs_gauge("ptpu_train_mfu",
                           "model-FLOPs-utilization of the last train "
                           "dispatch (obs.efficiency, chip-relative)") \
            if obs_on else None
        g_step_s = _obs_gauge("ptpu_train_step_seconds",
                              "measured wall seconds per optimizer "
                              "step (last dispatch)") \
            if obs_on else None
        logs = {}
        step_i = 0
        win_iter = iter(prefetch_to_device(loader, k, depth=depth))
        while True:
            t_wait = time.perf_counter() if obs_on else 0.0
            try:
                win = next(win_iter)
            except StopIteration:
                break
            if obs_on:
                now = time.perf_counter()
                h_wait.observe((now - t_wait) * 1e3)
                _obs.record_span("train.prefetch_wait", t_wait, now,
                                 cat="train")
            t_win = time.perf_counter() if obs_on else 0.0
            remaining = None if num_iters is None else num_iters - it_count
            # resume fast-forward / poison-window skip route through the
            # per-step fallback (a K-step program is one uninterruptible
            # dispatch — it cannot skip a step in its middle)
            pos = (self._train_step.step_count
                   if self._train_step is not None else 0)
            healing = (self._ff_remaining > 0
                       or self._skip_overlap(pos, pos + k))
            eff_x0 = None
            if win.full and not healing and \
                    (remaining is None or remaining >= k):
                x, y = self._split_batch(win.data)
                step = self._ensure_train_step(len(x))
                eff_x0 = x[0] if x else None

                def run_window(x=x, y=y):
                    # scan_steps times itself (`train.window`)
                    return LossWindow(step.scan_steps(k, *x, *y).value)

                if watchdog is not None:
                    # the K-step window is ONE dispatch: its deadline is
                    # K per-step budgets; the NaN scan coerces the
                    # returned LossWindow, so supervision shares the
                    # window's single counted fetch with the lazy
                    # losses below instead of paying its own transfer
                    window = watchdog.run(run_window, deadline_scale=k)
                else:
                    window = run_window()
                for j in range(k):
                    for cb in cbs:
                        cb.on_train_batch_begin(step_i)
                    logs = {"loss": LazyLoss(window, j)}
                    for cb in cbs:
                        cb.on_train_batch_end(step_i, logs)
                    step_i += 1
                    it_count += 1
            else:
                # trailing partial window / num_iters cap: per-step
                # program over the window's rows
                tail = list(win.rows())
                if remaining is not None:
                    tail = tail[:remaining]
                logs2, it_count, step_i = self._run_epoch_steps(
                    None, cbs, watchdog, it_count, num_iters,
                    step_i=step_i, batches=tail)
                logs = logs2 or logs
            if obs_on:
                dt_win = time.perf_counter() - t_win
                h_window.observe(dt_win * 1e3)
                if eff_x0 is not None:
                    # full fused window: K steps, one dispatch (the
                    # tail fallback exported per-step gauges itself)
                    self._observe_train_eff(g_mfu, g_step_s, dt_win,
                                            k, eff_x0)
            if num_iters is not None and it_count >= num_iters:
                break
        return logs, it_count

    def _observe_train_eff(self, g_mfu, g_step_s, dt_s, steps, x0):
        """Export ``ptpu_train_mfu`` + ``ptpu_train_step_seconds`` for
        one dispatch (a single step or a fused K-step window) — the
        ONE shared formula in obs/efficiency.py over the measured wall
        time (ISSUE 14: the bench records and these gauges must never
        disagree). Token accounting: integer inputs are token ids so
        every dim counts (a [K,B,S] super-batch is K*B*S tokens);
        float inputs count batch dims only (trailing feature dim
        excluded) — the nominal 6*N*T proxy efficiency.
        train_step_flops documents."""
        if g_mfu is None or dt_s <= 0 or self._train_step is None:
            return
        from ..obs import efficiency as eff
        step = self._train_step
        if getattr(self, "_eff_step", None) is not step:
            # param count is per-built-step (a rebuild may follow an
            # accumulate change); shapes only, no device sync
            self._eff_step = step
            self._eff_nparams = eff.tree_nelems(step.params)
        shape = tuple(getattr(x0, "shape", ()) or ())
        if not shape:
            return
        try:
            is_int = np.issubdtype(np.dtype(getattr(x0, "dtype", None)),
                                   np.integer)
        except TypeError:
            is_int = False
        dims = shape if is_int or len(shape) == 1 else shape[:-1]
        tokens = 1
        for d in dims:
            tokens *= int(d)
        g_mfu.set(eff.mfu(
            eff.train_step_flops(self._eff_nparams, tokens), dt_s))
        g_step_s.set(dt_s / max(1, int(steps)))

    def _skip_hit(self, pos: int) -> bool:
        return any(lo <= pos < hi for lo, hi in self._skip_windows)

    def _skip_overlap(self, lo: int, hi: int) -> bool:
        return any(a < hi and lo < b for a, b in self._skip_windows)

    def _emergency_save(self, kind: str):
        """Checkpoint-on-failure for the fit loop: atomic tmp+rename of
        the usual .pdparams/.pdopt pair under save_dir. Best-effort by
        contract (StepWatchdog swallows exceptions here so the original
        failure surfaces) — a hang may leave device state unreachable,
        in which case the last synced host copy is what gets saved."""
        if getattr(self, "_save_dir", None) is None:
            return
        os.makedirs(self._save_dir, exist_ok=True)
        prefix = os.path.join(self._save_dir, "on_failure")
        if kind != "hang":
            # on a hang the device may be wedged — syncing step state
            # from it would block THIS thread too, turning the
            # StepTimeout escape hatch back into a hang; save the last
            # host-synced copy instead
            self._sync()
        _save(self.network.state_dict(), prefix + ".pdparams.tmp")
        os.replace(prefix + ".pdparams.tmp", prefix + ".pdparams")
        if self._optimizer is not None:
            _save(self._optimizer.state_dict(), prefix + ".pdopt.tmp")
            os.replace(prefix + ".pdopt.tmp", prefix + ".pdopt")

    # -- eval / predict --------------------------------------------------
    def _sync(self):
        if self._train_step is not None:
            self._train_step.sync_to_model()

    def _forward_eval(self, inputs, labels=None, lazy=False):
        """Eager eval forward. With ``lazy`` the loss comes back as the
        raw DEVICE scalar (no host sync) — evaluate() batches the fetch
        over the whole pass instead of blocking per batch."""
        was_training = self.network.training
        self.network.eval()
        try:
            out = self.network(*_as_list(inputs))
            labels = _as_list(labels)
            loss = self._loss_value(out, labels) \
                if (self._loss is not None and labels) else None
            if loss is None:
                return out, None
            dev = loss.value if isinstance(loss, Tensor) else loss
            return out, (dev if lazy else float(loss))
        finally:
            if was_training:
                self.network.train()

    def eval_batch(self, inputs, labels=None):
        self._sync()
        return self._forward_eval(inputs, labels)

    def _infer_fn(self):
        """Jitted inference over the training step's device-resident state
        (no per-op dispatch, no sync copy); eager fallback otherwise."""
        if self._train_step is not None:
            return self._train_step.eval_fn()
        return None

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=1,
                 num_workers=0, callbacks=None, num_samples=None):
        """Parity: Model.evaluate (hapi/model.py:1740)."""
        from ..io.dataloader import DataLoader, Dataset
        loader = eval_data
        if isinstance(eval_data, Dataset):
            loader = DataLoader(eval_data, batch_size=batch_size,
                                num_workers=num_workers)
        for m in self._metrics:
            m.reset()
        cbs = list(callbacks or [])
        for cb in cbs:
            cb.set_model(self)
            cb.on_eval_begin()
        infer = self._infer_fn()
        if infer is None:
            self._sync()
        # per-batch losses stay ON DEVICE; the whole pass is fetched in
        # ONE batched device_get at the end (the per-batch float() here
        # used to cost a device->host round-trip every batch)
        losses, weights = [], []
        seen = 0
        for step_i, data in enumerate(loader):
            x, y = self._split_batch(data)
            if infer is not None:
                out = infer(*x)
                with_loss = self._loss is not None and y
                if with_loss:
                    lt = self._loss_value(out, y)
                    loss = lt.value if isinstance(lt, Tensor) else lt
                else:
                    loss = None
            else:
                out, loss = self._forward_eval(x, y, lazy=True)
            n = int(x[0].shape[0]) if hasattr(x[0], "shape") else 1
            seen += n
            if loss is not None:
                losses.append(loss)
                weights.append(n)
            for m in self._metrics:
                if hasattr(m, "compute"):
                    m.update(*m.compute(out, *y))
                else:
                    m.update(out, *y)
            for cb in cbs:
                cb.on_eval_batch_end(
                    step_i, {"loss": None if loss is None
                             else LazyLoss(LossWindow(loss))})
            if num_samples is not None and seen >= num_samples:
                break
        logs = {}
        if losses:
            import jax
            from ..framework import syncs
            syncs.record_sync()
            vals = [float(v) for v in jax.device_get(losses)]
            logs["loss"] = float(np.average(vals, weights=weights))
        for m in self._metrics:
            names = m.name()
            vals = m.accumulate()
            if isinstance(names, (list, tuple)):
                vals = vals if isinstance(vals, (list, tuple)) else [vals]
                logs.update(dict(zip(names, vals)))
            else:
                logs[names] = vals
        for cb in cbs:
            cb.on_eval_end(logs)
        if verbose:
            import sys
            print("Eval " + ", ".join(f"{k}: {v:.4f}"
                                      for k, v in logs.items()),
                  file=sys.stderr)
        return logs

    def predict_batch(self, inputs):
        self._sync()
        out, _ = self._forward_eval(inputs)
        return out

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, verbose=1, callbacks=None):
        """Parity: Model.predict (hapi/model.py:1991)."""
        from ..io.dataloader import DataLoader, Dataset
        loader = test_data
        if isinstance(test_data, Dataset):
            loader = DataLoader(test_data, batch_size=batch_size,
                                num_workers=num_workers)
        infer = self._infer_fn()
        if infer is None:
            self._sync()
        outs = []
        for data in loader:
            x, _ = self._split_batch(data)
            if infer is not None:
                out = infer(*x)
            else:
                out, _ = self._forward_eval(x)
            outs.append(out)
        if stack_outputs:
            if outs and isinstance(outs[0], (tuple, list)):
                return [Tensor(np.concatenate([o[i].numpy() for o in outs]))
                        for i in range(len(outs[0]))]
            return [Tensor(np.concatenate([o.numpy() for o in outs]))]
        return outs

    # -- io --------------------------------------------------------------
    def save(self, path, training=True):
        """Parity: Model.save — writes <path>.pdparams (+ .pdopt)."""
        self._sync()
        _save(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            _save(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        state = _load(path + ".pdparams")
        self.network.set_state_dict(state)
        opt_path = path + ".pdopt"
        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(opt_path):
            self._optimizer.set_state_dict(_load(opt_path))
        self._train_step = None
        return self

    def parameters(self, *a, **k):
        return self.network.parameters(*a, **k)

    def summary(self, input_size=None, dtype=None):
        n_params = sum(int(np.prod(p.shape))
                       for p in self.network.parameters())
        lines = [f"{self.network.__class__.__name__}: "
                 f"{n_params:,} parameters"]
        for name, sub in self.network.named_sublayers():
            cnt = sum(int(np.prod(p.shape))
                      for p in sub._parameters.values() if p is not None)
            if cnt:
                lines.append(f"  {name}: {cnt:,}")
        s = "\n".join(lines)
        print(s)
        trainable = sum(
            int(np.prod(p.shape)) for p in self.network.parameters()
            if getattr(p, "trainable", True) and not p.stop_gradient)
        return {"total_params": n_params, "trainable_params": trainable}
