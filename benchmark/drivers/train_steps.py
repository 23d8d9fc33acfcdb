"""Driver ``train_steps``: a user's training loop on one process.

The system under test is ``GPTForCausalLM`` + ``model.make_loss_fn()`` +
``AdamW`` + ``jit.TrainStep``; this file is the only one of the benchmark
that imports it. Everything it is compared with (weights, batches, the
reference, the counts of work) comes from the benchmark's own files.

A run: build the step with state made on the device from ``--seed``;
drive it through its first steps (the warm-up: first call, which
compiles or loads, plus ``warmup_steps - 1``), reading what `correct`
compares over the first ``compare_steps`` of them; then hand the same object to the window: a fresh batch drawn
on the host for every step, steps dispatched back to back, the loss
fetched every ``log_every`` steps, the window closed by the first fetch
at or after ``--seconds``. The clock runs from the sync that ends the
warm-up to the sync of that last fetch.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import time

import numpy as np

from benchmark import traffic as traffic_mod
from benchmark import work
from benchmark.trace import SPAN_PREFIX, WINDOW_SPAN

# canonical block leaf -> the program's name for it inside one block
_BLOCK = {"ln1_g": "ln_1.weight", "ln1_b": "ln_1.bias",
          "qkv_w": "attn.qkv.weight", "qkv_b": "attn.qkv.bias",
          "proj_w": "attn.out_proj.weight", "proj_b": "attn.out_proj.bias",
          "ln2_g": "ln_2.weight", "ln2_b": "ln_2.bias",
          "fc_w": "mlp.fc_in.weight", "fc_b": "mlp.fc_in.bias",
          "out_w": "mlp.fc_out.weight", "out_b": "mlp.fc_out.bias"}
_TOP = {"wte": "gpt.embeddings.word_embeddings.weight",
        "wpe": "gpt.embeddings.position_embeddings.weight",
        "lnf_g": "gpt.ln_f.weight", "lnf_b": "gpt.ln_f.bias"}


def program_layout(arch: dict, job: dict) -> dict:
    """The program's parameter name -> (canonical leaf, layer or None)."""
    out = {prog: (leaf, None) for leaf, prog in _TOP.items()}
    for leaf, prog in _BLOCK.items():
        if job["scan_layers"]:
            out["gpt.blocks." + prog.replace(".", "__")] = (leaf, None)
        else:
            for i in range(int(arch["num_layers"])):
                out[f"gpt.block_{i}.{prog}"] = (leaf, i)
    return out


def _stacked(layout: dict, prog: str) -> bool:
    """Whether the program keeps this parameter for all layers at once."""
    leaf, layer = layout[prog]
    return layer is None and leaf in _BLOCK


def _by_leaf(per_param: dict, layout: dict, num_layers: int) -> dict:
    """{program name: [parts] or [L, parts]} -> {canonical leaf: the
    same}, the unrolled program's layers stacked."""
    out: dict = {}
    for prog, (leaf, layer) in layout.items():
        v = np.asarray(per_param[prog], np.float64)
        if layer is None:
            out[leaf] = v
        else:
            out.setdefault(leaf, np.zeros((num_layers,) + v.shape))[layer] = v
    return out


class Session:
    """One run's program, its observations, and its comparison."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.cell["config"]
        self.arch, self.job = self.config, self.config["job"]
        self.traffic = ctx.cell["traffic"]
        self.reference = importlib.import_module(
            "benchmark.reference." + self.config["family"])
        self.spans: list = []          # (name, start, end) host seconds
        self.obs: dict = {}
        self.end_to_end: dict = {}
        self.attempted = self.failed = 0

    # ------------------------------------------------------------ set-up
    def _build(self):
        import jax
        import jax.numpy as jnp

        import paddle_tpu as paddle
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.jit.functional import load_state
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        arch, job = self.arch, self.job
        self.paddle = paddle
        cfg = GPTConfig(
            vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
            num_layers=arch["num_layers"], num_heads=arch["num_heads"],
            max_seq_len=arch["max_seq_len"], ffn_mult=arch["ffn_mult"],
            dropout=0.0, tie_embeddings=True,
            initializer_range=arch["initializer_range"],
            recompute=job["recompute"],
            recompute_policy=job["recompute_policy"],
            scan_layers=job["scan_layers"],
            fused_loss_chunk=job["fused_loss_chunk"])
        with paddle.LazyGuard():
            model = GPTForCausalLM(cfg)
        if job["compute_dtype"] != "bfloat16":
            raise ValueError("this driver casts the model with .bfloat16()")
        model.bfloat16()
        self.layout = program_layout(arch, job)
        want = {n: tuple(p.shape) for n, p in model.named_parameters()}
        ref = self.reference
        layout = self.layout

        def make(key):
            leaves = ref.canonical_weights(arch, key, jnp.bfloat16)
            return {prog: leaves[leaf] if layer is None
                    else leaves[leaf][layer]
                    for prog, (leaf, layer) in layout.items()}

        # every weight in one jitted call on the device, in the type the
        # job trains in
        params = jax.jit(make)(ref.seed_key(self.ctx.seed))
        got = {n: tuple(v.shape) for n, v in params.items()}
        if got != want:
            raise ValueError("the layout does not cover the program's "
                             f"parameters: {set(got) ^ set(want)}")
        load_state(model, params)
        del params
        opt = paddle.optimizer.AdamW(
            learning_rate=job["learning_rate"], beta1=job["beta1"],
            beta2=job["beta2"], epsilon=job["epsilon"],
            weight_decay=job["weight_decay"],
            multi_precision=job["master_weights"],
            parameters=model.parameters())
        self.model, self.opt = model, opt
        self.step = TrainStep(model, model.make_loss_fn(), opt)
        self.batches = traffic_mod.token_batches(
            self.traffic, arch["vocab_size"], self.ctx.seed)

    @contextlib.contextmanager
    def _span(self, name: str):
        """A host span on the benchmark's clock and, in a traced run, in
        the profiler's trace too."""
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def _one_step(self):
        """The window's own call and feed; the warm-up goes through it."""
        with self._span("make_batch"):
            ids = self.paddle.to_tensor(next(self.batches))
        with self._span("dispatch"):
            return self.step(ids, ids)

    def _fetch(self, loss) -> float:
        with self._span("fetch_loss"):
            return float(loss)

    # ------------------------------------------------- what is compared
    def _leaf_norms(self, values: dict, minus: dict = None) -> dict:
        """Canonical leaf -> the norms (``reference.leaf_norms``) of the
        program's arrays ``values`` (less ``minus``), keyed as its
        parameters are."""
        import jax
        import jax.numpy as jnp
        leaf_norms, layout = self.reference.leaf_norms, self.layout

        @jax.jit
        def norms(values, minus):
            out = {}
            for n, v in values.items():
                v = v.astype(jnp.float32)
                if minus is not None:
                    v = v - minus[n].astype(jnp.float32)
                out[n] = leaf_norms(v, layout[n][0], _stacked(layout, n))
            return out
        return _by_leaf(jax.device_get(norms(values, minus)), layout,
                        int(self.arch["num_layers"]))

    def _first_gradient_norms(self) -> dict:
        """Each leaf's norm of the first gradient as the optimizer got
        it, from Adam's first moment after one step: m1 = (1-b1) g."""
        got = self._leaf_norms({n: s["moment1"]
                                for n, s in self.step.opt_state.items()})
        scale = 1.0 / (1.0 - float(self.job["beta1"]))
        return {leaf: v * scale for leaf, v in got.items()}

    def _change_norms(self) -> dict:
        """Each leaf's norm of (what the optimizer updates now - the
        weights the run started from): the masters where the job keeps
        them, else the weights. The start is the model's own tensors,
        which ``TrainStep`` copies and never donates."""
        start = {n: p.value for n, p in self.model.named_parameters()}
        if self.job["master_weights"]:
            now = {n: s["master"] for n, s in self.step.opt_state.items()}
        else:
            now = self.step.params
        return self._leaf_norms(now, start)

    # ------------------------------------------------------------ the run
    def warm_up(self):
        """Build the step and drive it through its first steps, reading
        what `correct` compares."""
        import paddle_tpu.nn.functional as F
        from paddle_tpu.compilation import counters

        ctx, tr = self.ctx, self.traffic
        self._build()
        t_built = time.perf_counter()
        n_warm, n_cmp = int(tr["warmup_steps"]), int(tr["compare_steps"])
        if not 1 <= n_cmp <= n_warm:
            raise ValueError("compare_steps must lie in 1..warmup_steps")
        hits0, compiles0 = (counters.persistent_cache_hits(),
                            counters.xla_compiles())
        losses, grad_norms, change_norms = [], None, None
        for i in range(1, n_warm + 1):
            t0 = time.perf_counter()
            losses.append(self._fetch(self._one_step()))
            if i == 1:
                self.obs["first_step_s"] = time.perf_counter() - t0
                self.obs["first_step_cache_hits"] = \
                    counters.persistent_cache_hits() - hits0
                self.obs["first_step_compiles"] = \
                    counters.xla_compiles() - compiles0
                grad_norms = self._first_gradient_norms()
            if i == n_cmp:      # the next step donates this state
                change_norms = self._change_norms()
        self.readings = {"losses": losses[:n_cmp], "grad_norms": grad_norms,
                         "change_norms": change_norms}
        self.obs["attention_backend"] = \
            F.last_attention_dispatch().get("backend")
        ctx.say(f"set-up: {t_built - ctx.t0:.2f} s to the step built, "
                f"{time.perf_counter() - ctx.t0:.2f} s to the window")
        ctx.say(f"warm-up losses {losses}; first step "
                f"{self.obs['first_step_s']:.2f} s with "
                f"{self.obs['first_step_cache_hits']} persistent-cache hits "
                f"and {self.obs['first_step_compiles']} compiles; attention "
                f"backend {self.obs['attention_backend']}")
        return self

    def window(self):
        """The measured window, on the object that ``warm_up`` drove."""
        import jax

        from paddle_tpu.compilation import counters

        ctx, tr = self.ctx, self.traffic
        seconds = float(ctx.seconds)
        if ctx.trace:
            seconds = min(seconds, float(tr["trace_seconds"]))
            jax.profiler.start_trace(ctx.trace_dir)
        log_every = int(tr["log_every"])
        tokens_per_step = int(tr["batch"]) * int(tr["seq"])
        del self.spans[:]
        compiles0 = counters.xla_compiles()
        steps = 0
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            t_start = time.perf_counter()
            while True:
                loss = self._one_step()
                steps += 1
                if steps % log_every == 0:
                    last = self._fetch(loss)
                    if time.perf_counter() - t_start >= seconds:
                        break
            t_end = time.perf_counter()
        if ctx.trace:
            jax.profiler.stop_trace()
        window = t_end - t_start
        self.attempted = steps
        self.failed = 0 if np.isfinite(last) else steps
        self.end_to_end = {
            "train_tokens_per_s": steps * tokens_per_step / window,
            "setup_s": t_start - ctx.t0}
        self.obs.update(
            steps=steps, window_s=window, tokens=steps * tokens_per_step,
            compiles_in_window=counters.xla_compiles() - compiles0,
            traces_of_step=self.step._trace_count,
            spans=list(self.spans), last_loss=last,
            step_flops=work.gpt_train_flops(
                self.arch, int(tr["batch"]), int(tr["seq"])))
        ctx.say(f"window {window:.3f} s, {steps} steps, last loss {last}, "
                f"{self.obs['compiles_in_window']} compiles in the window, "
                f"{self.obs['traces_of_step']} trace(s) of the step")
        return self

    # ------------------------------------------------------------ correct
    def release(self):
        """Drop the program's state, so that the reference has the chip."""
        self.step = self.model = self.opt = self.batches = None
        gc.collect()

    def check(self) -> list:
        """[(name, value, limit)]: the program's first steps against the
        reference's, which is computed now, from the seed alone."""
        self.release()
        tr = self.traffic
        fresh = traffic_mod.token_batches(tr, self.arch["vocab_size"],
                                          self.ctx.seed)
        batches = [next(fresh) for _ in range(int(tr["compare_steps"]))]
        t0 = time.perf_counter()
        ref = self.reference.train_readings(self.arch, self.job,
                                            self.ctx.seed, batches)
        self.obs["reference_s"] = time.perf_counter() - t0
        self.ctx.say(f"reference {self.obs['reference_s']:.1f} s; losses "
                     f"{ref['losses']}")
        numbers = compare(self.readings, ref, self.ctx.say)
        numbers["attention_backend_differs"] = float(
            self.obs["attention_backend"] != self.job["attention_backend"])
        # a number is held to a limit only where the cell's file sets one:
        # one whose readings gave no upper end (PERF.md section 2) is read
        # and said, and decides nothing
        limits = self.ctx.cell["limits"]
        unknown = sorted(set(limits) - set(numbers))
        if unknown or len(limits) < 2:
            raise ValueError(f"limits of {self.ctx.cell['name']}: want two "
                             f"or more of {sorted(numbers)}, got {unknown}")
        for n, v in numbers.items():
            if n not in limits:
                self.ctx.say(f"read {n}: {v:.6g} (not compared)")
        return [(n, v, float(limits[n])) for n, v in numbers.items()
                if n in limits]


def _flat(norms: dict):
    """Every leaf's norms in one row, and what each entry is."""
    names = sorted(norms)
    values = [np.asarray(norms[n], np.float64) for n in names]
    labels = [f"{n}{list(i)}" for n, v in zip(names, values)
              for i in np.ndindex(v.shape)]
    return np.concatenate([v.ravel() for v in values]), labels


def worst_leaf_gap(got: dict, ref: dict, keep=None) -> tuple:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger, and which leaf that is. Block
    leaves count a layer each, and fused leaves a part each."""
    r, labels = _flat(ref)
    g, _ = _flat({n: got[n] for n in ref})
    if keep is None:
        keep = np.ones(len(r), bool)
    gap = np.where(keep, np.abs(g - r) / np.maximum(r, np.median(r[keep])),
                   0.0)
    if not np.all(np.isfinite(gap)):
        return float("inf"), "not finite"
    worst = int(np.argmax(gap))
    return float(gap[worst]), labels[worst]


def compare(got: dict, ref: dict, say=None) -> dict:
    """The numbers `correct` holds, each a gap that is 0 for a program
    that follows the reference exactly."""
    out = {}
    for i, (a, b) in enumerate(zip(got["losses"], ref["losses"])):
        gap = abs(a - b)
        out[f"loss_gap_step{i + 1}"] = gap if np.isfinite(gap) \
            else float("inf")
    out["grad_norm_gap"], at_grad = worst_leaf_gap(got["grad_norms"],
                                                   ref["grad_norms"])
    # leaves whose first gradient is nought to rounding in the reference
    # move under Adam by round-off alone: out, by a rule on the gradient
    g, _ = _flat(ref["grad_norms"])
    keep = g >= 1e-3 * np.median(g)
    out["change_norm_gap"], at_change = worst_leaf_gap(
        got["change_norms"], ref["change_norms"], keep)
    if say is not None:
        say(f"widest gaps at: gradient {at_grad}, change {at_change}; "
            f"{int(np.sum(~keep))} of {len(keep)} leaves without a gradient "
            "left out of the change")
    return out


def start(ctx) -> Session:
    return Session(ctx).warm_up().window()
