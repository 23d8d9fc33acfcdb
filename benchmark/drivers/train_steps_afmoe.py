"""Driver ``train_steps_afmoe``: ``train_steps``' training loop for the
afmoe family.

The system under test is ``AfmoeForCausalLM`` + ``model.make_loss_fn()`` +
``AdamW`` + ``jit.TrainStep``. What is general comes from
``benchmark/drivers/train_steps.py`` unchanged (the spans, the one call and
feed of warm-up and window, the warm-up's readings, the gaps of
``compare``); what that file writes round the GPT family is this file's
own: the model and the layout of its parameters, the norms by leaf, the
counts of needed work in the window, and a comparison that also holds the
first step's counts of tokens by expert.

Besides ``train_steps``' observations it leaves for the readers:
``op_scopes`` (``TrainStep.op_scopes()``, in a traced run),
``expert_load`` ([expert layers, published experts] at the window's last
fetch), ``landed_by_layer`` (the assignments that landed on the held
experts of each expert layer, a step, as the mean over the window's steps:
the ``expert_load_total`` buffers after the window minus before it) and
``moe_dispatch``.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import traffic as traffic_mod
from benchmark import work_afmoe
from benchmark.drivers import train_steps as base
from benchmark.trace import WINDOW_SPAN

# canonical leaf -> the program's name for it inside one block
_COMMON = {"ln_in_g": "input_layernorm.weight",
           "ln_post_attn_g": "post_attention_layernorm.weight",
           "ln_pre_mlp_g": "pre_mlp_layernorm.weight",
           "ln_post_mlp_g": "post_mlp_layernorm.weight",
           "q_w": "attn.q_proj.weight", "k_w": "attn.k_proj.weight",
           "v_w": "attn.v_proj.weight", "g_w": "attn.gate_proj.weight",
           "o_w": "attn.o_proj.weight", "q_norm_g": "attn.q_norm.weight",
           "k_norm_g": "attn.k_norm.weight"}
_DENSE = {"mlp_w1": "mlp.gate_proj.weight", "mlp_w3": "mlp.up_proj.weight",
          "mlp_w2": "mlp.down_proj.weight"}
_MOE = {"router_w": "mlp.router.weight", "exp_w1": "mlp.experts.w1",
        "exp_w3": "mlp.experts.w3", "exp_w2": "mlp.experts.w2",
        "sh_w1": "mlp.shared_expert.gate_proj.weight",
        "sh_w3": "mlp.shared_expert.up_proj.weight",
        "sh_w2": "mlp.shared_expert.down_proj.weight"}
_TOP = {"wte": "model.embed_tokens.weight", "lnf_g": "model.norm.weight",
        "head_w": "lm_head.weight"}


def program_layout(arch: dict) -> dict:
    """The program's parameter name -> (canonical leaf, its place in the
    leaf's stack or None)."""
    out = {prog: (leaf, None) for leaf, prog in _TOP.items()}
    dense = int(arch["num_dense_layers"])
    for i in range(int(arch["num_hidden_layers"])):
        for leaf, prog in _COMMON.items():
            out[f"model.block_{i}.{prog}"] = (leaf, i)
        for leaf, prog in (_DENSE if i < dense else _MOE).items():
            out[f"model.block_{i}.{prog}"] = (leaf, i if i < dense
                                              else i - dense)
    return out


def _by_leaf(per_param: dict, layout: dict) -> dict:
    """{program name: [parts]} -> {canonical leaf: [parts] or [stack, parts]}."""
    out: dict = {}
    for prog, (leaf, at) in layout.items():
        v = np.asarray(per_param[prog], np.float64)
        if at is None:
            out[leaf] = v
        else:
            out.setdefault(leaf, {})[at] = v
    return {leaf: v if isinstance(v, np.ndarray)
            else np.stack([v[i] for i in range(len(v))])
            for leaf, v in out.items()}


def load_gap(got, ref) -> float:
    """The share of an expert layer's assignments that the program landed
    on another expert than the reference did, as far as the counts by
    expert [layers, experts] show it; the worst layer's."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.sum(np.abs(got - ref), axis=-1)
                        / (2.0 * np.sum(ref, axis=-1))))


class Session(base.Session):
    """``train_steps.Session`` with the afmoe family's model, layout,
    work counts and comparison."""

    # ------------------------------------------------------------ set-up
    def _build(self):
        import jax
        import jax.numpy as jnp

        import paddle_tpu as paddle
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.jit.functional import load_state
        from paddle_tpu.models import AfmoeConfig, AfmoeForCausalLM

        arch, job, ref = self.arch, self.job, self.reference
        self.paddle = paddle
        if job["scan_layers"] or job["compute_dtype"] != "bfloat16":
            raise ValueError("this driver unrolls the layers and casts the "
                             "model with .bfloat16()")
        cfg = AfmoeConfig(
            vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
            intermediate_size=arch["intermediate_size"],
            moe_intermediate_size=arch["moe_intermediate_size"],
            num_hidden_layers=arch["num_hidden_layers"],
            num_dense_layers=arch["num_dense_layers"],
            num_attention_heads=arch["num_attention_heads"],
            num_key_value_heads=arch["num_key_value_heads"],
            head_dim=arch["head_dim"], layer_types=ref.layer_kinds(arch),
            sliding_window=arch["sliding_window"],
            rope_theta=arch["rope_theta"], rms_norm_eps=arch["rms_norm_eps"],
            num_experts=arch["num_experts_published"],
            experts_held=arch["num_experts"],
            expert_offset=arch["expert_offset"],
            num_experts_per_tok=arch["num_experts_per_tok"],
            num_shared_experts=arch["num_shared_experts"],
            route_norm=arch["route_norm"], route_scale=arch["route_scale"],
            load_balance_coeff=arch["load_balance_coeff"],
            mup_enabled=arch["mup_enabled"],
            initializer_range=arch["initializer_range"],
            max_seq_len=arch["max_position_embeddings"],
            recompute=job["recompute"],
            recompute_policy=job["recompute_policy"],
            fused_loss_chunk=job["fused_loss_chunk"])
        with paddle.LazyGuard():
            model = AfmoeForCausalLM(cfg)
        model.bfloat16()
        self.layout = layout = program_layout(arch)
        want = {n: tuple(p.shape) for n, p in model.named_parameters()}

        def make(key):
            leaves = ref.canonical_weights(arch, key, jnp.bfloat16)
            return {prog: leaves[leaf] if at is None else leaves[leaf][at]
                    for prog, (leaf, at) in layout.items()}

        # every weight in one jitted call on the device, in the type the
        # job trains in
        params = jax.jit(make)(ref.seed_key(self.ctx.seed))
        got = {n: tuple(v.shape) for n, v in params.items()}
        if got != want:
            raise ValueError("the layout does not cover the program's "
                             f"parameters: {set(got.items()) ^ set(want.items())}")
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

    # ------------------------------------------------- what is compared
    def _leaf_norms(self, values: dict, minus: dict = None) -> dict:
        import jax
        import jax.numpy as jnp
        leaf_norms, layout = self.reference.leaf_norms, self.layout
        z = self.reference.sizes(self.arch)
        held = (z["offset"], z["held"])

        @jax.jit
        def norms(values, minus):
            out = {}
            for n, v in values.items():
                v = v.astype(jnp.float32)
                if minus is not None:
                    v = v - minus[n].astype(jnp.float32)
                out[n] = leaf_norms(v, layout[n][0], held=held)
            return out
        return _by_leaf(jax.device_get(norms(values, minus)), layout)

    def _expert_load(self, buffer: str = "expert_load") -> np.ndarray:
        """[expert layers, published experts]: the live step's
        ``expert_load`` buffers (the last step's counts), or its
        ``expert_load_total`` (every step's so far), fetched."""
        import jax
        bufs = self.step.buffers
        return np.stack(jax.device_get(
            [bufs[n] for n in sorted(bufs) if n.endswith("." + buffer)]))

    def _first_gradient_norms(self) -> dict:
        # the warm-up calls this once, when step 1 has been fetched
        self._load_step1 = self._expert_load()
        return super()._first_gradient_norms()

    def warm_up(self):
        super().warm_up()
        self.readings["expert_load"] = self._load_step1
        return self

    # ------------------------------------------------------------ the run
    def window(self):
        """The measured window, on the object that ``warm_up`` drove:
        ``train_steps``' loop and clock, this family's counts of work."""
        import jax

        from paddle_tpu.compilation import counters
        from paddle_tpu.distributed.moe import last_moe_dispatch

        ctx, tr = self.ctx, self.traffic
        seconds = float(ctx.seconds)
        if ctx.trace:
            seconds = min(seconds, float(tr["trace_seconds"]))
            jax.profiler.start_trace(ctx.trace_dir)
        log_every = int(tr["log_every"])
        batch, seq = int(tr["batch"]), int(tr["seq"])
        del self.spans[:]
        compiles0 = counters.xla_compiles()
        total0 = self._expert_load("expert_load_total")
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
            "train_tokens_per_s": steps * batch * seq / window,
            "setup_s": t_start - ctx.t0}
        load = self._expert_load()
        z = self.reference.sizes(self.arch)
        held = slice(z["offset"], z["offset"] + z["held"])
        # what the window's steps landed here, a step; the needed work
        # grows no faster than the rows, so the mean counts none too much
        routed = self._expert_load("expert_load_total") - total0
        landed = routed[:, held].sum(axis=-1) / steps
        last_step = load[:, held].sum(axis=-1)
        self.obs.update(
            steps=steps, window_s=window, tokens=steps * batch * seq,
            compiles_in_window=counters.xla_compiles() - compiles0,
            traces_of_step=self.step._trace_count,
            spans=list(self.spans), last_loss=last,
            expert_load=load, landed_by_layer=[float(v) for v in landed],
            moe_dispatch=last_moe_dispatch(),
            step_flops=work_afmoe.afmoe_train_flops(
                self.arch, batch, seq, landed))
        if ctx.trace:       # one trace and one cache load of the step
            self.obs["op_scopes"] = self.step.op_scopes()
        ctx.say(f"window {window:.3f} s, {steps} steps, last loss {last}, "
                f"{self.obs['compiles_in_window']} compiles in the window, "
                f"{self.obs['traces_of_step']} trace(s) of the step; "
                f"assignments landed by layer, a step over the window "
                f"{[round(v, 1) for v in self.obs['landed_by_layer']]}, at "
                f"its last step {[float(v) for v in last_step]}; "
                f"expert layer {self.obs['moe_dispatch']}")
        return self

    # ------------------------------------------------------------ correct
    def check(self) -> list:
        """``train_steps``' comparison, and the first step's counts of
        tokens by expert against the reference's."""
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


def compare(got: dict, ref: dict, say=None) -> dict:
    """``train_steps.compare``'s gaps, with the routers' gradients apart
    from every other leaf's, and ``expert_load_gap``. A token whose
    near-tied choice bf16 flips moves its router's gradient by a whole
    assignment (PERF.md section 2): among all leaves the routers read
    widest and least steadily, and would hide what the others show. A
    router counts two parts (``reference.leaf_norms``): the columns of
    the experts held here and of those held elsewhere."""
    out = base.compare(got, ref, say)
    _, labels = base._flat(ref["grad_norms"])
    router = np.array([l.startswith("router_w") for l in labels])
    out["grad_norm_gap"], at = base.worst_leaf_gap(
        got["grad_norms"], ref["grad_norms"], ~router)
    out["router_grad_norm_gap"], at_router = base.worst_leaf_gap(
        got["grad_norms"], ref["grad_norms"], router)
    if say is not None:
        say(f"widest gradient gap outside the routers at: {at}; among the "
            f"routers ([layer, held here or elsewhere]) at: {at_router}")
    out["expert_load_gap"] = load_gap(got["expert_load"], ref["expert_load"])
    return out


def start(ctx) -> Session:
    return Session(ctx).warm_up().window()
