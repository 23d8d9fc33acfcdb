"""Driver ``train_steps_smallthinker``: ``train_steps``' training loop for
the SmallThinker family (window and NoPE full grouped attention from
head-major projections, ReLU-gated token-choice experts routed on the
block's input).

The system under test is ``SmallThinkerForCausalLM`` +
``model.make_loss_fn()`` + ``AdamW`` + ``jit.TrainStep``. Everything that is
general comes from ``benchmark/drivers/train_steps.py`` and, for a family
with an expert layer (the norms by leaf with the routers in two parts, the
counts of tokens by expert, the comparison that holds them), from
``benchmark/drivers/train_steps_afmoe.py``, both unchanged; this file's own
are the model and the layout of its parameters, and the counts of needed
work in the window.

It leaves for the readers what the afmoe driver leaves, under the same
names: ``op_scopes`` (in a traced run), ``expert_load``,
``landed_by_layer``, ``moe_dispatch`` (with ``activation``, ``score`` and
``router_input``) and ``step_flops``; and ``attention_dispatch``
(``F.last_attention_dispatch()``: the kernel, its blocks, ``layout``,
``window`` and ``kv_heads`` of the last layer traced).
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import traffic as traffic_mod
from benchmark import work_smallthinker
from benchmark.drivers import train_steps_afmoe as afmoe
from benchmark.drivers.train_steps_afmoe import compare  # noqa: F401
from benchmark.trace import WINDOW_SPAN

# canonical leaf -> the program's name for it inside one block
_BLOCK = {"ln1_g": "input_layernorm.weight",
          "ln2_g": "post_attention_layernorm.weight",
          "q_w": "attn.q_proj.weight", "k_w": "attn.k_proj.weight",
          "v_w": "attn.v_proj.weight", "o_w": "attn.o_proj.weight",
          "router_w": "mlp.router.weight", "exp_w1": "mlp.experts.w1",
          "exp_w3": "mlp.experts.w3", "exp_w2": "mlp.experts.w2"}


def program_layout(arch: dict) -> dict:
    """The program's parameter name -> (canonical leaf, its place in the
    leaf's stack or None)."""
    out = {prog: (leaf, None) for leaf, prog in afmoe._TOP.items()}
    for i in range(int(arch["num_hidden_layers"])):
        for leaf, prog in _BLOCK.items():
            out[f"model.block_{i}.{prog}"] = (leaf, i)
    return out


class Session(afmoe.Session):
    """The afmoe driver's session with this family's model, layout and
    work counts."""

    # ------------------------------------------------------------ set-up
    def _build(self):
        import jax
        import jax.numpy as jnp

        import paddle_tpu as paddle
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.jit.functional import load_state
        from paddle_tpu.models import (SmallThinkerConfig,
                                       SmallThinkerForCausalLM)

        arch, job, ref = self.arch, self.job, self.reference
        self.paddle = paddle
        if job["scan_layers"] or job["compute_dtype"] != "bfloat16":
            raise ValueError("this driver unrolls the layers and casts the "
                             "model with .bfloat16()")
        layouts = ref.layer_layouts(arch)
        cfg = SmallThinkerConfig(
            vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
            num_hidden_layers=arch["num_hidden_layers"],
            num_attention_heads=arch["num_attention_heads"],
            num_key_value_heads=arch["num_key_value_heads"],
            head_dim=arch["head_dim"],
            rope_layout=[r for r, _ in layouts],
            sliding_window_layout=[w for _, w in layouts],
            sliding_window_size=arch["sliding_window_size"],
            rope_theta=arch["rope_theta"], rms_norm_eps=arch["rms_norm_eps"],
            moe_ffn_hidden_size=arch["moe_ffn_hidden_size"],
            moe_num_primary_experts=arch["moe_num_primary_experts_published"],
            experts_held=arch["moe_num_primary_experts"],
            expert_offset=arch["expert_offset"],
            moe_num_active_primary_experts=arch[
                "moe_num_active_primary_experts"],
            moe_primary_router_apply_softmax=arch[
                "moe_primary_router_apply_softmax"],
            norm_topk_prob=arch["norm_topk_prob"],
            initializer_range=arch["initializer_range"],
            max_position_embeddings=arch["max_position_embeddings"],
            recompute=job["recompute"],
            recompute_policy=job["recompute_policy"],
            fused_loss_chunk=job["fused_loss_chunk"])
        with paddle.LazyGuard():
            model = SmallThinkerForCausalLM(cfg)
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

    # ------------------------------------------------------------ the run
    def window(self):
        """The measured window, on the object that ``warm_up`` drove:
        ``train_steps``' loop and clock, this family's counts of work."""
        import jax

        import paddle_tpu.nn.functional as F
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
        self.obs.update(
            steps=steps, window_s=window, tokens=steps * batch * seq,
            compiles_in_window=counters.xla_compiles() - compiles0,
            traces_of_step=self.step._trace_count,
            spans=list(self.spans), last_loss=last,
            expert_load=load, landed_by_layer=[float(v) for v in landed],
            moe_dispatch=last_moe_dispatch(),
            attention_dispatch=F.last_attention_dispatch(),
            step_flops=work_smallthinker.train_flops(
                self.arch, batch, seq, landed))
        if ctx.trace:       # one trace and one cache load of the step
            self.obs["op_scopes"] = self.step.op_scopes()
        ctx.say(f"window {window:.3f} s, {steps} steps, last loss {last}, "
                f"{self.obs['compiles_in_window']} compiles in the window, "
                f"{self.obs['traces_of_step']} trace(s) of the step; "
                f"assignments landed by layer, a step over the window "
                f"{[round(v, 1) for v in self.obs['landed_by_layer']]}; "
                f"expert layer {self.obs['moe_dispatch']}; attention "
                f"{self.obs['attention_dispatch']}")
        return self


def start(ctx) -> Session:
    return Session(ctx).warm_up().window()
