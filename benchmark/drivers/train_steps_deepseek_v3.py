"""Driver ``train_steps_deepseek_v3``: ``train_steps``' training loop for
the deepseek_v3 family (latent attention, token-choice experts).

The system under test is ``DeepseekV3ForCausalLM`` + ``model.make_loss_fn()``
+ ``AdamW`` + ``jit.TrainStep``. Everything that is general comes from
``benchmark/drivers/train_steps.py`` and, for a family with an expert
layer (the norms by leaf with the routers in two parts, the counts of
tokens by expert, the comparison that holds them), from
``benchmark/drivers/train_steps_afmoe.py``, both unchanged; this file's own
are the model and the layout of its parameters, and the counts of needed
work in the window.

It leaves for the readers what the afmoe driver leaves, under the same
names: ``op_scopes`` (in a traced run), ``expert_load``,
``landed_by_layer``, ``moe_dispatch`` and ``step_flops``; and
``attention_dispatch`` (``F.last_attention_dispatch()``: the kernel, its
blocks, ``head_dim_qk`` and ``head_dim_v``).
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import traffic as traffic_mod
from benchmark import work_deepseek_v3
from benchmark.drivers import train_steps_afmoe as afmoe
from benchmark.drivers.train_steps_afmoe import compare  # noqa: F401
from benchmark.trace import WINDOW_SPAN

# canonical leaf -> the program's name for it inside one block
_COMMON = {"ln1_g": "input_layernorm.weight",
           "ln2_g": "post_attention_layernorm.weight",
           "q_w": "attn.q_proj.weight", "kva_w": "attn.kv_a_proj.weight",
           "kv_norm_g": "attn.kv_a_norm.weight",
           "kvb_w": "attn.kv_b_proj.weight", "o_w": "attn.o_proj.weight"}


def program_layout(arch: dict) -> dict:
    """The program's parameter name -> (canonical leaf, its place in the
    leaf's stack or None)."""
    out = {prog: (leaf, None) for leaf, prog in afmoe._TOP.items()}
    dense = int(arch["first_k_dense_replace"])
    for i in range(int(arch["num_hidden_layers"])):
        for leaf, prog in _COMMON.items():
            out[f"model.block_{i}.{prog}"] = (leaf, i)
        for leaf, prog in (afmoe._DENSE if i < dense
                           else afmoe._MOE).items():
            out[f"model.block_{i}.{prog}"] = (leaf, i if i < dense
                                              else i - dense)
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
        from paddle_tpu.models import DeepseekV3Config, DeepseekV3ForCausalLM

        arch, job, ref = self.arch, self.job, self.reference
        self.paddle = paddle
        if job["scan_layers"] or job["compute_dtype"] != "bfloat16":
            raise ValueError("this driver unrolls the layers and casts the "
                             "model with .bfloat16()")
        cfg = DeepseekV3Config(
            vocab_size=arch["vocab_size"], hidden_size=arch["hidden_size"],
            intermediate_size=arch["intermediate_size"],
            moe_intermediate_size=arch["moe_intermediate_size"],
            num_hidden_layers=arch["num_hidden_layers"],
            first_k_dense_replace=arch["first_k_dense_replace"],
            num_attention_heads=arch["num_attention_heads"],
            kv_lora_rank=arch["kv_lora_rank"],
            qk_nope_head_dim=arch["qk_nope_head_dim"],
            qk_rope_head_dim=arch["qk_rope_head_dim"],
            v_head_dim=arch["v_head_dim"], rope_theta=arch["rope_theta"],
            rms_norm_eps=arch["rms_norm_eps"],
            n_routed_experts=arch["n_routed_experts_published"],
            experts_held=arch["n_routed_experts"],
            expert_offset=arch["expert_offset"],
            num_experts_per_tok=arch["num_experts_per_tok"],
            n_shared_experts=arch["n_shared_experts"],
            norm_topk_prob=arch["norm_topk_prob"],
            routed_scaling_factor=arch["routed_scaling_factor"],
            bias_update_rate=arch["bias_update_rate"],
            initializer_range=arch["initializer_range"],
            max_seq_len=arch["max_position_embeddings"],
            recompute=job["recompute"],
            recompute_policy=job["recompute_policy"],
            fused_loss_chunk=job["fused_loss_chunk"])
        with paddle.LazyGuard():
            model = DeepseekV3ForCausalLM(cfg)
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
            step_flops=work_deepseek_v3.train_flops(
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
