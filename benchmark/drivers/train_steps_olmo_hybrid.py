"""Driver ``train_steps_olmo_hybrid``: ``train_steps``' training loop for
the Olmo Hybrid family (Gated DeltaNet linear attention beside full
attention without positions).

The system under test is ``OlmoHybridForCausalLM`` + ``model.make_loss_fn()``
+ ``AdamW`` + ``jit.TrainStep``. Everything that is general comes from
``benchmark/drivers/train_steps.py`` unchanged: the build's order, the
warm-up and its readings, the window, its loop and its clock, and the
comparison. This file's own are the model and the layout of its parameters
(leaves in a stack a kind of layer: ``reference/olmo_hybrid.py``), the
counts of needed work (``benchmark/work_olmo_hybrid.py``, in the place of
the GPT family's for the window's call) and one more number for
`correct`: ``linear_attention_backend_differs``.

It leaves for the readers what ``train_steps`` leaves, and ``op_scopes`` (in
a traced run), ``attention_dispatch`` and ``linear_attention_dispatch``
(``F.last_linear_attention_dispatch()``: backend, chunk, heads, dk, dv).
"""
from __future__ import annotations

from unittest import mock

from benchmark import traffic as traffic_mod
from benchmark import work_olmo_hybrid
from benchmark.drivers import train_steps as base
from benchmark.drivers.train_steps import compare  # noqa: F401
from benchmark.drivers.train_steps_afmoe import _by_leaf

# canonical leaf -> the program's name for it inside one block
_COMMON = {"ln_attn_g": "post_attention_layernorm.weight",
           "ln_mlp_g": "post_feedforward_layernorm.weight",
           "mlp_w1": "mlp.gate_proj.weight", "mlp_w3": "mlp.up_proj.weight",
           "mlp_w2": "mlp.down_proj.weight"}
_LINEAR = {"lq_w": "q_proj.weight", "lk_w": "k_proj.weight",
           "lv_w": "v_proj.weight", "lg_w": "g_proj.weight",
           "la_w": "a_proj.weight", "lb_w": "b_proj.weight",
           "lo_w": "o_proj.weight", "lq_conv": "q_conv", "lk_conv": "k_conv",
           "lv_conv": "v_conv", "A_log": "A_log", "dt_bias": "dt_bias",
           "lo_norm": "o_norm"}
_FULL = {"q_w": "q_proj.weight", "k_w": "k_proj.weight",
         "v_w": "v_proj.weight", "o_w": "o_proj.weight",
         "q_norm_g": "q_proj.norm", "k_norm_g": "k_proj.norm"}
_TOP = {"wte": "model.embed_tokens.weight", "lnf_g": "model.norm.weight",
        "head_w": "lm_head.weight"}


def program_layout(kinds) -> dict:
    """The program's parameter name -> (canonical leaf, its place in the
    leaf's stack or None), for layers of ``kinds``."""
    out = {prog: (leaf, None) for leaf, prog in _TOP.items()}
    for i, kind in enumerate(kinds):
        j = kinds[:i].count(kind)
        block = f"model.block_{i}."
        for leaf, prog in _COMMON.items():
            out[block + prog] = (leaf, i)
        own, mixer = (_LINEAR, "linear_attn.") \
            if kind == "linear_attention" else (_FULL, "attn.")
        for leaf, prog in own.items():
            out[block + mixer + prog] = (leaf, j)
    return out


class Session(base.Session):
    """``train_steps.Session`` with this family's model, layout, work
    counts and one more number for `correct`."""

    # ------------------------------------------------------------ set-up
    def _build(self):
        import jax
        import jax.numpy as jnp

        import paddle_tpu as paddle
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.jit.functional import load_state
        from paddle_tpu.models import OlmoHybridConfig, OlmoHybridForCausalLM

        arch, job, ref = self.arch, self.job, self.reference
        self.paddle = paddle
        if job["scan_layers"] or job["compute_dtype"] != "bfloat16":
            raise ValueError("this driver unrolls the layers and casts the "
                             "model with .bfloat16()")
        kinds = ref.layer_kinds(arch)
        keys = ("vocab_size", "hidden_size", "intermediate_size",
                "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "linear_num_key_heads",
                "linear_num_value_heads", "linear_key_head_dim",
                "linear_value_head_dim", "linear_conv_kernel_dim",
                "linear_allow_neg_eigval", "rms_norm_eps",
                "max_position_embeddings", "initializer_range")
        cfg = OlmoHybridConfig(
            layer_types=kinds, recompute=job["recompute"],
            recompute_policy=job["recompute_policy"],
            fused_loss_chunk=job["fused_loss_chunk"],
            **{k: arch[k] for k in keys})
        with paddle.LazyGuard():
            model = OlmoHybridForCausalLM(cfg)
        model.bfloat16()
        self.layout = layout = program_layout(kinds)
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

        @jax.jit
        def norms(values, minus):
            out = {}
            for n, v in values.items():
                v = v.astype(jnp.float32)
                if minus is not None:
                    v = v - minus[n].astype(jnp.float32)
                out[n] = leaf_norms(v, layout[n][0])
            return out
        return _by_leaf(jax.device_get(norms(values, minus)), layout)

    # ------------------------------------------------------------ the run
    def warm_up(self):
        import paddle_tpu.nn.functional as F
        super().warm_up()
        self.obs["linear_attention_backend"] = \
            F.last_linear_attention_dispatch().get("backend")
        return self

    def window(self):
        """``train_steps``' window, its step counted by this family's
        rule."""
        import paddle_tpu.nn.functional as F
        with mock.patch.object(base.work, "gpt_train_flops",
                               work_olmo_hybrid.train_flops):
            super().window()
        self.obs.update(attention_dispatch=F.last_attention_dispatch(),
                        linear_attention_dispatch=(
                            F.last_linear_attention_dispatch()))
        if self.ctx.trace:  # one trace and one cache load of the step
            self.obs["op_scopes"] = self.step.op_scopes()
        self.ctx.say(f"attention {self.obs['attention_dispatch']}; linear "
                     f"attention {self.obs['linear_attention_dispatch']}")
        return self

    # ------------------------------------------------------------ correct
    def check(self) -> list:
        """``train_steps``' comparison, and whether the delta rule ran on
        the backend the job states."""
        limits = self.ctx.cell["limits"]
        mine = {"linear_attention_backend_differs": float(
            self.obs["linear_attention_backend"]
            != self.job["linear_attention_backend"])}
        cell = self.ctx.cell
        # train_steps' check refuses a limit of a number it does not make
        self.ctx.cell = dict(cell, limits={n: v for n, v in limits.items()
                                           if n not in mine})
        try:
            out = super().check()
        finally:
            self.ctx.cell = cell
        for n, v in mine.items():
            if n in limits:
                out.append((n, v, float(limits[n])))
            else:
                self.ctx.say(f"read {n}: {v:.6g} (not compared)")
        return out


def start(ctx) -> Session:
    return Session(ctx).warm_up().window()
