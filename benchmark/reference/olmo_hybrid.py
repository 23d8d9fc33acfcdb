"""Plain reference for the Olmo Hybrid family (allenai/Olmo-Hybrid-7B): a
decoder whose layers are Gated DeltaNet linear attention or full causal
attention without positions, each in OLMo 2/3's reordered post-norm block
with a SwiGLU MLP, in straightforward float32 ``jax.numpy`` at ``highest``
matmul precision, with mean next-token cross-entropy and AdamW with
decoupled decay.

It imports nothing of the program under test and takes nothing the program
made: weights and batches come from the seed through this file,
``benchmark/reference/gpt.py`` (the seed's key, AdamW, the fp8 control's
product), ``benchmark/reference/afmoe.py`` (RMSNorm, attention a block of
queries at a time, the head's loss in blocks, the norms by leaf) and
``benchmark/traffic.py``.

The layer equations (the configuration file's ``assumed`` gives each one's
source), n = RMSNorm with eps ``rms_norm_eps``:

- ``x = wte[ids]``; final RMSNorm; untied head.
- every layer: ``h = x + n(mixer(x))``; ``y = h + n(W2(silu(h W1) * h W3))``.
- linear mixer, per head of dk (q, k) and dv (v): ``q = l2(silu(conv(x
  Wq))) / sqrt(dk)``, ``k = l2(silu(conv(x Wk)))``, ``v = silu(conv(x Wv))``,
  conv the causal depthwise convolution of ``linear_conv_kernel_dim`` taps
  (``lax.conv_general_dilated``, zero before the start), l2 over the head
  with eps 1e-6; ``beta = 2 sigmoid(x Wb)``; ``g = -exp(A_log) softplus(x
  Wa + dt_bias)``; state S [dv, dk] from 0: ``S = exp(g) S; S = S + beta
  (v - S k) k^T; o = S q``, a token at a time; out = (n_dv(o) * w_o *
  silu(x Wg)) Wo, w_o one gain of dv for every head.
- full mixer: ``q = n(x Wq)``, ``k = n(x Wk)`` over the whole projection,
  then heads of ``hidden / heads``; no positions; softmax(q k^T /
  sqrt(hd)) causal; out = ctx Wo.

Departures, as the other references make them: weights are *stored* in the
type the job states and all arithmetic is float32; one step runs layer by
layer (forward keeping each layer's input, backward re-running one layer at
a time, a row at a time, under ``jax.vjp``); the recurrence is a
``lax.scan`` over positions checkpointed every ``SCAN_BLOCK`` positions,
attention a block of queries at a time and the head a block of positions
at a time, each recomputed in the backward pass. ``half_batch`` leaves out
half of the rows, or where the batch is one row, the second half of its
positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.afmoe import (_attention_row, _rms, head_loss_sum,
                                       leaf_norms)
from benchmark.reference.gpt import HIGHEST, MATMULS, _adamw, _dot, seed_key

__all__ = ["seed_key", "canonical_weights", "init_params", "leaf_norms",
           "train_readings", "loss_whole", "sizes", "layer_kinds",
           "delta_rule", "FAULTS"]

# canonical leaves: name -> (shape in terms of the sizes, kind); a group's
# leaves are stacked over the layers of that group
_COMMON = (
    ("ln_attn_g", ("H",), "ones"), ("ln_mlp_g", ("H",), "ones"),
    ("mlp_w1", ("H", "F"), "normal"), ("mlp_w3", ("H", "F"), "normal"),
    ("mlp_w2", ("F", "H"), "normal"),
)
_LINEAR = (
    ("lq_w", ("H", "QK"), "normal"), ("lk_w", ("H", "QK"), "normal"),
    ("lv_w", ("H", "VV"), "normal"), ("lg_w", ("H", "VV"), "normal"),
    ("la_w", ("H", "lh"), "normal"), ("lb_w", ("H", "lh"), "normal"),
    ("lo_w", ("VV", "H"), "normal"),
    ("lq_conv", ("QK", "K"), "conv"), ("lk_conv", ("QK", "K"), "conv"),
    ("lv_conv", ("VV", "K"), "conv"),
    ("A_log", ("lh",), "a_log"), ("dt_bias", ("lh",), "dt_bias"),
    ("lo_norm", ("dv",), "ones"),
)
_FULL = (
    ("q_w", ("H", "Q"), "normal"), ("k_w", ("H", "KV"), "normal"),
    ("v_w", ("H", "KV"), "normal"), ("o_w", ("Q", "H"), "normal"),
    ("q_norm_g", ("Q",), "ones"), ("k_norm_g", ("KV",), "ones"),
)
_TOP = (("wte", ("V", "H"), "normal"), ("lnf_g", ("H",), "ones"),
        ("head_w", ("H", "V"), "normal"))
COMMON_NAMES = tuple(n for n, _, _ in _COMMON)
LINEAR_NAMES = tuple(n for n, _, _ in _LINEAR)
FULL_NAMES = tuple(n for n, _, _ in _FULL)
TOP_NAMES = tuple(n for n, _, _ in _TOP)
GROUP = {**{n: "L" for n in COMMON_NAMES}, **{n: "Llin" for n in LINEAR_NAMES},
         **{n: "Lfull" for n in FULL_NAMES}}
# positions a checkpointed stretch of the recurrence: its backward pass
# keeps one state a stretch and the steps of one stretch at a time
SCAN_BLOCK = 256
# queries a block of attention: [30, Q_BLOCK, 8192] float32 scores
Q_BLOCK = 256
# the frequency base of the positions the ``rope_full`` fault adds (the
# configuration has none to give)
FAULT_THETA = 10000.0
# planted faults of `correct` (``train_readings(fault=...)``)
FAULTS = ("beta_single", "no_decay", "no_l2norm", "no_conv", "rope_full",
          "state_bf16", "no_gate")


def layer_kinds(arch: dict) -> tuple:
    """"linear_attention" or "full_attention" for each layer that is run:
    ``layer_types`` is the published list, whole; ``layers_kept`` names
    the published layers a cut keeps (the first ``num_hidden_layers`` where
    it is absent)."""
    kept = arch.get("layers_kept", range(int(arch["num_hidden_layers"])))
    out = tuple(arch["layer_types"][i] for i in kept)
    if len(out) != int(arch["num_hidden_layers"]):
        raise ValueError("layers_kept must name num_hidden_layers layers")
    return out


def sizes(arch: dict) -> dict:
    kinds = layer_kinds(arch)
    nh, nkv = int(arch["num_attention_heads"]), int(arch["num_key_value_heads"])
    h = int(arch["hidden_size"])
    lh, dk = int(arch["linear_num_value_heads"]), int(arch["linear_key_head_dim"])
    dv = int(arch["linear_value_head_dim"])
    if int(arch["linear_num_key_heads"]) != lh:
        raise ValueError("this reference writes as many key as value heads")
    return {"H": h, "F": int(arch["intermediate_size"]), "nh": nh,
            "nkv": nkv, "hd": h // nh, "Q": h, "KV": nkv * (h // nh),
            "lh": lh, "dk": dk, "dv": dv, "QK": lh * dk, "VV": lh * dv,
            "K": int(arch["linear_conv_kernel_dim"]),
            "V": int(arch["vocab_size"]), "L": len(kinds),
            "Llin": kinds.count("linear_attention"),
            "Lfull": kinds.count("full_attention"), "kinds": kinds}


def settings(arch: dict, fault: str = None) -> dict:
    """What the forward pass reads besides the sizes; ``fault`` plants one
    of ``FAULTS``."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    if arch.get("rope_parameters", {}).get("rope_theta") is not None:
        raise ValueError("this reference writes full layers without "
                         "positions (rope_theta null)")
    return dict(sizes(arch), eps=float(arch["rms_norm_eps"]),
                beta_scale=1.0 if fault == "beta_single"
                else 2.0 if arch["linear_allow_neg_eigval"] else 1.0,
                fault=fault)


def leaf_shapes(arch: dict) -> dict:
    z = sizes(arch)
    out = {n: tuple(z[d] for d in dims) for n, dims, _ in _TOP}
    for group in (_COMMON, _LINEAR, _FULL):
        out.update({n: (z[GROUP[n]],) + tuple(z[d] for d in dims)
                    for n, dims, _ in group})
    return out


def canonical_weights(arch: dict, key, dtype):
    """Every leaf from the key, traceable, drawn in float32 and rounded
    once to ``dtype``: Normal(0, initializer_range) matrices, unit gains,
    convolution taps U(-1/sqrt(K), 1/sqrt(K)) (PyTorch's Conv1d start),
    and the decay's two leaves as ``fla`` starts them: ``A_log = log(A)``,
    A ~ U(0, 16); ``dt_bias = dt + log(-expm1(-dt))``, dt log-uniform on
    [1e-3, 1e-1] floored at 1e-4."""
    std = float(arch["initializer_range"])
    taps = int(arch["linear_conv_kernel_dim"])
    shapes = leaf_shapes(arch)
    kinds = {n: k for n, _, k in _TOP + _COMMON + _LINEAR + _FULL}
    out = {}
    for i, name in enumerate(sorted(shapes)):
        k, shape = jax.random.fold_in(key, i), shapes[name]
        u = jax.random.uniform(k, shape, jnp.float32)
        if kinds[name] == "normal":
            v = std * jax.random.normal(k, shape, jnp.float32)
        elif kinds[name] == "conv":
            v = (2.0 * u - 1.0) / np.sqrt(taps)
        elif kinds[name] == "a_log":
            v = jnp.log(16.0 * u)
        elif kinds[name] == "dt_bias":
            dt = jnp.maximum(jnp.exp(u * (np.log(0.1) - np.log(1e-3))
                                     + np.log(1e-3)), 1e-4)
            v = dt + jnp.log(-jnp.expm1(-dt))
        else:
            v = jnp.ones(shape, jnp.float32)
        out[name] = v.astype(dtype)
    return out


def init_params(arch: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    return jax.jit(lambda k: canonical_weights(arch, k, dtype))(
        seed_key(seed))


# ---------------------------------------------------------------- forward

def _conv(x, w):
    """Causal depthwise convolution: x [B, S, C], w [C, K]."""
    taps = w.shape[-1]
    return jax.lax.conv_general_dilated(
        x, jnp.transpose(w)[:, None, :], window_strides=(1,),
        padding=[(taps - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1], precision=HIGHEST)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, state_dtype=jnp.float32):
    """One row: q, k [S, heads, dk], v [S, heads, dv], g, beta [S, heads]
    -> o [S, heads, dv], a position at a time; the state [heads, dv, dk]
    rounded to ``state_dtype`` after every position."""
    s, heads, dk = q.shape
    dv = v.shape[-1]
    blk = min(SCAN_BLOCK, s)
    if s % blk:
        raise ValueError(f"sequence {s} is no multiple of {blk}")

    def step(st, x):
        qt, kt, vt, gt, bt = x
        st = jnp.exp(gt)[:, None, None] * st
        err = vt - jnp.einsum("hvk,hk->hv", st, kt, precision=HIGHEST)
        st = st + bt[:, None, None] * err[:, :, None] * kt[:, None, :]
        st = st.astype(state_dtype).astype(jnp.float32)
        return st, jnp.einsum("hvk,hk->hv", st, qt, precision=HIGHEST)

    @jax.checkpoint
    def stretch(st, xs):
        return jax.lax.scan(step, st, xs)

    cut = lambda a: a.reshape((s // blk, blk) + a.shape[1:])
    _, o = jax.lax.scan(stretch, jnp.zeros((heads, dv, dk), jnp.float32),
                        tuple(cut(a) for a in (q, k, v, g, beta)))
    return o.reshape(s, heads, dv)


def _rope(x, theta):
    """x [B, S, heads, hd], half-split."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def linear_forward(p, x, cfg, mm):
    b, s, _ = x.shape
    lh, dk, dv, fault = cfg["lh"], cfg["dk"], cfg["dv"], cfg["fault"]

    def branch(w, conv):
        y = mm(x, w)
        return y if fault == "no_conv" else jax.nn.silu(_conv(y, conv))

    q = branch(p["lq_w"], p["lq_conv"]).reshape(b, s, lh, dk)
    k = branch(p["lk_w"], p["lk_conv"]).reshape(b, s, lh, dk)
    v = branch(p["lv_w"], p["lv_conv"]).reshape(b, s, lh, dv)
    if fault != "no_l2norm":
        q, k = _l2(q), _l2(k)
    q = q / np.sqrt(dk)
    beta = cfg["beta_scale"] * jax.nn.sigmoid(mm(x, p["lb_w"]))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(mm(x, p["la_w"])
                                               + p["dt_bias"])
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    state = jnp.bfloat16 if fault == "state_bf16" else jnp.float32
    o = jnp.stack([delta_rule(q[r], k[r], v[r], g[r], beta[r], state)
                   for r in range(b)])
    o = _rms(o, p["lo_norm"], cfg["eps"])
    if fault != "no_gate":
        o = o * jax.nn.silu(mm(x, p["lg_w"]).reshape(b, s, lh, dv))
    return mm(o.reshape(b, s, lh * dv), p["lo_w"])


def full_forward(p, x, cfg, mm):
    b, s, _ = x.shape
    nh, nkv, hd = cfg["nh"], cfg["nkv"], cfg["hd"]
    q = _rms(mm(x, p["q_w"]), p["q_norm_g"], cfg["eps"]).reshape(b, s, nh, hd)
    k = _rms(mm(x, p["k_w"]), p["k_norm_g"], cfg["eps"]).reshape(b, s, nkv, hd)
    v = mm(x, p["v_w"]).reshape(b, s, nkv, hd)
    if cfg["fault"] == "rope_full":
        q, k = _rope(q, FAULT_THETA), _rope(k, FAULT_THETA)
    q = q.reshape(b, s, nkv, nh // nkv, hd)
    ctx = jnp.stack([_attention_row(q[r], k[r], v[r], None, Q_BLOCK)
                     for r in range(b)])
    return mm(ctx.reshape(b, s, nh * hd), p["o_w"])


def layer_forward(p, x, kind, cfg, mm):
    """One layer; p holds its leaves in float32."""
    eps = cfg["eps"]
    mixer = linear_forward if kind == "linear_attention" else full_forward
    h = x + _rms(mixer(p, x, cfg, mm), p["ln_attn_g"], eps)
    y = mm(jax.nn.silu(mm(h, p["mlp_w1"])) * mm(h, p["mlp_w3"]), p["mlp_w2"])
    return h + _rms(y, p["ln_mlp_g"], eps)


def layer_params(w: dict, i: int, cfg) -> dict:
    """Layer i's leaves out of the stacks."""
    kinds = cfg["kinds"]
    j = kinds[:i].count(kinds[i])       # its place among its kind
    own = LINEAR_NAMES if kinds[i] == "linear_attention" else FULL_NAMES
    return {**{n: w[n][i] for n in COMMON_NAMES},
            **{n: w[n][j] for n in own}}


def loss_whole(params: dict, ids, arch: dict, mm=_dot, fault: str = None):
    """The whole model's mean loss in one expression (tests hold the
    layer-by-layer step below to ``jax.grad`` of this)."""
    cfg = settings(arch, fault)
    w = {n: v.astype(jnp.float32) for n, v in params.items()}
    x = w["wte"][ids]
    for i, kind in enumerate(cfg["kinds"]):
        x = layer_forward(layer_params(w, i, cfg), x, kind, cfg, mm)
    total = head_loss_sum(w["lnf_g"], w["head_w"], x, ids, cfg, mm)
    return total / (ids.shape[0] * (ids.shape[1] - 1))


# ---------------------------------------------------------------- one step

class Trainer:
    """The reference's training state and its layer-by-layer step
    (``reference/smallthinker.py``'s, for two kinds of layer whose leaves
    lie in stacks of their own beside the stacks every layer has)."""

    def __init__(self, arch: dict, job: dict, seed: int,
                 precision: str = "reference", fault: str = None):
        self.arch, self.cfg = arch, settings(arch, fault)
        self.opt = {k: float(job[k]) for k in
                    ("learning_rate", "beta1", "beta2", "epsilon",
                     "weight_decay")}
        self.compute_dtype = jnp.dtype(job["compute_dtype"])
        self.param_dtype = jnp.dtype(
            "float32" if job["master_weights"] else job["compute_dtype"])
        self.seed, self.mm = seed, MATMULS[precision]
        self._make = jax.jit(functools.partial(
            canonical_weights, arch, dtype=self.compute_dtype))
        w = self._make(seed_key(seed))
        self.params = {n: v.astype(self.param_dtype) for n, v in w.items()}
        # Adam's moments live on the host between updates: on the device
        # they are 7.4 GB at the cell's size, and a linear layer's backward
        # pass wants 6.7 GB of temporaries beside the weights
        self.m = {n: np.zeros(v.shape, np.float32)
                  for n, v in self.params.items()}
        self.v = {n: np.zeros(v.shape, np.float32)
                  for n, v in self.params.items()}
        self.t = 0
        self._build()

    def _build(self):
        cfg, mm, opt = self.cfg, self.mm, self.opt
        cd, pd = self.compute_dtype, self.param_dtype

        def cast(x):
            return x.astype(cd).astype(jnp.float32)

        # a program a kind of layer, the layer's place in the stacks an
        # argument
        def layer(stacks, i, j, kind):
            own = LINEAR_NAMES if kind == "linear_attention" else FULL_NAMES
            take = lambda n, at: cast(jax.lax.dynamic_index_in_dim(
                stacks[n], at, 0, keepdims=False))
            return {**{n: take(n, i) for n in COMMON_NAMES},
                    **{n: take(n, j) for n in own}}

        @jax.jit
        def embed(wte, ids):
            return cast(wte)[ids]

        @functools.partial(jax.jit, static_argnums=(4,))
        def fwd(stacks, i, j, x, kind):
            return layer_forward(layer(stacks, i, j, kind), x, kind, cfg, mm)

        @functools.partial(jax.jit, static_argnums=(5,))
        def bwd(stacks, i, j, x, dy, kind):
            _, pull = jax.vjp(
                lambda p, x_: layer_forward(p, x_, kind, cfg, mm),
                layer(stacks, i, j, kind), x)
            return pull(dy)

        @jax.jit
        def head(lnf_g, head_w, x, ids):
            f = lambda g, w, x_: head_loss_sum(g, w, x_, ids, cfg, mm)
            return jax.value_and_grad(f, argnums=(0, 1, 2))(
                cast(lnf_g), cast(head_w), x)

        @jax.jit
        def embed_grad(dx0, ids):
            dwte = jnp.zeros((cfg["V"], dx0.shape[-1]), jnp.float32)
            return dwte.at[ids].add(dx0)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def update_at(stacks, m, v, grads, at, t):
            """The leaves in ``grads`` (one layer's), each at its own
            place ``at[name]`` of its stack; ``m`` and ``v`` that layer's
            moments. Returns the stacks, the new moments and the norms."""
            norms = {}
            for n, g in grads.items():
                old = jax.lax.dynamic_index_in_dim(stacks[n], at[n], 0, False)
                p2, m[n], v[n] = _adamw(old.astype(jnp.float32), g, m[n],
                                        v[n], t, opt)
                stacks[n] = jax.lax.dynamic_update_index_in_dim(
                    stacks[n], p2.astype(stacks[n].dtype), at[n], 0)
                norms[n] = leaf_norms(g, n)
            return stacks, m, v, norms

        @functools.partial(jax.jit, donate_argnums=(0,))
        def update_top(tops, m, v, grads, t):
            norms = {}
            for n in TOP_NAMES:
                p2, m[n], v[n] = _adamw(tops[n].astype(jnp.float32),
                                        grads[n], m[n], v[n], t, opt)
                tops[n] = p2.astype(pd)
                norms[n] = leaf_norms(grads[n], n)
            return tops, m, v, norms

        self._embed, self._fwd, self._bwd, self._head = embed, fwd, bwd, head
        self._add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                            donate_argnums=0)
        self._embed_grad = embed_grad
        self._update_at, self._update_top = update_at, update_top

    def _split(self, tree):
        return ({n: v for n, v in tree.items() if n not in TOP_NAMES},
                {n: tree[n] for n in TOP_NAMES})

    def step(self, ids, half_batch: bool = False):
        """One optimizer step on the token ids [B, S]. Returns the loss and
        each leaf's gradient norms (stacked leaves: [layers of the stack,
        1])."""
        ids = jnp.asarray(np.asarray(ids), jnp.int32)
        if half_batch:
            ids = ids[: ids.shape[0] // 2] if ids.shape[0] > 1 \
                else ids[:, : ids.shape[1] // 2]
        cfg = self.cfg
        kinds = cfg["kinds"]
        places = [(i, kinds[:i].count(kinds[i])) for i in range(cfg["L"])]
        stacks, tops = self._split(self.params)
        self.params = None                         # donated below
        self.t += 1
        t = jnp.float32(self.t)
        n_tok = ids.shape[0] * (ids.shape[1] - 1)

        xs = [self._embed(tops["wte"], ids)]
        for i, j in places:
            xs.append(self._fwd(stacks, jnp.int32(i), jnp.int32(j), xs[-1],
                                kinds[i]))
        x_last = xs.pop()
        total, g_top, dxs = 0.0, None, []
        for lo in range(ids.shape[0]):      # a row at a time
            val, (dg, dw, dx) = self._head(tops["lnf_g"], tops["head_w"],
                                           x_last[lo:lo + 1],
                                           ids[lo:lo + 1])
            total = total + val
            g = {"lnf_g": dg, "head_w": dw}
            g_top = g if g_top is None else self._add(g_top, g)
            dxs.append(dx)
        del x_last
        dx = jnp.concatenate(dxs) / n_tok
        del dxs
        g_top = {n: g / n_tok for n, g in g_top.items()}
        loss = total / n_tok

        norms = {n: [None] * cfg[GROUP[n]] for n in GROUP}
        for i, j in reversed(places):
            x_in, dp, dx_in = xs.pop(), None, []
            for lo in range(ids.shape[0]):
                dp_r, dx_r = self._bwd(stacks, jnp.int32(i), jnp.int32(j),
                                       x_in[lo:lo + 1], dx[lo:lo + 1],
                                       kinds[i])
                dp = dp_r if dp is None else self._add(dp, dp_r)
                dx_in.append(dx_r)
            dx = jnp.concatenate(dx_in)
            del x_in, dx_in
            at = {n: i if n in COMMON_NAMES else j for n in dp}
            stacks, m, v, nrm = self._update_at(
                stacks, {n: self.m[n][at[n]] for n in dp},
                {n: self.v[n][at[n]] for n in dp}, dp,
                {n: jnp.int32(a) for n, a in at.items()}, t)
            for n in dp:
                self.m[n][at[n]], self.v[n][at[n]] = np.asarray(m[n]), \
                    np.asarray(v[n])
                norms[n][at[n]] = nrm[n]
        g_top["wte"] = self._embed_grad(dx, ids)
        tops, m, v, nrm_top = self._update_top(
            tops, {n: self.m[n] for n in TOP_NAMES},
            {n: self.v[n] for n in TOP_NAMES}, g_top, t)
        for n in TOP_NAMES:
            self.m[n], self.v[n] = np.asarray(m[n]), np.asarray(v[n])

        self.params = {**stacks, **tops}
        out = {n: np.asarray(jnp.stack(v)) for n, v in norms.items()}
        out.update({n: np.asarray(v) for n, v in nrm_top.items()})
        return float(loss), out

    def change_norms(self) -> dict:
        """Each leaf's norms of (stored value now - value at the start),
        as ``leaf_norms`` gives them."""
        start = self._make(seed_key(self.seed))

        @functools.partial(jax.jit, static_argnums=(2, 3))
        def gap(now, then, name, per_layer):
            return leaf_norms(now.astype(jnp.float32)
                              - then.astype(jnp.float32), name, per_layer)

        return {n: np.asarray(gap(self.params[n], start[n], n,
                                  n not in TOP_NAMES))
                for n in self.params}


def train_readings(arch: dict, job: dict, seed: int, batches,
                   precision: str = "reference", half_batch: bool = False,
                   fault: str = None) -> dict:
    """Follow the first ``len(batches)`` steps of a run from ``seed``.
    Returns the loss of each step, the norm of every leaf's first gradient
    and the norm of every leaf's change over the steps."""
    tr = Trainer(arch, job, seed, precision, fault)
    losses, first = [], None
    for ids in batches:
        loss, norms = tr.step(ids, half_batch=half_batch)
        losses.append(loss)
        if first is None:
            first = norms
    return {"losses": losses, "grad_norms": first,
            "change_norms": tr.change_norms()}
