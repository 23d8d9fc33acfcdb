"""Plain reference for the deepseek_v3 family (kakaocorp/kanana-2-30b-a3b):
a decoder with multi-head latent attention and, after the leading dense
layers, a token-choice expert layer beside shared experts, in
straightforward float32 ``jax.numpy`` at ``highest`` matmul precision, with
mean next-token cross-entropy, AdamW with decoupled decay and the
auxiliary-loss-free balancing of the expert bias.

It imports nothing of the program under test and takes nothing the program
made: weights and batches come from the seed through this file,
``benchmark/reference/gpt.py`` (the seed's key, AdamW, the fp8 control's
product), ``benchmark/reference/afmoe.py`` (RMSNorm, RoPE by pairs, SwiGLU,
the router and the expert layer, the head's loss in blocks, the norms by
leaf: the same equations, written once) and ``benchmark/traffic.py``.

The layer equations (K: the published ``config.json``; M: transformers'
``modeling_deepseek_v3.py``, listed under ``assumed`` in the
configuration's file), n = RMSNorm with eps K:

- ``x = wte[ids]``; final RMSNorm; untied head.
- layer: ``x += attn(n1(x))``; ``x += mlp(n2(x))``.
- attention (no query bottleneck, ``q_lora_rank`` null): ``q = h Wq`` as
  [heads, nope + rope] = (q_nope, q_pe); ``(c, k_pe) = h Wkva`` as
  (kv_lora_rank, rope): k_pe is ONE head, shared by all; ``(k_nope, v) =
  n_kv(c) Wkvb`` as [heads, nope + v_head_dim]; q_pe and k_pe turned by
  RoPE (theta K, pairs (x[2i], x[2i+1]), on the rope columns only);
  ``q = (q_nope, q_pe)``, ``k = (k_nope, k_pe)``; scores
  ``q k^T / sqrt(nope + rope)``, causal; ``out = (softmax(scores) v) Wo``.
- dense MLP (the leading layers): (silu(x W1) * (x W3)) W2.
- expert layer: ``reference/afmoe.py``'s ``moe_forward``: s = sigmoid(x Wr)
  over ALL published experts; sel = top-k(s + b); w = s[sel] /
  (sum s[sel] + 1e-20) * routed_scaling_factor; y = shared(x) + sum over
  the experts HELD HERE of w_e expert_e(x) (one chip's share of expert
  parallelism: what experts held elsewhere would add is left out); the
  ``n_shared_experts`` shared experts are one SwiGLU of their summed width.
- once a step, no gradient: b_e += rate * sign(mean(c) - c_e).

Departures, as the other references make them: weights are *stored* in the
type the job states and all arithmetic is float32; one step runs layer by
layer (forward keeping each layer's input, backward re-running one layer
at a time, a row at a time, under ``jax.vjp``); attention a block of
queries at a time and the head a block of positions at a time, each block
recomputed in the backward pass; the experts one at a time over every
token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.afmoe import (_rms, _rope, _swiglu, bias_update,
                                       head_loss_sum, leaf_norms,
                                       moe_forward)
from benchmark.reference.gpt import HIGHEST, MATMULS, _adamw, _dot, seed_key

__all__ = ["seed_key", "canonical_weights", "init_params", "leaf_norms",
           "train_readings", "loss_whole", "moe_forward", "sizes",
           "attention_forward"]

# canonical leaves: name -> (shape in terms of the sizes, kind), by group
_COMMON = (
    ("ln1_g", ("H",), "ones"), ("ln2_g", ("H",), "ones"),
    ("q_w", ("H", "Q"), "normal"), ("kva_w", ("H", "KVA"), "normal"),
    ("kv_norm_g", ("R",), "ones"), ("kvb_w", ("R", "KVB"), "normal"),
    ("o_w", ("O", "H"), "normal"),
)
_DENSE = (("mlp_w1", ("H", "F"), "normal"), ("mlp_w3", ("H", "F"), "normal"),
          ("mlp_w2", ("F", "H"), "normal"))
_MOE = (("router_w", ("H", "E"), "normal"),
        ("exp_w1", ("held", "H", "Fe"), "normal"),
        ("exp_w3", ("held", "H", "Fe"), "normal"),
        ("exp_w2", ("held", "Fe", "H"), "normal"),
        ("sh_w1", ("H", "Fs"), "normal"), ("sh_w3", ("H", "Fs"), "normal"),
        ("sh_w2", ("Fs", "H"), "normal"))
_TOP = (("wte", ("V", "H"), "normal"), ("lnf_g", ("H",), "ones"),
        ("head_w", ("H", "V"), "normal"))
COMMON_NAMES = tuple(n for n, _, _ in _COMMON)
DENSE_NAMES = tuple(n for n, _, _ in _DENSE)
MOE_NAMES = tuple(n for n, _, _ in _MOE)
TOP_NAMES = tuple(n for n, _, _ in _TOP)
# which stack a leaf lives in: "L" all layers, "Ld" dense, "Lm" expert
STACK = {**{n: "L" for n in COMMON_NAMES}, **{n: "Ld" for n in DENSE_NAMES},
         **{n: "Lm" for n in MOE_NAMES}}
# planted faults of `correct` (``train_readings(fault=...)``)
FAULTS = ("top5", "no_route_scale", "no_rope_k", "no_latent_norm",
          "scale_128")


def sizes(arch: dict) -> dict:
    nh = int(arch["num_attention_heads"])
    nope, rope = int(arch["qk_nope_head_dim"]), int(arch["qk_rope_head_dim"])
    dv, rank = int(arch["v_head_dim"]), int(arch["kv_lora_rank"])
    L, Ld = int(arch["num_hidden_layers"]), int(arch["first_k_dense_replace"])
    fe = int(arch["moe_intermediate_size"])
    return {"H": int(arch["hidden_size"]), "nh": nh, "nope": nope,
            "rope": rope, "dv": dv, "R": rank, "Q": nh * (nope + rope),
            "KVA": rank + rope, "KVB": nh * (nope + dv), "O": nh * dv,
            "F": int(arch["intermediate_size"]), "Fe": fe,
            "Fs": fe * int(arch["n_shared_experts"]),
            "E": int(arch.get("n_routed_experts_published",
                              arch["n_routed_experts"])),
            "held": int(arch["n_routed_experts"]),
            "offset": int(arch.get("expert_offset", 0)),
            "V": int(arch["vocab_size"]), "L": L, "Ld": Ld, "Lm": L - Ld}


def settings(arch: dict, fault: str = None) -> dict:
    """What the forward pass reads besides the sizes; ``fault`` plants
    one of ``FAULTS``."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    z = sizes(arch)
    top_k = int(arch["num_experts_per_tok"])
    scale_width = z["nope"] if fault == "scale_128" else z["nope"] + z["rope"]
    return dict(
        z, eps=float(arch["rms_norm_eps"]), theta=float(arch["rope_theta"]),
        scale=float(scale_width) ** -0.5,
        rope_k=fault != "no_rope_k", latent_norm=fault != "no_latent_norm",
        top_k=top_k - 1 if fault == "top5" else top_k,
        route_norm=bool(arch["norm_topk_prob"]),
        route_scale=1.0 if fault == "no_route_scale"
        else float(arch["routed_scaling_factor"]),
        coeff=float(arch["bias_update_rate"]))


def leaf_shapes(arch: dict) -> dict:
    z = sizes(arch)
    out = {n: tuple(z[d] for d in dims) for n, dims, _ in _TOP}
    for group in (_COMMON, _DENSE, _MOE):
        for n, dims, _ in group:
            out[n] = (z[STACK[n]],) + tuple(z[d] for d in dims)
    return out


def canonical_weights(arch: dict, key, dtype):
    """Every leaf from the key, traceable: Normal(0, initializer_range)
    matrices and unit gains, drawn in float32 and rounded once to
    ``dtype``. The router keeps its published width; the expert leaves
    hold the experts held here."""
    std = float(arch["initializer_range"])
    shapes = leaf_shapes(arch)
    kinds = {n: k for n, _, k in _TOP + _COMMON + _DENSE + _MOE}
    out = {}
    for i, name in enumerate(sorted(shapes)):
        if kinds[name] == "normal":
            v = std * jax.random.normal(jax.random.fold_in(key, i),
                                        shapes[name], jnp.float32)
        else:
            v = jnp.ones(shapes[name], jnp.float32)
        out[name] = v.astype(dtype)
    return out


def init_params(arch: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    return jax.jit(lambda k: canonical_weights(arch, k, dtype))(
        seed_key(seed))


# ---------------------------------------------------------------- forward

def _attention_row(q, k, v, scale, q_block: int = 512):
    """One sequence, causal: q and k [S, heads, d_qk], v [S, heads, d_v]
    -> [S, heads, d_v]; a block of queries at a time against every key,
    each block recomputed in the backward pass."""
    s = q.shape[0]
    qb = min(q_block, s)
    if s % qb:
        raise ValueError(f"sequence {s} is no multiple of {qb}")

    @jax.checkpoint
    def block(i0, qs):
        scores = jnp.einsum("qhd,khd->hqk", qs, k, precision=HIGHEST) * scale
        mask = jnp.arange(s)[None, :] <= i0 + jnp.arange(qb)[:, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)

    out = jax.lax.map(lambda a: block(*a),
                      (jnp.arange(0, s, qb),
                       q.reshape((s // qb, qb) + q.shape[1:])))
    return out.reshape((s,) + v.shape[1:])


def attention_forward(p, x, cfg, mm):
    b, s, _ = x.shape
    nh, nope, rope, dv = cfg["nh"], cfg["nope"], cfg["rope"], cfg["dv"]
    q = mm(x, p["q_w"]).reshape(b, s, nh, nope + rope)
    kva = mm(x, p["kva_w"])
    c, k_pe = kva[..., :cfg["R"]], kva[..., cfg["R"]:]
    if cfg["latent_norm"]:
        c = _rms(c, p["kv_norm_g"], cfg["eps"])
    kvb = mm(c, p["kvb_w"]).reshape(b, s, nh, nope + dv)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    q_pe = _rope(q[..., nope:], cfg["theta"])
    k_pe = k_pe[:, :, None, :]                  # one head, shared by all
    if cfg["rope_k"]:
        k_pe = _rope(k_pe, cfg["theta"])
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, s, nh, rope))],
                        axis=-1)
    ctx = jax.lax.map(lambda a: _attention_row(*a, cfg["scale"]), (q, k, v))
    return mm(ctx.reshape(b, s, nh * dv), p["o_w"])


def layer_forward(p, x, bias, is_moe, cfg, mm):
    """One layer; p holds its leaves in float32. Returns (x, counts)."""
    eps = cfg["eps"]
    x = x + attention_forward(p, _rms(x, p["ln1_g"], eps), cfg, mm)
    y = _rms(x, p["ln2_g"], eps)
    if is_moe:
        y, counts = moe_forward(p, y, bias, cfg, mm)
    else:
        y = _swiglu(y, p["mlp_w1"], p["mlp_w3"], p["mlp_w2"], mm)
        counts = jnp.zeros((cfg["E"],), jnp.float32)
    return x + y, counts


def layer_params(w: dict, i: int, cfg) -> dict:
    """Layer i's leaves out of the stacks."""
    out = {n: w[n][i] for n in COMMON_NAMES}
    if i < cfg["Ld"]:
        out.update({n: w[n][i] for n in DENSE_NAMES})
    else:
        out.update({n: w[n][i - cfg["Ld"]] for n in MOE_NAMES})
    return out


def loss_whole(params: dict, bias, ids, arch: dict, mm=_dot,
               fault: str = None):
    """The whole model's mean loss in one expression, and each expert
    layer's counts [Lm, E] (tests hold the layer-by-layer step below to
    ``jax.grad`` of this). ``bias`` [Lm, E]."""
    cfg = settings(arch, fault)
    w = {n: v.astype(jnp.float32) for n, v in params.items()}
    x = w["wte"][ids]
    counts = []
    for i in range(cfg["L"]):
        moe = i >= cfg["Ld"]
        x, c = layer_forward(layer_params(w, i, cfg), x,
                             bias[i - cfg["Ld"]] if moe else None, moe,
                             cfg, mm)
        if moe:
            counts.append(c)
    total = head_loss_sum(w["lnf_g"], w["head_w"], x, ids, cfg, mm)
    return total / (ids.shape[0] * (ids.shape[1] - 1)), jnp.stack(counts)


# ---------------------------------------------------------------- one step

class Trainer:
    """The reference's training state and its layer-by-layer step
    (``reference/afmoe.py``'s, for this family's two kinds of layer)."""

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
        self.m = {n: jnp.zeros(v.shape, jnp.float32)
                  for n, v in self.params.items()}
        self.v = {n: jnp.zeros(v.shape, jnp.float32)
                  for n, v in self.params.items()}
        self.bias = jnp.zeros((self.cfg["Lm"], self.cfg["E"]), jnp.float32)
        self.counts = None           # [Lm, E] of the last step
        self.t = 0
        self._build()

    def _build(self):
        cfg, mm, opt = self.cfg, self.mm, self.opt
        cd, pd = self.compute_dtype, self.param_dtype
        held = (cfg["offset"], cfg["held"])

        def cast(x):
            return x.astype(cd).astype(jnp.float32)

        # a program a KIND of layer (dense or experts), the layer's place
        # in its stacks an argument: two forward and two backward programs
        # for any depth
        def layer(stacks, i, j, moe):
            names = COMMON_NAMES + (MOE_NAMES if moe else DENSE_NAMES)
            return {n: cast(jax.lax.dynamic_index_in_dim(
                stacks[n], i if STACK[n] == "L" else j, 0, keepdims=False))
                for n in names}

        @jax.jit
        def embed(wte, ids):
            return cast(wte)[ids]

        @functools.partial(jax.jit, static_argnums=(5,))
        def fwd(stacks, i, j, x, bias, moe):
            return layer_forward(layer(stacks, i, j, moe), x, bias, moe,
                                 cfg, mm)

        @functools.partial(jax.jit, static_argnums=(6,))
        def bwd(stacks, i, j, x, bias, dy, moe):
            _, pull = jax.vjp(
                lambda p, x_: layer_forward(p, x_, bias, moe, cfg, mm)[0],
                layer(stacks, i, j, moe), x)
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

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def update_layer(stacks, m, v, grads, i, j, t):
            norms = {}
            for n, g in grads.items():
                at = i if STACK[n] == "L" else j
                take = lambda a: jax.lax.dynamic_index_in_dim(a, at, 0, False)
                put = lambda a, new: jax.lax.dynamic_update_index_in_dim(
                    a, new.astype(a.dtype), at, 0)
                p2, m2, v2 = _adamw(take(stacks[n]).astype(jnp.float32), g,
                                    take(m[n]), take(v[n]), t, opt)
                stacks[n] = put(stacks[n], p2)
                m[n], v[n] = put(m[n], m2), put(v[n], v2)
                norms[n] = leaf_norms(g, n, held=held)
            return stacks, m, v, norms

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
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
        self._update_layer, self._update_top = update_layer, update_top

    def _split(self, tree):
        return ({n: v for n, v in tree.items() if n not in TOP_NAMES},
                {n: tree[n] for n in TOP_NAMES})

    def step(self, ids, half_batch: bool = False):
        """One optimizer step on the token ids [B, S] and one update of
        the expert bias. Returns the loss and each leaf's gradient norms
        (stacked leaves: [layers of that kind, 1])."""
        ids = jnp.asarray(np.asarray(ids), jnp.int32)
        if half_batch:
            ids = ids[: ids.shape[0] // 2]
        cfg = self.cfg
        L, Ld = cfg["L"], cfg["Ld"]
        stacks, tops = self._split(self.params)
        ms, mt = self._split(self.m)
        vs, vt = self._split(self.v)
        self.params = self.m = self.v = None       # donated below
        self.t += 1
        t = jnp.float32(self.t)
        n_tok = ids.shape[0] * (ids.shape[1] - 1)

        def place(i):
            """Layer i: (its place in the stack of its kind of MLP, its
            bias, whether it holds experts)."""
            moe = i >= Ld
            j = i - Ld if moe else i
            return jnp.int32(j), self.bias[j] if moe else None, moe

        xs, counts = [self._embed(tops["wte"], ids)], []
        for i in range(L):
            j, bias, moe = place(i)
            x, c = self._fwd(stacks, jnp.int32(i), j, xs[-1], bias, moe)
            xs.append(x)
            if moe:
                counts.append(c)
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

        norms = {n: [None] * cfg[STACK[n]] for n in STACK}
        for i in reversed(range(L)):
            x_in, dp, dx_in = xs.pop(), None, []
            j, bias, moe = place(i)
            for lo in range(ids.shape[0]):
                dp_r, dx_r = self._bwd(stacks, jnp.int32(i), j,
                                       x_in[lo:lo + 1], bias,
                                       dx[lo:lo + 1], moe)
                dp = dp_r if dp is None else self._add(dp, dp_r)
                dx_in.append(dx_r)
            dx = jnp.concatenate(dx_in)
            del x_in, dx_in
            stacks, ms, vs, nrm = self._update_layer(
                stacks, ms, vs, dp, jnp.int32(i), j, t)
            for n, v in nrm.items():
                norms[n][i if STACK[n] == "L" else int(j)] = v
        g_top["wte"] = self._embed_grad(dx, ids)
        tops, mt, vt, nrm_top = self._update_top(tops, mt, vt, g_top, t)

        self.counts = jnp.stack(counts)
        self.bias = bias_update(self.bias, self.counts, cfg["coeff"])
        self.params = {**stacks, **tops}
        self.m, self.v = {**ms, **mt}, {**vs, **vt}
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
                              - then.astype(jnp.float32), name, per_layer,
                              (self.cfg["offset"], self.cfg["held"]))

        return {n: np.asarray(gap(self.params[n], start[n], n, n in STACK))
                for n in self.params}


def train_readings(arch: dict, job: dict, seed: int, batches,
                   precision: str = "reference", half_batch: bool = False,
                   fault: str = None) -> dict:
    """Follow the first ``len(batches)`` steps of a run from ``seed``.
    Returns the loss of each step, the norm of every leaf's first
    gradient, the norm of every leaf's change over the steps, the first
    step's counts of tokens by expert [Lm, E] and the expert bias after
    the steps [Lm, E]."""
    tr = Trainer(arch, job, seed, precision, fault)
    losses, first, load = [], None, None
    for ids in batches:
        loss, norms = tr.step(ids, half_batch=half_batch)
        losses.append(loss)
        if first is None:
            first, load = norms, np.asarray(tr.counts)
    return {"losses": losses, "grad_norms": first,
            "change_norms": tr.change_norms(), "expert_load": load,
            "expert_bias": np.asarray(tr.bias)}
