"""Plain reference for the SmallThinker family
(PowerInfer/SmallThinker-21BA3B-Instruct): a decoder whose every layer is
grouped-query attention, a causal window with RoPE or full causal with no
positions, and a token-choice layer of ReLU-gated experts whose router reads
the block's input, in straightforward float32 ``jax.numpy`` at ``highest``
matmul precision, with mean next-token cross-entropy and AdamW with
decoupled decay.

It imports nothing of the program under test and takes nothing the program
made: weights and batches come from the seed through this file,
``benchmark/reference/gpt.py`` (the seed's key, AdamW, the fp8 control's
product), ``benchmark/reference/afmoe.py`` (RMSNorm, grouped attention a
block of queries at a time with or without a window, the head's loss in
blocks, the norms by leaf: the same equations, written once) and
``benchmark/traffic.py``.

The layer equations (K: the published ``config.json``; M: the published
modeling code ``modeling_smallthinker.py`` and llama.cpp's
``llm_build_smallthinker``, listed under ``assumed`` in the configuration's
file), n = RMSNorm with eps K:

- ``x = wte[ids]``; final RMSNorm; untied head.
- layer i on x: ``logits = x Wr`` ON x AS IT ENTERS THE LAYER, before the
  attention and before any norm; ``sel = top-k(logits)``;
  ``w = softmax(logits[sel])`` over the chosen ones; then
  ``h = x + attn(n1(x))``; ``y = h + sum over the experts HELD HERE of
  w_e * (relu(n2(h) W1_e) * (n2(h) W3_e)) W2_e`` (one chip's share of expert
  parallelism: what experts held elsewhere would add is left out; there is
  no shared expert, so nothing is counted once).
- attention: q, k, v = x Wq, x Wk, x Wv (no biases) as [heads, head_dim],
  query head h reads key/value head h // group; where ``rope_layout[i]`` is
  1, q and k turned by RoPE (theta K, half-split: column j of the first
  half pairs with column j of the second, angle pos * theta^(-j / half)),
  where 0 no position enters at all; scores q k^T / sqrt(head_dim);
  position i sees keys j with 0 <= i - j < window where
  ``sliding_window_layout[i]`` is 1 and j <= i where 0; out = (softmax v) Wo.
- no expert bias, no auxiliary loss term: the counts of tokens by expert are
  read and move nothing.

Departures, as the other references make them: weights are *stored* in the
type the job states and all arithmetic is float32; one step runs layer by
layer (forward keeping each layer's input, backward re-running one layer at
a time, a row at a time, under ``jax.vjp``); attention a block of queries at
a time (a window layer's block against the span of keys it can see, so that
[28, 16384, 16384] scores never exist whole) and the head a block of
positions at a time, each block recomputed in the backward pass; the experts
by a plain loop over the held ones, each over every token with the token's
weight for it (nought where it did not choose it). ``half_batch`` leaves out
half of the rows, or where the batch is one row, the second half of its
positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.afmoe import (_attention_row, _rms, draw_leaves,
                                       head_loss_sum, leaf_norms)
from benchmark.reference.gpt import MATMULS, _adamw, _dot, seed_key

__all__ = ["seed_key", "canonical_weights", "init_params", "leaf_norms",
           "train_readings", "loss_whole", "moe_forward", "sizes",
           "layer_layouts", "attention_forward"]

# canonical leaves: name -> (shape in terms of the sizes, kind)
_LAYER = (
    ("ln1_g", ("H",), "ones"), ("ln2_g", ("H",), "ones"),
    ("q_w", ("H", "Q"), "normal"), ("k_w", ("H", "KV"), "normal"),
    ("v_w", ("H", "KV"), "normal"), ("o_w", ("Q", "H"), "normal"),
    ("router_w", ("H", "E"), "normal"),
    ("exp_w1", ("held", "H", "Fe"), "normal"),
    ("exp_w3", ("held", "H", "Fe"), "normal"),
    ("exp_w2", ("held", "Fe", "H"), "normal"),
)
_TOP = (("wte", ("V", "H"), "normal"), ("lnf_g", ("H",), "ones"),
        ("head_w", ("H", "V"), "normal"))
LAYER_NAMES = tuple(n for n, _, _ in _LAYER)
TOP_NAMES = tuple(n for n, _, _ in _TOP)
# queries a block of attention: a full layer's block is [28, Q_BLOCK, 16384]
# float32 scores, and a layer's backward pass holds four of that size beside
# the float32 state
Q_BLOCK = 256
# planted faults of `correct` (``train_readings(fault=...)``)
FAULTS = ("top5", "no_window", "rope_full", "router_after_attention",
          "silu", "softmax_all")


def sizes(arch: dict) -> dict:
    nh, nkv = int(arch["num_attention_heads"]), int(arch["num_key_value_heads"])
    hd = int(arch["head_dim"])
    return {"H": int(arch["hidden_size"]), "Q": nh * hd, "KV": nkv * hd,
            "hd": hd, "nh": nh, "nkv": nkv,
            "Fe": int(arch["moe_ffn_hidden_size"]),
            "E": int(arch.get("moe_num_primary_experts_published",
                              arch["moe_num_primary_experts"])),
            "held": int(arch["moe_num_primary_experts"]),
            "offset": int(arch.get("expert_offset", 0)),
            "V": int(arch["vocab_size"]),
            "L": int(arch["num_hidden_layers"])}


def layer_layouts(arch: dict) -> tuple:
    """(rope, window) of each layer that is run, 0 or 1 each:
    ``rope_layout`` and ``sliding_window_layout`` are the published lists,
    whole; ``layers_kept`` names the published layers a cut keeps (the
    first ``num_hidden_layers`` where it is absent)."""
    kept = arch.get("layers_kept", range(int(arch["num_hidden_layers"])))
    out = tuple((int(arch["rope_layout"][i]),
                 int(arch["sliding_window_layout"][i])) for i in kept)
    if len(out) != int(arch["num_hidden_layers"]):
        raise ValueError("layers_kept must name num_hidden_layers layers")
    return out


def settings(arch: dict, fault: str = None) -> dict:
    """What the forward pass reads besides the sizes; ``fault`` plants one
    of ``FAULTS``."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    if not arch["moe_primary_router_apply_softmax"]:
        raise ValueError("this reference writes the softmax-of-chosen rule")
    top_k = int(arch["moe_num_active_primary_experts"])
    return dict(
        sizes(arch), layouts=layer_layouts(arch),
        eps=float(arch["rms_norm_eps"]), theta=float(arch["rope_theta"]),
        window=None if fault == "no_window"
        else int(arch["sliding_window_size"]),
        rope_full=fault == "rope_full",
        top_k=top_k - 1 if fault == "top5" else top_k,
        router_after_attention=fault == "router_after_attention",
        gate=jax.nn.silu if fault == "silu" else jax.nn.relu,
        softmax_all=fault == "softmax_all")


def leaf_shapes(arch: dict) -> dict:
    z = sizes(arch)
    out = {n: tuple(z[d] for d in dims) for n, dims, _ in _TOP}
    out.update({n: (z["L"],) + tuple(z[d] for d in dims)
                for n, dims, _ in _LAYER})
    return out


def canonical_weights(arch: dict, key, dtype):
    """Every leaf from the key by ``afmoe.draw_leaves`` (``start_ranges``:
    the start a cell trains from). The router keeps its published width;
    the expert leaves hold the experts held here."""
    return draw_leaves(arch, key, dtype, leaf_shapes(arch),
                       {n: k for n, _, k in _TOP + _LAYER})


def init_params(arch: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    return jax.jit(lambda k: canonical_weights(arch, k, dtype))(
        seed_key(seed))


# ---------------------------------------------------------------- forward

def _rope(x, theta):
    """x [B, S, heads, hd], half-split: (x[j], x[j + hd/2]) turned by
    pos * theta^(-j / (hd/2))."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention_forward(p, x, layout, cfg, mm):
    rope, sliding = layout
    b, s, _ = x.shape
    nh, nkv, hd = cfg["nh"], cfg["nkv"], cfg["hd"]
    q = mm(x, p["q_w"]).reshape(b, s, nh, hd)
    k = mm(x, p["k_w"]).reshape(b, s, nkv, hd)
    v = mm(x, p["v_w"]).reshape(b, s, nkv, hd)
    if rope or cfg["rope_full"]:
        q, k = _rope(q, cfg["theta"]), _rope(k, cfg["theta"])
    window = cfg["window"] if sliding else None
    q = q.reshape(b, s, nkv, nh // nkv, hd)   # head h = (h // group, h % group)
    # afmoe's row: scores over sqrt(hd), the band or the triangle. A plain
    # loop over the rows: under a ``lax.map`` the backward pass keeps a copy
    # of k and v a block of queries (8.6 GB at 16,384 positions)
    ctx = jnp.stack([_attention_row(q[r], k[r], v[r], window, Q_BLOCK)
                     for r in range(b)])
    return mm(ctx.reshape(b, s, nh * hd), p["o_w"])


def route(p, x, cfg, mm):
    """x [T, H], the router's own input -> (sel [T, k] over the published
    experts, their weights [T, k], counts [E])."""
    logits = mm(x, p["router_w"])
    top, sel = jax.lax.top_k(logits, cfg["top_k"])
    if cfg["softmax_all"]:      # over all, not renormalised over the chosen
        w = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), sel, axis=-1)
    else:
        w = jax.nn.softmax(top, axis=-1)
    counts = jnp.sum(jax.nn.one_hot(sel, cfg["E"], dtype=jnp.float32),
                     axis=(0, 1))
    return sel, w, counts


def moe_forward(p, x, x_router, cfg, mm=_dot):
    """The expert layer on x [..., H] under the routing of ``x_router``
    [..., H]: (output, counts [E]). ``p`` holds this layer's leaves; the
    experts held are ``exp_*``'s leading axis, numbers ``offset .. offset +
    held`` of the published ones."""
    flat = x.reshape(-1, x.shape[-1])
    sel, w, counts = route(p, x_router.reshape(flat.shape), cfg, mm)
    # each token's weight for every published expert, nought where not
    # chosen; the columns of the experts held here
    cw = jnp.einsum("tk,tke->te", w,
                    jax.nn.one_hot(sel, cfg["E"], dtype=jnp.float32))
    held = p["exp_w1"].shape[0]
    cw = jax.lax.dynamic_slice_in_dim(cw, cfg["offset"], held, axis=1)
    gate = cfg["gate"]

    one = jax.checkpoint(lambda w1, w3, w2, c: c[:, None] * mm(
        gate(mm(flat, w1)) * mm(flat, w3), w2))

    def add(acc, e):
        return acc + one(*e), None
    y, _ = jax.lax.scan(add, jnp.zeros_like(flat),
                        (p["exp_w1"], p["exp_w3"], p["exp_w2"], cw.T))
    return y.reshape(x.shape), counts


def layer_forward(p, x, layout, cfg, mm):
    """One layer; p holds its leaves in float32. Returns (x, counts)."""
    eps = cfg["eps"]
    h = x + attention_forward(p, _rms(x, p["ln1_g"], eps), layout, cfg, mm)
    y = _rms(h, p["ln2_g"], eps)
    y, counts = moe_forward(
        p, y, y if cfg["router_after_attention"] else x, cfg, mm)
    return h + y, counts


def loss_whole(params: dict, ids, arch: dict, mm=_dot, fault: str = None):
    """The whole model's mean loss in one expression, and each layer's
    counts [L, E] (tests hold the layer-by-layer step below to ``jax.grad``
    of this)."""
    cfg = settings(arch, fault)
    w = {n: v.astype(jnp.float32) for n, v in params.items()}
    x = w["wte"][ids]
    counts = []
    for i in range(cfg["L"]):
        x, c = layer_forward({n: w[n][i] for n in LAYER_NAMES}, x,
                             cfg["layouts"][i], cfg, mm)
        counts.append(c)
    total = head_loss_sum(w["lnf_g"], w["head_w"], x, ids, cfg, mm)
    return total / (ids.shape[0] * (ids.shape[1] - 1)), jnp.stack(counts)


# ---------------------------------------------------------------- one step

class Trainer:
    """The reference's training state and its layer-by-layer step
    (``reference/afmoe.py``'s, for one kind of layer in two layouts)."""

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
        self.counts = None           # [L, E] of the last step
        self.t = 0
        self._build()

    def _build(self):
        cfg, mm, opt = self.cfg, self.mm, self.opt
        cd, pd = self.compute_dtype, self.param_dtype
        held = (cfg["offset"], cfg["held"])

        def cast(x):
            return x.astype(cd).astype(jnp.float32)

        # a program a layout of layer (rope or not, window or not), the
        # layer's place in the stacks an argument
        def layer(stacks, i):
            return {n: cast(jax.lax.dynamic_index_in_dim(
                stacks[n], i, 0, keepdims=False)) for n in LAYER_NAMES}

        @jax.jit
        def embed(wte, ids):
            return cast(wte)[ids]

        @functools.partial(jax.jit, static_argnums=(3,))
        def fwd(stacks, i, x, layout):
            return layer_forward(layer(stacks, i), x, layout, cfg, mm)

        @functools.partial(jax.jit, static_argnums=(4,))
        def bwd(stacks, i, x, dy, layout):
            _, pull = jax.vjp(
                lambda p, x_: layer_forward(p, x_, layout, cfg, mm)[0],
                layer(stacks, i), x)
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
        def update_layer(stacks, m, v, grads, i, t):
            norms = {}
            for n, g in grads.items():
                take = lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False)
                put = lambda a, new: jax.lax.dynamic_update_index_in_dim(
                    a, new.astype(a.dtype), i, 0)
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
        """One optimizer step on the token ids [B, S]. Returns the loss and
        each leaf's gradient norms (stacked leaves: [layers, parts])."""
        ids = jnp.asarray(np.asarray(ids), jnp.int32)
        if half_batch:
            ids = ids[: ids.shape[0] // 2] if ids.shape[0] > 1 \
                else ids[:, : ids.shape[1] // 2]
        cfg = self.cfg
        L = cfg["L"]
        stacks, tops = self._split(self.params)
        ms, mt = self._split(self.m)
        vs, vt = self._split(self.v)
        self.params = self.m = self.v = None       # donated below
        self.t += 1
        t = jnp.float32(self.t)
        n_tok = ids.shape[0] * (ids.shape[1] - 1)

        xs, counts = [self._embed(tops["wte"], ids)], []
        for i in range(L):
            x, c = self._fwd(stacks, jnp.int32(i), xs[-1],
                             cfg["layouts"][i])
            xs.append(x)
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

        norms = {n: [None] * L for n in LAYER_NAMES}
        for i in reversed(range(L)):
            x_in, dp, dx_in = xs.pop(), None, []
            for lo in range(ids.shape[0]):
                dp_r, dx_r = self._bwd(stacks, jnp.int32(i),
                                       x_in[lo:lo + 1], dx[lo:lo + 1],
                                       cfg["layouts"][i])
                dp = dp_r if dp is None else self._add(dp, dp_r)
                dx_in.append(dx_r)
            dx = jnp.concatenate(dx_in)
            del x_in, dx_in
            stacks, ms, vs, nrm = self._update_layer(
                stacks, ms, vs, dp, jnp.int32(i), t)
            for n, v in nrm.items():
                norms[n][i] = v
        g_top["wte"] = self._embed_grad(dx, ids)
        tops, mt, vt, nrm_top = self._update_top(tops, mt, vt, g_top, t)

        self.counts = jnp.stack(counts)
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

        return {n: np.asarray(gap(self.params[n], start[n], n,
                                  n in LAYER_NAMES))
                for n in self.params}


def train_readings(arch: dict, job: dict, seed: int, batches,
                   precision: str = "reference", half_batch: bool = False,
                   fault: str = None) -> dict:
    """Follow the first ``len(batches)`` steps of a run from ``seed``.
    Returns the loss of each step, the norm of every leaf's first gradient,
    the norm of every leaf's change over the steps and the first step's
    counts of tokens by expert [L, E]."""
    tr = Trainer(arch, job, seed, precision, fault)
    losses, first, load = [], None, None
    for ids in batches:
        loss, norms = tr.step(ids, half_batch=half_batch)
        losses.append(loss)
        if first is None:
            first, load = norms, np.asarray(tr.counts)
    return {"losses": losses, "grad_norms": first,
            "change_norms": tr.change_norms(), "expert_load": load}
