"""Plain reference for the afmoe family (Arcee Trinity): a decoder whose
layers are unlike one another, in straightforward float32 ``jax.numpy`` at
``highest`` matmul precision, with mean next-token cross-entropy, AdamW
with decoupled decay and the auxiliary-loss-free balancing of the expert
bias.

It imports nothing of the program under test and takes nothing the program
made: weights and batches come from the seed through this file,
``benchmark/reference/gpt.py`` (the seed's key, AdamW, the fp8 control's
product) and ``benchmark/traffic.py``.

The layer equations (K: the published ``config.json``; M: the published
modeling code and Arcee's report, listed under ``assumed`` in the
configuration's file):

- ``x = wte[ids] * sqrt(H)``; final RMSNorm; untied head.
- layer: ``x += norm_post_attn(attn(norm_in(x)))``;
  ``x += norm_post_mlp(mlp(norm_pre_mlp(x)))``; RMSNorm, eps K.
- attention: q, k, v, g = x Wq, x Wk, x Wv, x Wg (no biases); q and k
  RMSNorm per head; RoPE (theta K, pairs (x[2i], x[2i+1])) in
  ``sliding_attention`` layers only; scores q k^T / sqrt(hd), query head
  h reads key/value head h // group; position i sees keys j with
  0 <= i - j < window in ``sliding_attention`` layers and j <= i in
  ``full_attention`` layers; out = (softmax(scores) v * sigmoid(g)) Wo.
- dense MLP (the leading layers): (silu(x W1) * (x W3)) W2.
- expert layer: s = sigmoid(x Wr) over ALL published experts;
  sel = top-k(s + b), b the expert bias (a buffer); w = s[sel] /
  (sum s[sel] + 1e-20) * route_scale; y = shared(x) + sum over the
  experts HELD HERE of w_e expert_e(x): what experts held elsewhere would
  add is left out (one chip's share of expert parallelism).
- once a step, no gradient: b_e += coeff * sign(mean(c) - c_e), c_e the
  step's count of tokens that chose expert e.

Departures, as ``reference/gpt.py`` makes them: weights are *stored* in
the type the job states and all arithmetic is float32; one step runs
layer by layer (forward keeping each layer's input, backward re-running
one layer at a time, a row at a time, under ``jax.vjp``). Attention is
computed a block of queries at a time and the head a block of positions
at a time, each block recomputed in the backward pass
(``jax.checkpoint``), and the experts one at a time over every token
with the token's weight for that expert (nought where it did not choose
it): the arithmetic is that of the whole expressions, in blocks that fit
beside the float32 state at 2 x 8192 tokens.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.gpt import HIGHEST, MATMULS, _adamw, _dot, seed_key

__all__ = ["seed_key", "canonical_weights", "draw_leaves", "init_params",
           "leaf_norms", "train_readings", "loss_whole", "moe_forward",
           "sizes", "layer_kinds"]

# canonical leaves: name -> (shape in terms of the sizes, kind), by group
_COMMON = (
    ("ln_in_g", ("H",), "ones"), ("ln_post_attn_g", ("H",), "ones"),
    ("ln_pre_mlp_g", ("H",), "ones"), ("ln_post_mlp_g", ("H",), "ones"),
    ("q_w", ("H", "Q"), "normal"), ("k_w", ("H", "KV"), "normal"),
    ("v_w", ("H", "KV"), "normal"), ("g_w", ("H", "Q"), "normal"),
    ("o_w", ("Q", "H"), "normal"),
    ("q_norm_g", ("hd",), "ones"), ("k_norm_g", ("hd",), "ones"),
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
FAULTS = ("top7", "no_route_scale", "no_window", "rope_full",
          "norm_sum_no_grad")


def sizes(arch: dict) -> dict:
    nh, nkv = int(arch["num_attention_heads"]), int(arch["num_key_value_heads"])
    hd = int(arch["head_dim"])
    L, Ld = int(arch["num_hidden_layers"]), int(arch["num_dense_layers"])
    fe = int(arch["moe_intermediate_size"])
    return {"H": int(arch["hidden_size"]), "Q": nh * hd, "KV": nkv * hd,
            "hd": hd, "nh": nh, "nkv": nkv, "F": int(arch["intermediate_size"]),
            "Fe": fe, "Fs": fe * int(arch["num_shared_experts"]),
            "E": int(arch.get("num_experts_published", arch["num_experts"])),
            "held": int(arch["num_experts"]),
            "offset": int(arch.get("expert_offset", 0)),
            "V": int(arch["vocab_size"]), "L": L, "Ld": Ld, "Lm": L - Ld}


def layer_kinds(arch: dict) -> tuple:
    """The kind of attention of each layer that is run: ``layer_types``
    is the published list, whole; ``layers_kept`` names the published
    layers a cut keeps (all of them where it is absent)."""
    kept = arch.get("layers_kept", range(int(arch["num_hidden_layers"])))
    kinds = tuple(arch["layer_types"][i] for i in kept)
    if len(kinds) != int(arch["num_hidden_layers"]):
        raise ValueError("layers_kept must name num_hidden_layers layers")
    return kinds


def settings(arch: dict, fault: str = None) -> dict:
    """What the forward pass reads besides the sizes; ``fault`` plants
    one of ``FAULTS``."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    z = sizes(arch)
    kinds = layer_kinds(arch)
    top_k = int(arch["num_experts_per_tok"])
    return dict(
        z, kinds=kinds, eps=float(arch["rms_norm_eps"]),
        theta=float(arch["rope_theta"]),
        window=None if fault == "no_window" else int(arch["sliding_window"]),
        rope_full=fault == "rope_full",
        top_k=top_k - 1 if fault == "top7" else top_k,
        route_norm=bool(arch["route_norm"]),
        route_scale=1.0 if fault == "no_route_scale"
        else float(arch["route_scale"]),
        norm_sum_no_grad=fault == "norm_sum_no_grad",
        coeff=float(arch["load_balance_coeff"]))


def leaf_shapes(arch: dict) -> dict:
    z = sizes(arch)
    out = {n: tuple(z[d] for d in dims) for n, dims, _ in _TOP}
    for group in (_COMMON, _DENSE, _MOE):
        for n, dims, _ in group:
            out[n] = (z[STACK[n]],) + tuple(z[d] for d in dims)
    return out


def draw_leaves(arch: dict, key, dtype, shapes: dict, kinds: dict) -> dict:
    """Every leaf from the key, traceable: Normal(0, std) matrices and
    unit gains, drawn in float32 and rounded once to ``dtype``; std is
    ``initializer_range``, or the leaf's own entry in ``start_ranges``
    where the configuration gives one (the start a cell trains from: its
    file says why). A name there that is no matrix drawn from a normal is
    refused. The one draw rule of the families whose leaves are matrices
    and gains alone."""
    std = float(arch["initializer_range"])
    own = arch.get("start_ranges", {})
    drawn = {n for n in shapes if kinds[n] == "normal"}
    if set(own) - drawn:
        raise ValueError(f"start_ranges names no leaf drawn from a normal: "
                         f"{sorted(set(own) - drawn)}")
    out = {}
    for i, name in enumerate(sorted(shapes)):
        if kinds[name] == "normal":
            v = float(own.get(name, std)) * jax.random.normal(
                jax.random.fold_in(key, i), shapes[name], jnp.float32)
        else:
            v = jnp.ones(shapes[name], jnp.float32)
        out[name] = v.astype(dtype)
    return out


def canonical_weights(arch: dict, key, dtype):
    """Every leaf from the key by ``draw_leaves``. The router keeps its
    published width; the expert leaves hold the experts held here."""
    return draw_leaves(arch, key, dtype, leaf_shapes(arch),
                       {n: k for n, _, k in _TOP + _COMMON + _DENSE + _MOE})


def init_params(arch: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    return jax.jit(lambda k: canonical_weights(arch, k, dtype))(
        seed_key(seed))


# ---------------------------------------------------------------- forward

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """x [B, S, heads, hd]: pairs (x[2i], x[2i+1]) turned by
    pos * theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     -1).reshape(x.shape)


def _attention_row(q, k, v, window, q_block: int = 512):
    """One sequence: q [S, nkv, group, hd], k and v [S, nkv, hd] ->
    [S, nkv, group, hd]; a block of queries at a time against every key,
    each block recomputed in the backward pass."""
    s, hd = q.shape[0], q.shape[-1]
    qb = min(q_block, s)
    if s % qb:
        raise ValueError(f"sequence {s} is no multiple of {qb}")

    # a window layer's block of queries sees no key outside a span of
    # window + block keys: only those are multiplied
    span = s if window is None else min(s, window + qb)

    @jax.checkpoint
    def block(i0, qs):
        j0 = jnp.clip(i0 + qb - span, 0, s - span)
        ks = jax.lax.dynamic_slice_in_dim(k, j0, span, axis=0)
        vs = jax.lax.dynamic_slice_in_dim(v, j0, span, axis=0)
        scores = jnp.einsum("qngd,knd->ngqk", qs, ks, precision=HIGHEST)
        scores = scores / math.sqrt(hd)
        i = i0 + jnp.arange(qb)[:, None]
        j = j0 + jnp.arange(span)[None, :]
        mask = j <= i
        if window is not None:
            mask = mask & (i - j < window)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("ngqk,knd->qngd", probs, vs, precision=HIGHEST)

    out = jax.lax.map(lambda a: block(*a),
                      (jnp.arange(0, s, qb), q.reshape((s // qb, qb)
                                                       + q.shape[1:])))
    return out.reshape(q.shape)


def attention_forward(p, x, kind, cfg, mm):
    b, s, _ = x.shape
    nh, nkv, hd = cfg["nh"], cfg["nkv"], cfg["hd"]
    q = mm(x, p["q_w"]).reshape(b, s, nh, hd)
    k = mm(x, p["k_w"]).reshape(b, s, nkv, hd)
    v = mm(x, p["v_w"]).reshape(b, s, nkv, hd)
    g = mm(x, p["g_w"])
    q = _rms(q, p["q_norm_g"], cfg["eps"])
    k = _rms(k, p["k_norm_g"], cfg["eps"])
    sliding = kind == "sliding_attention"
    if sliding or cfg["rope_full"]:
        q, k = _rope(q, cfg["theta"]), _rope(k, cfg["theta"])
    window = cfg["window"] if sliding else None
    q = q.reshape(b, s, nkv, nh // nkv, hd)   # head h = (h // group, h % group)
    ctx = jax.lax.map(lambda a: _attention_row(*a, window), (q, k, v))
    return mm(ctx.reshape(b, s, nh * hd) * jax.nn.sigmoid(g), p["o_w"])


def _swiglu(x, w1, w3, w2, mm):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def route(p, x, bias, cfg, mm):
    """x [T, H] -> (sel [T, k] over the published experts, their weights
    [T, k], counts [E])."""
    s = jax.nn.sigmoid(mm(x, p["router_w"]))
    _, sel = jax.lax.top_k(s + bias, cfg["top_k"])
    w = jnp.take_along_axis(s, sel, axis=-1)
    if cfg["route_norm"]:
        total = jnp.sum(w, axis=-1, keepdims=True)
        if cfg.get("norm_sum_no_grad"):     # a backward pass that forgets
            total = jax.lax.stop_gradient(total)    # the normalising sum
        w = w / (total + 1e-20)
    w = w * cfg["route_scale"]
    counts = jnp.sum(jax.nn.one_hot(sel, cfg["E"], dtype=jnp.float32),
                     axis=(0, 1))
    return sel, w, counts


def moe_forward(p, x, bias, cfg, mm=_dot, shared: bool = True):
    """The expert layer on x [..., H]: (output, counts [E]). ``p`` holds
    this layer's leaves; the experts held are ``exp_*``'s leading axis,
    numbers ``offset .. offset + held`` of the published ones."""
    flat = x.reshape(-1, x.shape[-1])
    sel, w, counts = route(p, flat, bias, cfg, mm)
    # each token's weight for every published expert, nought where not
    # chosen; the columns of the experts held here
    cw = jnp.einsum("tk,tke->te", w,
                    jax.nn.one_hot(sel, cfg["E"], dtype=jnp.float32))
    held = p["exp_w1"].shape[0]
    cw = jax.lax.dynamic_slice_in_dim(cw, cfg["offset"], held, axis=1)

    one = jax.checkpoint(lambda w1, w3, w2, c: c[:, None]
                         * _swiglu(flat, w1, w3, w2, mm))

    def add(acc, e):
        return acc + one(*e), None
    y, _ = jax.lax.scan(add, jnp.zeros_like(flat),
                        (p["exp_w1"], p["exp_w3"], p["exp_w2"], cw.T))
    if shared:
        y = y + _swiglu(flat, p["sh_w1"], p["sh_w3"], p["sh_w2"], mm)
    return y.reshape(x.shape), counts


def layer_forward(p, x, bias, kind, is_moe, cfg, mm):
    """One layer; p holds its leaves in float32. Returns (x, counts)."""
    eps = cfg["eps"]
    att = attention_forward(p, _rms(x, p["ln_in_g"], eps), kind, cfg, mm)
    x = x + _rms(att, p["ln_post_attn_g"], eps)
    y = _rms(x, p["ln_pre_mlp_g"], eps)
    if is_moe:
        y, counts = moe_forward(p, y, bias, cfg, mm)
    else:
        y = _swiglu(y, p["mlp_w1"], p["mlp_w3"], p["mlp_w2"], mm)
        counts = jnp.zeros((cfg["E"],), jnp.float32)
    return x + _rms(y, p["ln_post_mlp_g"], eps), counts


def head_loss_sum(lnf_g, head_w, x, ids, cfg, mm, block: int = 2048):
    """Sum over the rows given of the next-token losses (position i
    predicts token i+1; the last position predicts nothing), a block of
    positions at a time."""
    b, s, h = x.shape
    y = _rms(x, lnf_g, cfg["eps"])
    labels = jnp.roll(ids, -1, axis=1)
    live = (jnp.arange(s) < s - 1).astype(jnp.float32)
    blk = min(block, s)
    if s % blk:
        raise ValueError(f"sequence {s} is no multiple of {blk}")

    @jax.checkpoint
    def part(y_, labels_, live_):
        logp = jax.nn.log_softmax(mm(y_, head_w), axis=-1)
        gold = jnp.take_along_axis(logp, labels_[..., None], axis=-1)[..., 0]
        return -jnp.sum(gold * live_)

    cut = lambda a: jnp.moveaxis(
        a.reshape((b, s // blk, blk) + a.shape[2:]), 1, 0)
    parts = jax.lax.map(lambda a: part(*a),
                        (cut(y), cut(labels), live.reshape(s // blk, blk)))
    return jnp.sum(parts)


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
    x = w["wte"][ids] * math.sqrt(cfg["H"])
    counts = []
    for i in range(cfg["L"]):
        moe = i >= cfg["Ld"]
        x, c = layer_forward(layer_params(w, i, cfg), x,
                             bias[i - cfg["Ld"]] if moe else None,
                             cfg["kinds"][i], moe, cfg, mm)
        if moe:
            counts.append(c)
    total = head_loss_sum(w["lnf_g"], w["head_w"], x, ids, cfg, mm)
    return total / (ids.shape[0] * (ids.shape[1] - 1)), jnp.stack(counts)


def bias_update(bias, counts, coeff):
    """b_e += coeff * sign(mean(c) - c_e)."""
    return bias + coeff * jnp.sign(
        jnp.mean(counts, axis=-1, keepdims=True) - counts)


# ---------------------------------------------------------------- one step

def leaf_norms(x, name: str, per_layer: bool = False, held=None):
    """The norm of a leaf: [1], or [L, 1] for a stacked layer leaf (the
    experts of a layer held here count as one). A router's counts two
    parts where ``held`` = (offset, count) is given, [2] or [L, 2]: the
    columns of the experts held here, and the columns of those held
    elsewhere, which no expert's output reaches and whose gradient comes
    through the normalising sum alone."""
    x = x.astype(jnp.float32)
    if name == "router_w" and held is not None:
        here = (jnp.arange(x.shape[-1]) - held[0]) // held[1] == 0
        parts = [jnp.where(here, x, 0), jnp.where(here, 0, x)]
    else:
        parts = [x]
    if per_layer:
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            v.reshape(v.shape[0], -1)), axis=-1)) for v in parts], axis=-1)
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(v))) for v in parts])


class Trainer:
    """The reference's training state and its layer-by-layer step
    (``reference/gpt.py``'s, for layers that are unlike one another)."""

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

        # a program a KIND of layer (attention kind, dense or experts),
        # the layer's place in its stacks an argument: three forward and
        # three backward programs for any depth
        def layer(stacks, i, j, moe):
            names = COMMON_NAMES + (MOE_NAMES if moe else DENSE_NAMES)
            return {n: cast(jax.lax.dynamic_index_in_dim(
                stacks[n], i if STACK[n] == "L" else j, 0, keepdims=False))
                for n in names}

        def run(p, x, bias, kind, moe):
            return layer_forward(p, x, bias, kind, moe, cfg, mm)

        @jax.jit
        def embed(wte, ids):
            return cast(wte)[ids] * math.sqrt(cfg["H"])

        @functools.partial(jax.jit, static_argnums=(5, 6))
        def fwd(stacks, i, j, x, bias, kind, moe):
            return run(layer(stacks, i, j, moe), x, bias, kind, moe)

        @functools.partial(jax.jit, static_argnums=(6, 7))
        def bwd(stacks, i, j, x, bias, dy, kind, moe):
            _, pull = jax.vjp(lambda p, x_: run(p, x_, bias, kind, moe)[0],
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
            return dwte.at[ids].add(dx0 * math.sqrt(cfg["H"]))

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
            bias, its kind of attention, whether it holds experts)."""
            moe = i >= Ld
            j = i - Ld if moe else i
            return (jnp.int32(j), self.bias[j] if moe else None,
                    cfg["kinds"][i], moe)

        xs, counts = [self._embed(tops["wte"], ids)], []
        for i in range(L):
            j, bias, kind, moe = place(i)
            x, c = self._fwd(stacks, jnp.int32(i), j, xs[-1], bias, kind,
                             moe)
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
            j, bias, kind, moe = place(i)
            for lo in range(ids.shape[0]):
                dp_r, dx_r = self._bwd(stacks, jnp.int32(i), j,
                                       x_in[lo:lo + 1], bias,
                                       dx[lo:lo + 1], kind, moe)
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
