"""Plain reference for the GPT family: a pre-LN decoder with learned
positions, tied head, exact GELU, causal softmax attention, mean
next-token cross-entropy and AdamW with decoupled decay, in
straightforward float32 ``jax.numpy`` at ``highest`` matmul precision.

It imports nothing of the program under test and takes nothing the
program made: weights and batches come from the seed through this file
and ``benchmark/traffic.py``. What follows the published description
(GPT-3, arXiv:2005.14165, section 2.1; Adam as arXiv:1412.6980 with the
decoupled decay of arXiv:1711.05101) and where it departs:

- weights are *stored* in the type the configuration's job states
  (``param_dtype``: bfloat16, or float32 masters whose bfloat16 rounding
  is what the forward pass multiplies with) and all arithmetic is
  float32: the storage rounding is part of what the configuration
  states, the arithmetic precision is what `correct` holds the program
  to;
- one training step is run layer by layer (forward keeping each block's
  input, backward re-running one block at a time under ``jax.vjp`` and
  applying that block's update at once), so that the 1.3B-width cell's
  reference fits beside nothing else on a 16 GB chip. The arithmetic is
  that of a whole-model ``jax.grad``; a test holds the two equal.

``matmul`` below is the one place a lower precision can be put in the
reference's place: ``fp8`` is the control of `correct` (the nearest
precision under the bfloat16 the configurations state).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

# canonical leaves: name -> (shape in terms of the sizes, kind)
_BLOCK_LEAVES = (
    ("ln1_g", ("H",), "ones"), ("ln1_b", ("H",), "zeros"),
    ("qkv_w", ("H", "3H"), "normal"), ("qkv_b", ("3H",), "zeros"),
    ("proj_w", ("H", "H"), "normal"), ("proj_b", ("H",), "zeros"),
    ("ln2_g", ("H",), "ones"), ("ln2_b", ("H",), "zeros"),
    ("fc_w", ("H", "F"), "normal"), ("fc_b", ("F",), "zeros"),
    ("out_w", ("F", "H"), "normal"), ("out_b", ("H",), "zeros"),
)
_TOP_LEAVES = (
    ("wte", ("V", "H"), "normal"), ("wpe", ("S", "H"), "normal"),
    ("lnf_g", ("H",), "ones"), ("lnf_b", ("H",), "zeros"),
)
BLOCK_NAMES = tuple(n for n, _, _ in _BLOCK_LEAVES)
TOP_NAMES = tuple(n for n, _, _ in _TOP_LEAVES)
LN_EPS = 1e-5


def sizes(arch: dict) -> dict:
    h = int(arch["hidden_size"])
    return {"H": h, "3H": 3 * h, "F": int(arch["ffn_mult"]) * h,
            "V": int(arch["vocab_size"]), "S": int(arch["max_seq_len"]),
            "L": int(arch["num_layers"]), "nh": int(arch["num_heads"])}


def leaf_shapes(arch: dict) -> dict:
    """Canonical leaf name -> shape; block leaves are stacked [L, ...]."""
    z = sizes(arch)
    out = {n: tuple(z[d] for d in dims) for n, dims, _ in _TOP_LEAVES}
    out.update({n: (z["L"],) + tuple(z[d] for d in dims)
                for n, dims, _ in _BLOCK_LEAVES})
    return out


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def canonical_weights(arch: dict, key, dtype):
    """Every leaf from the key, traceable: Normal(0, initializer_range)
    matrices, unit gains, zero biases (how a run of this family
    starts), drawn in float32 and rounded once to ``dtype``."""
    std = float(arch["initializer_range"])
    shapes = leaf_shapes(arch)
    kinds = {n: k for n, _, k in _TOP_LEAVES + _BLOCK_LEAVES}
    out = {}
    for i, name in enumerate(sorted(shapes)):
        shape = shapes[name]
        if kinds[name] == "normal":
            v = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
        else:
            v = jnp.full(shape, 1.0 if kinds[name] == "ones" else 0.0,
                         jnp.float32)
        out[name] = v.astype(dtype)
    return out


def init_params(arch: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    return jax.jit(lambda k: canonical_weights(arch, k, dtype))(
        seed_key(seed))


# ---------------------------------------------------------------- matmuls

def _dot(a, b):
    return jnp.matmul(a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _to_fp8(x, dtype):
    """Per-tensor scaled cast to an 8-bit float and back to float32."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _fp8_matmul(a, b):
    return _dot(_to_fp8(a, jnp.float8_e4m3fn), _to_fp8(b, jnp.float8_e4m3fn))


def _fp8_fwd(a, b):
    return _fp8_matmul(a, b), (a, b)


def _fp8_bwd(res, dy):
    a, b = res
    qa, qb = _to_fp8(a, jnp.float8_e4m3fn), _to_fp8(b, jnp.float8_e4m3fn)
    qd = _to_fp8(dy, jnp.float8_e5m2)
    da = _dot(qd, jnp.swapaxes(qb, -1, -2))
    db = _dot(jnp.swapaxes(qa.reshape(-1, qa.shape[-1]), 0, 1),
              qd.reshape(-1, qd.shape[-1]))
    return da, db


_fp8_matmul.defvjp(_fp8_fwd, _fp8_bwd)

# precision name -> the product of an activation [..., K] with a weight
# [K, N]. "reference" is the yardstick; the others stand in the program's
# place to show that `correct` can fail.
MATMULS = {"reference": _dot, "fp8": _fp8_matmul}


# ---------------------------------------------------------------- forward

def _layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _attention_row(q, k, v):
    """One sequence: q, k, v [S, nh, hd] -> [S, nh, hd], causal."""
    s, _, hd = q.shape
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST)
    scores = scores / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)


def block_forward(p: dict, x, nh: int, mm):
    """One pre-LN block; p holds this layer's leaves in float32."""
    b, s, h = x.shape
    y = _layer_norm(x, p["ln1_g"], p["ln1_b"])
    qkv = mm(y, p["qkv_w"]) + p["qkv_b"]
    q, k, v = (t.reshape(b, s, nh, h // nh)
               for t in jnp.split(qkv, 3, axis=-1))
    # a row at a time: the [nh, S, S] scores of every row at once would
    # not fit beside the optimizer state at the timed sizes
    ctx = jax.lax.map(lambda qkv_: _attention_row(*qkv_), (q, k, v))
    x = x + mm(ctx.reshape(b, s, h), p["proj_w"]) + p["proj_b"]
    y = _layer_norm(x, p["ln2_g"], p["ln2_b"])
    y = jax.nn.gelu(mm(y, p["fc_w"]) + p["fc_b"], approximate=False)
    return x + mm(y, p["out_w"]) + p["out_b"]


def head_loss_sum(lnf_g, lnf_b, wte, x, ids, mm):
    """Sum over the rows given of the next-token losses: position i of
    the final-normed hidden state predicts token i+1."""
    y = _layer_norm(x, lnf_g, lnf_b)[:, :-1]
    logits = mm(y, wte.T)
    logp = jax.nn.log_softmax(logits, axis=-1)
    gold = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
    return -jnp.sum(gold)


def loss_whole(params: dict, ids, arch: dict, mm=_dot):
    """The whole model's mean loss in one expression (tests hold the
    layer-by-layer step below to ``jax.grad`` of this)."""
    z = sizes(arch)
    w = {n: v.astype(jnp.float32) for n, v in params.items()}
    x = w["wte"][ids] + w["wpe"][: ids.shape[1]]
    for layer in range(z["L"]):
        x = block_forward({n: w[n][layer] for n in BLOCK_NAMES}, x,
                          z["nh"], mm)
    total = head_loss_sum(w["lnf_g"], w["lnf_b"], w["wte"], x, ids, mm)
    return total / (ids.shape[0] * (ids.shape[1] - 1))


# ---------------------------------------------------------------- one step

def _adamw(p, g, m, v, t, opt):
    """One leaf: p float32 (the stored value, or its master), returns the
    new float32 value and moments. Decay is decoupled and applied to
    every leaf, as the job states."""
    b1, b2 = opt["beta1"], opt["beta2"]
    lr = opt["learning_rate"]
    p = p * (1.0 - lr * opt["weight_decay"])
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    lr_t = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    return p - lr_t * m / (jnp.sqrt(v) + opt["epsilon"]), m, v


# leaves that hold several matrices side by side along their last axis
# are compared part by part: the key third of the fused qkv bias has no
# gradient under softmax, and would hide in the norm of the whole leaf
PARTS = {"qkv_w": 3, "qkv_b": 3}


def leaf_norms(x, name: str, per_layer: bool = False):
    """The norm of each part of a leaf: [parts], or [L, parts] for a
    stacked block leaf."""
    parts = PARTS.get(name, 1)
    x = x.astype(jnp.float32)
    lead = x.shape[:1] if per_layer else ()
    x = x.reshape(lead + (-1, parts, x.shape[-1] // parts))
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=(-3, -1)))


class Trainer:
    """The reference's training state and its layer-by-layer step.

    ``param_dtype`` is how the job stores the weights it updates
    (bfloat16: no master, each update rounds; float32: masters).
    ``compute_dtype`` is the rounding the forward pass sees.
    """

    def __init__(self, arch: dict, job: dict, seed: int,
                 precision: str = "reference", block_tokens: int = 2048):
        self.arch, self.z = arch, sizes(arch)
        self.opt = {k: float(job[k]) for k in
                    ("learning_rate", "beta1", "beta2", "epsilon",
                     "weight_decay")}
        self.compute_dtype = jnp.dtype(job["compute_dtype"])
        self.param_dtype = jnp.dtype(
            "float32" if job["master_weights"] else job["compute_dtype"])
        self.seed = seed
        self.mm = MATMULS[precision]
        self.block_tokens = block_tokens
        # masters start from the rounded weights, as a job that casts
        # its model and then builds its optimizer starts
        # kept to make the start again for ``change_norms``: the start
        # itself would be 2 B/param more beside the state
        self._make = jax.jit(functools.partial(
            canonical_weights, arch, dtype=self.compute_dtype))
        w = self._make(seed_key(seed))
        self.params = {n: v.astype(self.param_dtype) for n, v in w.items()}
        self.m = {n: jnp.zeros(v.shape, jnp.float32)
                  for n, v in self.params.items()}
        self.v = {n: jnp.zeros(v.shape, jnp.float32)
                  for n, v in self.params.items()}
        self.t = 0
        self._build()

    def _build(self):
        # nothing below closes over ``self``: a cycle through the jitted
        # functions would keep a finished trainer's state on the chip
        # until the collector ran, beside the next one's
        nh, vocab, mm, opt = self.z["nh"], self.z["V"], self.mm, self.opt
        cd, pd = self.compute_dtype, self.param_dtype

        def cast(x):
            return x.astype(cd).astype(jnp.float32)

        def layer(blocks, i):
            return {n: cast(jax.lax.dynamic_index_in_dim(
                blocks[n], i, 0, keepdims=False)) for n in BLOCK_NAMES}

        @jax.jit
        def embed(wte, wpe, ids):
            return cast(wte)[ids] + cast(wpe)[: ids.shape[1]]

        @jax.jit
        def fwd(blocks, i, x):
            return block_forward(layer(blocks, i), x, nh, mm)

        @jax.jit
        def bwd(blocks, i, x, dy):
            _, pull = jax.vjp(lambda p, x_: block_forward(p, x_, nh, mm),
                              layer(blocks, i), x)
            return pull(dy)

        @jax.jit
        def head(lnf_g, lnf_b, wte, x, ids):
            f = lambda g, b, w, x_: head_loss_sum(g, b, w, x_, ids, mm)
            return jax.value_and_grad(f, argnums=(0, 1, 2, 3))(
                cast(lnf_g), cast(lnf_b), cast(wte), x)

        @jax.jit
        def embed_grads(dx0, ids):
            dwte = jnp.zeros((vocab, dx0.shape[-1]), jnp.float32)
            return dwte.at[ids].add(dx0), jnp.sum(dx0, axis=0)

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def update_layer(blocks, m, v, grads, i, t):
            norms = {}
            for n in BLOCK_NAMES:
                p = jax.lax.dynamic_index_in_dim(blocks[n], i, 0, False)
                p2, m2, v2 = _adamw(
                    p.astype(jnp.float32), grads[n],
                    jax.lax.dynamic_index_in_dim(m[n], i, 0, False),
                    jax.lax.dynamic_index_in_dim(v[n], i, 0, False), t, opt)
                blocks[n] = jax.lax.dynamic_update_index_in_dim(
                    blocks[n], p2.astype(pd), i, 0)
                m[n] = jax.lax.dynamic_update_index_in_dim(m[n], m2, i, 0)
                v[n] = jax.lax.dynamic_update_index_in_dim(v[n], v2, i, 0)
                norms[n] = leaf_norms(grads[n], n)
            return blocks, m, v, norms

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
        self._embed_grads = embed_grads
        self._update_layer, self._update_top = update_layer, update_top

    def _split(self, tree):
        return ({n: tree[n] for n in BLOCK_NAMES},
                {n: tree[n] for n in TOP_NAMES})

    def step(self, ids, half_batch: bool = False):
        """One optimizer step on the token ids [B, S]. Returns the loss
        and each leaf's gradient norms (``leaf_norms``; block leaves:
        [L, parts]).
        ``half_batch`` plants the fault of a step that leaves out the
        second half of the rows and takes its mean over the rest."""
        ids = jnp.asarray(np.asarray(ids), jnp.int32)
        if half_batch:
            ids = ids[: ids.shape[0] // 2]
        L = self.z["L"]
        blocks, tops = self._split(self.params)
        mb, mt = self._split(self.m)
        vb, vt = self._split(self.v)
        self.params = self.m = self.v = None       # donated below
        self.t += 1
        t = jnp.float32(self.t)
        n_tok = ids.shape[0] * (ids.shape[1] - 1)

        xs = [self._embed(tops["wte"], tops["wpe"], ids)]
        for i in range(L):
            xs.append(self._fwd(blocks, i, xs[-1]))
        x_last = xs.pop()
        total = 0.0
        g_top = {"lnf_g": 0.0, "lnf_b": 0.0, "wte": 0.0}
        dxs = []
        # the head and each block's backward pass take the rows in
        # blocks of about block_tokens tokens, so that the scores and
        # the logits of one block fit beside the optimizer state
        r = max(1, self.block_tokens // ids.shape[1])
        for lo in range(0, ids.shape[0], r):
            val, (dg, db, dw, dx) = self._head(
                tops["lnf_g"], tops["lnf_b"], tops["wte"],
                x_last[lo:lo + r], ids[lo:lo + r])
            total = total + val
            g_top = {"lnf_g": g_top["lnf_g"] + dg, "lnf_b": g_top["lnf_b"] + db,
                     "wte": g_top["wte"] + dw}
            dxs.append(dx)
        del x_last
        dx = jnp.concatenate(dxs) / n_tok
        del dxs
        g_top = {n: g / n_tok for n, g in g_top.items()}
        loss = total / n_tok

        norms = {n: [None] * L for n in BLOCK_NAMES}
        for i in reversed(range(L)):
            x_in, dp, dx_in = xs.pop(), None, []
            for lo in range(0, ids.shape[0], r):
                dp_r, dx_r = self._bwd(blocks, i, x_in[lo:lo + r],
                                       dx[lo:lo + r])
                dp = dp_r if dp is None else self._add(dp, dp_r)
                dx_in.append(dx_r)
            dx = jnp.concatenate(dx_in)
            del x_in, dx_in
            blocks, mb, vb, nrm = self._update_layer(blocks, mb, vb, dp,
                                                     i, t)
            for n in BLOCK_NAMES:
                norms[n][i] = nrm[n]
        dwte, dwpe = self._embed_grads(dx, ids)
        g_top["wte"] = g_top["wte"] + dwte
        s = ids.shape[1]
        g_top["wpe"] = jnp.zeros(tops["wpe"].shape, jnp.float32
                                 ).at[:s].set(dwpe)
        tops, mt, vt, nrm_top = self._update_top(tops, mt, vt, g_top, t)

        self.params = {**blocks, **tops}
        self.m, self.v = {**mb, **mt}, {**vb, **vt}
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
                                  n in BLOCK_NAMES))
                for n in TOP_NAMES + BLOCK_NAMES}


def train_readings(arch: dict, job: dict, seed: int, batches,
                   precision: str = "reference",
                   half_batch: bool = False) -> dict:
    """Follow the first ``len(batches)`` steps of a run from ``seed``.
    Returns the loss of each step, the norm of every leaf's first
    gradient and the norm of every leaf's change over the steps."""
    tr = Trainer(arch, job, seed, precision)
    losses, first = [], None
    for ids in batches:
        loss, norms = tr.step(ids, half_batch=half_batch)
        losses.append(loss)
        if first is None:
            first = norms
    return {"losses": losses, "grad_norms": first,
            "change_norms": tr.change_norms()}
