"""Mixture-of-Experts with expert parallelism.

Parity: MoELayer (python/paddle/incubate/distributed/models/moe/
moe_layer.py:261) + gates (moe/gate/{naive,gshard,switch}_gate.py) +
the global_scatter/global_gather all-to-all routing ops
(paddle/fluid/operators/collective/global_scatter_op.cc). TPU-native
(GShard formulation): expert FFN weights are STACKED [E, ...] with dim 0
sharded over the "ep" mesh axis; token routing is two einsums against a
dispatch mask — when the E dim is sharded, GSPMD lowers exactly the
all-to-all pair the reference implements as explicit collective ops.
Capacity-bounded top-1 (Switch) and top-2 (GShard) gates with the standard
load-balancing auxiliary loss.

Beside it, ``TokenChoiceMoE``: the dropless token-choice layer of today's
sparse decoders (sigmoid scores, top-k of many, a bias that balances the
load without an auxiliary loss, a shared expert). It is told which of the
published experts it holds, routes over all of them, sorts the assignments
that land here by expert and multiplies each group with its expert in one
grouped product. Which layer when: ``MoELayer`` is the Paddle parity API
(softmax gate, top-1/top-2, capacity, GELU experts with biases) and
materialises [tokens, experts, capacity] tensors, so it is for few experts
and short batches; ``TokenChoiceMoE`` is for everything else.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..autograd import tape as _tape
from ..core.tensor import Parameter, Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer_base import Layer
from . import mesh as mesh_mod

__all__ = ["MoELayer", "SwitchGate", "GShardGate", "NaiveGate",
           "TokenChoiceMoE", "last_moe_dispatch", "EXPERTS_RESULT"]


class _BaseGate(Layer):
    def __init__(self, d_model, num_experts):
        super().__init__()
        self.d_model = d_model
        self.num_experts = num_experts
        self.weight = self.create_parameter(
            [d_model, num_experts], default_initializer=I.XavierUniform())


class SwitchGate(_BaseGate):
    """Top-1 routing (Switch Transformer). Parity: moe/gate/switch_gate.py."""
    top_k = 1


class GShardGate(_BaseGate):
    """Top-2 routing. Parity: moe/gate/gshard_gate.py."""
    top_k = 2


NaiveGate = GShardGate  # reference NaiveGate is top-2 without noise


def _gating(logits, top_k: int, capacity: int):
    """Build dispatch/combine tensors (GShard einsum formulation).

    logits: [T, E]. Returns dispatch [T, E, C] (0/1), combine [T, E, C]
    (weights), aux_loss (load balancing, Shazeer et al.).
    """
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    # aux loss: E * sum_e(mean_t(gate_prob_e) * mean_t(is_top1_e))
    top1 = jnp.argmax(probs, axis=-1)
    me = probs.mean(axis=0)
    ce = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32), axis=0)
    aux = jnp.sum(me * ce) * E

    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    residual_probs = probs
    # slots already taken per expert by earlier rounds — round-k positions
    # must be offset past them or 1st/2nd-choice tokens collide in a slot
    taken = jnp.zeros((E,), jnp.float32)
    gate_sum = jnp.zeros((T,), jnp.float32)  # sum of CHOSEN gate probs
    for k in range(top_k):
        idx = jnp.argmax(residual_probs, axis=-1)              # [T]
        gate_k = jnp.take_along_axis(residual_probs, idx[:, None],
                                     axis=-1)[:, 0]            # [T]
        gate_sum = gate_sum + gate_k
        mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)       # [T, E]
        # position of each token within its expert's queue
        pos = ((jnp.cumsum(mask, axis=0) - 1.0) + taken[None, :]) * mask
        keep = (pos < capacity) * mask
        pos_c = jax.nn.one_hot(
            (pos * keep).astype(jnp.int32), capacity,
            dtype=jnp.float32) * keep[..., None]               # [T, E, C]
        dispatch = dispatch + pos_c
        combine = combine + gate_k[:, None, None] * pos_c
        taken = taken + keep.sum(axis=0)
        residual_probs = residual_probs * (1.0 - mask)

    if top_k > 1:
        # normalize over the chosen gates (GShard g_i/(g1+g2)); dividing by
        # surviving weights instead would zero the router's task gradient
        combine = combine / jnp.maximum(gate_sum, 1e-9)[:, None, None]
    # top_k == 1 (Switch): scale by the raw gate prob so the router learns
    # from the task loss
    combine = combine * dispatch
    return dispatch, combine, aux


class MoELayer(Layer):
    """Parity: MoELayer (moe_layer.py:261).

    experts: FFN experts constructed internally (d_model -> d_hidden ->
    d_model, GELU), weights stacked over the expert dim and annotated for
    the "ep" mesh axis. `capacity_factor` bounds tokens per expert
    (reference: capacity in gate impls).
    """

    def __init__(self, d_model, d_hidden, num_experts, gate="gshard",
                 top_k=None, capacity_factor=1.25, group=None,
                 recompute_interval=0, name=None):
        super().__init__()
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.capacity_factor = float(capacity_factor)
        if isinstance(gate, str):
            gate = {"gshard": GShardGate, "naive": GShardGate,
                    "switch": SwitchGate}[gate](d_model, num_experts)
        self.gate = gate
        self.top_k = top_k or getattr(gate, "top_k", 2)

        def expert_param(shape):
            p = Parameter(I.XavierUniform()(shape, "float32"))
            p.sharding_axes = ("ep",) + (None,) * (len(shape) - 1)
            return p

        self.w_in = self.add_parameter(
            "w_in", expert_param([num_experts, d_model, d_hidden]))
        self.b_in = self.add_parameter(
            "b_in", expert_param([num_experts, d_hidden]))
        self.w_out = self.add_parameter(
            "w_out", expert_param([num_experts, d_hidden, d_model]))
        self.b_out = self.add_parameter(
            "b_out", expert_param([num_experts, d_model]))
        self._l_aux = None
        # Switch-Transformer coefficient; the weighted aux loss is added to
        # the training objective by TrainStep/ParallelTrainStep via
        # framework.aux_loss
        self.aux_loss_weight = 0.01

    @property
    def l_aux(self) -> Optional[Tensor]:
        """Load-balancing aux loss of the last forward (reference exposes
        gate loss for the trainer to add)."""
        return self._l_aux

    def forward(self, x):
        """x: [.., S, d_model] (any leading dims)."""
        lead = x.shape[:-1]
        T = 1
        for d in lead:
            T *= int(d)
        E = self.num_experts
        C = max(int(self.capacity_factor * self.top_k * T / E), 1)

        def fn(xv, gw, wi, bi, wo, bo):
            flat = xv.reshape((T, self.d_model))
            logits = flat @ gw.astype(flat.dtype)
            dispatch, combine, aux = _gating(logits, self.top_k, C)
            dispatch = dispatch.astype(flat.dtype)
            combine = combine.astype(flat.dtype)
            # route: [T,E,C],[T,d] -> [E,C,d]  (GSPMD: all-to-all over ep)
            expert_in = jnp.einsum("tec,td->ecd", dispatch, flat)
            h = jax.nn.gelu(
                jnp.einsum("ecd,edh->ech", expert_in, wi) + bi[:, None, :])
            out_e = jnp.einsum("ech,ehd->ecd", h, wo) + bo[:, None, :]
            # un-route: [T,E,C],[E,C,d] -> [T,d]
            out = jnp.einsum("tec,ecd->td", combine, out_e)
            return out.reshape(xv.shape), aux

        out, aux = _tape.apply(fn, x, self.gate.weight, self.w_in,
                               self.b_in, self.w_out, self.b_out,
                               _op_name="moe")
        # report to the active training engine (weighted); _l_aux is kept
        # for eager inspection but holds a tracer when forward runs under
        # jit — use the aux_loss_scope value in that case
        from ..framework.aux_loss import add_aux_loss
        add_aux_loss(self.aux_loss_weight * (
            aux.value if hasattr(aux, "value") else aux))
        self._l_aux = aux   # tpulint: disable=traced-attr-mutation
        return out


# ---------------------------------------------------------------------------
# dropless token-choice layer
# ---------------------------------------------------------------------------

# what the last traced ``TokenChoiceMoE`` call did (as
# ``F.last_attention_dispatch()`` for attention): {"kernel",
# "experts_held", "experts_published", "top_k", "rows_ladder", "rows_bound",
# "tiling", "combine", "activation", "score", "router_input"}
_last_moe = {}


def last_moe_dispatch() -> dict:
    """The most recent ``TokenChoiceMoE`` dispatch: ``kernel`` (what
    multiplies the sorted rows with their experts), ``experts_held`` of
    ``experts_published``, ``top_k``, ``rows_ladder`` (the static sizes, in
    sorted rows, the layer is built for, ascending: a call runs at the
    first that holds the assignments that landed here, so its gathers,
    masks and elementwise passes cost by that rung and not by the last),
    ``rows_bound`` (the last rung, the most the sorted path holds;
    more assignments than that landing here take the dense path, none is
    dropped), ``tiling`` (the tiles of the forward products by w1 and w3
    and by w2 at ``rows_bound``, ``_gmm_tiles``; a lower rung takes its
    own by the same rule), ``combine`` (what sums the sorted rows by
    token, in the combine and in the transpose of the dispatch's gather
    alike: ``kernel`` and ``token_tile``, the tokens a group of its
    grouped product, ``_rows_to_tokens``),
    ``activation`` (the experts' gate: "silu" | "relu"), ``score`` (the
    router's rule: "sigmoid" | "softmax_of_chosen") and ``router_input``
    ("expert_input": the layer routed on the tensor its experts read;
    "given": the caller handed in a ``route()``, of whatever tensor it
    chose)."""
    return dict(_last_moe)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# rows a tile of the grouped products
_GMM_ROWS = 512


def _gmm_tiles(rows: int, k: int, n: int) -> tuple:
    """(rows, contraction, columns) a tile of ONE grouped product
    [rows, k] x [groups, k, n], from its own shapes: 512 rows, and of k and
    of n the largest divisor up to 1024 in whole 128-lane tiles, so that no
    tile is partial. Read on the chip (PERF.md section 6): (512, 1024, 1024)
    at [32768, 2048] x [16, 2048, 1024] (PR 30), and at expert width 768
    (512, 1024, 768) / (512, 768, 1024) against one tuple for all three
    products of a layer (PR 34)."""
    def whole(d):
        t = min(1024, d)
        while d % t and t > 128:
            t -= 128
        return t if d % t == 0 else d
    return (min(_GMM_ROWS, rows), whole(k), whole(n))


# the ladder of sorted rows a layer is built for, in even shares of the
# assignments (what a uniform routing lands on the experts held here). A
# call runs at the first rung that holds what landed (``_laddered``), so
# the gathers, masks and elementwise passes round the products cost by the
# rung taken: a balanced routing (0.99 - 1.02 shares a layer on every seed
# of the SmallThinker cell, PERF.md section 2) pays for 1.25, a drifting
# layer for the top. (So do the two sums of rows by token a layer and step,
# the combine and the gather's transpose, since they are a gather of the
# rung's rows and a grouped product over them, ``_rows_to_tokens``; as
# scatter-adds the chip took 8 - 9 ms a call at any rung, PERF.md section
# 6, PRs 37 and 39.) The top is three shares because of what landed on the
# chip (PERF.md section 6, PR 30): a router
# trained from a random start without a warm-up put up to 2.2 even shares
# on one layer's held experts within 25 steps, and with a top of 2 two
# seeds of nine took the dense path for some steps. Before the ladder
# every call paid for the top. Two rungs and no third between them: every
# rung is fourteen more kernels a layer in the step's program, 3 s of every
# warm set-up on the chip, and a rung at 2 shares saved the one cell whose
# layers drift 0.4% of a step
_ROWS_OVER_EVEN = (1.25, 3)


def _megablox():
    """The module of the library's grouped kernels (the package's own
    ``gmm`` attribute is its differentiable wrapper, not this)."""
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


@jax.custom_vjp
def _gmm(lhs, rhs, sizes):
    """The library's megablox product with the tiles of each of its three
    products (forward, and in the backward pass d lhs and d rhs) from that
    product's own shapes: the library's own ``custom_vjp`` hands one tuple
    to all three, and d lhs contracts over n where forward contracts
    over k."""
    return _megablox().gmm(lhs, rhs, sizes, lhs.dtype,
                           _gmm_tiles(lhs.shape[0], *rhs.shape[1:]))


def _gmm_fwd(lhs, rhs, sizes):
    return _gmm(lhs, rhs, sizes), (lhs, rhs, sizes)


def _gmm_bwd(res, grad):
    backend = _megablox()
    lhs, rhs, sizes = res
    groups, k, n = rhs.shape
    d_lhs = backend.gmm(grad, rhs, sizes, lhs.dtype,
                        _gmm_tiles(grad.shape[0], n, k), transpose_rhs=True)
    d_rhs = backend.tgmm(lhs.swapaxes(0, 1), grad, sizes, rhs.dtype,
                         _gmm_tiles(lhs.shape[0], k, n), None, groups)
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _grouped_dot(lhs, rhs, sizes):
    """lhs [rows, k] sorted by group, rhs [groups, k, n], sizes [groups]
    -> [rows, n]: rows of group g times rhs[g]. Rows past the groups'
    sum hold no result. On the chip the library's megablox kernel, which
    visits the tiles that hold rows and no others; elsewhere XLA's
    ragged dot."""
    if _on_tpu():
        return _gmm(lhs, rhs, sizes)
    return lax.ragged_dot(lhs, rhs, sizes)


@jax.custom_vjp
def _sorted_weights(wgt, slot, pos):
    """wgt [T, k] -> the weight of each sorted row, wgt.ravel()[slot].
    Differentiated as it stands it would scatter scalars one at a time;
    its transpose is written as the gather it is (``pos``: where each
    assignment stands among the rows, len(slot) for one that does not)."""
    return wgt.reshape(-1)[slot]


def _sorted_weights_fwd(wgt, slot, pos):
    return wgt.reshape(-1)[slot], pos


def _sorted_weights_bwd(pos, d_rows):
    return jnp.concatenate([d_rows, jnp.zeros((1,), d_rows.dtype)])[pos], \
        None, None


_sorted_weights.defvjp(_sorted_weights_fwd, _sorted_weights_bwd)


# tokens a tile of ``_rows_to_tokens``' product
_TOKEN_TILE = 256


def _token_tile(tokens: int) -> int:
    """How many consecutive tokens share a group of ``_rows_to_tokens``'
    grouped product, from the shapes alone: 256, or every token (in whole
    sublanes) where there are fewer. The one-hot operand is [rows, tile]
    and the product costs 2 * rows * tile * d operations, so a smaller tile
    is less work and more groups: on the chip 128 read within 0.02 ms of
    256 and 512 up to 0.08 ms slower, of 0.36 - 0.56 a call (PERF.md
    section 6, PR 39). The product's result is
    [tokens / tile, tile, d]: a tile that made it the shape of the experts'
    stack [held, d_expert, d] would have the benchmark's readers count
    these kernels among the experts' products (they find those by
    shape)."""
    return _TOKEN_TILE if tokens >= _TOKEN_TILE else -(-tokens // 8) * 8


def _by_token_tiles(rows: int, tile: int, y) -> tuple:
    """(rows, tokens, columns) a tile of ``_rows_to_tokens``' product on
    the chip: 256 rows (a tile of 256 tokens owns 384 landed rows of an
    even routing in the SmallThinker cell, so a longer tile of rows
    straddles more groups), and the whole width where the kernel's
    buffers for it (the float32 accumulator, two result blocks, two
    blocks of rows) stay within 10 MiB of the 16 it may take, else as
    ``_gmm_tiles``. Read on the chip (PERF.md section 6, PR 39): at
    [30720, 2560] (256, 256, 2560) 0.56 ms against (512, 256, 640) 0.83;
    (1024, 256, 2560) and (512, 512, 2560) do not fit."""
    tm = 256 if rows % 256 == 0 else min(_GMM_ROWS, rows)
    d, size = y.shape[1], y.dtype.itemsize
    fits = d * (4 * tile + 2 * size * (tile + tm)) <= 10 * 2 ** 20
    return tm, tile, d if fits else _gmm_tiles(rows, tile, d)[2]


def _rows_to_tokens(y, back, tokens):
    """out[t] = the sum of the sorted rows y[r] whose token is t, [tokens,
    d], summed in float32. ``back``: ``perm`` and ``col`` [rows], the
    sorted row and the place within its tile of tokens of each landed
    assignment in TOKEN order, ``tile_sizes``, the landed rows of each tile
    of tokens, and ``landed`` (``_sorted_index``).

    The rows are put in token order (a gather of rows) and summed by ONE
    grouped product whose groups are the tiles of tokens: a one-hot
    [rows, tile], one at (r, col[r]) for a landed row, times the rows
    [rows, d], contracted over each tile's own rows. On the chip the
    library's megablox ``tgmm`` (the kernel of the experts' weight
    gradients), which visits the tiles of rows that hold landed rows and no
    others and writes noughts for a tile of tokens that owns none; elsewhere
    XLA's dot ragged in its contracted dimension. As a scatter-add of
    model-width rows the chip sorted the token indices, permuted the rows
    and added them one at a time: 8 - 9 ms a call whatever the rows, where
    this gather takes 1.2 and the product under one (PERF.md section 6,
    PR 39)."""
    perm, col, tile_sizes, landed = back
    rows, tiles = y.shape[0], tile_sizes.shape[0]
    tile = _token_tile(tokens)
    live = jnp.arange(rows) < landed
    # slots past what landed name no row: their one-hot row is noughts
    # (and neither backend reads rows past the groups' sum)
    onehot = ((col[:, None] == jnp.arange(tile, dtype=col.dtype))
              & live[:, None]).astype(y.dtype)
    by_token = y[jnp.where(live, perm, 0)]
    if _on_tpu():
        out = _megablox().tgmm(onehot.swapaxes(0, 1), by_token, tile_sizes,
                               y.dtype, _by_token_tiles(rows, tile, y), None,
                               tiles)
    else:
        out = lax.ragged_dot_general(
            onehot, by_token, tile_sizes, lax.RaggedDotDimensionNumbers(
                (([0], [0]), ([], [])), [0], []),
            preferred_element_type=jnp.float32).astype(y.dtype)
    return out.reshape(tiles * tile, -1)[:tokens]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _tokens_to_rows(tokens, x, tok, back):
    """x [tokens, d] -> the sorted rows' inputs x[tok]. Its transpose is
    the sum of the rows by token, written as ``_rows_to_tokens`` and not
    as the scatter-add that differentiating the gather would give."""
    return x[tok]


def _tokens_to_rows_fwd(tokens, x, tok, back):
    return x[tok], back


def _tokens_to_rows_bwd(tokens, back, d_rows):
    return _rows_to_tokens(d_rows, back, tokens), None, None


_tokens_to_rows.defvjp(_tokens_to_rows_fwd, _tokens_to_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine(tokens, y, tok, back):
    """The sorted rows y back to their tokens, ``_rows_to_tokens``; its
    transpose is the gather it is."""
    return _rows_to_tokens(y, back, tokens)


def _combine_fwd(tokens, y, tok, back):
    return _rows_to_tokens(y, back, tokens), tok


def _combine_bwd(tokens, tok, d_out):
    return d_out[tok], None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


# the gate's activation of a gated expert, by its name in the layer's
# settings: SwiGLU (arXiv:2002.05202) or its ReLU form, ReGLU
_GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _glu(x, w1, w3, w2, dot, act):
    return dot(act(dot(x, w1)) * dot(x, w3), w2)


def _sorted_index(local, here, held):
    """The index work of the sorted path, which no rung's size enters:
    ``order`` [T * k], the assignments sorted by the held expert they chose
    (a stable sort; those held elsewhere last), ``pos`` [T, k], where each
    assignment stands in that order, ``sizes`` [held], the rows of each
    expert's group; for ``_rows_to_tokens``, ``perm`` and ``col``
    [T * k], the sorted row and the place within its tile of tokens of
    each landed assignment in TOKEN order, and ``tile_sizes``, the landed
    assignments of each tile of tokens; and ``landed``, their sum. Made
    once a call, outside the conditional, so that it waits for nothing but
    the routing.

    Three sorts and no gather: on the chip a sort of these 98,304 keys
    with what it carries along takes 0.2 ms and a gather of as many
    integers 0.5 - 0.75 (PERF.md section 6, PR 39), so whatever follows a
    permutation rides the sort as a further operand. The first gives the
    sorted keys for the groups' bounds beside ``order``; ``order`` sorted
    again is ``pos``; and the landed rows sorted by their assignment's
    number are in token order (a token's assignments are numbered
    together), carrying their own number, ``perm``, and their token's
    place in its tile, ``col``."""
    with jax.named_scope("sort"):
        tokens, k = here.shape
        count = jnp.arange(tokens * k, dtype=jnp.int32)
        key = jnp.where(here, local, held).reshape(-1).astype(jnp.int32)
        key, order = lax.sort((key, count), num_keys=1, is_stable=True)
        bounds = jnp.searchsorted(key, jnp.arange(held + 1, dtype=key.dtype))
        sizes = jnp.diff(bounds).astype(jnp.int32)
        landed = bounds[held]
        pos = lax.sort((order, count), num_keys=1)[1].reshape(here.shape)
        tile = _token_tile(tokens)
        _, perm, col = lax.sort(
            (jnp.where(count < landed, order, tokens * k), count,
             order // k % tile), num_keys=1)
        tile_sizes = jnp.sum(
            jnp.pad(here, ((0, -tokens % tile), (0, 0))).reshape(-1, tile * k),
            axis=1, dtype=jnp.int32)
        return order, pos, sizes, perm, col, tile_sizes, landed


def _routed_sorted(x, w1, w3, w2, wgt, here, index, rows, act):
    """The held experts' part for x [T, d] by sorted rows: ``rows`` of
    them, which must hold every assignment that landed here. ``index``:
    ``_sorted_index`` of the routing, of which this takes the first
    ``rows``."""
    order, pos, sizes, perm, col, tile_sizes, landed = index
    T, k = here.shape
    with jax.named_scope("dispatch"):
        # where each assignment stands in the sorted order; `rows` (the
        # row of noughts) for one that is held elsewhere
        pos = jnp.where(here & (pos < rows), pos, rows)
        first = lambda a: jnp.pad(a, (0, max(0, rows - T * k)))[:rows]
        slot = first(order)
        tok = slot // k
        back = (first(perm), first(col), tile_sizes, landed)
        # rows past the groups are written by no product, forward or
        # backward, and hold whatever the buffer held: nought on both
        # sides
        live = (jnp.arange(rows) < landed)[:, None]
        rows_in = jnp.where(live, _tokens_to_rows(T, x, tok, back), 0)
    with jax.named_scope("products"):
        y = _glu(rows_in, w1, w3, w2,
                 functools.partial(_grouped_dot, sizes=sizes), act)
    with jax.named_scope("combine"):
        y = jnp.where(live, y, 0)
        y = y * _sorted_weights(wgt, slot, pos)[:, None].astype(y.dtype)
        # each row back to its token: the rows in token order, then one
        # grouped product by tiles of tokens. (A gather of [T, k] rows
        # through `pos` with a sum over k reads every assignment of every
        # token where a share of them lands: 6.1 ms for 3.0 on the chip,
        # PERF.md PR 30.)
        return _combine(T, y, tok, back)


def _rung(landed, ladder):
    """Which of ``ladder``'s ascending sizes is the first to hold
    ``landed`` rows; ``len(ladder)`` when none does."""
    return sum((landed > rows).astype(jnp.int32) for rows in ladder)


def _routed_dense(x, w1, w3, w2, wgt, local, here, act):
    """The same part with no bound on the rows: every held expert over
    every token, by the token's weight for it (nought where it did not
    choose it). ``held`` times the work; the path of a routing that lands
    more here than the sorted rows hold."""
    held = w1.shape[0]
    with jax.named_scope("dispatch"):
        cw = jnp.einsum("tk,tke->te", jnp.where(here, wgt, 0),
                        jax.nn.one_hot(local, held, dtype=wgt.dtype))

    @jax.checkpoint
    def one(a, b, c, w):
        with jax.named_scope("products"):
            y = _glu(x, a, b, c, jnp.dot, act)
        with jax.named_scope("combine"):
            return w[:, None].astype(x.dtype) * y

    def add(acc, e):
        y = one(*e)
        with jax.named_scope("combine"):
            return acc + y, None
    return lax.scan(add, jnp.zeros_like(x), (w1, w3, w2, cw.T))[0]


# the ``checkpoint_name`` of the held experts' result [T, d] where it is
# differentiated: ``distributed/recompute.py``'s named policies keep it, so
# that a block that reads it again in its backward pass (a norm behind the
# layer, ``models/afmoe.py``) does not run the conditional a third time
# for it; where nothing reads it, nothing is kept
EXPERTS_RESULT = "expert_layer_result"


@functools.lru_cache(maxsize=None)
def _traced_once(path, on_tpu, *static):
    """``path`` with its last arguments ``static`` under ``jax.jit``, one
    function object for each: every layer of a model, and in each the
    forward conditional, its forward rule and the backward conditional,
    then share one trace and one lowering of a path at given shapes, where
    each would trace it anew (PERF.md section 6, PR 37). ``on_tpu``
    only keys the cache: the path asks ``_on_tpu()`` itself when traced."""
    def traced(*args):
        return path(*args, *static)
    traced.__name__ = path.__name__.strip("_")
    return jax.jit(traced)


def _paths(ladder, act, local, here, index):
    """The ladder's conditional as functions of (x, w1, w3, w2, wgt): the
    sorted path at each of ``ladder``'s sizes, the dense path last."""
    def rung(rows):
        run = _traced_once(_routed_sorted, _on_tpu(), rows, act)
        return lambda *data: run(*data, here, index)
    dense = _traced_once(_routed_dense, _on_tpu(), act)
    return [rung(rows) for rows in ladder] + [
        lambda *data: dense(*data, local, here)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _laddered(ladder, act, x, w1, w3, w2, wgt, local, here, index):
    """One conditional: the first of ``ladder``'s sizes that holds the
    assignments that landed, the dense path past the last.

    Differentiated as it stands, a conditional hands its backward pass
    the union of its branches' residuals, and the branch taken writes
    noughts for the others': the tight rung wrote and held what the wide
    ones keep (on the chip 14 ms a step and 1.7 GiB in the SmallThinker
    cell, where the rung saved 33; padding every rung's residuals to one
    shape cost as much in copies; PERF.md section 6, PR 37). So this keeps
    its inputs alone, and the backward pass is one conditional too, whose
    branch runs its path again and then its transpose: nothing sized by a
    rung crosses a conditional. In a recomputed block (every cell's) that
    costs nothing: the result is named ``EXPERTS_RESULT`` and kept where
    the backward pass reads it, so the recomputed forward conditional has
    no reader and goes, and a path still runs twice a step. A layer
    trained without recomputation runs its experts' forward twice where it
    ran once, and keeps none of their residuals between the passes."""
    return lax.switch(_rung(index[-1], ladder),
                      _paths(ladder, act, local, here, index),
                      x, w1, w3, w2, wgt)


def _laddered_fwd(ladder, act, *args):
    return checkpoint_name(_laddered(ladder, act, *args), EXPERTS_RESULT), \
        args


def _laddered_bwd(ladder, act, args, grad):
    *data, local, here, index = args
    pulls = [lambda *d, path=path: jax.vjp(path, *d)[1](grad)
             for path in _paths(ladder, act, local, here, index)]
    return (*lax.switch(_rung(index[-1], ladder), pulls, *data),
            None, None, None)


_laddered.defvjp(_laddered_fwd, _laddered_bwd)


def _routed(x, w1, w3, w2, wgt, sel, offset, ladder, act):
    """The part of the ``held`` experts w1, w3, w2, published as
    ``offset .. offset + held``, for x [T, d] under the choice sel and
    weights wgt [T, k], at the first of ``ladder``'s sizes that holds the
    assignments that landed here, dense past the last."""
    held = w1.shape[0]
    local = sel - offset
    here = (local >= 0) & (local < held)
    w1, w3, w2 = (w.astype(x.dtype) for w in (w1, w3, w2))
    return _laddered(ladder, act, x, w1, w3, w2, wgt, local, here,
                     _sorted_index(local, here, held))


class _Router(Layer):
    """Scores of every published expert and the choice among them.
    ``score`` "sigmoid": a sigmoid of each logit, the chosen ones' over
    their sum where ``route_norm``; "softmax_of_chosen": the choice by the
    raw logits and a softmax over the chosen ones alone (which sums to 1:
    ``route_norm`` changes nothing)."""

    def __init__(self, d_model, num_experts, top_k, route_norm, route_scale,
                 init, score="sigmoid"):
        super().__init__()
        if score not in ("sigmoid", "softmax_of_chosen"):
            raise ValueError(f"score {score!r}: sigmoid or "
                             "softmax_of_chosen")
        self.score = score
        self.top_k, self.route_norm = int(top_k), bool(route_norm)
        self.route_scale = float(route_scale)
        self.num_experts = int(num_experts)
        self.weight = self.create_parameter([d_model, num_experts],
                                            default_initializer=init)

    def forward(self, x, bias):
        """x [T, d] -> (sel [T, k] int32, weights [T, k] f32, counts
        [E] f32). The bias enters the choice, never the weight."""
        softmax = self.score == "softmax_of_chosen"

        def scores(xv, w):
            logits = jnp.dot(xv, w.astype(xv.dtype),
                             preferred_element_type=jnp.float32)
            return logits if softmax else jax.nn.sigmoid(logits)

        def choose(s, b):
            sel = lax.top_k(s + b, self.top_k)[1].astype(jnp.int32)
            counts = jnp.sum(jax.nn.one_hot(sel, self.num_experts,
                                            dtype=jnp.float32), axis=(0, 1))
            return sel, counts

        def weigh(s, sel):
            w = jnp.take_along_axis(s, sel, axis=-1)
            if softmax:
                w = jax.nn.softmax(w, axis=-1)
            elif self.route_norm:
                w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
            return w * self.route_scale

        s = _tape.apply(scores, x, self.weight, _op_name="moe_scores")
        with _tape.no_grad():
            sel, counts = _tape.apply(choose, s, bias, _op_name="moe_choose")
        w = _tape.apply(weigh, s, sel, _op_name="moe_weigh")
        return sel, w, counts


class _Experts(Layer):
    """The gated experts held here, w2(act(w1 x) * w3 x), stacked
    [held, ...] over "ep"."""

    def __init__(self, d_model, d_expert, held, offset, published, top_k,
                 init, activation="silu"):
        super().__init__()
        if activation not in _GATES:
            raise ValueError(f"activation {activation!r}: one of "
                             f"{sorted(_GATES)}")
        self.activation = activation
        self.held, self.offset = int(held), int(offset)
        self.published, self.top_k = int(published), int(top_k)
        self.ladder = None      # ``rows_ladder`` of the last call traced

        def stacked(shape):
            p = Parameter(init(shape, "float32"))
            p.sharding_axes = ("ep",) + (None,) * (len(shape) - 1)
            return p
        self.w1 = self.add_parameter("w1", stacked([held, d_model, d_expert]))
        self.w3 = self.add_parameter("w3", stacked([held, d_model, d_expert]))
        self.w2 = self.add_parameter("w2", stacked([held, d_expert, d_model]))

    def rows_ladder(self, tokens: int) -> tuple:
        """The sizes, in sorted rows, a call over ``tokens`` tokens may
        run at, ascending: ``_ROWS_OVER_EVEN`` times what an even routing
        lands here, each at most what any routing can (each token's top-k
        are distinct experts) and in whole tiles; equal rungs are one. From
        shapes alone."""
        worst = tokens * min(self.top_k, self.held)
        even = -(-tokens * self.top_k * self.held // self.published)

        def whole(rows):
            unit = _GMM_ROWS if rows >= _GMM_ROWS else 16
            return -(-rows // unit) * unit
        return tuple(sorted({whole(min(worst, math.ceil(share * even)))
                             for share in _ROWS_OVER_EVEN}))

    def rows_bound(self, tokens: int) -> int:
        """The most sorted rows the grouped products are built for, the
        ladder's last rung: what lands past it takes the dense path."""
        return self.rows_ladder(tokens)[-1]

    def forward(self, x, sel, wgt):
        """x [T, d], sel and wgt [T, k] -> sum over the experts held
        here of wgt * expert(x), [T, d]."""
        ladder = self.rows_ladder(int(x.shape[0]))
        # static, of the shapes alone: ``note_load`` counts the call's rung
        # by the ladder the call was built with
        self.ladder = ladder   # tpulint: disable=traced-attr-mutation
        _last_moe.clear()
        _last_moe.update(
            kernel="megablox_gmm" if _on_tpu() else "xla_ragged_dot",
            experts_held=self.held, experts_published=self.published,
            top_k=self.top_k, rows_ladder=ladder, rows_bound=ladder[-1],
            activation=self.activation,
            combine={"kernel": "megablox_tgmm_by_token_tile" if _on_tpu()
                     else "xla_ragged_dot_by_token_tile",
                     "token_tile": _token_tile(int(x.shape[0]))},
            tiling={"w1_w3": _gmm_tiles(ladder[-1], *self.w1.shape[1:]),
                    "w2": _gmm_tiles(ladder[-1], *self.w2.shape[1:])})

        fn = functools.partial(_routed, offset=self.offset, ladder=ladder,
                               act=_GATES[self.activation])
        return _tape.apply(fn, x, self.w1, self.w3, self.w2, wgt, sel,
                           _op_name="moe_experts")


class TokenChoiceMoE(Layer):
    """Dropless token-choice mixture of gated experts (SwiGLU;
    ``activation="relu"``: ReGLU).

    ``num_experts`` is the published count the router scores;
    ``experts_held`` of them, from ``expert_offset`` on, live here (one
    chip's share of expert parallelism; all of them by default).
    Assignments to experts held elsewhere add nothing here: with every
    share's output and the shared expert counted once, the shares add up
    to the whole layer. Nothing is dropped under any routing.

    The experts run on the assignments that land here sorted by expert, at
    a static number of rows chosen per call from a short ladder
    (``experts.rows_ladder(tokens)``: 1.25 and 3 even shares of the
    assignments, from shapes alone): the first rung that holds what
    landed, and past the last (three shares: what a router trained from a
    random start was seen to land, ``_ROWS_OVER_EVEN``) a dense path of
    every held expert over every token. So the cost of the gathers, masks
    and elementwise passes round the products follows the rung taken, the
    count that landed alone chooses it, and the buffer ``rows_rung_total`` says how often
    each was (one entry a rung, the dense path last; a running count of
    calls that ``note_load`` adds to).

    ``score`` is the router's rule (``_Router``). The router reads what
    the experts read unless the caller routes apart: ``route(t)`` gives
    the choice, weights and counts for another tensor of the same tokens
    (the block's input, before attention, in ``models/smallthinker.py``),
    and ``forward(x, routing=...)`` runs the experts on x under it. The
    sort and the sizes of the dispatch then depend on ``t`` alone.

    ``forward(x)`` returns ``(y, counts)``: counts [num_experts] of the
    tokens that chose each expert, a VALUE, so that the layer runs under
    ``jax.checkpoint``; the caller hands it to ``note_load`` outside the
    recomputed region, which keeps it in the buffer ``expert_load``, adds
    it to the buffer ``expert_load_total`` (the counts of every step so
    far: the difference of two readings is what a span of steps routed)
    and moves the buffer ``expert_bias`` by ``bias_update_rate * sign(mean
    - count)`` (auxiliary-loss-free balancing, arXiv:2408.15664). The
    buffers ride ``TrainStep``'s buffers and stay float32 whatever the
    weights are cast to.
    """

    _fixed_dtype_buffers = frozenset({"expert_bias", "expert_load",
                                      "expert_load_total",
                                      "rows_rung_total"})

    def __init__(self, d_model, d_expert, num_experts, top_k,
                 experts_held=None, expert_offset=0, shared_expert=None,
                 route_norm=True, route_scale=1.0, bias_update_rate=0.001,
                 initializer_range=0.02, score="sigmoid",
                 activation="silu"):
        super().__init__()
        held = num_experts if experts_held is None else int(experts_held)
        if not 0 <= expert_offset <= num_experts - held:
            raise ValueError(f"experts {expert_offset}..{expert_offset + held}"
                             f" are not among the {num_experts} published")
        init = I.Normal(0.0, initializer_range)
        self.router = _Router(d_model, num_experts, top_k, route_norm,
                              route_scale, init, score)
        self.experts = _Experts(d_model, d_expert, held, expert_offset,
                                num_experts, top_k, init, activation)
        self.shared_expert = shared_expert
        self.bias_update_rate = float(bias_update_rate)
        self.register_buffer("expert_bias", Tensor(
            jnp.zeros((num_experts,), jnp.float32), stop_gradient=True))
        for name in ("expert_load", "expert_load_total"):
            self.register_buffer(name, Tensor(
                jnp.zeros((num_experts,), jnp.float32), stop_gradient=True))
        self.register_buffer("rows_rung_total", Tensor(
            jnp.zeros((len(_ROWS_OVER_EVEN) + 1,), jnp.float32),
            stop_gradient=True))

    def route(self, x):
        """x [..., d_model] -> (sel [T, k], weights [T, k], counts
        [num_experts]) over its T tokens: what ``forward`` takes as
        ``routing``."""
        from .. import tensor as T
        return self.router(T.reshape(x, [-1, x.shape[-1]]), self.expert_bias)

    def forward(self, x, routing=None):
        """x [..., d_model] -> (y, counts); ``routing``: a ``route()`` of
        the same tokens, made from another tensor than x."""
        from .. import tensor as T
        flat = T.reshape(x, [-1, x.shape[-1]])
        sel, wgt, counts = self.router(flat, self.expert_bias) \
            if routing is None else routing
        y = self.experts(flat, sel, wgt)
        _last_moe.update(score=self.router.score,
                         router_input="expert_input" if routing is None
                         else "given")
        if self.shared_expert is not None:
            y = y + self.shared_expert(flat)
        return T.reshape(y, list(x.shape)), counts

    def note_load(self, counts):
        """Keep a step's counts, count the rung its call took and move
        the bias by them (no gradient). Call it once a training step,
        outside any recomputed region."""
        c = counts.value if isinstance(counts, Tensor) else counts
        c = lax.stop_gradient(c).astype(jnp.float32)
        self.expert_load.value = c
        self.expert_load_total.value = self.expert_load_total.value + c
        e = self.experts
        if e.ladder is not None:
            # the rung by the forward's own rule; where equal rungs were
            # one the entries between stay nought and the last is still
            # the dense path's
            taken = _rung(jnp.sum(c[e.offset:e.offset + e.held]), e.ladder)
            last = self.rows_rung_total.shape[0] - 1
            self.rows_rung_total.value = self.rows_rung_total.value + \
                jax.nn.one_hot(jnp.where(taken < len(e.ladder), taken, last),
                               last + 1, dtype=jnp.float32)
        self.expert_bias.value = self.expert_bias.value + \
            self.bias_update_rate * jnp.sign(jnp.mean(c) - c)
