"""Loss functionals.

Parity: python/paddle/nn/functional/loss.py (+ softmax_with_cross_entropy —
the TP-sharded variant lives in ..distributed.parallel_cross_entropy,
matching reference c_softmax_with_cross_entropy_op).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...autograd.tape import apply
from ...core.tensor import Tensor
from ...framework.env import bool_env
from ...kernels.fused_ce import ce_bwd, ce_fwd, online_lse

__all__ = [
    "cross_entropy", "softmax_with_cross_entropy", "binary_cross_entropy",
    "binary_cross_entropy_with_logits", "mse_loss", "l1_loss", "nll_loss",
    "kl_div", "smooth_l1_loss", "margin_ranking_loss", "hinge_embedding_loss",
    "cosine_embedding_loss", "triplet_margin_loss", "log_loss", "square_error_cost",
    "sigmoid_focal_loss", "ctc_loss", "poisson_nll_loss", "multi_label_soft_margin_loss",
    "soft_margin_loss", "gaussian_nll_loss", "multi_margin_loss",
    "triplet_margin_with_distance_loss", "hsigmoid_loss", "rnnt_loss",
    "fused_linear_cross_entropy",
]


def _reduce(v, reduction):
    if reduction == "mean":
        return jnp.mean(v)
    if reduction == "sum":
        return jnp.sum(v)
    return v


@jax.custom_vjp
def _fused_softmax_ce(lg, idx):
    """Hard-label softmax cross-entropy over the last axis without ever
    materializing log_softmax: per = logsumexp(lg) - lg[idx].

    The role of the reference's fused softmax-CE kernels
    (paddle/phi/kernels/gpu/cross_entropy_kernel.cu): the naive
    composition materializes two fp32 [N, vocab] arrays (profiled at
    ~10ms/step on the GPT-125M bench); here forward is two streaming
    reductions and backward is one fused elementwise pass.
    """
    per, _ = _fused_softmax_ce_fwd(lg, idx)
    return per


def _fused_softmax_ce_fwd(lg, idx):
    m = jax.lax.stop_gradient(jnp.max(lg, axis=-1))
    mf = m.astype(jnp.float32)
    # convert+sub+exp fuse into the reduce: one pass over lg, no fp32 copy
    s = jnp.sum(jnp.exp(lg.astype(jnp.float32) - mf[..., None]), axis=-1)
    gold = jnp.take_along_axis(lg, idx[..., None], axis=-1)[..., 0]
    per = jnp.log(s) + mf - gold.astype(jnp.float32)
    return per, (lg, idx, mf, s)


def _fused_softmax_ce_bwd(res, g):
    lg, idx, mf, s = res
    p = jnp.exp(lg.astype(jnp.float32) - mf[..., None]) / s[..., None]
    onehot = (jnp.arange(lg.shape[-1], dtype=idx.dtype)
              == idx[..., None])
    dlg = (p - onehot.astype(jnp.float32)) * g[..., None].astype(jnp.float32)
    return dlg.astype(lg.dtype), None


_fused_softmax_ce.defvjp(_fused_softmax_ce_fwd, _fused_softmax_ce_bwd)


def _fused_ce_on() -> bool:
    """A/B knob for the Pallas fused-CE kernels (ISSUE 19). Trace-time
    read, like the flash_attention fusion knobs."""
    return bool_env("PADDLE_TPU_FUSED_CE", False)


@jax.custom_vjp
def _pallas_softmax_ce(lg, idx):
    """kernels/fused_ce.py dispatch (PADDLE_TPU_FUSED_CE): forward is
    ONE streaming pass per row — the (max, sum-exp) logsumexp monoid —
    and backward one pass with the one-hot folded into the epilogue.
    On TPU the passes are the Pallas kernels; on CPU the forward uses
    ``online_lse`` (the monoid as one variadic ``lax.reduce``, which XLA
    compiles to a single pass — measured: the separate max pass and the
    materialized exp of ``_fused_softmax_ce`` both disappear from the
    train-step inventory)."""
    per, _ = _pallas_softmax_ce_fwd(lg, idx)
    return per


def _pallas_softmax_ce_fwd(lg, idx):
    shp, V = lg.shape[:-1], lg.shape[-1]
    lg2 = lg.reshape(-1, V)
    idx2 = idx.reshape(-1).astype(jnp.int32)
    from .flash_attention import _on_tpu
    if _on_tpu():
        per, lse = ce_fwd(lg2, idx2)
    else:
        lse = online_lse(lg2)
        gold = jnp.take_along_axis(lg2, idx2[:, None], axis=-1)[:, 0]
        per = lse - gold.astype(jnp.float32)
    return per.reshape(shp), (lg, idx2, lse)


def _pallas_softmax_ce_bwd(res, g):
    lg, idx2, lse = res
    V = lg.shape[-1]
    lg2 = lg.reshape(-1, V)
    g2 = g.reshape(-1).astype(jnp.float32)
    from .flash_attention import _on_tpu
    if _on_tpu():
        dlg = ce_bwd(lg2, idx2, lse, g2)
    else:
        p = jnp.exp(lg2.astype(jnp.float32) - lse[:, None])
        onehot = (jnp.arange(V, dtype=jnp.int32) == idx2[:, None])
        dlg = ((p - onehot.astype(jnp.float32))
               * g2[:, None]).astype(lg.dtype)
    return dlg.reshape(lg.shape), None


_pallas_softmax_ce.defvjp(_pallas_softmax_ce_fwd, _pallas_softmax_ce_bwd)


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0, name=None):
    """Parity: paddle.nn.functional.cross_entropy. Computes in fp32 for
    stability regardless of input dtype (bf16-safe)."""
    lbl = label.value if isinstance(label, Tensor) else jnp.asarray(label)

    def f(logits, *w):
        if (use_softmax and not soft_label and not w
                and label_smoothing == 0.0
                and axis in (-1, logits.ndim - 1)
                and not (lbl.ndim == logits.ndim and lbl.shape == logits.shape
                         and jnp.issubdtype(lbl.dtype, jnp.floating))):
            idx = lbl
            if idx.ndim == logits.ndim:
                idx = jnp.squeeze(idx, axis=-1)
            idx_c = jnp.clip(idx, 0, logits.shape[-1] - 1).astype(jnp.int32)
            ce = (_pallas_softmax_ce if _fused_ce_on()
                  else _fused_softmax_ce)
            per = ce(logits, idx_c)
            mask = (idx != ignore_index)
            per = jnp.where(mask, per, 0.0)
            if reduction == "mean":
                return jnp.sum(per) / jnp.maximum(jnp.sum(mask), 1)
            return _reduce(per, reduction)
        lg = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(lg, axis=axis) if use_softmax else jnp.log(
            jnp.maximum(lg, 1e-30))
        if soft_label or (lbl.ndim == logp.ndim and lbl.shape == logp.shape
                          and jnp.issubdtype(lbl.dtype, jnp.floating)):
            tgt = lbl.astype(jnp.float32)
            if label_smoothing > 0:
                k = logp.shape[axis]
                tgt = (1 - label_smoothing) * tgt + label_smoothing / k
            per = -jnp.sum(tgt * logp, axis=axis)
        else:
            idx = lbl
            if idx.ndim == logp.ndim:
                idx = jnp.squeeze(idx, axis=axis)
            idx_c = jnp.clip(idx, 0, logp.shape[axis] - 1)
            per = -jnp.take_along_axis(
                logp, idx_c[..., None].astype(jnp.int32), axis=axis)[..., 0]
            if label_smoothing > 0:
                k = logp.shape[axis]
                smooth = -jnp.mean(logp, axis=axis)
                per = (1 - label_smoothing) * per + label_smoothing * smooth
            mask = (idx != ignore_index)
            per = jnp.where(mask, per, 0.0)
            if w:
                wt = jnp.take(w[0], idx_c, axis=0)
                per = per * wt
            if reduction == "mean":
                denom = (jnp.maximum(jnp.sum(jnp.take(w[0], idx_c, axis=0)
                                             * mask), 1e-12)
                         if w else jnp.maximum(jnp.sum(mask), 1))
                return jnp.sum(per) / denom
        return _reduce(per, reduction)

    args = [input] + ([weight] if weight is not None else [])
    return apply(f, *args, _op_name="cross_entropy")


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none",
                         axis=axis)
    from .activation import softmax as softmax_fn
    from ...tensor.manipulation import unsqueeze
    loss = unsqueeze(loss, axis)
    if return_softmax:
        return loss, softmax_fn(logits, axis=axis)
    return loss


def binary_cross_entropy(input, label, weight=None, reduction="mean",
                         name=None):
    def f(p, t, *w):
        per = -(t * jnp.log(jnp.maximum(p, 1e-12))
                + (1 - t) * jnp.log(jnp.maximum(1 - p, 1e-12)))
        if w:
            per = per * w[0]
        return _reduce(per, reduction)
    args = [input, label] + ([weight] if weight is not None else [])
    return apply(f, *args, _op_name="binary_cross_entropy")


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None,
                                     name=None):
    def f(lg, t, *rest):
        lg32 = lg.astype(jnp.float32)
        t32 = t.astype(jnp.float32)
        maxv = jnp.maximum(-lg32, 0.0)
        per = (1 - t32) * lg32 + maxv + jnp.log(
            jnp.exp(-maxv) + jnp.exp(-lg32 - maxv))
        i = 0
        if pos_weight is not None:
            pw = rest[i]; i += 1
            log_w = (pw - 1) * t32 + 1
            per = per * log_w
        if weight is not None:
            per = per * rest[i]
        return _reduce(per, reduction)
    args = [logit, label]
    if pos_weight is not None:
        args.append(pos_weight)
    if weight is not None:
        args.append(weight)
    return apply(f, *args, _op_name="bce_with_logits")


def mse_loss(input, label, reduction="mean", name=None):
    return apply(lambda a, b: _reduce(jnp.square(a - b), reduction),
                 input, label, _op_name="mse_loss")


def square_error_cost(input, label):
    return apply(lambda a, b: jnp.square(a - b), input, label,
                 _op_name="square_error_cost")


def l1_loss(input, label, reduction="mean", name=None):
    return apply(lambda a, b: _reduce(jnp.abs(a - b), reduction),
                 input, label, _op_name="l1_loss")


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    lbl = label.value if isinstance(label, Tensor) else jnp.asarray(label)

    def f(logp, *w):
        idx_c = jnp.clip(lbl, 0, logp.shape[1] - 1).astype(jnp.int32)
        per = -jnp.take_along_axis(logp, idx_c[:, None], axis=1)[:, 0]
        mask = lbl != ignore_index
        per = jnp.where(mask, per, 0.0)
        if w:
            wt = jnp.take(w[0], idx_c, axis=0) * mask
            if reduction == "mean":
                return jnp.sum(per * jnp.take(w[0], idx_c, axis=0)) / \
                    jnp.maximum(jnp.sum(wt), 1e-12)
            per = per * jnp.take(w[0], idx_c, axis=0)
        elif reduction == "mean":
            return jnp.sum(per) / jnp.maximum(jnp.sum(mask), 1)
        return _reduce(per, reduction)

    args = [input] + ([weight] if weight is not None else [])
    return apply(f, *args, _op_name="nll_loss")


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    def f(lp, t):
        if log_target:
            per = jnp.exp(t) * (t - lp)
        else:
            per = t * (jnp.log(jnp.maximum(t, 1e-12)) - lp)
        if reduction == "batchmean":
            return jnp.sum(per) / lp.shape[0]
        return _reduce(per, reduction)
    return apply(f, input, label, _op_name="kl_div")


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    def f(a, b):
        d = jnp.abs(a - b)
        per = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
        return _reduce(per, reduction)
    return apply(f, input, label, _op_name="smooth_l1_loss")


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    return apply(lambda a, b, t: _reduce(
        jnp.maximum(-t * (a - b) + margin, 0.0), reduction),
        input, other, label, _op_name="margin_ranking_loss")


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    return apply(lambda a, t: _reduce(
        jnp.where(t == 1, a, jnp.maximum(margin - a, 0.0)), reduction),
        input, label, _op_name="hinge_embedding_loss")


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean",
                          name=None):
    def f(a, b, t):
        cos = jnp.sum(a * b, axis=-1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1), 1e-12)
        per = jnp.where(t == 1, 1 - cos, jnp.maximum(cos - margin, 0.0))
        return _reduce(per, reduction)
    return apply(f, input1, input2, label, _op_name="cosine_embedding_loss")


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean", name=None):
    def f(a, pos, neg):
        dp = jnp.linalg.norm(a - pos + epsilon, ord=p, axis=-1)
        dn = jnp.linalg.norm(a - neg + epsilon, ord=p, axis=-1)
        if swap:
            dn2 = jnp.linalg.norm(pos - neg + epsilon, ord=p, axis=-1)
            dn = jnp.minimum(dn, dn2)
        return _reduce(jnp.maximum(dp - dn + margin, 0.0), reduction)
    return apply(f, input, positive, negative, _op_name="triplet_margin_loss")


def log_loss(input, label, epsilon=1e-4, name=None):
    return apply(lambda p, t: -t * jnp.log(p + epsilon)
                 - (1 - t) * jnp.log(1 - p + epsilon),
                 input, label, _op_name="log_loss")


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    def f(lg, t, *nrm):
        p = jax.nn.sigmoid(lg)
        ce = jnp.maximum(lg, 0) - lg * t + jnp.log1p(jnp.exp(-jnp.abs(lg)))
        p_t = p * t + (1 - p) * (1 - t)
        a_t = alpha * t + (1 - alpha) * (1 - t)
        per = a_t * jnp.power(1 - p_t, gamma) * ce
        if nrm:
            per = per / nrm[0]
        return _reduce(per, reduction)
    args = [logit, label] + ([normalizer] if normalizer is not None else [])
    return apply(f, *args, _op_name="sigmoid_focal_loss")


def soft_margin_loss(input, label, reduction="mean", name=None):
    return apply(lambda a, t: _reduce(jnp.log1p(jnp.exp(-t * a)), reduction),
                 input, label, _op_name="soft_margin_loss")


def multi_label_soft_margin_loss(input, label, weight=None, reduction="mean",
                                 name=None):
    def f(a, t, *w):
        per = -(t * jax.nn.log_sigmoid(a) + (1 - t) * jax.nn.log_sigmoid(-a))
        per = jnp.mean(per, axis=-1)
        if w:
            per = per * w[0]
        return _reduce(per, reduction)
    args = [input, label] + ([weight] if weight is not None else [])
    return apply(f, *args, _op_name="multi_label_soft_margin_loss")


def poisson_nll_loss(input, label, log_input=True, full=False, epsilon=1e-8,
                     reduction="mean", name=None):
    def f(a, t):
        if log_input:
            per = jnp.exp(a) - t * a
        else:
            per = a - t * jnp.log(a + epsilon)
        if full:
            stirling = t * jnp.log(t + epsilon) - t + 0.5 * jnp.log(
                2 * jnp.pi * (t + epsilon))
            per = per + jnp.where(t > 1, stirling, 0.0)
        return _reduce(per, reduction)
    return apply(f, input, label, _op_name="poisson_nll_loss")


def gaussian_nll_loss(input, label, variance, full=False, epsilon=1e-6,
                      reduction="mean", name=None):
    def f(mu, t, var):
        var = jnp.maximum(var, epsilon)
        per = 0.5 * (jnp.log(var) + jnp.square(mu - t) / var)
        if full:
            per = per + 0.5 * jnp.log(jnp.asarray(2 * jnp.pi))
        return _reduce(per, reduction)
    return apply(f, input, label, variance, _op_name="gaussian_nll_loss")


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC via dynamic-program in lax.scan (reference: warpctc op)."""
    lp = log_probs.value if isinstance(log_probs, Tensor) else log_probs
    # paddle layout: (T, B, C)
    def f(logits):
        import optax
        t_, b_, c_ = logits.shape
        lgb = jnp.transpose(logits, (1, 0, 2))  # (B,T,C)
        lbl = labels.value if isinstance(labels, Tensor) else labels
        pad_mask = jnp.arange(t_)[None, :] >= jnp.asarray(
            input_lengths.value if isinstance(input_lengths, Tensor)
            else input_lengths)[:, None]
        lens = jnp.asarray(
            label_lengths.value if isinstance(label_lengths, Tensor)
            else label_lengths)
        lbl_mask = jnp.arange(lbl.shape[1])[None, :] >= lens[:, None]
        per = optax.ctc_loss(lgb, pad_mask, lbl, lbl_mask, blank_id=blank)
        if reduction == "mean":
            # reference contract (loss.py:1688): 'mean' divides each
            # sample's loss by its label length, THEN averages (torch
            # ctc_loss semantics) — not a plain mean of raw losses
            return jnp.mean(per / jnp.maximum(lens.astype(per.dtype), 1))
        return _reduce(per, reduction)
    return apply(f, log_probs, _op_name="ctc_loss")


def multi_margin_loss(input, label, p: int = 1, margin: float = 1.0,
                      weight=None, reduction="mean", name=None):
    """Parity: nn/functional/loss.py multi_margin_loss — per-sample
    mean_j!=y max(0, margin - x_y + x_j)^p, optionally class-weighted."""

    def f(x, y, *w):
        C = x.shape[1]
        xy = jnp.take_along_axis(x, y[:, None].astype(jnp.int32), 1)
        hinge = jnp.maximum(0.0, margin - xy + x)
        if p != 1:
            hinge = hinge ** p
        if w:
            hinge = hinge * w[0][y.astype(jnp.int32)][:, None]
        onehot = jax.nn.one_hot(y.astype(jnp.int32), C, dtype=x.dtype)
        per = (hinge * (1 - onehot)).sum(1) / C
        return _reduce(per, reduction)

    args = [input, label] + ([weight] if weight is not None else [])
    return apply(f, *args, _op_name="multi_margin_loss")


def triplet_margin_with_distance_loss(input, positive, negative,
                                      distance_function=None,
                                      margin: float = 1.0, swap=False,
                                      reduction="mean", name=None):
    """Parity: nn/functional/loss.py triplet_margin_with_distance_loss."""
    if distance_function is None:
        from .common import pairwise_distance

        def distance_function(a, b):
            return pairwise_distance(a, b)
    d_pos = distance_function(input, positive)
    d_neg = distance_function(input, negative)
    if swap:
        d_pn = distance_function(positive, negative)
        d_neg = _t_min(d_neg, d_pn)

    def f(dp, dn):
        return _reduce(jnp.maximum(0.0, dp - dn + margin), reduction)

    return apply(f, d_pos, d_neg,
                 _op_name="triplet_margin_with_distance_loss")


def _t_min(a, b):
    def f(x, y):
        return jnp.minimum(x, y)
    return apply(f, a, b, _op_name="minimum")


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Parity: nn/functional/loss.py:892 hsigmoid_loss. Default tree is
    the word2vec heap layout the reference's SimpleCode implements
    (node = ((num_classes + c) >> (d+1)) - 1, bit = ((num_classes + c)
    >> d) & 1): per-sample loss = sum over the path of BCE-with-logits.
    Custom trees come in via path_table/path_code (host arrays)."""
    import numpy as _np
    from ...core.tensor import Tensor as _T

    lbl = _np.asarray(label.value if isinstance(label, _T) else label)
    lbl = lbl.reshape(-1).astype(_np.int64)
    if path_table is not None:
        table = _np.asarray(path_table.value if isinstance(path_table, _T)
                            else path_table)[lbl]
        code = _np.asarray(path_code.value if isinstance(path_code, _T)
                           else path_code)[lbl]
        valid = table >= 0
        table = _np.where(valid, table, 0)
    else:
        codes = lbl + num_classes
        depth = int(_np.max([int(c).bit_length() for c in codes])) - 1
        table = _np.zeros((len(lbl), depth), _np.int64)
        code = _np.zeros((len(lbl), depth), _np.float32)
        valid = _np.zeros((len(lbl), depth), bool)
        for i, c in enumerate(codes):
            d = 0
            while c > 1:
                table[i, d] = (c >> 1) - 1
                code[i, d] = c & 1
                valid[i, d] = True
                c >>= 1
                d += 1

    def f(x, w, *b):
        wt = w[table]                          # (N, D, feat)
        logits = jnp.einsum("nf,ndf->nd", x, wt)
        if b:
            logits = logits + b[0].reshape(-1)[table]
        codej = jnp.asarray(code, x.dtype)
        validj = jnp.asarray(valid, x.dtype)
        bce = jnp.maximum(logits, 0) - logits * codej \
            + jnp.log1p(jnp.exp(-jnp.abs(logits)))
        return (bce * validj).sum(-1, keepdims=True)

    args = [input, weight] + ([bias] if bias is not None else [])
    return apply(f, *args, _op_name="hsigmoid_loss")


def rnnt_loss(input, label, input_lengths, label_lengths, blank=0,
              fastemit_lambda=0.001, reduction="mean", name=None):
    """Parity: nn/functional/loss.py rnnt_loss (RNA/RNN-T transducer).

    input: (B, T, U, D) joint-network logits with U = max_label_len + 1;
    forward-variable DP in log space via nested lax.scan (T outer, U
    inner prefix recurrence) — one compiled program, batch-parallel.
    """

    def f(x, y, t_len, u_len):
        B, T, U, D = x.shape
        lp = jax.nn.log_softmax(x, -1)
        blank_lp = lp[..., blank]                        # (B, T, U)
        yi = y.astype(jnp.int32)
        emit_lp = jnp.take_along_axis(
            lp[:, :, :-1, :], jnp.broadcast_to(
                yi[:, None, :, None], (B, T, U - 1, 1)), -1)[..., 0]
        if fastemit_lambda:
            # FastEmit (arXiv 2010.11148) as warp-transducer implements
            # it: scale the EMISSION gradient by (1 + lambda) while
            # leaving the loss value unchanged — the identity
            # e' = (1+l)e - stop_grad(l*e) has value e, gradient (1+l).
            # Applied before the -inf pad (the identity is nan at -inf).
            emit_lp = (1.0 + fastemit_lambda) * emit_lp \
                - jax.lax.stop_gradient(fastemit_lambda * emit_lp)
        emit_lp = jnp.pad(emit_lp, ((0, 0), (0, 0), (0, 1)),
                          constant_values=-jnp.inf)      # (B, T, U)
        neg_inf = jnp.asarray(-jnp.inf, x.dtype)

        def t_scan(alpha_prev, t):
            # horizontal (blank) moves from row t-1
            from_blank = jnp.where(
                t == 0,
                jnp.where(jnp.arange(U) == 0, 0.0, neg_inf)[None, :],
                alpha_prev + blank_lp[:, jnp.maximum(t - 1, 0), :])
            # vertical (emit) moves within row t, left-to-right
            em_row = emit_lp[:, t, :]

            def inner(carry, u):
                cur = jnp.where(
                    u == 0, from_blank[:, 0],
                    jnp.logaddexp(from_blank[:, u],
                                  carry + em_row[:, jnp.maximum(u - 1, 0)]))
                return cur, cur

            _, rows = jax.lax.scan(inner, jnp.full((B,), neg_inf, x.dtype),
                                   jnp.arange(U))
            alpha = jnp.moveaxis(rows, 0, 1)             # (B, U)
            return alpha, alpha

        _, alphas = jax.lax.scan(t_scan, jnp.full((B, U), neg_inf, x.dtype),
                                 jnp.arange(T))
        alphas = jnp.moveaxis(alphas, 0, 1)              # (B, T, U)
        bt = jnp.arange(B)
        t_last = t_len.astype(jnp.int32) - 1
        u_last = u_len.astype(jnp.int32)                 # U-1 per sample
        ll = alphas[bt, t_last, u_last] + blank_lp[bt, t_last, u_last]
        per = -ll
        return _reduce(per, reduction)

    return apply(f, input, label, input_lengths, label_lengths,
                 _op_name="rnnt_loss")


def _head_logits(xi, w, tw):
    """One chunk through the head: ``[C, H]`` to f32 logits ``[C, V]``."""
    return jnp.matmul(xi, w.T if tw else w,
                      preferred_element_type=jnp.float32)


def _head_chunk_loss(lg, ii, ignore_index):
    """Summed loss of a chunk's tokens that count, from its f32 logits;
    beside it what the logits' gradient is made of."""
    m = jnp.max(lg, axis=-1)
    s = jnp.sum(jnp.exp(lg - m[:, None]), axis=-1)
    safe = jnp.clip(ii, 0, lg.shape[-1] - 1).astype(jnp.int32)
    gold = jnp.take_along_axis(lg, safe[:, None], axis=-1)[:, 0]
    valid = ii != ignore_index
    loss = jnp.sum(jnp.where(valid, jnp.log(s) + m - gold, 0.0))
    return loss, (m, s, safe, valid)


def _head_count(ic, ignore_index):
    return jnp.maximum(jnp.sum(ic != ignore_index), 1).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _chunked_head_ce(xc, w, ic, tw, ignore_index):
    """Mean cross-entropy of ``xc [n, C, H]`` through the head ``w`` over
    the labels ``ic [n, C]`` that are not ``ignore_index``, a chunk at a
    time. Reverse-mode only (a ``custom_vjp``)."""
    sums = jax.lax.map(
        lambda a: _head_chunk_loss(_head_logits(a[0], w, tw), a[1],
                                   ignore_index)[0], (xc, ic))
    return jnp.sum(sums) / _head_count(ic, ignore_index)


def _chunked_head_ce_fwd(xc, w, ic, tw, ignore_index):
    # The loss is a mean of per-token terms, so a chunk's logit gradient
    # (softmax - onehot) * valid / count is known with its logits, up to
    # the scalar from above: the two gradient products (the transposes of
    # the chunk's own product) run here, on the logits the loss was read
    # from, and nothing [*, V] outlives a chunk.
    count = _head_count(ic, ignore_index)

    def body(dw, args):
        xi, ii = args
        lg, pull = jax.vjp(lambda x, w_: _head_logits(x, w_, tw), xi, w)
        loss, (m, s, safe, valid) = _head_chunk_loss(lg, ii, ignore_index)
        dlg = (jnp.exp(lg - m[:, None]) / s[:, None]
               - jax.nn.one_hot(safe, lg.shape[-1], dtype=jnp.float32)) \
            * (valid.astype(jnp.float32) / count)[:, None]
        dxi, dwi = pull(dlg)
        return dw + dwi, (loss, dxi)

    dw, (sums, dxc) = jax.lax.scan(body, jnp.zeros_like(w), (xc, ic))
    return jnp.sum(sums) / count, (dxc, dw)


def _chunked_head_ce_bwd(tw, ignore_index, res, g):
    dxc, dw = res
    return (g * dxc).astype(dxc.dtype), (g * dw).astype(dw.dtype), None


_chunked_head_ce.defvjp(_chunked_head_ce_fwd, _chunked_head_ce_bwd)


def fused_linear_cross_entropy(hidden, weight, label, chunk_size=512,
                               ignore_index=-100, transpose_weight=None,
                               name=None):
    """LM-head projection + softmax cross-entropy WITHOUT materializing
    the [N, vocab] logits.

    The reference composes a matmul with its fused CE kernel
    (cross_entropy_kernel.cu), so the full logits tensor lives in HBM in
    both passes — at GPT geometry (8k tokens x 50k vocab) that is ~824 MB
    bf16 forward plus the same again for dlogits in backward. Here tokens
    stream through the projection in chunks and each chunk's logits are
    computed ONCE: under differentiation the forward pass of a chunk
    forms ``softmax - onehot`` from the logits it has in hand and runs
    both gradient products at once (``dW`` accumulates across chunks in
    the scan's carry), so the backward pass only scales ``dx`` and ``dW``
    by the incoming cotangent. Peak extra memory is O(chunk_size x vocab)
    instead of O(N x vocab) — the lever that turns LM-head memory from
    batch-bound into a constant. Reverse-mode only (a ``custom_vjp``, as
    ``_fused_softmax_ce`` is): ``jax.jvp`` through it raises.

    hidden: [N, H] or [B, S, H]; label: int [N] or [B, S];
    weight: [V, H] (embedding/tied layout) or [H, V]
    (``transpose_weight=False``). ``transpose_weight=None`` infers: a
    square weight is ambiguous and raises. Mean reduction over
    non-ignored tokens (the LM-training contract).
    """
    lbl = label.value if isinstance(label, Tensor) else jnp.asarray(label)

    def f(x, w):
        H = x.shape[-1]
        tw = transpose_weight
        if tw is None:
            if w.shape[0] == w.shape[1]:
                raise ValueError(
                    "fused_linear_cross_entropy: square weight is "
                    "ambiguous — pass transpose_weight explicitly")
            tw = w.shape[-1] == H          # [V, H] -> project with w.T
        xf = x.reshape(-1, H)
        idx = lbl.reshape(-1)
        N = xf.shape[0]
        C = max(1, min(int(chunk_size), N))
        pad = (-N) % C
        if pad:
            xf = jnp.concatenate(
                [xf, jnp.zeros((pad, H), xf.dtype)], axis=0)
            idx = jnp.concatenate(
                [idx, jnp.full((pad,), ignore_index, idx.dtype)], axis=0)
        return _chunked_head_ce(xf.reshape(-1, C, H), w, idx.reshape(-1, C),
                                bool(tw), ignore_index)

    return apply(f, hidden, weight, _op_name="fused_linear_cross_entropy")
