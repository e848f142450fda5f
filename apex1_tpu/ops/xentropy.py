"""Fused softmax cross-entropy with label smoothing — Pallas TPU kernels.

Reference: ``apex/contrib/csrc/xentropy/xentropy_kernel.cu`` wrapped by
``apex/contrib/xentropy/softmax_xentropy.py :: SoftmaxCrossEntropyLoss``.

The reference's win is ACTIVATION MEMORY: forward saves only per-row
stats (not the softmax probabilities); backward recomputes ``softmax(x)``
from logits + the saved logsumexp and writes the gradient "in-place" into
the logits buffer. Exactly reproduced here: residuals are
``(logits, labels, lse)`` and the bwd kernel recomputes ``exp(x - lse)`` —
for a 50k+ vocab this saves the full (tokens × vocab) probability tensor. (With
``jax.jit`` donation the dx buffer aliases the logits buffer, matching the
in-place trick.)

Loss (label smoothing ε, ``smoothing``):
    loss_i = (1-ε) * (lse_i - x_i[t_i]) + ε * (lse_i - mean_k x_i[k])
    dx_i   = softmax(x_i) - (1-ε)·onehot(t_i) - ε/K
``padding_idx`` rows (``ignore_index``) produce loss 0 and zero grad.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex1_tpu.ops._common import (NEG_INF, interpret_mode, kernel_call,
                                   out_struct, pad_to, use_pallas)
from apex1_tpu.tuning import tuned_row_block



def _fwd_kernel(x_ref, t_ref, loss_ref, lse_ref, *,
                smoothing, true_k, padding_idx):
    x = x_ref[...].astype(jnp.float32)
    t = t_ref[...]  # (rows, 1) int32
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < true_k
    xm = jnp.where(valid, x, NEG_INF)
    m = jnp.max(xm, axis=1, keepdims=True)
    e = jnp.where(valid, jnp.exp(xm - m), 0.0)
    s = jnp.sum(e, axis=1, keepdims=True)
    lse = m + jnp.log(s)
    tgt_logit = jnp.sum(jnp.where(col == t, x, 0.0), axis=1, keepdims=True)
    sum_x = jnp.sum(jnp.where(valid, x, 0.0), axis=1, keepdims=True)
    loss = ((1.0 - smoothing) * (lse - tgt_logit)
            + smoothing * (lse - sum_x / true_k))
    if padding_idx is not None:
        loss = jnp.where(t == padding_idx, 0.0, loss)
    loss_ref[...] = loss
    lse_ref[...] = lse


def _bwd_kernel(x_ref, t_ref, lse_ref, dloss_ref, dx_ref, *,
                smoothing, true_k, padding_idx):
    x = x_ref[...].astype(jnp.float32)
    t = t_ref[...]
    lse = lse_ref[...]
    dloss = dloss_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < true_k
    p = jnp.where(valid, jnp.exp(x - lse), 0.0)  # recomputed softmax
    grad = p - (1.0 - smoothing) * (col == t) - smoothing / true_k
    grad = jnp.where(valid, grad, 0.0)
    if padding_idx is not None:
        dloss = jnp.where(t == padding_idx, 0.0, dloss)
    dx_ref[...] = (grad * dloss).astype(dx_ref.dtype)


def _specs(k, br):
    row = pl.BlockSpec((br, k), lambda i: (i, 0),
                       memory_space=pltpu.VMEM)
    stat = pl.BlockSpec((br, 1), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    return row, stat


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _fused_xent(logits, labels, smoothing, padding_idx, num_classes,
                block_rows):
    return _fused_xent_fwd(logits, labels, smoothing, padding_idx,
                           num_classes, block_rows)[0]


def _fused_xent_fwd(logits, labels, smoothing, padding_idx, num_classes,
                    block_rows):
    shape = logits.shape
    k = shape[-1] if num_classes is None else num_classes
    x2 = logits.reshape(-1, shape[-1])
    t2 = labels.reshape(-1, 1).astype(jnp.int32)
    br = tuned_row_block("xentropy", x2.shape[1], rows=x2.shape[0],
                         dtype=logits.dtype, requested=block_rows)
    x2p, rows = pad_to(x2, 0, br)
    x2p, _ = pad_to(x2p, 1, 128)
    t2p, _ = pad_to(t2, 0, br, value=-1)
    row, stat = _specs(x2p.shape[1], br)
    loss, lse = kernel_call(
        functools.partial(_fwd_kernel, smoothing=smoothing, true_k=k,
                          padding_idx=padding_idx),
        name="xentropy_fwd",
        grid=(pl.cdiv(x2p.shape[0], br),),
        in_specs=[row, stat],
        out_specs=(stat, stat),
        out_shape=(out_struct((x2p.shape[0], 1), jnp.float32, x2p, t2p),
                   out_struct((x2p.shape[0], 1), jnp.float32, x2p, t2p)),
        interpret=interpret_mode(),
    )(x2p, t2p)
    loss = loss[:rows, 0].reshape(shape[:-1])
    return loss, (logits, labels, lse)


def _fused_xent_bwd(smoothing, padding_idx, num_classes, block_rows, res,
                    dloss):
    logits, labels, lse = res
    shape = logits.shape
    k = shape[-1] if num_classes is None else num_classes
    x2 = logits.reshape(-1, shape[-1])
    t2 = labels.reshape(-1, 1).astype(jnp.int32)
    d2 = dloss.reshape(-1, 1).astype(jnp.float32)
    br = tuned_row_block("xentropy", x2.shape[1], rows=x2.shape[0],
                         dtype=logits.dtype, requested=block_rows)
    x2p, rows = pad_to(x2, 0, br)
    x2p, _ = pad_to(x2p, 1, 128)
    t2p, _ = pad_to(t2, 0, br, value=-1)
    d2p, _ = pad_to(d2, 0, br)
    row, stat = _specs(x2p.shape[1], br)
    dx = kernel_call(
        functools.partial(_bwd_kernel, smoothing=smoothing, true_k=k,
                          padding_idx=padding_idx),
        name="xentropy_bwd",
        grid=(pl.cdiv(x2p.shape[0], br),),
        in_specs=[row, stat, stat, stat],
        out_specs=row,
        out_shape=out_struct(x2p.shape, logits.dtype, x2p, t2p, lse, d2p),
        interpret=interpret_mode(),
    )(x2p, t2p, lse, d2p)
    return dx[:rows, :shape[-1]].reshape(shape), None


_fused_xent.defvjp(_fused_xent_fwd, _fused_xent_bwd)


def _xla_xent(logits, labels, smoothing, padding_idx, num_classes=None):
    if num_classes is not None and num_classes != logits.shape[-1]:
        logits = logits[..., :num_classes]
    x = logits.astype(jnp.float32)
    k = x.shape[-1]
    lse = jax.nn.logsumexp(x, axis=-1, keepdims=True)
    tgt = jnp.take_along_axis(x, labels[..., None].astype(jnp.int32),
                              axis=-1)
    loss = ((1.0 - smoothing) * (lse - tgt)
            + smoothing * (lse - jnp.mean(x, axis=-1, keepdims=True)))
    loss = loss[..., 0]
    if padding_idx is not None:
        loss = jnp.where(labels == padding_idx, 0.0, loss)
    return loss


def softmax_cross_entropy_loss(logits, labels, *, smoothing: float = 0.0,
                               padding_idx: int | None = None,
                               num_classes: int | None = None,
                               block_rows: int | None = None):
    """``apex.contrib.xentropy.SoftmaxCrossEntropyLoss.apply(logits, labels,
    smoothing, padding_idx, half_to_float)`` equivalent.

    Returns per-token loss (reduce with mean/sum yourself, as the reference
    does). ``padding_idx`` tokens contribute zero loss and zero gradient.
    ``num_classes``: treat only the first N logit columns as real classes —
    lets callers keep Megatron-style lane-padded vocab logits (the extra
    columns are masked in-kernel, no slice copy; their grads are zero).
    ``block_rows``: static rows-per-grid-step; ``None`` resolves tuning
    table > heuristic (`apex1_tpu.tuning.tuned_row_block`).
    """
    if num_classes is not None and not (
            0 < num_classes <= logits.shape[-1]):
        raise ValueError(f"num_classes {num_classes} must be in "
                         f"(0, {logits.shape[-1]}]")
    if use_pallas():
        return _fused_xent(logits, labels, float(smoothing), padding_idx,
                           num_classes, block_rows)
    return _xla_xent(logits, labels, smoothing, padding_idx, num_classes)


def masked_next_token_mean(losses, segment_ids):
    """Mean of next-token losses over VALID targets in a packed batch:
    a target in a different segment than its input token (document
    boundary) or in the padding segment (< 0) is not a target.
    ``losses``: (B, S-1) per-position CE of predicting token t+1;
    ``segment_ids``: (B, S). Shared by the packed GPT-2/Llama loss fns."""
    valid = ((segment_ids[:, :-1] == segment_ids[:, 1:])
             & (segment_ids[:, :-1] >= 0)).astype(losses.dtype)
    return jnp.sum(losses * valid) / jnp.maximum(jnp.sum(valid), 1.0)
