"""Vocab-parallel cross-entropy — reference
``apex/transformer/tensor_parallel/cross_entropy.py ::
vocab_parallel_cross_entropy``.

Reference algorithm over vocab-sharded logits, reproduced step for step:
  1. local max → all-reduce MAX          (numerical stability)
  2. local Σ exp(x−max) → all-reduce SUM (denominator)
  3. target logit gathered via the local-range mask trick → all-reduce SUM
  4. loss = log(Σexp) − (target − max)
Backward is local: softmax_shard − onehot_shard (custom_vjp, no collective —
the reference's backward is likewise local).

Runs inside ``shard_map`` over the tp axis. Label smoothing follows the
newer reference signature (``label_smoothing`` arg).

``vocab_parallel_linear_cross_entropy`` below goes a step further than the
reference: the LM-head matmul is fused INTO the vocab-parallel CE
(``ops/linear_xent.py`` kernels per shard + pmax/psum stat merge), so not
even the local logits slice materializes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from apex1_tpu.core.mesh import AXIS_TP
from apex1_tpu.ops._common import NEG_INF, use_pallas


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def vocab_parallel_cross_entropy(logits_shard, targets, label_smoothing=0.0,
                                 axis_name=AXIS_TP):
    """``logits_shard``: (..., V/tp) this rank's vocab slice; ``targets``:
    (...) global vocab ids (replicated). Returns per-token loss
    (replicated)."""
    loss, _ = _fwd(logits_shard, targets, label_smoothing, axis_name)
    return loss


def _stats(logits_shard, targets, axis_name):
    x = logits_shard.astype(jnp.float32)
    per = x.shape[-1]
    start = jax.lax.axis_index(axis_name) * per
    local_max = jnp.max(x, axis=-1)
    gmax = jax.lax.pmax(local_max, axis_name)
    e = jnp.exp(x - gmax[..., None])
    gsum = jax.lax.psum(jnp.sum(e, axis=-1), axis_name)
    # target-logit mask trick
    local_t = targets - start
    in_shard = (local_t >= 0) & (local_t < per)
    local_t = jnp.clip(local_t, 0, per - 1)
    tgt = jnp.take_along_axis(x, local_t[..., None], axis=-1)[..., 0]
    tgt = jax.lax.psum(jnp.where(in_shard, tgt, 0.0), axis_name)
    return x, gmax, gsum, tgt, in_shard, local_t, start, per


def _fwd(logits_shard, targets, label_smoothing, axis_name):
    x, gmax, gsum, tgt, in_shard, local_t, start, per = _stats(
        logits_shard, targets, axis_name)
    lse = gmax + jnp.log(gsum)
    loss = lse - tgt
    if label_smoothing:
        vocab = per * jax.lax.axis_size(axis_name)
        mean_x = jax.lax.psum(jnp.sum(x, axis=-1), axis_name) / vocab
        loss = ((1.0 - label_smoothing) * loss
                + label_smoothing * (lse - mean_x))
    return loss, (logits_shard, targets, gmax, gsum)


def _bwd(label_smoothing, axis_name, res, dloss):
    logits_shard, targets, gmax, gsum = res
    x = logits_shard.astype(jnp.float32)
    per = x.shape[-1]
    start = jax.lax.axis_index(axis_name) * per
    p = jnp.exp(x - gmax[..., None]) / gsum[..., None]
    local_t = targets - start
    in_shard = (local_t >= 0) & (local_t < per)
    onehot = ((jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
               == jnp.clip(local_t, 0, per - 1)[..., None])
              & in_shard[..., None])
    grad = p - (1.0 - label_smoothing) * onehot
    if label_smoothing:
        vocab = per * jax.lax.axis_size(axis_name)
        grad = grad - label_smoothing / vocab
    grad = grad * dloss[..., None]
    return grad.astype(logits_shard.dtype), None


vocab_parallel_cross_entropy.defvjp(
    lambda lg, t, ls, ax: _fwd(lg, t, ls, ax),
    _bwd)


# ---------------------------------------------------------------------------
# Fused LM-head + vocab-parallel CE: the `ops.linear_xent` kernels composed
# over the tp axis — each rank's W shard (V/tp, H) produces partial
# online-softmax stats (never materializing even the LOCAL logits slice),
# merged with pmax/psum. A capability the reference does NOT have (its
# vocab-parallel CE takes materialized sharded logits). Both the Pallas
# and the XLA-composite implementations share ONE hand-written custom_vjp
# (collectives live inside fwd/bwd), so correctness never depends on
# shard_map's transpose conventions for replicated operands.
# ---------------------------------------------------------------------------

def _xla_shard_stats(x2, w_shard, t2, off, k):
    """jnp twin of ``ops.linear_xent.shard_stats`` (materializes the local
    logits slice — the gold / CPU path)."""
    logits = jnp.einsum("th,vh->tv", x2.astype(jnp.float32),
                        w_shard.astype(jnp.float32))
    gcol = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) + off
    valid = gcol < k
    xm = jnp.where(valid, logits, NEG_INF)
    m = jnp.max(xm, axis=-1)
    l = jnp.sum(jnp.where(valid, jnp.exp(xm - m[:, None]), 0.0), axis=-1)
    tgt = jnp.sum(jnp.where(gcol == t2, logits, 0.0), axis=-1)
    sumx = jnp.sum(jnp.where(valid, logits, 0.0), axis=-1)
    return m, l, tgt, sumx


def _xla_shard_grads(x2, w_shard, t2, lse, dloss, off, smoothing,
                     padding_idx, k):
    """jnp twin of ``ops.linear_xent.shard_grads``."""
    logits = jnp.einsum("th,vh->tv", x2.astype(jnp.float32),
                        w_shard.astype(jnp.float32))
    gcol = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) + off
    valid = gcol < k
    p = jnp.where(valid, jnp.exp(logits - lse[:, None]), 0.0)
    g = p - (1.0 - smoothing) * (gcol == t2) - smoothing / k
    g = jnp.where(valid, g, 0.0)
    dl = dloss.astype(jnp.float32)
    if padding_idx is not None:
        dl = jnp.where(t2[:, 0] == padding_idx, 0.0, dl)
    g = g * dl[:, None]
    dx = (g @ w_shard.astype(jnp.float32)).astype(x2.dtype)
    dw = (g.T @ x2.astype(jnp.float32)).astype(w_shard.dtype)
    return dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _vp_fused(x2, w_shard, t2, axis_name, smoothing, padding_idx,
              num_classes, fused_merge=False):
    return _vp_fused_fwd(x2, w_shard, t2, axis_name, smoothing,
                         padding_idx, num_classes, fused_merge)[0]


def _vp_merge(m, l, tgt, sumx, axis_name):
    gmax = jax.lax.pmax(m, axis_name)
    gsum = jax.lax.psum(l * jnp.exp(m - gmax), axis_name)
    return (gmax + jnp.log(gsum), jax.lax.psum(tgt, axis_name),
            jax.lax.psum(sumx, axis_name))


def _vp_k(w_shard, axis_name, num_classes):
    vocab = w_shard.shape[0] * jax.lax.axis_size(axis_name)
    return num_classes if num_classes is not None else vocab


def _vp_fused_fwd(x2, w_shard, t2, axis_name, smoothing, padding_idx,
                  num_classes, fused_merge=False):
    k = _vp_k(w_shard, axis_name, num_classes)
    off = jax.lax.axis_index(axis_name) * w_shard.shape[0]
    if fused_merge:
        # fused comm-kernel form (ops.fused_collective): the kernel's
        # final vocab tile packs [m, l, tgt, sumx] into ONE stat stream
        # and the cross-shard ladder collapses to pmax + one packed
        # psum (2 collectives instead of 4) — bitwise the decomposed
        # path's numbers (packed psum reduces lanes independently)
        from apex1_tpu.ops.fused_collective import (
            fused_vocab_parallel_merge)
        if use_pallas():
            from apex1_tpu.ops.linear_xent import shard_stats_packed
            stats = shard_stats_packed(x2, w_shard, t2, col_offset=off,
                                       num_classes=k)
        else:
            m, l, tgt, sumx = _xla_shard_stats(x2, w_shard, t2, off, k)
            stats = jnp.stack([m, l, tgt, sumx], axis=-1)
        lse, tgt, sumx = fused_vocab_parallel_merge(stats, axis_name)
    else:
        if use_pallas():
            from apex1_tpu.ops.linear_xent import shard_stats
            m, l, tgt, sumx = shard_stats(x2, w_shard, t2, col_offset=off,
                                          num_classes=k)
        else:
            m, l, tgt, sumx = _xla_shard_stats(x2, w_shard, t2, off, k)
        lse, tgt, sumx = _vp_merge(m, l, tgt, sumx, axis_name)
    loss = ((1.0 - smoothing) * (lse - tgt)
            + smoothing * (lse - sumx / k))
    if padding_idx is not None:
        loss = jnp.where(t2[:, 0] == padding_idx, 0.0, loss)
    return loss, (x2, w_shard, t2, lse)


def _vp_fused_bwd(axis_name, smoothing, padding_idx, num_classes,
                  fused_merge, res, dloss):
    x2, w_shard, t2, lse = res
    k = _vp_k(w_shard, axis_name, num_classes)
    off = jax.lax.axis_index(axis_name) * w_shard.shape[0]
    if use_pallas():
        from apex1_tpu.ops.linear_xent import shard_grads
        dx_part, dw = shard_grads(x2, w_shard, t2, lse, dloss,
                                  col_offset=off, smoothing=smoothing,
                                  padding_idx=padding_idx, num_classes=k)
    else:
        dx_part, dw = _xla_shard_grads(x2, w_shard, t2, lse, dloss, off,
                                       smoothing, padding_idx, k)
    # dx is SHARD-PARTIAL (this rank saw only its vocab columns): the
    # cross-shard sum belongs to the ONE input collective the wrapper
    # applied (copy-region bwd psum, or all_gather bwd reduce-scatter) —
    # summing here as well would double-count (Megatron's CE backward is
    # likewise local)
    return dx_part, dw, np.zeros(t2.shape, dtype=jax.dtypes.float0)


_vp_fused.defvjp(_vp_fused_fwd, _vp_fused_bwd)


def vocab_parallel_linear_cross_entropy(x, w_shard, labels, *,
                                        axis_name=AXIS_TP,
                                        label_smoothing: float = 0.0,
                                        padding_idx: int | None = None,
                                        num_classes: int | None = None,
                                        sequence_parallel_input=False,
                                        fused: bool = False):
    """CE of ``softmax(x @ global_Wᵀ)`` with W vocab-sharded over
    ``axis_name`` — on TPU, logits (even the local slice) never
    materialize. Runs inside ``shard_map``; shards must be equal-sized
    (Megatron ``VocabUtility`` equal-split convention).

    ``w_shard`` (V/tp, H) is this rank's rows; ``labels`` are GLOBAL
    vocab ids over the GLOBAL token set. Like the reference's
    ``ColumnParallelLinear``, the op applies exactly ONE input collective
    so activation gradients come out right (the kernel's dx cotangent is
    shard-partial):

    - ``sequence_parallel_input=False`` (default): ``x`` (..., H) is
      replicated across tp → copy-to-region (identity fwd, psum bwd).
    - ``True``: ``x`` (..., H) is this rank's SEQUENCE shard (leading
      token axis sharded over tp; ≙ Megatron SP's gather before the
      head) → internal tiled all_gather (bwd reduce-scatter). The
      returned loss covers the GLOBAL token set, replicated.

    Returns per-token fp32 loss, identical on every rank.
    ``num_classes`` masks global lane-pad columns.

    ``fused=True`` (opt-in, default off = the untouched legacy path):
    the fused comm-kernel merge — per-shard stats packed into one
    kernel output by the final vocab tile
    (`ops.linear_xent.shard_stats_packed`) and the pmax/psum ladder
    collapsed to TWO collectives
    (`ops.fused_collective.fused_vocab_parallel_merge`). Bitwise the
    same loss as ``fused=False`` (pinned by test_fused_collective).
    Two collectives EMITTED against four; XLA's combiner merges the
    ladder's three psums, so both forms compile to two (ROADMAP D4).
    """
    from apex1_tpu.transformer.tensor_parallel.mappings import (
        copy_to_tensor_model_parallel_region)
    if x.shape[-1] != w_shard.shape[-1]:
        raise ValueError(f"hidden mismatch: x {x.shape} vs w_shard "
                         f"{w_shard.shape}")
    x2 = x.reshape(-1, x.shape[-1])
    if sequence_parallel_input:
        x2 = jax.lax.all_gather(x2, axis_name, axis=0, tiled=True)
    else:
        x2 = copy_to_tensor_model_parallel_region(x2, axis_name)
    t2 = labels.reshape(-1, 1).astype(jnp.int32)
    if t2.shape[0] != x2.shape[0]:
        raise ValueError(
            f"labels cover {t2.shape[0]} tokens but x has {x2.shape[0]} "
            "(labels must span the GLOBAL token set)")
    vocab = w_shard.shape[0] * jax.lax.axis_size(axis_name)
    if num_classes is not None and not (0 < num_classes <= vocab):
        raise ValueError(f"num_classes {num_classes} must be in "
                         f"(0, {vocab}]")
    loss = _vp_fused(x2, w_shard, t2, axis_name, float(label_smoothing),
                     padding_idx, num_classes, bool(fused))
    lead = labels.shape
    return loss.reshape(lead)
