"""Fused scale+mask+softmax — Pallas TPU kernels.

Reference: ``csrc/megatron/scaled_masked_softmax{,_cuda}.cu``,
``scaled_upper_triang_masked_softmax*``, ``generic_scaled_masked_softmax*``
(warp-level fused fwd+bwd, seqlen-specialized), exposed through
``apex/transformer/functional/fused_softmax.py :: FusedScaleMaskSoftmax``.

Semantics:
    y  = softmax(scale * x + mask)        (mask additive, -inf-style)
    causal ("upper_triang") variant applies the upper-triangular -inf mask
    dx = scale * y * (dy - Σ_k dy·y)      (saved: y — same as reference bwd)

TPU design: scores are processed as (B, H, Sq, Sk) blocks — grid
(B, H, Sq-blocks) with the key axis as the lane dim — so a broadcast mask
(B, 1, Sq, Sk) is indexed per block and never materialized at full
(B, H, Sq, Sk) size. Padded key lanes are excluded from the sum (zeroed
after exp), so fully-masked rows match the XLA gold exactly. The
seqlen-specialized CUDA templates (≤2k/4k) are unnecessary — one kernel
serves all sizes via the grid.
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


def _fwd_kernel(x_ref, mask_ref, y_ref, *, scale, causal, true_k):
    x = x_ref[...].astype(jnp.float32) * scale  # (1, 1, BQ, K)
    if mask_ref is not None:
        x = x + mask_ref[...].astype(jnp.float32)  # broadcasts over dims of 1
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 3)
    if causal:
        q0 = pl.program_id(2) * x.shape[2]
        q_idx = q0 + jax.lax.broadcasted_iota(jnp.int32, x.shape, 2)
        x = jnp.where(col > q_idx, NEG_INF, x)
    m = jnp.max(x, axis=3, keepdims=True)
    e = jnp.exp(x - m)
    if true_k != x.shape[3]:
        e = jnp.where(col < true_k, e, 0.0)  # padded lanes leave the sum
    s = jnp.sum(e, axis=3, keepdims=True)
    y_ref[...] = (e / s).astype(y_ref.dtype)


def _bwd_kernel(y_ref, dy_ref, dx_ref, *, scale):
    y = y_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    dot = jnp.sum(y * dy, axis=1, keepdims=True)
    dx_ref[...] = (scale * y * (dy - dot)).astype(dx_ref.dtype)


def _pallas_softmax_fwd(x4, mask4, scale, causal, true_k, bq):
    b, h, sq, k = x4.shape
    x_spec = pl.BlockSpec((1, 1, bq, k),
                          lambda bi, hi, qi: (bi, hi, qi, 0),
                          memory_space=pltpu.VMEM)
    grid = (b, h, pl.cdiv(sq, bq))
    if mask4 is not None:
        mb, mh, msq, msk = mask4.shape
        mq_block = bq if msq != 1 else 1
        mk_block = k if msk != 1 else 1  # size-1 key dim stays broadcast

        def mask_index(bi, hi, qi):
            return (bi if mb != 1 else 0, hi if mh != 1 else 0,
                    qi if msq != 1 else 0, 0)

        m_spec = pl.BlockSpec((1, 1, mq_block, mk_block), mask_index,
                              memory_space=pltpu.VMEM)
        kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                                   true_k=true_k)
        in_specs, args = [x_spec, m_spec], (x4, mask4)
    else:
        kernel = functools.partial(
            lambda xr, yr, **kw: _fwd_kernel(xr, None, yr, **kw),
            scale=scale, causal=causal, true_k=true_k)
        in_specs, args = [x_spec], (x4,)
    return kernel_call(
        kernel,
        name="softmax_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=x_spec,
        out_shape=out_struct(x4.shape, x4.dtype, *args),
        interpret=interpret_mode(),
    )(*args)


def _pallas_softmax_bwd(y2, dy2, scale, bq):
    rows, k = y2.shape
    row = pl.BlockSpec((bq, k), lambda i: (i, 0),
                       memory_space=pltpu.VMEM)
    return kernel_call(
        functools.partial(_bwd_kernel, scale=scale),
        name="softmax_bwd",
        grid=(pl.cdiv(rows, bq),),
        in_specs=[row, row],
        out_specs=row,
        out_shape=out_struct((rows, k), y2.dtype, y2, dy2),
        interpret=interpret_mode(),
    )(y2, dy2)


def _as4d(x):
    """(..., sq, sk) -> (B, H, sq, sk) with leading dims split B=prod[:-3]."""
    shape = x.shape
    if x.ndim == 2:
        return x.reshape(1, 1, *shape), shape
    if x.ndim == 3:
        return x.reshape(shape[0], 1, shape[1], shape[2]), shape
    b = 1
    for s in shape[:-3]:
        b *= s
    return x.reshape(b, shape[-3], shape[-2], shape[-1]), shape


def _mask4d(mask, x_shape4):
    """Reshape a broadcastable mask to 4-D with dims in {1, full}."""
    b, h, sq, sk = x_shape4
    mshape = mask.shape
    # left-pad to 4 dims
    m = mask.reshape((1,) * (4 - mask.ndim) + mshape) if mask.ndim < 4 \
        else mask.reshape((-1,) + mshape[-3:])
    for ax, full in enumerate((b, h, sq, sk)):
        if m.shape[ax] not in (1, full):
            raise ValueError(
                f"mask shape {mask.shape} not broadcastable to {x_shape4}")
    return m


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused_softmax(x, mask, scale, causal, block_rows):
    return _fused_softmax_fwd(x, mask, scale, causal, block_rows)[0]


def _fused_softmax_fwd(x, mask, scale, causal, block_rows):
    x4, shape = _as4d(x)
    true_k = x4.shape[-1]
    bq = tuned_row_block("fused_softmax", x4.shape[3], rows=x4.shape[2],
                         dtype=x.dtype, requested=block_rows)
    x4p, sq = pad_to(x4, 2, bq)
    x4p, _ = pad_to(x4p, 3, 128)
    if mask is not None:
        m4 = _mask4d(mask, x4.shape)
        if m4.shape[2] != 1:
            m4, _ = pad_to(m4, 2, bq)
        if m4.shape[3] != 1:  # size-1 key dim rides kernel broadcast
            m4, _ = pad_to(m4, 3, 128)
    else:
        m4 = None
    y = _pallas_softmax_fwd(x4p, m4, scale, causal, true_k, bq)
    y = y[:, :, :sq, :true_k].reshape(shape)
    return y, y


def _fused_softmax_bwd(scale, causal, block_rows, y, dy):
    y2 = y.reshape(-1, y.shape[-1])
    true_k = y2.shape[1]
    bq = tuned_row_block("fused_softmax", y2.shape[1], rows=y2.shape[0],
                         dtype=y.dtype, requested=block_rows)
    y2p, rows = pad_to(y2, 0, bq)
    y2p, _ = pad_to(y2p, 1, 128)
    dy2 = dy.reshape(-1, dy.shape[-1])
    dy2p, _ = pad_to(dy2, 0, bq)
    dy2p, _ = pad_to(dy2p, 1, 128)
    dx = _pallas_softmax_bwd(y2p, dy2p, scale, bq)
    dx = dx[:rows, :true_k].reshape(y.shape)
    return dx, None


_fused_softmax.defvjp(_fused_softmax_fwd, _fused_softmax_bwd)


def _xla_softmax(x, mask, scale, causal):
    x32 = x.astype(jnp.float32) * scale
    if mask is not None:
        x32 = x32 + mask.astype(jnp.float32)
    if causal:
        sq, sk = x.shape[-2], x.shape[-1]
        q = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        kk = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        x32 = jnp.where(kk > q, NEG_INF, x32)
    return jax.nn.softmax(x32, axis=-1).astype(x.dtype)


def scaled_masked_softmax(x, mask=None, *, scale: float = 1.0,
                          block_rows: int | None = None):
    """``scaled_masked_softmax_cuda`` equivalent.

    ``x``: (..., sq, sk) attention scores; ``mask``: additive mask
    broadcastable to ``x`` (use large negative values for masked positions,
    e.g. ``ops.NEG_INF``) — broadcast dims stay size-1 all the way into the
    kernel. ``block_rows``: static rows-per-grid-step; ``None`` resolves
    tuning table > heuristic (`apex1_tpu.tuning.tuned_row_block`).
    """
    if use_pallas():
        return _fused_softmax(x, mask, float(scale), False, block_rows)
    return _xla_softmax(x, mask, scale, False)


def scaled_upper_triang_masked_softmax(x, *, scale: float = 1.0,
                                       block_rows: int | None = None):
    """``scaled_upper_triang_masked_softmax_cuda`` equivalent (causal)."""
    if use_pallas():
        return _fused_softmax(x, None, float(scale), True, block_rows)
    return _xla_softmax(x, None, scale, True)


class FusedScaleMaskSoftmax:
    """API-parity adapter — reference ``apex/transformer/functional/
    fused_softmax.py :: FusedScaleMaskSoftmax`` (chooses kernel vs fallback
    via ``is_kernel_available``; here dispatch is `_common.use_pallas`).

    ``attn_mask_type``: "causal" or "padding" (or the
    `transformer.enums.AttnMaskType` enum).
    """

    def __init__(self, attn_mask_type="padding",
                 scale: float | None = None,
                 scaled_masked_softmax_fusion: bool = True):
        if hasattr(attn_mask_type, "name"):  # AttnMaskType enum
            attn_mask_type = attn_mask_type.name
        self.attn_mask_type = attn_mask_type
        self.scale = 1.0 if scale is None else scale
        self.fusion = scaled_masked_softmax_fusion

    def is_kernel_available(self, *_):
        return self.fusion and use_pallas()

    def __call__(self, x, mask=None):
        if self.attn_mask_type == "causal":
            return scaled_upper_triang_masked_softmax(x, scale=self.scale)
        return scaled_masked_softmax(x, mask, scale=self.scale)
