"""Fused LayerNorm / RMSNorm — Pallas TPU kernels.

Reference: ``csrc/layer_norm_cuda_kernel.cu :: cuApplyLayerNorm,
cuComputeGradInput`` (exposed as ``fused_layer_norm_cuda``), the faster
``apex/contrib/csrc/layer_norm`` ("fast layer norm"), and the Python wrappers
``apex/normalization/fused_layer_norm.py :: FusedLayerNorm, FusedRMSNorm,
MixedFusedLayerNorm``.

Reference semantics preserved:
- forward saves per-row ``mean`` and ``invvar`` (rstd) for the backward;
- "Mixed" dtype behaviour: bf16/fp16 input with fp32 γ/β; stats always
  accumulated in fp32 (the CUDA kernels template on ACC_T=float);
- RMSNorm variant (no mean subtraction, no β);
- ``memory_efficient``: recompute in backward instead of saving activations
  (`jax.checkpoint` around the op — RNG-exact replay is free in JAX).

TPU design: rows tiled (BLOCK_ROWS, H) into VMEM; one grid step normalizes a
row block on the VPU — the CUDA Welford loop collapses to a two-moment
reduction because the whole row is VMEM-resident. The backward emits dx in
the same pass and accumulates dγ/dβ across row blocks in a VMEM accumulator
mapped to a fixed output block (grid steps are sequential on a TensorCore),
≙ the reference's staged column-reduction second kernel. Ragged edges are
handled by client-side neutral padding (rows to BLOCK_ROWS, H to lane
multiples) — XLA fuses the pad/slice.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex1_tpu.obs.regions import region
from apex1_tpu.ops._common import (as_rows, interpret_mode, kernel_call,
                                   mosaic_dtype, out_struct, pad_to, to_mosaic,
                                   use_pallas)
from apex1_tpu.tuning import tuned_row_block


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *,
                eps: float, true_h: int, rms: bool):
    x = x_ref[...].astype(jnp.float32)
    inv_h = 1.0 / true_h
    if rms:
        mean = jnp.zeros((x.shape[0], 1), jnp.float32)
    else:
        mean = jnp.sum(x, axis=1, keepdims=True) * inv_h
    xc = x - mean
    # zero-padded H columns contribute (0-mean)^2 to the raw sum; correct by
    # summing x*x and x separately over true_h instead
    if rms:
        var = jnp.sum(x * x, axis=1, keepdims=True) * inv_h
    else:
        var = jnp.sum(x * x, axis=1, keepdims=True) * inv_h - mean * mean
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    y = xhat * g_ref[...].astype(jnp.float32)
    if b_ref is not None:
        y = y + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, dy_ref,
                dx_ref, dg_ref, db_ref, *, true_h: int, rms: bool):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    gamma = g_ref[...].astype(jnp.float32)
    mean = mean_ref[...]
    rstd = rstd_ref[...]
    xhat = (x - mean) * rstd
    wdy = dy * gamma
    inv_h = 1.0 / true_h
    c1 = jnp.sum(xhat * wdy, axis=1, keepdims=True) * inv_h
    if rms:
        dx = (wdy - xhat * c1) * rstd
    else:
        c2 = jnp.sum(wdy, axis=1, keepdims=True) * inv_h
        dx = (wdy - c2 - xhat * c1) * rstd
    dx_ref[...] = dx.astype(dx_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        if db_ref is not None:
            db_ref[...] = jnp.zeros_like(db_ref)

    dg_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    if db_ref is not None:
        db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)


def _specs(h, br):
    row = pl.BlockSpec((br, h), lambda i: (i, 0),
                       memory_space=pltpu.VMEM)
    vec = pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM)
    stat = pl.BlockSpec((br, 1), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    return row, vec, stat


def _pallas_fwd(x2, gamma2, beta2, eps, true_h, rms, br):
    rows, h = x2.shape
    row, vec, stat = _specs(h, br)
    if beta2 is not None:
        kernel = functools.partial(_fwd_kernel, eps=eps, true_h=true_h,
                                   rms=rms)
        in_specs, args = [row, vec, vec], (x2, gamma2, beta2)
    else:
        kernel = functools.partial(
            lambda xr, gr, yr, mr, rr, **kw: _fwd_kernel(
                xr, gr, None, yr, mr, rr, **kw),
            eps=eps, true_h=true_h, rms=rms)
        in_specs, args = [row, vec], (x2, gamma2)
    return kernel_call(
        kernel,
        name="layer_norm_fwd",
        grid=(pl.cdiv(rows, br),),
        in_specs=in_specs,
        out_specs=(row, stat, stat),
        out_shape=(out_struct((rows, h), x2.dtype, x2, gamma2),
                   out_struct((rows, 1), jnp.float32, x2, gamma2),
                   out_struct((rows, 1), jnp.float32, x2, gamma2)),
        interpret=interpret_mode(),
    )(*args)


def _pallas_bwd(x2, gamma2, mean, rstd, dy2, true_h, rms, with_beta, br):
    rows, h = x2.shape
    row, vec, stat = _specs(h, br)
    if with_beta:
        kernel = functools.partial(_bwd_kernel, true_h=true_h, rms=rms)
        out_specs = (row, vec, vec)
        out_shape = (out_struct((rows, h), x2.dtype, x2, gamma2, dy2),
                     out_struct((1, h), jnp.float32, x2, gamma2, dy2),
                     out_struct((1, h), jnp.float32, x2, gamma2, dy2))
    else:
        kernel = functools.partial(
            lambda xr, gr, mr, rr, dyr, dxr, dgr, **kw: _bwd_kernel(
                xr, gr, mr, rr, dyr, dxr, dgr, None, **kw),
            true_h=true_h, rms=rms)
        out_specs = (row, vec)
        out_shape = (out_struct((rows, h), x2.dtype, x2, gamma2, dy2),
                     out_struct((1, h), jnp.float32, x2, gamma2, dy2))
    return kernel_call(
        kernel,
        name="layer_norm_bwd",
        grid=(pl.cdiv(rows, br),),
        in_specs=[row, vec, stat, stat, row],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret_mode(),
    )(x2, gamma2, mean, rstd, dy2)


# --------------------------------------------------------------------------
# custom_vjp plumbing
# --------------------------------------------------------------------------

def _prep(x, gamma, beta, block_rows=None):
    x2, shape = as_rows(x)
    h = x2.shape[-1]
    # computed ONCE; launchers take it. None = table > heuristic.
    br = tuned_row_block("layer_norm", h, rows=x2.shape[0],
                         dtype=x.dtype, requested=block_rows)
    x2p, rows = pad_to(x2, 0, br)
    x2p, _ = pad_to(x2p, 1, 128)
    g2 = pad_to(gamma.reshape(1, -1), 1, 128)[0]
    b2 = pad_to(beta.reshape(1, -1), 1, 128)[0] if beta is not None else None
    return x2p, g2, b2, shape, h, rows, br


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_norm(x, gamma, beta, eps, rms, block_rows):
    return _fused_norm_fwd(x, gamma, beta, eps, rms, block_rows)[0]


def _fused_norm_fwd(x, gamma, beta, eps, rms, block_rows):
    x2p, g2, b2, shape, h, rows, br = _prep(x, gamma, beta, block_rows)
    y, mean, rstd = _pallas_fwd(x2p, g2, b2, eps, h, rms, br)
    y = y[:rows, :h].reshape(shape)
    return y, (x, gamma, beta, mean, rstd)


def _fused_norm_bwd(eps, rms, block_rows, res, dy):
    x, gamma, beta, mean, rstd = res
    x2p, g2, _, shape, h, rows, br = _prep(x, gamma, beta, block_rows)
    dy2, _ = as_rows(dy)
    dy2p, _ = pad_to(dy2, 0, br)
    dy2p, _ = pad_to(dy2p, 1, 128)
    outs = _pallas_bwd(x2p, g2, mean, rstd, dy2p, h, rms,
                       with_beta=beta is not None, br=br)
    dx = outs[0][:rows, :h].reshape(shape)
    dg = outs[1][0, :h].astype(gamma.dtype)
    if beta is not None:
        db = outs[2][0, :h].astype(beta.dtype)
        return dx, dg, db
    return dx, dg, None


_fused_norm.defvjp(_fused_norm_fwd, _fused_norm_bwd)


# --------------------------------------------------------------------------
# XLA composite (gold / fallback)
# --------------------------------------------------------------------------

def _xla_norm(x, gamma, beta, eps, rms):
    x32 = x.astype(jnp.float32)
    mean = 0.0 if rms else jnp.mean(x32, axis=-1, keepdims=True)
    xc = x32 - mean
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    if not rms:
        var = var - jnp.square(mean)
    y = xc * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)
    if beta is not None:
        y = y + beta.astype(jnp.float32)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

def _norm(x, gamma, beta, eps, rms, block_rows):
    if not use_pallas():
        return _xla_norm(x, gamma, beta, eps, rms)
    kdt = mosaic_dtype(x.dtype)  # fp16 -> bf16 on compiled TPU
    gamma, beta = to_mosaic(gamma, beta)  # O3_fp16 params
    if kdt != x.dtype:
        return _fused_norm(x.astype(kdt), gamma, beta, eps, rms,
                           block_rows).astype(x.dtype)
    return _fused_norm(x, gamma, beta, eps, rms, block_rows)


def layer_norm(x, gamma, beta, *, eps: float = 1e-5,
               block_rows: int | None = None):
    """Fused LayerNorm over the last axis. bf16/fp16 ``x`` with fp32 ``γ/β``
    is the reference "MixedFused" path; output keeps ``x.dtype``.
    ``block_rows``: static rows-per-grid-step; ``None`` resolves tuning
    table > heuristic (`apex1_tpu.tuning.tuned_row_block`)."""
    with region("norm"):
        return _norm(x, gamma, beta, eps, False, block_rows)


def rms_norm(x, gamma, *, eps: float = 1e-6,
             block_rows: int | None = None):
    """Fused RMSNorm (``FusedRMSNorm`` — stock torch lacked it)."""
    with region("norm"):
        return _norm(x, gamma, None, eps, True, block_rows)


# --------------------------------------------------------------------------
# module API — drop-in parity with apex.normalization
# --------------------------------------------------------------------------

import flax.linen as nn  # noqa: E402


def _flat_h(normalized_shape) -> int:
    if isinstance(normalized_shape, int):
        return normalized_shape
    h = 1
    for s in normalized_shape:
        h *= s
    return h


class FusedLayerNorm(nn.Module):
    """``apex.normalization.FusedLayerNorm(normalized_shape, eps,
    elementwise_affine, memory_efficient)`` equivalent (flax module).
    Multi-dim ``normalized_shape`` is flattened into the fused kernel's row
    axis, as the reference wrapper does. γ/β live in fp32 ("mixed" kernels).
    """

    normalized_shape: int | Sequence[int]
    eps: float = 1e-5
    elementwise_affine: bool = True
    memory_efficient: bool = False

    @nn.compact
    def __call__(self, x):
        h = _flat_h(self.normalized_shape)
        orig = x.shape
        x = x.reshape(orig[: x.ndim - (1 if isinstance(
            self.normalized_shape, int) else len(self.normalized_shape))]
            + (h,))
        if self.elementwise_affine:
            gamma = self.param("scale", nn.initializers.ones, (h,),
                               jnp.float32)
            beta = self.param("bias", nn.initializers.zeros, (h,),
                              jnp.float32)
        else:
            gamma, beta = jnp.ones((h,), jnp.float32), None
        fn = functools.partial(layer_norm, eps=self.eps)
        if self.memory_efficient:
            fn = jax.checkpoint(fn)
        return fn(x, gamma, beta).reshape(orig)


class FusedRMSNorm(nn.Module):
    """``apex.normalization.FusedRMSNorm`` equivalent."""

    normalized_shape: int | Sequence[int]
    eps: float = 1e-6
    elementwise_affine: bool = True
    memory_efficient: bool = False

    @nn.compact
    def __call__(self, x):
        h = _flat_h(self.normalized_shape)
        orig = x.shape
        x = x.reshape(orig[: x.ndim - (1 if isinstance(
            self.normalized_shape, int) else len(self.normalized_shape))]
            + (h,))
        if self.elementwise_affine:
            gamma = self.param("scale", nn.initializers.ones, (h,),
                               jnp.float32)
        else:
            gamma = jnp.ones((h,), jnp.float32)
        fn = functools.partial(rms_norm, eps=self.eps)
        if self.memory_efficient:
            fn = jax.checkpoint(fn)
        return fn(x, gamma).reshape(orig)
