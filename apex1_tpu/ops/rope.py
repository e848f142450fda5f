"""Fused rotary positional embedding — Pallas TPU kernel.

Reference: ``csrc/megatron/fused_rotary_positional_embedding.{cpp,_cuda.cu}``
(fwd/bwd apply, sbhd/thd layouts).

Both rotation conventions are provided:
- ``interleaved=False`` (NeoX/Llama "half" style, the reference's
  ``rotate_half``): x1 = x[..., :d/2], x2 = x[..., d/2:],
  out = [x1·cos − x2·sin, x2·cos + x1·sin]
- ``interleaved=True`` (GPT-J style): even/odd lanes form the pairs.

The backward of a rotation is the rotation by −θ — implemented as the same
kernel with sin negated (what the reference's bwd kernel does), exposed via
``custom_vjp`` so autodiff never materializes the big intermediate.

Layout: (..., seq, heads, head_dim) or (..., seq, head_dim); cos/sin are
(seq, head_dim/2) fp32 tables.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex1_tpu.ops._common import (interpret_mode, kernel_call,
                                   out_struct, use_pallas)
from apex1_tpu.tuning import tuned_row_block


def rope_tables(positions, head_dim: int, *, base: float = 10000.0,
                dtype=jnp.float32):
    """cos/sin tables: (len(positions), head_dim/2)."""
    half = head_dim // 2
    inv_freq = 1.0 / (base ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def _rope_kernel(x1_ref, x2_ref, cos_ref, sin_ref, o1_ref, o2_ref):
    x1 = x1_ref[...].astype(jnp.float32)
    x2 = x2_ref[...].astype(jnp.float32)
    c = cos_ref[...]
    s = sin_ref[...]
    o1_ref[...] = (x1 * c - x2 * s).astype(o1_ref.dtype)
    o2_ref[...] = (x2 * c + x1 * s).astype(o2_ref.dtype)


def _pallas_rope(x1, x2, cos_r, sin_r, block_rows=None):
    rows, half = x1.shape
    # 4 ins + 2 outs double-buffered; None = table > heuristic
    br = tuned_row_block("rope", half, rows=rows, dtype=x1.dtype,
                         requested=block_rows)
    row = pl.BlockSpec((br, half), lambda i: (i, 0),
                       memory_space=pltpu.VMEM)
    return kernel_call(
        _rope_kernel,
        name="rope",
        grid=(pl.cdiv(rows, br),),
        in_specs=[row, row, row, row],
        out_specs=(row, row),
        out_shape=(out_struct(x1.shape, x1.dtype, x1, x2, cos_r, sin_r),
                   out_struct(x2.shape, x2.dtype, x1, x2, cos_r, sin_r)),
        interpret=interpret_mode(),
    )(x1, x2, cos_r, sin_r)


def _split(x, interleaved):
    if interleaved:
        return x[..., 0::2], x[..., 1::2]
    half = x.shape[-1] // 2
    return x[..., :half], x[..., half:]


def _merge(o1, o2, interleaved):
    if interleaved:
        return jnp.stack([o1, o2], axis=-1).reshape(
            o1.shape[:-1] + (o1.shape[-1] * 2,))
    return jnp.concatenate([o1, o2], axis=-1)


def _infer_seq_axis(x, seq_len: int) -> int:
    """Pick the sequence axis: prefer -3 ("seq, heads, head_dim" layout),
    then -2 ("seq, head_dim"); both must match the table length."""
    for ax in (x.ndim - 3, x.ndim - 2):
        if ax >= 0 and x.shape[ax] == seq_len:
            return ax
    raise ValueError(
        f"cannot infer sequence axis: no axis of {x.shape} at -3/-2 matches "
        f"the cos/sin table length {seq_len}; pass seq_axis explicitly")


def _apply(x, cos, sin, interleaved, seq_axis, block_rows=None):
    """Shared fwd path; bwd = fwd with −sin (rotation transpose)."""
    shape = x.shape
    half = shape[-1] // 2
    seq = shape[seq_axis]
    x1, x2 = _split(x, interleaved)
    # broadcast tables over batch/heads -> row layout (R, half)
    bshape = [1] * x.ndim
    bshape[seq_axis] = seq
    bshape[-1] = half
    if cos.ndim == 3:
        # per-row tables (B, seq, half) — packed/varlen batches where
        # positions restart per segment (≙ the reference's thd variant)
        bshape[0] = cos.shape[0]
    c = jnp.broadcast_to(cos.astype(jnp.float32).reshape(bshape),
                         x1.shape).reshape(-1, half)
    s = jnp.broadcast_to(sin.astype(jnp.float32).reshape(bshape),
                         x1.shape).reshape(-1, half)
    if use_pallas() and half % 128 == 0:
        o1, o2 = _pallas_rope(x1.reshape(-1, half), x2.reshape(-1, half),
                              c, s, block_rows)
        o1 = o1.reshape(x1.shape)
        o2 = o2.reshape(x2.shape)
    else:
        c = c.reshape(x1.shape)
        s = s.reshape(x1.shape)
        x1f = x1.astype(jnp.float32)
        x2f = x2.astype(jnp.float32)
        o1 = (x1f * c - x2f * s).astype(x.dtype)
        o2 = (x2f * c + x1f * s).astype(x.dtype)
    return _merge(o1, o2, interleaved).reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rope(x, cos, sin, interleaved, seq_axis, block_rows):
    return _apply(x, cos, sin, interleaved, seq_axis, block_rows)


def _rope_fwd(x, cos, sin, interleaved, seq_axis, block_rows):
    return _apply(x, cos, sin, interleaved, seq_axis, block_rows), \
        (cos, sin)


def _rope_bwd(interleaved, seq_axis, block_rows, res, dy):
    cos, sin = res
    return _apply(dy, cos, -sin, interleaved, seq_axis, block_rows), \
        None, None


_rope.defvjp(_rope_fwd, _rope_bwd)


def apply_rotary_pos_emb(x, cos, sin, *, interleaved: bool = False,
                         seq_axis: int | None = None,
                         block_rows: int | None = None):
    """Apply RoPE. ``x``: (..., seq, heads, head_dim) or (..., seq,
    head_dim); ``cos/sin``: (seq, head_dim/2) from `rope_tables`, or
    (B, seq, head_dim/2) per-row tables for packed/varlen batches
    (positions restarting per segment — the reference's thd variant).
    The sequence axis is inferred from the table length (prefer -3, then
    -2); pass ``seq_axis`` when ambiguous. ``block_rows``: static
    rows-per-grid-step; ``None`` resolves tuning table > heuristic
    (`apex1_tpu.tuning.tuned_row_block`)."""
    if x.shape[-1] % 2:
        raise ValueError("head_dim must be even for RoPE")
    if cos.ndim == 3 and cos.shape[0] != x.shape[0]:
        raise ValueError(
            f"per-row tables {cos.shape} need leading dim == batch "
            f"{x.shape[0]}")
    seq_len = cos.shape[1] if cos.ndim == 3 else cos.shape[0]
    if seq_axis is None:
        seq_axis = _infer_seq_axis(x, seq_len)
    else:
        seq_axis = seq_axis % x.ndim
    return _rope(x, cos, sin, interleaved, seq_axis, block_rows)
