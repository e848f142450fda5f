"""Weight-only int8 quantized matmul — the TPU decode path.

Beyond-reference capability (the reference accelerates training only; its
closest artifact is the fp16 weight cast of amp O2, `apex/amp/_initialize.py
:: _initialize`): autoregressive decode is HBM-bandwidth-bound — every step
streams every weight once for a handful of rows of compute — so halving
weight bytes nearly halves step time. Weights are stored int8 with
per-output-channel fp32 scales and dequantized INSIDE the Pallas kernel's
VMEM tiles (bf16 cast → MXU matmul → fp32 accumulate → scale on the final
K block), so the bf16 weight matrix is never materialized in HBM.

- :func:`quantize_int8` — symmetric per-out-channel quantization of a
  ``(N, K)`` weight (max-abs / 127).
- :func:`int8_matmul` — ``y = x @ (wq * scale).T`` with the dequant fused;
  differentiable in ``x`` only (weights are frozen at decode time).

Dispatch follows `ops._common` (``set_impl`` / ``force_impl``): the XLA
composite (explicit dequant then matmul) is the parity gold and the
fallback for unaligned shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex1_tpu.ops._common import (interpret_mode, kernel_call,
                                   out_struct, use_pallas)


def quantize_int8(w, *, axis: int = -1):
    """Symmetric per-channel int8 quantization of a 2-D weight.

    ``w``: (N, K) with ``axis`` the contraction (K) axis — each of the N
    output channels gets one fp32 scale = max|w| / 127 over its K entries.
    Returns ``(wq int8 (N, K), scale fp32 (N,))`` with
    ``w ≈ wq * scale[:, None]``.
    """
    if w.ndim != 2:
        raise ValueError(f"quantize_int8 expects a 2-D weight, got "
                         f"{w.shape}")
    if axis not in (0, 1, -1, -2):
        raise ValueError(f"axis must name one of the 2 dims, got {axis}")
    if axis in (0, -2):
        w = w.T
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=1) / 127.0
    scale = jnp.maximum(scale, 1e-12)  # all-zero channels stay zero
    wq = jnp.clip(jnp.round(wf / scale[:, None]), -127, 127)
    return wq.astype(jnp.int8), scale


def _dequant_matmul_xla(x, wq, scale):
    """Gold composite: explicit dequant then matmul (XLA fuses the dequant
    into the dot's operand stream, but still reads int8 + writes bf16
    unless it fuses — the kernel guarantees the fusion). The per-channel
    scale stays fp32 and multiplies the fp32 accumulator output, exactly
    as the Pallas kernel does — both paths share one numerics contract
    (a bf16-cast scale here would make the gold ~0.4% noisier than the
    kernel it golds, and shape-dependent, since this composite is also
    the unaligned-shape fallback). The ACTIVATION is cast to bf16 for
    the same reason: the kernel feeds the MXU bf16 activations, and an
    fp32-x composite would make fp32 callers' results shape-dependent
    (kernel on aligned shapes, more-precise composite on unaligned —
    found by the int8 shape fuzz, round 5). Production decode passes
    bf16 activations, where this cast is a no-op."""
    y = jnp.matmul(x.astype(jnp.bfloat16), wq.astype(jnp.bfloat16).T,
                   preferred_element_type=jnp.float32)
    return y * scale.astype(jnp.float32)


def _int8_mm_kernel(x_ref, wq_ref, scale_ref, o_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xb = x_ref[...]
    wb = wq_ref[...].astype(jnp.bfloat16)          # dequant lives in VMEM
    o_ref[...] += jnp.dot(xb, wb.T,
                          preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _scale():
        o_ref[...] *= scale_ref[...].astype(jnp.float32)


def _fit_block(size: int, want: int) -> int:
    """Largest multiple-of-128 DIVISOR of ``size`` that is <= ``want``.
    Blocks must tile the dim exactly: a pl.cdiv ragged tail block would
    read out-of-bounds K columns and accumulate garbage into every
    output (there is no pad_to here — weights are static, callers
    shouldn't pay a per-call pad copy). The gate guarantees
    ``size % 128 == 0``, so 128 always divides."""
    units = size // 128
    for cand in range(min(want // 128, units), 0, -1):
        if units % cand == 0:
            return cand * 128
    return 128


def _pallas_int8_matmul(x, wq, scale, block_n: int, block_k: int):
    T, K = x.shape
    N = wq.shape[0]
    bn = _fit_block(N, block_n)
    bk = _fit_block(K, block_k)
    grid = (N // bn, K // bk)
    return kernel_call(
        _int8_mm_kernel,
        name="int8_matmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec((T, bk), lambda n, k: (0, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, bk), lambda n, k: (n, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda n, k: (0, n),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((T, bn), lambda n, k: (0, n),
                               memory_space=pltpu.VMEM),
        out_shape=out_struct((T, N), jnp.float32, x, wq, scale),
        interpret=interpret_mode(),
    )(x, wq, scale.reshape(1, N))


def _aligned_for_kernel(T, N, K):
    # int8 VMEM tiles are (32, 128); bf16 (16, 128). Demand lane (128)
    # alignment on both matmul dims and a sublane-friendly row count —
    # everything else takes the composite (decode shapes from real models
    # are 128-aligned; tiny test configs are not, and padding tiny cases
    # would be pure overhead).
    return N % 128 == 0 and K % 128 == 0 and T <= 1024


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def int8_matmul(x, wq, scale, block_n: int | None = None,
                block_k: int | None = None):
    """``y = x @ (wq * scale[:, None]).T`` — (T, K) @ (K, N) -> (T, N).

    ``x`` bf16/fp32 activations, ``wq`` int8 (N, K), ``scale`` fp32 (N,)
    (from :func:`quantize_int8`). fp32 accumulation; output fp32 (cast at
    the call site). Differentiable in ``x`` only — weight cotangents are
    zero (decode-time weights are frozen; quantization is not trained
    through). ``block_n``/``block_k``: static Pallas tile requests
    (divisor-fitted to N/K); ``None`` resolves tuning-table winner for
    this (generation, N, K) > the (256, 512) defaults.
    """
    return _int8_matmul_fwd(x, wq, scale, block_n, block_k)[0]


def _resolve_blocks(N, K, block_n, block_k):
    """Explicit request > tuning table (keyed on the weight dims — both
    128-aligned by `_aligned_for_kernel`) > the (256, 512) defaults."""
    if block_n is None or block_k is None:
        from apex1_tpu import tuning
        tuned = tuning.lookup("int8_matmul", {"N": N, "K": K},
                              "int8") or {}
        block_n = block_n if block_n is not None else tuned.get("block_n")
        block_k = block_k if block_k is not None else tuned.get("block_k")
    return block_n or 256, block_k or 512


def _int8_matmul_fwd(x, wq, scale, block_n, block_k):
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = wq.shape[0]
    x2 = x.reshape(-1, K)
    if use_pallas() and _aligned_for_kernel(x2.shape[0], N, K):
        block_n, block_k = _resolve_blocks(N, K, block_n, block_k)
        x8 = x2
        if x8.shape[0] % 8:  # sublane-pad the (tiny) row dim
            pad = 8 - x8.shape[0] % 8
            x8 = jnp.pad(x8, ((0, pad), (0, 0)))
        y = _pallas_int8_matmul(x8.astype(jnp.bfloat16), wq, scale,
                                block_n, block_k)[:x2.shape[0]]
    else:
        y = _dequant_matmul_xla(x2, wq, scale)
    # residuals carry only what bwd reads: the weights and x's DTYPE (as
    # a 0-sized proto array — saving x itself would keep the whole
    # (..., K) activation alive just to call .astype on dx)
    return y.reshape(*lead, N), (jnp.zeros((0,), x.dtype), wq, scale)


def _int8_matmul_bwd(block_n, block_k, res, dy):
    x_proto, wq, scale = res
    # fp32 AD transpose of the fwd contract y = (x₁₆ @ wq₁₆ᵀ)·s₃₂: the
    # scale rides the fp32 cotangent and the whole dot runs fp32 (int8
    # weight values are exact in any float width, and dx is a
    # test/tooling path — decode weights are frozen — so precision
    # beats MXU-operand casting). The previous form cast BOTH dy and
    # the scale to bf16, the same shape-dependent-numerics class
    # ADVICE r4 flagged on the fwd composite — caught by the int8
    # shape fuzz.
    dx = jnp.matmul(dy.astype(jnp.float32) * scale.astype(jnp.float32),
                    wq.astype(jnp.float32),
                    preferred_element_type=jnp.float32).astype(
                        x_proto.dtype)
    return dx, jnp.zeros_like(wq), jnp.zeros_like(scale)


int8_matmul.defvjp(_int8_matmul_fwd, _int8_matmul_bwd)
