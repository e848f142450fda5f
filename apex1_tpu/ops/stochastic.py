"""In-kernel stochasticity — counter-based dropout masks + the fused
bias-dropout-add(-LayerNorm) Pallas family.

Reference capability: the ``csrc/multihead_attn``/fmha kernels fuse
attention-probability dropout between softmax and AV inside every
forward/backward pair, and Megatron-style stacks fuse the
``bias_dropout_add`` residual epilogue (flash-attn's
``fused_dropout_add_ln``). The TPU-native answer is COUNTER-BASED masks:

- **no mask tensor is ever stored** — forward and backward both derive
  the keep mask from an int32 seed plus position counters (the same
  recompute-instead-of-save trade the flash kernels already make for
  probabilities), so dropout adds zero activation memory;
- **on TPU** the mask comes from the hardware PRNG: each kernel grid
  step re-seeds with ``pltpu.prng_seed(*_seed_words(seed, salt, row0,
  col0))`` (salt ≙ batch·H+head for attention, 0 for row kernels;
  row0/col0 are GLOBAL tile offsets; Mosaic takes at most two seed
  words, so the four counters are hashed into two) and draws one
  ``pltpu.prng_random_bits`` tile — streams are keyed on position, so
  the mask is independent of grid iteration order and of ring-shard
  visiting order, and context-parallel shards draw disjoint,
  shift-invariant streams (their global k-offset is folded into the
  counter);
- **off TPU** (Pallas interpret mode + the XLA composites, where the
  Mosaic PRNG primitives do not lower) the same counters feed a uint32
  avalanche hash evaluated per element at its GLOBAL position — the
  interpret-mode kernels and the XLA gold produce BIT-IDENTICAL masks,
  which is what makes the recompute-identity testable on the CPU suite.

Determinism contract (docs/perf_playbook.md "In-kernel dropout"): same
(seed, shape, positions) → bit-identical mask across calls and jit
boundaries, per backend. The mask is NOT bitwise-matched to a
``jax.random.bernoulli`` composite (different PRNG) — statistical
parity only; and the TPU hardware-PRNG mask differs bitwise from the
CPU hash mask (each is internally consistent between forward and
backward).

Seeds are PLAIN int32 words, not ``jax.random`` keys: deriving one per
call site via ``jax.random.randint(rng, (), 0, SEED_MAX)`` (or
``fold_seed`` for per-layer streams) is the sanctioned idiom — graftlint
APX103 knows a seed consumed by ``pltpu.prng_seed`` is not key reuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex1_tpu.ops._common import (as_rows, interpret_mode, kernel_call,
                                   mosaic_dtype, out_struct, pad_to, to_mosaic,
                                   use_pallas)
from apex1_tpu.ops.layer_norm import layer_norm, rms_norm
from apex1_tpu.tuning import tuned_row_block

SEED_MAX = 0x7FFFFFFF  # jax.random.randint upper bound for seed derivation

_GOLDEN = 0x9E3779B9   # 2^32/φ — Weyl increment for salting
_C_ROW = 0x85EBCA6B    # odd multipliers: murmur3 finalizer constants
_C_COL = 0xC2B2AE35


def _mix32(x):
    """'lowbias32' avalanche finalizer on uint32 lanes (bijective).
    Constants are NUMPY scalars: they fold into the kernel jaxpr as
    literals instead of captured traced constants (pallas_call rejects
    closure-captured arrays)."""
    x = x ^ (x >> 16)
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> 16)


def hash_bits_u32(seed, salt, row, col):
    """Counter-based uint32 stream: one word per (seed, salt, row, col).

    ``seed``/``salt`` are int32 scalars (or broadcastable arrays);
    ``row``/``col`` int32 position counters. Chained bijective mixes:
    for a fixed (seed, salt) the map row→h is a bijection and col
    perturbs a fully-mixed word, so neighbouring positions decorrelate
    (keep-rate tests in tests/test_stochastic.py hold at p=0.1/0.5).
    The salt branch gets its own avalanche before row enters — salt and
    row must NOT be algebraically interchangeable, or (salt=a, row=b)
    and (salt=b, row=a) would draw identical streams and per-head masks
    would be pairwise correlated across (batch·head, q-row) pairs.
    """
    h = _mix32(_stream_u32(seed, salt)
               ^ row.astype(jnp.uint32) * np.uint32(_C_ROW))
    return _mix32(h ^ col.astype(jnp.uint32) * np.uint32(_C_COL))


def _stream_u32(seed, salt):
    """The (seed, salt) stage of `hash_bits_u32`: one fully mixed word
    per stream, before any position enters."""
    s = _mix32(jnp.asarray(seed).astype(jnp.uint32) + np.uint32(_GOLDEN))
    return _mix32(s ^ jnp.asarray(salt).astype(jnp.uint32)
                  * np.uint32(_C_ROW))


def threshold_u32(p: float) -> np.uint32:
    """Drop threshold: keep iff bits >= round(p·2^32) (uint32 compare).
    A numpy scalar (static per-trace), never a traced array — kernels
    consume it as a literal."""
    if not 0.0 < p < 1.0:
        raise ValueError(f"dropout p must be in (0, 1), got {p}")
    return np.uint32(min(int(round(p * 4294967296.0)), 0xFFFFFFFF))


def attn_keep_mask(seed, num_batch, num_heads, rows, cols, p):
    """Attention-probability keep mask at GLOBAL positions — the XLA
    composite analog of the kernels' tile draws. ``rows``/``cols`` are
    (Sq, Sk) int32 global-position grids (caller folds in its q/k
    offsets); returns bool (num_batch, num_heads, Sq, Sk).

    The single source of truth for the composite mask: the flash
    composite forward (`attention._xla_attention`) and the ring backward
    (`parallel.ring_attention`) both derive it here, so the
    forward/backward recompute identity cannot drift between files.
    Per-(batch, head) streams fold ``b·H + h`` into the salt — the same
    keying as the kernels."""
    shp = (num_batch, num_heads, 1, 1)
    salt = (jax.lax.broadcasted_iota(jnp.int32, shp, 0) * num_heads
            + jax.lax.broadcasted_iota(jnp.int32, shp, 1))
    bits = hash_bits_u32(jnp.asarray(seed, jnp.int32), salt,
                         rows[None, None], cols[None, None])
    return bits >= threshold_u32(p)


def _seed_words(seed, salt, row0, col0):
    """Two int32 PRNG seed words from the four tile counters — Mosaic's
    ``prng_seed`` rejects more than two. The scalar `hash_bits_u32`
    chain, one word per stage: word 0 is the (seed, salt) stream, word 1
    that stream hashed with the GLOBAL tile offset, so the pair is a
    pure function of exactly the counters the forward and both backward
    kernels share."""
    row0, col0 = jnp.asarray(row0, jnp.int32), jnp.asarray(col0, jnp.int32)
    # same-width integer converts wrap (Mosaic integers are signless); a
    # scalar bitcast_convert_type does not lower ('tpu.bitcast' is
    # vector-only)
    return (_stream_u32(seed, salt).astype(jnp.int32),
            hash_bits_u32(seed, salt, row0, col0).astype(jnp.int32))


def tile_keep_mask(shape, thr, seed, salt, row0, col0, *, interp: bool):
    """(bool) keep mask for one kernel tile at GLOBAL offset (row0, col0).

    ``interp`` is the kernel's static interpret flag: on real TPU the
    tile is one hardware-PRNG draw seeded on the position counters; in
    interpret mode each element hashes its global position (bit-equal to
    the XLA composites' mask). Forward and backward kernels call this
    with identical arguments — that IS the recompute identity.
    """
    if interp:
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + row0
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + col0
        bits = hash_bits_u32(seed, salt, row, col)
    else:
        pltpu.prng_seed(*_seed_words(seed, salt, row0, col0))
        bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return bits >= thr


def seed_from_key(key):
    """Derive an int32 dropout seed from a ``jax.random`` key — the
    sanctioned call-site idiom (one consumption of the key; the seed
    itself is reused freely by forward+backward recompute)."""
    return jax.random.randint(key, (), 0, SEED_MAX, jnp.int32)


def fold_seed(seed, salt: int):
    """Per-site stream derivation from one base seed (≙ ``fold_in`` for
    int32 seeds): call sites that share a base seed MUST fold distinct
    static salts or they draw identical masks."""
    s = jnp.asarray(seed, jnp.int32).astype(jnp.uint32)
    s = _mix32(s + np.uint32((_GOLDEN * (salt + 1)) & 0xFFFFFFFF))
    # int32 seeds stay non-negative so they round-trip through SMEM refs
    # and jax.random.randint-derived seeds share the same value range
    return (s & np.uint32(SEED_MAX)).astype(jnp.int32)


# --------------------------------------------------------------------------
# fused bias + dropout + residual-add (row kernel)
# --------------------------------------------------------------------------

def _bda_fwd_kernel(seed_ref, x_ref, b_ref, r_ref, o_ref, *,
                    thr, inv_keep, br, interp):
    x = x_ref[...].astype(jnp.float32)
    if b_ref is not None:
        x = x + b_ref[...].astype(jnp.float32)
    keep = tile_keep_mask(x.shape, thr, seed_ref[0, 0], 0,
                          pl.program_id(0) * br, 0, interp=interp)
    y = jnp.where(keep, x * inv_keep, 0.0) + r_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _bda_bwd_kernel(seed_ref, dy_ref, dx_ref, db_ref, *,
                    thr, inv_keep, br, interp):
    dy = dy_ref[...].astype(jnp.float32)
    keep = tile_keep_mask(dy.shape, thr, seed_ref[0, 0], 0,
                          pl.program_id(0) * br, 0, interp=interp)
    dx = jnp.where(keep, dy * inv_keep, 0.0)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    if db_ref is not None:
        @pl.when(pl.program_id(0) == 0)
        def _():
            db_ref[...] = jnp.zeros_like(db_ref)

        # padded rows carry zero dy — their contribution is exact zero
        db_ref[...] += jnp.sum(dx, axis=0, keepdims=True)


def _bda_prep(x, block_rows):
    x2, shape = as_rows(x)
    h = x2.shape[-1]
    br = tuned_row_block("bias_dropout_add", h, rows=x2.shape[0],
                         dtype=x.dtype, requested=block_rows)
    x2p, rows = pad_to(x2, 0, br)
    x2p, _ = pad_to(x2p, 1, 128)
    return x2p, shape, h, rows, br


def _bda_specs(h, br):
    row = pl.BlockSpec((br, h), lambda i: (i, 0), memory_space=pltpu.VMEM)
    vec = pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM)
    smem = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    return row, vec, smem


def _bda_pallas_fwd(x2p, b2, r2p, seed, p, br):
    rows, hp = x2p.shape
    row, vec, smem = _bda_specs(hp, br)
    sarr = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    kw = dict(thr=threshold_u32(p), inv_keep=1.0 / (1.0 - p), br=br,
              interp=interpret_mode())
    if b2 is not None:
        kernel = functools.partial(_bda_fwd_kernel, **kw)
        in_specs, args = [smem, row, vec, row], (sarr, x2p, b2, r2p)
    else:
        kernel = functools.partial(
            lambda sr, xr, rr, orf, **k: _bda_fwd_kernel(
                sr, xr, None, rr, orf, **k), **kw)
        in_specs, args = [smem, row, row], (sarr, x2p, r2p)
    return kernel_call(
        kernel,
        name="bias_dropout_add_fwd",
        grid=(pl.cdiv(rows, br),),
        in_specs=in_specs,
        out_specs=row,
        out_shape=out_struct((rows, hp), x2p.dtype, x2p, r2p),
        interpret=interpret_mode(),
    )(*args)


def _bda_pallas_bwd(dy2p, seed, p, br, with_bias):
    rows, hp = dy2p.shape
    row, vec, smem = _bda_specs(hp, br)
    sarr = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    kw = dict(thr=threshold_u32(p), inv_keep=1.0 / (1.0 - p), br=br,
              interp=interpret_mode())
    if with_bias:
        kernel = functools.partial(_bda_bwd_kernel, **kw)
        out_specs = (row, vec)
        out_shape = (out_struct((rows, hp), dy2p.dtype, dy2p),
                     out_struct((1, hp), jnp.float32, dy2p))
    else:
        kernel = functools.partial(
            lambda sr, dyr, dxr, **k: _bda_bwd_kernel(
                sr, dyr, dxr, None, **k), **kw)
        out_specs = row
        out_shape = out_struct((rows, hp), dy2p.dtype, dy2p)
    return kernel_call(
        kernel,
        name="bias_dropout_add_bwd",
        grid=(pl.cdiv(rows, br),),
        in_specs=[smem, row],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret_mode(),
    )(sarr, dy2p)


def _bda_xla_mask(seed, rows, h):
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, h), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, h), 1)
    return hash_bits_u32(seed, 0, row, col)


def _bda_xla(x, residual, bias, seed, p):
    """XLA composite — the SAME counter hash at global positions, so the
    interpret-mode kernel and this gold are bit-identical on CPU."""
    x2, shape = as_rows(x)
    rows, h = x2.shape
    xb = x2.astype(jnp.float32)
    if bias is not None:
        xb = xb + bias.reshape(1, -1).astype(jnp.float32)
    keep = _bda_xla_mask(seed, rows, h) >= threshold_u32(p)
    r2, _ = as_rows(residual)
    y = (jnp.where(keep, xb * (1.0 / (1.0 - p)), 0.0)
         + r2.astype(jnp.float32))
    return y.astype(x.dtype).reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _bda(x, residual, bias, seed, p, has_bias, block_rows):
    return _bda_fwd(x, residual, bias, seed, p, has_bias, block_rows)[0]


def _bda_fwd(x, residual, bias, seed, p, has_bias, block_rows):
    x2p, shape, h, rows, br = _bda_prep(x, block_rows)
    r2, _ = as_rows(residual)
    r2p, _ = pad_to(r2, 0, br)
    r2p, _ = pad_to(r2p, 1, 128)
    b2 = (pad_to(bias.reshape(1, -1), 1, 128)[0] if has_bias else None)
    y = _bda_pallas_fwd(x2p, b2, r2p, seed, p, br)
    y = y[:rows, :h].reshape(shape)
    # dtype tokens (zero-size, never materialized) instead of the live
    # activations: the backward needs only the seed — that is the whole
    # zero-mask-storage point of the counter-based design
    return y, (seed, jnp.zeros((0,), residual.dtype),
               jnp.zeros((0,) + jnp.shape(bias)[1:], bias.dtype))


def _bda_bwd(p, has_bias, block_rows, res, dy):
    seed, rtok, btok = res
    xdtype = dy.dtype  # the fwd output carries x.dtype
    dy2, _ = as_rows(dy)
    h = dy2.shape[-1]
    br = tuned_row_block("bias_dropout_add", h, rows=dy2.shape[0],
                         dtype=xdtype, requested=block_rows)
    dy2p, rows = pad_to(dy2, 0, br)
    dy2p, _ = pad_to(dy2p, 1, 128)
    outs = _bda_pallas_bwd(dy2p.astype(xdtype), seed, p, br, has_bias)
    if has_bias:
        dx = outs[0][:rows, :h].reshape(dy.shape)
        db = outs[1][0, :h].astype(btok.dtype)
    else:
        dx = outs[:rows, :h].reshape(dy.shape)
        db = jnp.zeros((1,), btok.dtype)  # the dummy bias operand's ct
    f0 = np.zeros((), dtype=jax.dtypes.float0)
    return (dx.astype(xdtype), dy.astype(rtok.dtype), db, f0)


_bda.defvjp(_bda_fwd, _bda_bwd)


def fused_bias_dropout_add(x, residual, *, p: float, seed=None, bias=None,
                           block_rows: int | None = None):
    """``dropout(x + bias)/(1-p) + residual`` in one row-kernel pass —
    the Megatron ``bias_dropout_add`` / flash-attn ``dropout_add``
    epilogue, with the keep mask recomputed from ``seed`` in the
    backward (zero mask storage).

    ``p == 0.0`` lowers to the plain composite add (bit-for-bit the
    pre-existing epilogue — there is nothing stochastic to fuse).
    ``seed``: int32 scalar (required when p > 0); derive per call site
    via `seed_from_key` / `fold_seed` — two sites sharing a seed draw
    IDENTICAL masks. ``bias``: optional (H,) vector, differentiable.
    ``block_rows``: static rows-per-grid-step; None resolves tuning
    table > heuristic (kernel ``bias_dropout_add`` in tuning.registry).
    """
    if residual.shape != x.shape:
        raise ValueError(f"residual shape {residual.shape} != x shape "
                         f"{x.shape}")
    if bias is not None and bias.shape != (x.shape[-1],):
        raise ValueError(f"bias must be ({x.shape[-1]},), got "
                         f"{bias.shape}")
    p = float(p)
    if p == 0.0:
        y = x if bias is None else x + bias.astype(x.dtype)
        return y + residual.astype(x.dtype)
    if seed is None:
        raise ValueError("dropout p > 0 needs an explicit int32 seed "
                         "(seed_from_key/fold_seed at the call site)")
    if use_pallas():
        # fp16 is a storage dtype on TPU (Mosaic has no f16): compiled
        # kernels take bf16 and the result is cast back — identity off
        # TPU (see ops._common.mosaic_dtype)
        io_dtype = x.dtype
        kdt = mosaic_dtype(io_dtype)
        x, residual, bias = to_mosaic(x, residual, bias)
        dummy = jnp.zeros((1,), jnp.float32)
        out = _bda(x, residual, bias if bias is not None else dummy,
                   jnp.asarray(seed, jnp.int32), p, bias is not None,
                   block_rows)
        return out.astype(io_dtype) if kdt != io_dtype else out
    return _bda_xla(x, residual, bias, jnp.asarray(seed, jnp.int32), p)


def fused_dropout_add_layer_norm(x, residual, gamma, beta, *, p: float,
                                 seed=None, bias=None, eps: float = 1e-5,
                                 rms: bool = False, prenorm: bool = False,
                                 block_rows: int | None = None):
    """``LN(dropout(x + bias)/(1-p) + residual)`` — the reference's
    ``fused_dropout_add_ln`` / Megatron pre-LN residual epilogue. The
    dropout-add rides the row kernel above; the norm rides the existing
    Pallas LN (`apex1_tpu.ops.layer_norm`), so both memory-bound
    elementwise chains stay fused on TPU.

    ``prenorm=True`` also returns the pre-norm sum z (the residual
    stream the next layer consumes): ``(y, z)``; else just ``y``.
    ``rms=True`` swaps LayerNorm for RMSNorm (``beta`` ignored).
    """
    z = fused_bias_dropout_add(x, residual, p=p, seed=seed, bias=bias,
                               block_rows=block_rows)
    if rms:
        y = rms_norm(z, gamma, eps=eps)
    else:
        y = layer_norm(z, gamma, beta, eps=eps)
    return (y, z) if prenorm else y
