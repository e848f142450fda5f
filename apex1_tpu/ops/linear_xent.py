"""Fused LM-head + softmax cross-entropy ("vocab flash") — Pallas TPU.

Capability extension of ``apex/contrib/xentropy`` (see ``ops/xentropy.py``):
the reference kernel fuses softmax+CE but still takes materialized logits.
For an LM head the logits tensor ``x @ Wᵀ`` is (tokens, vocab) — at fp32,
1.6 GB for GPT-2 (50k vocab, 8k tokens) and 4.2 GB for Llama-3 (128k vocab)
per step, twice (forward write + backward read). On TPU the HBM traffic for
that tensor dominates the whole loss computation, so this kernel fuses the
head matmul INTO the cross entropy with the flash-attention recipe
(``ops/attention.py``): the vocab axis is tiled onto the sequential Pallas
grid, each (token-block × vocab-block) logit tile lives only in
VMEM/registers, and the running (max, sum-exp, target-logit, sum-logits)
statistics ride in VMEM scratch. Backward recomputes the tile logits from
``(x, W, lse)`` — the same recompute-instead-of-save trade the reference's
xentropy kernel makes — and accumulates ``dx = g·W`` (vocab-innermost grid)
and ``dW = gᵀ·x`` (token-innermost grid) in fp32 scratch.

Loss semantics match ``softmax_cross_entropy_loss`` exactly (label
smoothing ε, ``padding_idx`` rows → zero loss/grad, ``num_classes`` masks
lane-padded vocab rows of W in-kernel).

**Tensor-parallel form**: a traced ``col_offset`` scalar (SMEM, like the
ring offsets in ``ops/attention.py``) shifts the global column ids, and
``shard_stats``/``shard_grads`` expose the per-shard partial statistics /
gradients so ``transformer.tensor_parallel.cross_entropy ::
vocab_parallel_linear_cross_entropy`` can merge them across the ``tp``
axis (pmax/psum) — the Megatron vocab-parallel CE with the head matmul
fused in, which the reference does not have.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex1_tpu.ops._common import (NEG_INF, interpret_mode, kernel_call,
                                   mosaic_dtype, out_struct, pad_to, to_mosaic,
                                   use_pallas)

_LANES = 128


def _blk(size: int, requested: int) -> int:
    return min(requested, max(16, ((size + 15) // 16) * 16))


def _tile(x_ref, w_ref):
    """One (bt, bv) logit tile on the MXU — native-dtype operands (bf16
    rides the fast MXU path), fp32 accumulation."""
    return jax.lax.dot_general(x_ref[...], w_ref[...],
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _cols(s_shape, vi, bv, off, true_v, true_k):
    """(local col, global col, validity) for one tile. Validity needs BOTH
    bounds: local (pad rows of this W shard) and global (lane-padded or
    shard-truncated vocab)."""
    lcol = jax.lax.broadcasted_iota(jnp.int32, s_shape, 1) + vi * bv
    gcol = lcol + off
    return gcol, (lcol < true_v) & (gcol < true_k)


def _grad_tile(s, t, lse, gcol, valid, smoothing, true_k, padding_idx, dl):
    """dloss/dlogits for one tile: softmax − (1−ε)·onehot − ε/K, scaled by
    the (padding-masked) upstream cotangent."""
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    g = p - (1.0 - smoothing) * (gcol == t) - smoothing / true_k
    g = jnp.where(valid, g, 0.0)
    if padding_idx is not None:
        dl = jnp.where(t == padding_idx, 0.0, dl)
    return g * dl


def _fwd_kernel(x_ref, w_ref, t_ref, off_ref, *out_and_scratch,
                smoothing, true_k, true_v, padding_idx, bv, n_v,
                emit_stats):
    # emit_stats: False = loss+lse outputs; True = four (bt, 1) stat
    # outputs; "packed" = ONE (bt, 4) [m, l, tgt, sumx] output written
    # by the final vocab tile (the fused-collective form: one stat
    # stream to HBM instead of four, consumed by
    # ops.fused_collective.fused_vocab_parallel_merge)
    if emit_stats == "packed":
        pk_ref = out_and_scratch[0]
    elif emit_stats:
        m_ref, l_ref, tgt_ref, sx_ref = out_and_scratch[:4]
    else:
        loss_ref, lse_ref = out_and_scratch[:2]
    m_scr, l_scr, tgt_scr, sx_scr = out_and_scratch[-4:]
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        tgt_scr[...] = jnp.zeros_like(tgt_scr)
        sx_scr[...] = jnp.zeros_like(sx_scr)

    s = _tile(x_ref, w_ref)
    t = t_ref[...]  # (bt, 1) int32
    gcol, valid = _cols(s.shape, vi, bv, off_ref[0, 0], true_v, true_k)
    sm = jnp.where(valid, s, NEG_INF)
    m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(sm, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    e = jnp.where(valid, jnp.exp(sm - m_new), 0.0)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_prev * corr
                                  + jnp.sum(e, axis=1, keepdims=True),
                                  l_scr.shape)
    tgt_scr[...] += jnp.sum(jnp.where(gcol == t, s, 0.0), axis=1,
                            keepdims=True)
    sx_scr[...] += jnp.sum(jnp.where(valid, s, 0.0), axis=1, keepdims=True)

    @pl.when(vi == n_v - 1)
    def _():
        if emit_stats == "packed":
            pk_ref[...] = jnp.concatenate(
                [m_scr[:, :1], l_scr[:, :1], tgt_scr[:, :1],
                 sx_scr[:, :1]], axis=1)
        elif emit_stats:
            m_ref[...] = m_scr[:, :1]
            l_ref[...] = l_scr[:, :1]
            tgt_ref[...] = tgt_scr[:, :1]
            sx_ref[...] = sx_scr[:, :1]
        else:
            lse = m_scr[:, :1] + jnp.log(l_scr[:, :1])
            loss = ((1.0 - smoothing) * (lse - tgt_scr[:, :1])
                    + smoothing * (lse - sx_scr[:, :1] / true_k))
            if padding_idx is not None:
                loss = jnp.where(t == padding_idx, 0.0, loss)
            loss_ref[...] = loss
            lse_ref[...] = lse


def _bwd_dx_kernel(x_ref, w_ref, t_ref, off_ref, lse_ref, dl_ref,
                   dx_ref, dx_acc, *,
                   smoothing, true_k, true_v, padding_idx, bv, n_v):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _():
        dx_acc[...] = jnp.zeros_like(dx_acc)

    s = _tile(x_ref, w_ref)
    gcol, valid = _cols(s.shape, vi, bv, off_ref[0, 0], true_v, true_k)
    g = _grad_tile(s, t_ref[...], lse_ref[...], gcol, valid,
                   smoothing, true_k, padding_idx, dl_ref[...])
    w = w_ref[...]
    dx_acc[...] += jax.lax.dot_general(
        g.astype(w.dtype), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(vi == n_v - 1)
    def _():
        dx_ref[...] = dx_acc[...].astype(dx_ref.dtype)


def _bwd_dw_kernel(x_ref, w_ref, t_ref, off_ref, lse_ref, dl_ref,
                   dw_ref, dw_acc, *,
                   smoothing, true_k, true_v, padding_idx, bv, n_t):
    vi, ti = pl.program_id(0), pl.program_id(1)  # token axis innermost

    @pl.when(ti == 0)
    def _():
        dw_acc[...] = jnp.zeros_like(dw_acc)

    s = _tile(x_ref, w_ref)
    gcol, valid = _cols(s.shape, vi, bv, off_ref[0, 0], true_v, true_k)
    g = _grad_tile(s, t_ref[...], lse_ref[...], gcol, valid,
                   smoothing, true_k, padding_idx, dl_ref[...])
    x = x_ref[...]
    dw_acc[...] += jax.lax.dot_general(            # gᵀ · x
        g.astype(x.dtype), x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ti == n_t - 1)
    def _():
        dw_ref[...] = dw_acc[...].astype(dw_ref.dtype)


def _auto_blocks(Hp, block_t, block_v, dtype=jnp.bfloat16):
    """Resolve (block_t, block_v) with the documented precedence
    (docs/ops.md): explicit argument > tuning-table winner
    (`apex1_tpu.tuning`, keyed on generation x dtype x padded hidden)
    > the analytic heuristic below.

    The heuristic shrinks default blocks so the fp32 accumulators
    (dx_acc (bt, Hp), dw_acc (bv, Hp)) + operand blocks stay within ~a
    quarter of the generation's VMEM budget
    (`core.capability.vmem_budget`) at large hidden sizes (Llama-3 8B:
    H=4096; 70B: 8192). Explicitly requested blocks are honored
    as-is."""
    from apex1_tpu.core.capability import vmem_budget
    req_t, req_v = block_t, block_v  # caller-explicit (for the OOM warn)
    if block_t is None or block_v is None:
        from apex1_tpu import tuning
        tuned = tuning.lookup("linear_xent", {"Hp": Hp}, dtype) or {}
        block_t = block_t if block_t is not None else tuned.get("block_t")
        block_v = block_v if block_v is not None else tuned.get("block_v")
    acc_budget = vmem_budget() // 4
    # BOTH fp32 accumulators (dx (bt, Hp) + dw (bv, Hp)) share the frame
    # with double-buffered operand tiles; bound their SUM, with the 3/4
    # headroom established by AOT memory analysis at H=4096 (bt+bv=512
    # OOMs, 384 fits — tools/aot_check.py --flagship,
    # perf_results/aot_full_r3.log; not yet timed on hardware)
    cap_total = max(32, int(acc_budget * 0.75) // (4 * Hp) // 16 * 16)
    bt = block_t if block_t is not None else min(
        256, max(16, cap_total // 3 // 16 * 16))
    bv = block_v if block_v is not None else min(
        512, max(16, cap_total - bt))
    if bt + bv > cap_total:
        # only reachable when at least one block is EXPLICIT — auto
        # sizing stays within cap_total and tuning-table entries are
        # VMEM-validated against the same accumulator bound before the
        # lookup serves them. Warn (not clamp: the caller may know their
        # generation better than the capability table) so a hardware OOM
        # is attributable to the request, not to mis-sized defaults.
        import warnings
        desc = " + ".join(
            f"{name}={val} ({'requested' if req is not None else 'auto'})"
            for name, val, req in (("block_t", bt, req_t),
                                   ("block_v", bv, req_v)))
        warnings.warn(
            f"linear_cross_entropy: {desc} exceed the AOT-verified VMEM "
            f"headroom ({cap_total} rows at Hp={Hp}) for this TPU "
            f"generation — expect Mosaic VMEM OOM; drop the explicit "
            f"block(s) to use auto sizing", stacklevel=3)
    return bt, bv


def _prep(x2, weight, t2, block_t, block_v):
    T, H = x2.shape
    V = weight.shape[0]
    Hp = ((H + _LANES - 1) // _LANES) * _LANES
    block_t, block_v = _auto_blocks(Hp, block_t, block_v, x2.dtype)
    bt, bv = _blk(T, block_t), _blk(V, block_v)
    xp, _ = pad_to(x2, 0, bt)
    xp, _ = pad_to(xp, 1, _LANES)
    wp, _ = pad_to(weight, 0, bv)
    wp, _ = pad_to(wp, 1, _LANES)
    tp, _ = pad_to(t2, 0, bt, value=-1)
    g = dict(T=T, H=H, V=V, bt=bt, bv=bv, Hp=xp.shape[1],
             n_t=xp.shape[0] // bt, n_v=wp.shape[0] // bv)
    return xp, wp, tp, g


def _specs(g, *, for_dw=False):
    """Grid is (ti, vi) for fwd/dx and (vi, ti) for dW (``for_dw``)."""
    def ix(i0, i1):
        return (i1, i0) if for_dw else (i0, i1)

    x_spec = pl.BlockSpec((g["bt"], g["Hp"]),
                          lambda i0, i1: (ix(i0, i1)[0], 0),
                          memory_space=pltpu.VMEM)
    w_spec = pl.BlockSpec((g["bv"], g["Hp"]),
                          lambda i0, i1: (ix(i0, i1)[1], 0),
                          memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((g["bt"], 1),
                             lambda i0, i1: (ix(i0, i1)[0], 0),
                             memory_space=pltpu.VMEM)
    off_spec = pl.BlockSpec((1, 1), lambda *_: (0, 0),
                            memory_space=pltpu.SMEM)
    return x_spec, w_spec, stat_spec, off_spec


def _off_array(off):
    return jnp.asarray(off, jnp.int32).reshape(1, 1)


def shard_stats(x2, w_shard, t2, *, col_offset=0, num_classes=None,
                block_t=None, block_v=None):
    """Per-shard online-softmax partials ``(m, l, tgt, sumx)`` — each
    (T,) fp32 — over the GLOBAL columns ``[col_offset, col_offset + V_l)``
    this shard's ``w_shard`` (V_l, H) covers. NOT differentiable on its
    own; the vocab-parallel wrapper owns the VJP."""
    xp, wp, tp, g = _prep(x2, w_shard, t2, block_t, block_v)
    k = num_classes if num_classes is not None else g["V"]
    x_spec, w_spec, stat_spec, off_spec = _specs(g)
    Tp = g["n_t"] * g["bt"]
    outs = kernel_call(
        functools.partial(_fwd_kernel, smoothing=0.0, true_k=k,
                          true_v=g["V"], padding_idx=None, bv=g["bv"],
                          n_v=g["n_v"], emit_stats=True),
        name="linear_xent_stats",
        grid=(g["n_t"], g["n_v"]),
        in_specs=[x_spec, w_spec, stat_spec, off_spec],
        out_specs=(stat_spec,) * 4,
        out_shape=(out_struct((Tp, 1), jnp.float32, xp, wp, tp),) * 4,
        scratch_shapes=[pltpu.VMEM((g["bt"], _LANES), jnp.float32)] * 4,
        interpret=interpret_mode(),
    )(xp, wp, tp, _off_array(col_offset))
    return tuple(o[:g["T"], 0] for o in outs)


def shard_stats_packed(x2, w_shard, t2, *, col_offset=0, num_classes=None,
                       block_t=None, block_v=None):
    """`shard_stats` with the four per-shard stats PACKED into one
    (T, 4) ``[m, l, tgt, sumx]`` output by the kernel's final vocab
    tile — one stat stream to HBM instead of four, and the shape
    `ops.fused_collective.fused_vocab_parallel_merge` consumes with a
    single packed psum (two collectives total instead of four). The
    packed values are bit-identical to `shard_stats`' (same scratch
    reads, same tile). NOT differentiable on its own; the vocab-parallel
    wrapper owns the VJP."""
    xp, wp, tp, g = _prep(x2, w_shard, t2, block_t, block_v)
    k = num_classes if num_classes is not None else g["V"]
    x_spec, w_spec, stat_spec, off_spec = _specs(g)
    pk_spec = pl.BlockSpec((g["bt"], 4), lambda i0, i1: (i0, 0),
                           memory_space=pltpu.VMEM)
    Tp = g["n_t"] * g["bt"]
    packed = kernel_call(
        functools.partial(_fwd_kernel, smoothing=0.0, true_k=k,
                          true_v=g["V"], padding_idx=None, bv=g["bv"],
                          n_v=g["n_v"], emit_stats="packed"),
        name="linear_xent_pack",
        grid=(g["n_t"], g["n_v"]),
        in_specs=[x_spec, w_spec, stat_spec, off_spec],
        out_specs=pk_spec,
        out_shape=out_struct((Tp, 4), jnp.float32, xp, wp, tp),
        scratch_shapes=[pltpu.VMEM((g["bt"], _LANES), jnp.float32)] * 4,
        interpret=interpret_mode(),
    )(xp, wp, tp, _off_array(col_offset))
    return packed[:g["T"]]


def shard_grads(x2, w_shard, t2, lse, dloss, *, col_offset=0,
                smoothing=0.0, padding_idx=None, num_classes=None,
                block_t=None, block_v=None):
    """Per-shard gradients given the GLOBAL logsumexp: returns
    ``(dx_partial, dw_shard)`` — dx must still be summed across shards
    (each shard only saw its own vocab columns)."""
    xp, wp, tp, g = _prep(x2, w_shard, t2, block_t, block_v)
    k = num_classes if num_classes is not None else g["V"]
    lse_p, _ = pad_to(lse.reshape(-1, 1).astype(jnp.float32), 0, g["bt"])
    dl, _ = pad_to(dloss.reshape(-1, 1).astype(jnp.float32), 0, g["bt"])
    off = _off_array(col_offset)
    kern = dict(smoothing=smoothing, true_k=k, true_v=g["V"],
                padding_idx=padding_idx, bv=g["bv"])

    x_spec, w_spec, stat_spec, off_spec = _specs(g)
    dx = kernel_call(
        functools.partial(_bwd_dx_kernel, n_v=g["n_v"], **kern),
        name="linear_xent_dx",
        grid=(g["n_t"], g["n_v"]),
        in_specs=[x_spec, w_spec, stat_spec, off_spec, stat_spec,
                  stat_spec],
        out_specs=x_spec,
        out_shape=out_struct(xp.shape, x2.dtype, xp, wp, tp, lse_p, dl),
        scratch_shapes=[pltpu.VMEM((g["bt"], g["Hp"]), jnp.float32)],
        interpret=interpret_mode(),
    )(xp, wp, tp, off, lse_p, dl)[:g["T"], :g["H"]]

    x_spec, w_spec, stat_spec, off_spec = _specs(g, for_dw=True)
    dw = kernel_call(
        functools.partial(_bwd_dw_kernel, n_t=g["n_t"], **kern),
        name="linear_xent_dw",
        grid=(g["n_v"], g["n_t"]),
        in_specs=[x_spec, w_spec, stat_spec, off_spec, stat_spec,
                  stat_spec],
        out_specs=w_spec,
        out_shape=out_struct(wp.shape, w_shard.dtype, xp, wp, tp,
                             lse_p, dl),
        scratch_shapes=[pltpu.VMEM((g["bv"], g["Hp"]), jnp.float32)],
        interpret=interpret_mode(),
    )(xp, wp, tp, off, lse_p, dl)[:g["V"], :g["H"]]
    return dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused(x2, weight, t2, smoothing, padding_idx, num_classes,
           block_t, block_v):
    return _fused_fwd(x2, weight, t2, smoothing, padding_idx, num_classes,
                      block_t, block_v)[0]


def _fused_fwd(x2, weight, t2, smoothing, padding_idx, num_classes,
               block_t, block_v):
    xp, wp, tp, g = _prep(x2, weight, t2, block_t, block_v)
    k = num_classes if num_classes is not None else g["V"]
    x_spec, w_spec, stat_spec, off_spec = _specs(g)
    Tp = g["n_t"] * g["bt"]
    loss, lse = kernel_call(
        functools.partial(_fwd_kernel, smoothing=smoothing, true_k=k,
                          true_v=g["V"], padding_idx=padding_idx,
                          bv=g["bv"], n_v=g["n_v"], emit_stats=False),
        name="linear_xent_fwd",
        grid=(g["n_t"], g["n_v"]),
        in_specs=[x_spec, w_spec, stat_spec, off_spec],
        out_specs=(stat_spec, stat_spec),
        out_shape=(out_struct((Tp, 1), jnp.float32, xp, wp, tp),
                   out_struct((Tp, 1), jnp.float32, xp, wp, tp)),
        scratch_shapes=[pltpu.VMEM((g["bt"], _LANES), jnp.float32)] * 4,
        interpret=interpret_mode(),
    )(xp, wp, tp, _off_array(0))
    return loss[:g["T"], 0], (x2, weight, t2, lse[:g["T"], 0])


def _fused_bwd(smoothing, padding_idx, num_classes, block_t, block_v,
               res, dloss):
    x2, weight, t2, lse = res
    dx, dw = shard_grads(x2, weight, t2, lse, dloss,
                         smoothing=smoothing, padding_idx=padding_idx,
                         num_classes=num_classes,
                         block_t=block_t, block_v=block_v)
    f0 = np.zeros(t2.shape, dtype=jax.dtypes.float0)
    return dx, dw, f0


_fused.defvjp(_fused_fwd, _fused_bwd)


def _xla_linear_xent(x, weight, labels, smoothing, padding_idx, num_classes):
    """Composite gold: materializes logits (what this kernel avoids)."""
    from apex1_tpu.ops.xentropy import _xla_xent
    logits = jnp.einsum("th,vh->tv", x.astype(jnp.float32),
                        weight.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    return _xla_xent(logits, labels, smoothing, padding_idx, num_classes)


def linear_cross_entropy(x, weight, labels, *, smoothing: float = 0.0,
                         padding_idx: int | None = None,
                         num_classes: int | None = None,
                         block_t: int | None = None,
                         block_v: int | None = None):
    """Per-token CE of ``softmax(x @ weightᵀ)`` without materializing the
    logits — ``x`` (..., H), ``weight`` (V, H) (an embedding table for tied
    LM heads), ``labels`` (...,) int. Returns (...,) fp32 losses.

    Semantics ≡ ``softmax_cross_entropy_loss(x @ weightᵀ, labels, ...)``
    (``ops/xentropy.py``): label ``smoothing``, zero loss/grad at
    ``padding_idx`` rows, ``num_classes`` masking of lane-padded vocab rows.
    """
    if x.shape[-1] != weight.shape[-1]:
        raise ValueError(f"hidden mismatch: x {x.shape} vs weight "
                         f"{weight.shape}")
    if num_classes is not None and not (0 < num_classes <= weight.shape[0]):
        raise ValueError(f"num_classes {num_classes} must be in "
                         f"(0, {weight.shape[0]}]")
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    t2 = labels.reshape(-1, 1).astype(jnp.int32)
    if use_pallas():
        # fp16 is a storage dtype on TPU (Mosaic has no f16): the kernel
        # takes bf16; the fp32 loss output needs no restore — see
        # ops._common.mosaic_dtype
        x2, weight = to_mosaic(x2, weight)
        loss = _fused(x2, weight, t2, float(smoothing), padding_idx,
                      num_classes, block_t, block_v)
    else:
        loss = _xla_linear_xent(x2, weight, t2[:, 0], smoothing,
                                padding_idx, num_classes)
    return loss.reshape(lead)
