"""Flash attention — Pallas TPU kernels.

Reference capability: ``apex/contrib/fmha/fmha.py :: FMHAFun`` (+
``apex/contrib/csrc/fmha/``, seqlen ≤ 512, head-dim 64, varlen via
cu_seqlens) and ``apex/contrib/multihead_attn`` (fused full-MHA blocks).
The reference kernels materialize (or tile) the full score matrix per CTA;
the TPU-native design is a flash/online-softmax kernel with NO seqlen cap:

- **forward**: grid ``(B, H, num_q_blocks, num_k_blocks)`` with the key axis
  innermost; VMEM scratch carries the running ``(max, sum, acc)`` across key
  blocks (TPU grid iteration is sequential, so scratch persists); saves only
  ``(out, logsumexp)`` — activation memory O(S·D), not O(S²).
- **backward**: recomputes probabilities from ``q·kᵀ`` and the saved
  logsumexp (the same recompute-instead-of-save trade the reference's
  xentropy kernel makes); two kernels — dq (key-innermost) and dk/dv
  (query-innermost accumulation).
- **varlen**: ``segment_ids`` — positions in different segments never
  attend (≙ the reference fmha's cu_seqlens packed batches).
- **GQA/MQA**: ``k``/``v`` may have fewer heads than ``q`` (grouped by
  index-map arithmetic, no materialized repeat).
- **ring/context parallel**: traced ``q_offset``/``k_offset`` scalars (SMEM)
  shift the global positions used by the causal mask, and the op can return
  the per-shard ``lse`` so `apex1_tpu.parallel.ring_attention` can merge
  partial results around an ICI ring — differentiably (the custom VJP
  handles the lse cotangent: ∂lse/∂s = softmax(s) ⇒ ds += p·dlse).

Shapes: ``q`` (B, Hq, Sq, D); ``k``/``v`` (B, Hkv, Sk, D), Hq % Hkv == 0.
Accumulation is fp32 regardless of input dtype (bf16 inputs feed the MXU
directly; only the running statistics are fp32) — matching the reference's
fp16-in/fp32-accumulate kernels.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex1_tpu.ops._common import (NEG_INF, interpret_mode, kernel_call,
                                   out_struct, pad_to, to_mosaic, use_pallas)
from apex1_tpu.ops.stochastic import (attn_keep_mask, threshold_u32,
                                      tile_keep_mask)

_LANES = 128


def _keep_tile(sd_ref, qo_ref, ko_ref, qi, ki, bq, bk, b, h, *,
               dropout_p, n_h, interp):
    """Attention-probability keep mask for the (qi, ki) score tile —
    counter-based on (seed, batch·n_h+head, GLOBAL q start, GLOBAL k
    start), so the mask is independent of grid iteration order and of
    ring-shard visiting order, and context-parallel shards (whose
    ``k_off`` differs) draw disjoint, shift-invariant streams. Forward
    and both backward kernels call this with identical arguments per
    tile — the recompute identity the custom VJPs rely on."""
    return tile_keep_mask(
        (bq, bk), threshold_u32(dropout_p), sd_ref[0, 0], b * n_h + h,
        qi * bq + qo_ref[0, 0], ki * bk + ko_ref[0, 0], interp=interp)


def _block(size: int, requested: int) -> int:
    """Block size: the requested tile, shrunk for tiny inputs (≥16-aligned
    so bf16 (16, 128) sublane tiling stays legal)."""
    return min(requested, max(16, ((size + 15) // 16) * 16))


def _env_block(name):
    """Documented MANUAL override (``APEX1_ATTN_BLOCK_Q/K``) — for pinning
    a block size on hardware without code edits. Read at TRACE time, so
    the jit cache does NOT key on it: changing the env mid-process serves
    stale executables. For sweeps, pass explicit ``block_q/block_k``
    instead (static args — N candidates compile N executables in one
    process; ``tools/tune_kernels.py`` drives this)."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not an integer") from None
    if val <= 0 or val % 16:
        raise ValueError(f"{name} must be a positive multiple of 16 "
                         f"(TPU sublane tiling), got {val}")
    return val


def _auto_blocks(D, block_q, block_k, dtype=jnp.bfloat16, seq=128):
    """Resolve block sizes with the documented precedence (docs/ops.md):

        explicit argument > APEX1_ATTN_BLOCK_Q/K env override
        > tuning-table winner (`apex1_tpu.tuning`, keyed on generation
          x dtype x padded head dim x the power-of-two bucket of the
          key sequence length — block preference shifts with grid size,
          so a 1k-seq winner never governs a 16k program)
        > analytic heuristic.

    The heuristic: small tiles (128×128) make the grid huge and the
    per-step MXU work tiny — grid/DMA overheads then dominate (round-1
    v5e profile attributed ~5× to the 128×128 grid on GPT-2 shapes,
    BASELINE.md "Round 1 measurements"). Defaults target a ≤1 MiB fp32
    score tile (512×512) and shrink with the padded head dim so q/k/v
    blocks + accumulators + double-buffered operands stay inside the
    generation's VMEM budget (`core.capability.vmem_budget` — the
    runtime analog of the reference's per-sm kernel specialization in
    csrc/fmha). 512 block_k keeps the fp32 score tile at 1 MiB (bq=512);
    the step from 1024 halves peak usage for one extra grid level."""
    from apex1_tpu.core.capability import vmem_budget

    Dp = max(_LANES, ((D + _LANES - 1) // _LANES) * _LANES)
    # env consulted ONLY for unresolved blocks: explicit arguments stay
    # immune to a stale/malformed pin in the environment (the sweep
    # driver passes explicit candidates and must not die on one)
    env_q = _env_block("APEX1_ATTN_BLOCK_Q") if block_q is None else None
    env_k = _env_block("APEX1_ATTN_BLOCK_K") if block_k is None else None
    tuned = {}
    if (block_q is None and env_q is None) or \
            (block_k is None and env_k is None):
        from apex1_tpu import tuning
        tuned = tuning.lookup(
            "flash_attention",
            {"Dp": Dp, "Sb": tuning.seq_bucket(seq)}, dtype) or {}
    small_vmem = vmem_budget() < 12 * 2**20
    default = 256 if (Dp > 512 or small_vmem) else 512
    if block_q is None:
        block_q = env_q or tuned.get("block_q") or default
    if block_k is None:
        block_k = env_k or tuned.get("block_k") or default
    return block_q, block_k


def _mask_for(qi, ki, bq, bk, *, causal, true_sq, true_sk, q_off, k_off,
              qseg, kseg):
    """(bq, bk) validity mask for one score block. Padded rows/cols are
    invalid; causal compares GLOBAL positions (local + traced offset)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + qi * bq
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ki * bk
    mask = (col < true_sk) & (row < true_sq)
    if causal:
        mask &= (col + k_off) <= (row + q_off)
    if qseg is not None:
        mask &= qseg == kseg  # (bq,1) == (1,bk) broadcast
    return mask


def _fwd_kernel(q_ref, k_ref, v_ref, qo_ref, ko_ref, *seg_and_out,
                scale, causal, true_sq, true_sk, has_segs, has_bias, n_k,
                dropout_p=0.0, n_h=0, interp=False):
    rest = list(seg_and_out)
    sd_ref = rest.pop(0) if dropout_p > 0.0 else None
    if has_segs:
        qseg_ref, kseg_ref = rest[0], rest[1]
        rest = rest[2:]
        qseg, kseg = qseg_ref[0], kseg_ref[0]  # (bq,1), (1,bk)
    else:
        qseg = kseg = None
    bias_ref = rest.pop(0) if has_bias else None
    o_ref, lse_ref, acc, m_scr, l_scr = rest
    qi, ki = pl.program_id(2), pl.program_id(3)
    if dropout_p > 0.0:
        # program ids hoisted OUT of the pl.when-guarded compute: inside
        # the cond body the primitive has no interpret-mode lowering;
        # guarded so the p=0 kernel jaxpr stays identical to pre-dropout
        b, h = pl.program_id(0), pl.program_id(1)
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(ki == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    def compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        # native-dtype operands: bf16 inputs ride the MXU's bf16 path with
        # fp32 accumulation (an fp32 upcast before the dot would run the MXU
        # ~8x slower); running statistics stay fp32
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_bias:
            # additive logit bias (T5 rel-pos / arbitrary masks):
            # s = qk·scale + bias, matching scaled_masked_softmax
            s = s + bias_ref[0, 0].astype(jnp.float32)
        mask = _mask_for(qi, ki, bq, bk, causal=causal, true_sq=true_sq,
                         true_sk=true_sk, q_off=qo_ref[0, 0],
                         k_off=ko_ref[0, 0], qseg=qseg, kseg=kseg)
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        e = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * corr + jnp.sum(e, axis=1, keepdims=True)
        v = v_ref[0, 0]
        if dropout_p > 0.0:
            # dropout BETWEEN softmax and AV (the reference fmha fusion
            # point): the softmax denominator l accumulates the
            # UNdropped e, only the AV contribution is masked+rescaled,
            # so (out, lse) merge exactly across ring shards
            keep = _keep_tile(sd_ref, qo_ref, ko_ref, qi, ki, bq, bk,
                              b, h, dropout_p=dropout_p, n_h=n_h,
                              interp=interp)
            e_av = jnp.where(keep, e * (1.0 / (1.0 - dropout_p)), 0.0)
        else:
            e_av = e
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            e_av.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # skip blocks entirely above the diagonal (no valid positions):
        # saves the strictly-upper-triangular ~half of the MXU work
        pl.when((ki * bk + ko_ref[0, 0])
                <= (qi * bq + bq - 1 + qo_ref[0, 0]))(compute)
    else:
        compute()

    @pl.when(ki == n_k - 1)
    def _():
        l = l_scr[:, :1]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc[...] / safe).astype(o_ref.dtype)
        # finite NEG_INF sentinel for empty rows keeps ring merges exact
        lse_ref[0, 0] = jnp.where(l > 0.0, m_scr[:, :1] + jnp.log(safe),
                                  NEG_INF)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dlse_ref,
                   qo_ref, ko_ref, *seg_and_out,
                   scale, causal, true_sq, true_sk, has_segs, has_bias,
                   n_k, dropout_p=0.0, n_h=0, interp=False):
    rest = list(seg_and_out)
    sd_ref = rest.pop(0) if dropout_p > 0.0 else None
    if has_segs:
        qseg_ref, kseg_ref = rest[0], rest[1]
        rest = rest[2:]
        qseg, kseg = qseg_ref[0], kseg_ref[0]
    else:
        qseg = kseg = None
    bias_ref = rest.pop(0) if has_bias else None
    dq_ref, dq_acc = rest
    qi, ki = pl.program_id(2), pl.program_id(3)
    if dropout_p > 0.0:
        b, h = pl.program_id(0), pl.program_id(1)  # hoisted, see _fwd
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        mask = _mask_for(qi, ki, bq, bk, causal=causal, true_sq=true_sq,
                         true_sk=true_sk, q_off=qo_ref[0, 0],
                         k_off=ko_ref[0, 0], qseg=qseg, kseg=kseg)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0, 0]), 0.0)
        do = do_ref[0, 0]
        v = v_ref[0, 0]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            # out = Σ drop∘softmax(s)·v with drop a CONSTANT mask ⇒
            # ds = p·(drop·dp − δ + dlse): the recomputed mask scales
            # only the dp term (δ already carries the dropped weights
            # through do·out)
            keep = _keep_tile(sd_ref, qo_ref, ko_ref, qi, ki, bq, bk,
                              b, h, dropout_p=dropout_p, n_h=n_h,
                              interp=interp)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_p)), 0.0)
        ds = p * (dp - dlt_ref[0, 0] + dlse_ref[0, 0]) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when((ki * bk + ko_ref[0, 0])
                <= (qi * bq + bq - 1 + qo_ref[0, 0]))(compute)
    else:
        compute()

    @pl.when(ki == n_k - 1)
    def _():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dlse_ref,
                    qo_ref, ko_ref, *seg_and_out,
                    scale, causal, true_sq, true_sk, has_segs, has_bias,
                    n_q, group, dropout_p=0.0, n_h=0, interp=False):
    # Grid (b, hkv, ki, gi, qi): the GQA group axis sits between the key
    # block and the (innermost) query block, so dk/dv for one kv head
    # accumulate across the whole group in VMEM scratch and are written
    # ONCE at Hkv granularity — no (B, Hq, Sk, D) fp32 partials in HBM
    # (VERDICT r1 weak#4), and each k/v block is fetched once per group
    # sweep instead of once per q head.
    rest = list(seg_and_out)
    sd_ref = rest.pop(0) if dropout_p > 0.0 else None
    if has_segs:
        qseg_ref, kseg_ref = rest[0], rest[1]
        rest = rest[2:]
        qseg, kseg = qseg_ref[0], kseg_ref[0]
    else:
        qseg = kseg = None
    bias_ref = rest.pop(0) if has_bias else None
    dk_ref, dv_ref, dk_acc, dv_acc = rest
    ki, gi, qi = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    if dropout_p > 0.0:
        # hoisted (see _fwd_kernel); q head on this grid is hkv·group+gi
        b, hq = pl.program_id(0), pl.program_id(1) * group + gi
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when((gi == 0) & (qi == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        mask = _mask_for(qi, ki, bq, bk, causal=causal, true_sq=true_sq,
                         true_sk=true_sk, q_off=qo_ref[0, 0],
                         k_off=ko_ref[0, 0], qseg=qseg, kseg=kseg)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0, 0]), 0.0)
        do = do_ref[0, 0]
        v = v_ref[0, 0]
        if dropout_p > 0.0:
            # hq = hkv·group + gi — the SAME salt the forward used for
            # this (b, h, qi, ki) tile
            keep = _keep_tile(
                sd_ref, qo_ref, ko_ref, qi, ki, bq, bk, b, hq,
                dropout_p=dropout_p, n_h=n_h, interp=interp)
            inv = 1.0 / (1.0 - dropout_p)
            p_av = jnp.where(keep, p * inv, 0.0)  # dv sees DROPPED probs
        else:
            keep = None
            p_av = p
        dv_acc[...] += jax.lax.dot_general(                  # p_avᵀ · do
            p_av.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            dp = jnp.where(keep, dp * inv, 0.0)
        ds = p * (dp - dlt_ref[0, 0] + dlse_ref[0, 0]) * scale
        dk_acc[...] += jax.lax.dot_general(                  # dsᵀ · q
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when((qi * bq + bq - 1 + qo_ref[0, 0])
                >= (ki * bk + ko_ref[0, 0]))(compute)
    else:
        compute()

    @pl.when((gi == group - 1) & (qi == n_q - 1))
    def _():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _dbias_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dlse_ref,
                  qo_ref, ko_ref, *seg_and_out,
                  scale, causal, true_sq, true_sk, has_segs, n_r,
                  rh=1, dropout_p=0.0, n_h=0, interp=False):
    """dbias = Σ_broadcast p·(dp − δ + dlse) — one extra recompute pass.
    Grid (Bb, Hb, qi, ki, r) with the broadcast sweep r INNERMOST: every
    revisit of a dbias output block is consecutive, so accumulation
    lives in VMEM scratch and each block is written once (no O(B·H·S²)
    partials in HBM — the whole point of biasing the flash kernel).
    ``rh`` is the head broadcast factor Hq//Hb — with the grid sizes it
    reconstructs the TRUE (b, h) this sweep step visits, so the dropout
    mask salt matches the forward's."""
    rest = list(seg_and_out)
    sd_ref = rest.pop(0) if dropout_p > 0.0 else None
    if has_segs:
        qseg_ref, kseg_ref = rest[0], rest[1]
        rest = rest[2:]
        qseg, kseg = qseg_ref[0], kseg_ref[0]
    else:
        qseg = kseg = None
    bias_ref, dbias_ref, db_acc = rest
    qi, ki, r = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    if dropout_p > 0.0:
        # true (b, h) of this sweep step (bidx/hidx inverted from the
        # index maps) — hoisted out of the pl.when-guarded compute
        b = pl.program_id(0) + (r // rh) * pl.num_programs(0)
        h = pl.program_id(1) + (r % rh) * pl.num_programs(1)
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(r == 0)
    def _():
        db_acc[...] = jnp.zeros_like(db_acc)

    def compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # p must come from the FULL logits (qk·scale + bias) minus the
        # saved lse, which was computed over the biased scores
        s = s + bias_ref[0, 0].astype(jnp.float32)
        mask = _mask_for(qi, ki, bq, bk, causal=causal, true_sq=true_sq,
                         true_sk=true_sk, q_off=qo_ref[0, 0],
                         k_off=ko_ref[0, 0], qseg=qseg, kseg=kseg)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0, 0]), 0.0)
        do = do_ref[0, 0]
        v = v_ref[0, 0]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            keep = _keep_tile(sd_ref, qo_ref, ko_ref, qi, ki, bq, bk,
                              b, h, dropout_p=dropout_p, n_h=n_h,
                              interp=interp)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_p)), 0.0)
        # dS w.r.t. the PRE-scale logits s_full — no trailing ·scale
        # (that factor belongs to d(qk), not d(bias))
        db_acc[...] += p * (dp - dlt_ref[0, 0] + dlse_ref[0, 0])

    if causal:
        pl.when((ki * bk + ko_ref[0, 0])
                <= (qi * bq + bq - 1 + qo_ref[0, 0]))(compute)
    else:
        compute()

    @pl.when(r == n_r - 1)
    def _():
        dbias_ref[0, 0] = db_acc[...].astype(dbias_ref.dtype)


def _prep(q, k, v, qseg, kseg, has_segs, block_q, block_k):
    """Pad operands to block multiples; returns padded arrays + geometry."""
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    bq, bk = _block(Sq, block_q), _block(Sk, block_k)
    qp, _ = pad_to(q, 2, bq)
    qp, _ = pad_to(qp, 3, _LANES)
    kp, _ = pad_to(k, 2, bk)
    kp, _ = pad_to(kp, 3, _LANES)
    vp, _ = pad_to(v, 2, bk)
    vp, _ = pad_to(vp, 3, _LANES)
    if has_segs:
        # qseg → (B, Sq, 1) / kseg → (B, 1, Sk): 2-D refs, no in-kernel
        # transpose; pad value -1 ≠ -2 so padded q never matches padded k
        qs, _ = pad_to(qseg.astype(jnp.int32)[:, :, None], 1, bq, value=-1)
        ks, _ = pad_to(kseg.astype(jnp.int32)[:, None, :], 2, bk, value=-2)
    else:
        qs = ks = None
    geom = dict(B=B, Hq=Hq, Hkv=Hkv, group=Hq // Hkv, Sq=Sq, Sk=Sk, D=D,
                bq=bq, bk=bk, n_q=qp.shape[2] // bq, n_k=kp.shape[2] // bk,
                Dp=qp.shape[3])
    return qp, kp, vp, qs, ks, geom


def _common_specs(g):
    """Block specs shared by the fwd and dq kernels — grid (b, h, qi, ki)."""
    group = g["group"]
    q_spec = pl.BlockSpec((1, 1, g["bq"], g["Dp"]),
                          lambda b, h, qi, ki: (b, h, qi, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, 1, g["bk"], g["Dp"]),
                           lambda b, h, qi, ki: (b, h // group, ki, 0),
                           memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((1, 1, g["bq"], 1),
                             lambda b, h, qi, ki: (b, h, qi, 0),
                             memory_space=pltpu.VMEM)
    off_spec = pl.BlockSpec((1, 1), lambda *_: (0, 0),
                            memory_space=pltpu.SMEM)
    qseg_spec = pl.BlockSpec((1, g["bq"], 1),
                             lambda b, h, qi, ki: (b, qi, 0),
                             memory_space=pltpu.VMEM)
    kseg_spec = pl.BlockSpec((1, 1, g["bk"]),
                             lambda b, h, qi, ki: (b, 0, ki),
                             memory_space=pltpu.VMEM)
    return q_spec, kv_spec, stat_spec, off_spec, qseg_spec, kseg_spec


def _dkv_specs(g):
    """Block specs for the dk/dv kernel — grid (b, hkv, ki, gi, qi): the
    q head is ``hkv * group + gi``; dk/dv blocks index (b, hkv, ki)."""
    group = g["group"]
    q_spec = pl.BlockSpec(
        (1, 1, g["bq"], g["Dp"]),
        lambda b, hkv, ki, gi, qi: (b, hkv * group + gi, qi, 0),
        memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, 1, g["bk"], g["Dp"]),
                           lambda b, hkv, ki, gi, qi: (b, hkv, ki, 0),
                           memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec(
        (1, 1, g["bq"], 1),
        lambda b, hkv, ki, gi, qi: (b, hkv * group + gi, qi, 0),
        memory_space=pltpu.VMEM)
    off_spec = pl.BlockSpec((1, 1), lambda *_: (0, 0),
                            memory_space=pltpu.SMEM)
    qseg_spec = pl.BlockSpec((1, g["bq"], 1),
                             lambda b, hkv, ki, gi, qi: (b, qi, 0),
                             memory_space=pltpu.VMEM)
    kseg_spec = pl.BlockSpec((1, 1, g["bk"]),
                             lambda b, hkv, ki, gi, qi: (b, 0, ki),
                             memory_space=pltpu.VMEM)
    dkv_spec = pl.BlockSpec((1, 1, g["bk"], g["Dp"]),
                            lambda b, hkv, ki, gi, qi: (b, hkv, ki, 0),
                            memory_space=pltpu.VMEM)
    return q_spec, kv_spec, stat_spec, off_spec, qseg_spec, kseg_spec, \
        dkv_spec


def _off_arrays(q_off, k_off):
    return (jnp.asarray(q_off, jnp.int32).reshape(1, 1),
            jnp.asarray(k_off, jnp.int32).reshape(1, 1))


def _prep_bias(bias, g):
    """Pad the additive-bias operand to block multiples. Accepts
    (1|B, 1|Hq, Sq, Sk); broadcast dims stay size-1 all the way into the
    kernels via their index maps."""
    B, Hq = g["B"], g["Hq"]
    if bias.ndim != 4:
        raise ValueError(f"bias must be (1|B, 1|H, Sq, Sk), got rank "
                         f"{bias.ndim}")
    Bb, Hb, sq, sk = bias.shape
    if Bb not in (1, B) or Hb not in (1, Hq):
        raise ValueError(f"bias batch/head dims {Bb, Hb} must be 1 or "
                         f"match (B={B}, H={Hq})")
    if (sq, sk) != (g["Sq"], g["Sk"]):
        raise ValueError(f"bias trailing dims {sq, sk} must equal "
                         f"(Sq={g['Sq']}, Sk={g['Sk']})")
    bp, _ = pad_to(bias, 2, g["bq"])
    bp, _ = pad_to(bp, 3, g["bk"])
    return bp, Bb, Hb


def _bias_spec(g, Bb, Hb, *, dkv=False):
    """Bias block spec for the fwd/dq grid (b, h, qi, ki) or — with
    ``dkv`` — the dk/dv grid (b, hkv, ki, gi, qi)."""
    group = g["group"]
    if dkv:
        return pl.BlockSpec(
            (1, 1, g["bq"], g["bk"]),
            lambda b, hkv, ki, gi, qi: (
                b if Bb > 1 else 0,
                (hkv * group + gi) if Hb > 1 else 0, qi, ki),
            memory_space=pltpu.VMEM)
    return pl.BlockSpec(
        (1, 1, g["bq"], g["bk"]),
        lambda b, h, qi, ki: (b if Bb > 1 else 0, h if Hb > 1 else 0,
                              qi, ki),
        memory_space=pltpu.VMEM)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11, 12, 13))
def _flash(q, k, v, qseg, kseg, q_off, k_off, seed,
           scale, causal, has_segs, block_q, block_k, dropout_p):
    out, lse, _ = _flash_fwd_impl(q, k, v, qseg, kseg, q_off, k_off,
                                  scale, causal, has_segs, block_q,
                                  block_k, dropout_p=dropout_p, seed=seed)
    return out, lse


def _drop_kw(dropout_p, g):
    """Kernel kwargs for the dropout path. EMPTY at p == 0 so the
    pallas_call partials (and the lowered kernels) stay byte-identical
    to the pre-dropout programs — the pinned bit-for-bit contract."""
    if dropout_p <= 0.0:
        return {}
    return dict(dropout_p=dropout_p, n_h=g["Hq"], interp=interpret_mode())


def _flash_fwd_impl(q, k, v, qseg, kseg, q_off, k_off,
                    scale, causal, has_segs, block_q, block_k,
                    bias=None, dropout_p=0.0, seed=None):
    qp, kp, vp, qs, ks, g = _prep(q, k, v, qseg, kseg, has_segs,
                                  block_q, block_k)
    q_spec, kv_spec, stat_spec, off_spec, qseg_spec, kseg_spec = \
        _common_specs(g)
    in_specs = [q_spec, kv_spec, kv_spec, off_spec, off_spec]
    args = [qp, kp, vp, *_off_arrays(q_off, k_off)]
    if dropout_p > 0.0:
        in_specs += [off_spec]
        args += [jnp.asarray(seed, jnp.int32).reshape(1, 1)]
    if has_segs:
        in_specs += [qseg_spec, kseg_spec]
        args += [qs, ks]
    has_bias = bias is not None
    if has_bias:
        bp, Bb, Hb = _prep_bias(bias, g)
        in_specs += [_bias_spec(g, Bb, Hb)]
        args += [bp]
    Sqp = g["n_q"] * g["bq"]
    out_p, lse_p = kernel_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          true_sq=g["Sq"], true_sk=g["Sk"],
                          has_segs=has_segs, has_bias=has_bias,
                          n_k=g["n_k"], **_drop_kw(dropout_p, g)),
        name="flash_fwd",
        grid=(g["B"], g["Hq"], g["n_q"], g["n_k"]),
        in_specs=in_specs,
        out_specs=(q_spec, stat_spec),
        out_shape=(
            out_struct((g["B"], g["Hq"], Sqp, g["Dp"]), q.dtype,
                       qp, kp, vp),
            out_struct((g["B"], g["Hq"], Sqp, 1), jnp.float32,
                       qp, kp, vp)),
        scratch_shapes=[
            pltpu.VMEM((g["bq"], g["Dp"]), jnp.float32),
            pltpu.VMEM((g["bq"], _LANES), jnp.float32),
            pltpu.VMEM((g["bq"], _LANES), jnp.float32)],
        interpret=interpret_mode(),
    )(*args)
    out = out_p[:, :, :g["Sq"], :g["D"]]
    lse = lse_p[:, :, :g["Sq"], 0]
    return out, lse, lse_p


def _flash_fwd(q, k, v, qseg, kseg, q_off, k_off, seed,
               scale, causal, has_segs, block_q, block_k, dropout_p):
    out, lse, lse_p = _flash_fwd_impl(q, k, v, qseg, kseg, q_off, k_off,
                                      scale, causal, has_segs,
                                      block_q, block_k,
                                      dropout_p=dropout_p, seed=seed)
    return (out, lse), (q, k, v, qseg, kseg, q_off, k_off, seed, out,
                        lse_p)


def _flash_bwd_impl(scale, causal, has_segs, block_q, block_k, res, cts,
                    bias=None, cast=True, dropout_p=0.0):
    """``cast=False`` returns dk/dv in their native fp32 kernel output
    dtype (dq is q.dtype either way — the dq kernel's out_shape): the
    ring backward accumulates per-shard dk/dv across the ring and a
    round-trip through k.dtype before that fp32 sum would discard the
    very precision the kernels paid for.

    With ``dropout_p > 0`` every backward kernel recomputes the
    forward's keep mask from the seed residual — the same
    recompute-instead-of-save trade the kernels already make for the
    probabilities."""
    q, k, v, qseg, kseg, q_off, k_off, seed, out, lse_p = res
    dout, dlse = cts
    qp, kp, vp, qs, ks, g = _prep(q, k, v, qseg, kseg, has_segs,
                                  block_q, block_k)
    Sqp = g["n_q"] * g["bq"]
    dop, _ = pad_to(dout.astype(q.dtype), 2, g["bq"])
    dop, _ = pad_to(dop, 3, _LANES)
    # δ_i = Σ_d dout·out — padded regions are zero so no masking needed
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    dlt_p, _ = pad_to(delta[..., None], 2, g["bq"])
    dlse_p, _ = pad_to(dlse.astype(jnp.float32)[..., None], 2, g["bq"])

    stat_args = [lse_p, dlt_p, dlse_p, *_off_arrays(q_off, k_off)]
    n_seed = 0
    if dropout_p > 0.0:
        stat_args += [jnp.asarray(seed, jnp.int32).reshape(1, 1)]
        n_seed = 1  # one extra SMEM scalar operand per launch
    has_bias = bias is not None
    if has_bias:
        bp, Bb, Hb = _prep_bias(bias, g)
    kern = dict(scale=scale, causal=causal, true_sq=g["Sq"],
                true_sk=g["Sk"], has_segs=has_segs,
                **_drop_kw(dropout_p, g))

    # dq: grid (b, h, qi, ki), key axis innermost
    q_spec, kv_spec, stat_spec, off_spec, qseg_spec, kseg_spec = \
        _common_specs(g)
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec,
                stat_spec, off_spec, off_spec]
    in_specs += [off_spec] * n_seed
    args = [qp, kp, vp, dop] + stat_args
    if has_segs:
        in_specs += [qseg_spec, kseg_spec]
        args += [qs, ks]
    if has_bias:
        in_specs += [_bias_spec(g, Bb, Hb)]
        args += [bp]
    dq = kernel_call(
        functools.partial(_bwd_dq_kernel, n_k=g["n_k"],
                          has_bias=has_bias, **kern),
        name="flash_dq",
        grid=(g["B"], g["Hq"], g["n_q"], g["n_k"]),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=out_struct((g["B"], g["Hq"], Sqp, g["Dp"]), q.dtype,
                             qp, kp, vp, dop),
        scratch_shapes=[pltpu.VMEM((g["bq"], g["Dp"]), jnp.float32)],
        interpret=interpret_mode(),
    )(*args)[:, :, :g["Sq"], :g["D"]]

    # dk/dv: grid (b, hkv, ki, gi, qi) — query axis innermost, GQA group
    # axis above it, so group accumulation happens in VMEM scratch and the
    # outputs are written at Hkv granularity (no Hq-sized fp32 partials)
    q_spec, kv_spec, stat_spec, off_spec, qseg_spec, kseg_spec, dkv_spec = \
        _dkv_specs(g)
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec,
                stat_spec, off_spec, off_spec]
    in_specs += [off_spec] * n_seed
    args = [qp, kp, vp, dop] + stat_args
    if has_segs:
        in_specs += [qseg_spec, kseg_spec]
        args += [qs, ks]
    if has_bias:
        in_specs += [_bias_spec(g, Bb, Hb, dkv=True)]
        args += [bp]
    Skp = g["n_k"] * g["bk"]
    dk, dv = kernel_call(
        functools.partial(_bwd_dkv_kernel, n_q=g["n_q"], group=g["group"],
                          has_bias=has_bias, **kern),
        name="flash_dkv",
        grid=(g["B"], g["Hkv"], g["n_k"], g["group"], g["n_q"]),
        in_specs=in_specs,
        out_specs=(dkv_spec, dkv_spec),
        out_shape=(
            out_struct((g["B"], g["Hkv"], Skp, g["Dp"]), jnp.float32,
                       qp, kp, vp, dop),
            out_struct((g["B"], g["Hkv"], Skp, g["Dp"]), jnp.float32,
                       qp, kp, vp, dop)),
        scratch_shapes=[pltpu.VMEM((g["bk"], g["Dp"]), jnp.float32),
                        pltpu.VMEM((g["bk"], g["Dp"]), jnp.float32)],
        interpret=interpret_mode(),
    )(*args)
    dk = dk[:, :, :g["Sk"], :g["D"]]
    dv = dv[:, :, :g["Sk"], :g["D"]]

    dbias = None
    if has_bias:
        # dbias pass: grid (Bb, Hb, qi, ki, r) — the broadcast sweep r
        # is innermost so the (bb, hb, qi, ki) output block's revisits
        # are consecutive and accumulate in scratch
        RB, RH = g["B"] // Bb, g["Hq"] // Hb
        n_r = RB * RH

        def bidx(bb, r):
            return bb + (r // RH) * Bb

        def hidx(hb, r):
            return hb + (r % RH) * Hb

        def spec4(blk, imap):
            return pl.BlockSpec(blk, imap, memory_space=pltpu.VMEM)

        q_spec_b = spec4((1, 1, g["bq"], g["Dp"]),
                         lambda bb, hb, qi, ki, r:
                         (bidx(bb, r), hidx(hb, r), qi, 0))
        kv_spec_b = spec4((1, 1, g["bk"], g["Dp"]),
                          lambda bb, hb, qi, ki, r:
                          (bidx(bb, r), hidx(hb, r) // g["group"], ki, 0))
        stat_spec_b = spec4((1, 1, g["bq"], 1),
                            lambda bb, hb, qi, ki, r:
                            (bidx(bb, r), hidx(hb, r), qi, 0))
        off_spec_b = pl.BlockSpec((1, 1), lambda *_: (0, 0),
                                  memory_space=pltpu.SMEM)
        qseg_spec_b = spec4((1, g["bq"], 1),
                            lambda bb, hb, qi, ki, r: (bidx(bb, r), qi, 0))
        kseg_spec_b = spec4((1, 1, g["bk"]),
                            lambda bb, hb, qi, ki, r: (bidx(bb, r), 0, ki))
        bias_spec_b = spec4((1, 1, g["bq"], g["bk"]),
                            lambda bb, hb, qi, ki, r: (bb, hb, qi, ki))
        db_spec = spec4((1, 1, g["bq"], g["bk"]),
                        lambda bb, hb, qi, ki, r: (bb, hb, qi, ki))
        in_specs = [q_spec_b, kv_spec_b, kv_spec_b, q_spec_b, stat_spec_b,
                    stat_spec_b, stat_spec_b, off_spec_b, off_spec_b]
        in_specs += [off_spec_b] * n_seed
        args = [qp, kp, vp, dop] + stat_args
        if has_segs:
            in_specs += [qseg_spec_b, kseg_spec_b]
            args += [qs, ks]
        in_specs += [bias_spec_b]
        args += [bp]
        dbias_p = kernel_call(
            functools.partial(_dbias_kernel, n_r=n_r, **kern,
                              **({"rh": RH} if dropout_p > 0.0 else {})),
            name="flash_dbias",
            grid=(Bb, Hb, g["n_q"], g["n_k"], n_r),
            in_specs=in_specs,
            out_specs=db_spec,
            out_shape=out_struct(
                (Bb, Hb, Sqp, g["n_k"] * g["bk"]), jnp.float32,
                qp, kp, vp, dop, bp),
            scratch_shapes=[pltpu.VMEM((g["bq"], g["bk"]), jnp.float32)],
            interpret=interpret_mode(),
        )(*args)
        dbias = dbias_p[:, :, :g["Sq"], :g["Sk"]]

    f0 = lambda x: np.zeros(jnp.shape(x), dtype=jax.dtypes.float0)
    if cast:
        dk, dv = dk.astype(k.dtype), dv.astype(v.dtype)
    grads = (dq.astype(q.dtype), dk, dv,
             f0(qseg), f0(kseg), f0(q_off), f0(k_off), f0(seed))
    return grads, dbias


def _flash_bwd(scale, causal, has_segs, block_q, block_k, dropout_p,
               res, cts):
    grads, _ = _flash_bwd_impl(scale, causal, has_segs, block_q, block_k,
                               res, cts, dropout_p=dropout_p)
    return grads


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12, 13, 14))
def _flash_with_bias(q, k, v, bias, qseg, kseg, q_off, k_off, seed,
                     scale, causal, has_segs, block_q, block_k, dropout_p):
    out, lse, _ = _flash_fwd_impl(q, k, v, qseg, kseg, q_off, k_off,
                                  scale, causal, has_segs, block_q,
                                  block_k, bias=bias, dropout_p=dropout_p,
                                  seed=seed)
    return out, lse


def _flash_with_bias_fwd(q, k, v, bias, qseg, kseg, q_off, k_off, seed,
                         scale, causal, has_segs, block_q, block_k,
                         dropout_p):
    out, lse, lse_p = _flash_fwd_impl(q, k, v, qseg, kseg, q_off, k_off,
                                      scale, causal, has_segs,
                                      block_q, block_k, bias=bias,
                                      dropout_p=dropout_p, seed=seed)
    return (out, lse), (q, k, v, bias, qseg, kseg, q_off, k_off, seed,
                        out, lse_p)


def _flash_with_bias_bwd(scale, causal, has_segs, block_q, block_k,
                         dropout_p, res, cts):
    q, k, v, bias, qseg, kseg, q_off, k_off, seed, out, lse_p = res
    grads, dbias = _flash_bwd_impl(
        scale, causal, has_segs, block_q, block_k,
        (q, k, v, qseg, kseg, q_off, k_off, seed, out, lse_p), cts,
        bias=bias, dropout_p=dropout_p)
    dq, dk, dv, fqs, fks, fqo, fko, fsd = grads
    return (dq, dk, dv, dbias.astype(bias.dtype), fqs, fks, fqo, fko, fsd)


_flash_with_bias.defvjp(_flash_with_bias_fwd, _flash_with_bias_bwd)


def _xla_attention(q, k, v, qseg, kseg, q_off, k_off, scale, causal,
                   with_lse=False, bias=None, dropout_p=0.0, seed=None):
    """XLA-composite gold: identical semantics incl. empty-row handling.
    Probability dropout uses the SAME counter hash at global positions
    as the interpret-mode kernels — bit-identical masks on CPU."""
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    if Hq != Hkv:
        k = jnp.repeat(k, Hq // Hkv, axis=1)
        v = jnp.repeat(v, Hq // Hkv, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
    mask = jnp.ones((B, 1, Sq, Sk), bool)
    if causal:
        mask &= ((col + k_off) <= (row + q_off))[None, None]
    if qseg is not None:
        mask &= (qseg[:, None, :, None] == kseg[:, None, None, :])
    # masked scores (not raw s) inside exp: for rows with NO valid keys
    # m == NEG_INF and exp(s - m) would overflow to inf, poisoning the VJP
    # with inf·0 = NaN; exp(sm - m) is exp(0) = 1 there (then zeroed), and
    # the inner where blocks the masked-branch gradient entirely
    sm = jnp.where(mask, s, NEG_INF)
    m = jnp.max(sm, axis=-1, keepdims=True)
    e = jnp.where(mask, jnp.exp(sm - m), 0.0)
    l = jnp.sum(e, axis=-1, keepdims=True)
    probs = e / jnp.where(l > 0, l, 1.0)
    if dropout_p > 0.0:
        keep = attn_keep_mask(seed, B, Hq, row + q_off, col + k_off,
                              dropout_p)
        # denominator l stays UNdropped (lse is dropout-free); only the
        # AV weights are masked+rescaled — matches the kernels
        probs = jnp.where(keep, probs * (1.0 / (1.0 - dropout_p)), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs,
                     v.astype(jnp.float32)).astype(q.dtype)
    if not with_lse:
        return out
    lse = jnp.where(l > 0, m + jnp.log(jnp.where(l > 0, l, 1.0)),
                    NEG_INF)[..., 0]
    return out, lse


def _norm_segments(segment_ids, Sq, Sk):
    if segment_ids is None:
        return False, None, None
    if isinstance(segment_ids, (tuple, list)):
        qseg, kseg = segment_ids
    else:
        if Sq != Sk:
            raise ValueError("pass (q_seg, k_seg) when Sq != Sk")
        qseg = kseg = segment_ids
    return True, qseg, kseg


def flash_attention(q, k, v, *, causal: bool = False, segment_ids=None,
                    sm_scale: float | None = None, q_offset=0, k_offset=0,
                    block_q: int | None = None, block_k: int | None = None,
                    return_lse: bool = False, bias=None,
                    dropout_p: float = 0.0, dropout_seed=None):
    """Flash attention over (B, H, S, D) operands.

    ``segment_ids``: (B, S) int array (self-attention) or a
    ``(q_seg, k_seg)`` pair — tokens attend only within equal ids
    (≙ fmha's cu_seqlens varlen batches).
    ``q_offset``/``k_offset``: traced global-position offsets for the
    causal mask (used by ring/context parallelism; 0 for plain use).
    ``block_q``/``block_k``: static kernel tile sizes. ``None`` (the
    default) resolves via `apex1_tpu.tuning`: env override
    (``APEX1_ATTN_BLOCK_Q/K``) > persisted tuning-table winner for this
    (generation, dtype, padded head dim) > analytic heuristic. Explicit
    values are honored verbatim — they are static arguments, so an
    in-process sweep of N candidates (``tools/tune_kernels.py``)
    compiles exactly N executables with no jit-cache
    cross-contamination.
    ``return_lse``: also return the fp32 logsumexp (B, H, Sq) — needed to
    merge partial-attention results (ring attention).
    ``bias``: additive logit bias (1|B, 1|H, Sq, Sk) — T5-style relative
    position bias or an arbitrary additive mask; differentiable (dbias
    via a dedicated broadcast-accumulating backward pass), so the O(S²)
    composite path is never needed for bias-bearing attention.
    ``dropout_p``/``dropout_seed``: attention-probability dropout FUSED
    between softmax and AV inside the kernels (≙ the reference fmha /
    multihead_attn fusion point) — no mask tensor is ever stored; the
    backward recomputes the mask from the int32 seed. The mask is
    counter-based on (seed, batch·H+head, global q pos, global k pos),
    so it is deterministic per (seed, backend), independent of grid
    order, and ring/context-parallel shards draw disjoint streams via
    their ``k_offset``. Derive seeds per call site with
    `apex1_tpu.ops.stochastic.seed_from_key` / `fold_seed`. ``lse`` (and
    the softmax denominator) stay dropout-free, which is what keeps ring
    merges exact. dropout_p=0 lowers to the exact pre-dropout kernel.
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError("expected (B, H, S, D) operands")
    if q.shape[1] % k.shape[1] != 0:
        raise ValueError(f"Hq={q.shape[1]} not a multiple of "
                         f"Hkv={k.shape[1]}")
    scale = (1.0 / float(np.sqrt(q.shape[-1]))
             if sm_scale is None else float(sm_scale))
    # fp16 (the O*_fp16 AMP policies) is a storage dtype on TPU: Mosaic
    # has no f16, so compiled kernels run bf16 and the result is cast
    # back — see ops._common.mosaic_dtype. Resolved BEFORE the block
    # lookup so the tuning table keys on the dtype the kernel compiles.
    io_dtype = q.dtype
    if use_pallas():
        # an f16 bias hits the same Mosaic f16 wall as q/k/v
        q, k, v, bias = to_mosaic(q, k, v, bias)
    block_q, block_k = _auto_blocks(q.shape[3], block_q, block_k, q.dtype,
                                    k.shape[2])
    has_segs, qseg, kseg = _norm_segments(segment_ids, q.shape[2],
                                          k.shape[2])
    if bias is not None:
        # validate for BOTH backends: a bias shape the kernel rejects
        # must not silently broadcast on the XLA fallback (code
        # validated on CPU would then crash on TPU)
        B, Hq, Sq = q.shape[0], q.shape[1], q.shape[2]
        Sk = k.shape[2]
        if bias.ndim != 4:
            raise ValueError(f"bias must be (1|B, 1|H, Sq, Sk), got "
                             f"rank {bias.ndim}")
        if (bias.shape[0] not in (1, B) or bias.shape[1] not in (1, Hq)
                or bias.shape[2:] != (Sq, Sk)):
            raise ValueError(f"bias shape {bias.shape} must be "
                             f"(1|{B}, 1|{Hq}, {Sq}, {Sk})")
    dropout_p = float(dropout_p)
    if not 0.0 <= dropout_p < 1.0:
        raise ValueError(f"dropout_p must be in [0, 1), got {dropout_p}")
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 needs an explicit int32 "
                         "dropout_seed (ops.stochastic.seed_from_key / "
                         "fold_seed at the call site)")
    seed = (jnp.asarray(dropout_seed, jnp.int32) if dropout_p > 0.0
            else jnp.zeros((), jnp.int32))
    if use_pallas():
        dummy = jnp.zeros((1, 1), jnp.int32)
        if bias is not None:
            out, lse = _flash_with_bias(
                q, k, v, bias,
                qseg if has_segs else dummy,
                kseg if has_segs else dummy,
                q_offset, k_offset, seed,
                scale, causal, has_segs, block_q, block_k, dropout_p)
        else:
            out, lse = _flash(q, k, v,
                              qseg if has_segs else dummy,
                              kseg if has_segs else dummy,
                              q_offset, k_offset, seed,
                              scale, causal, has_segs, block_q, block_k,
                              dropout_p)
    else:
        out, lse = _xla_attention(q, k, v, qseg, kseg, q_offset, k_offset,
                                  scale, causal, with_lse=True, bias=bias,
                                  dropout_p=dropout_p, seed=seed)
    if out.dtype != io_dtype:
        out = out.astype(io_dtype)  # fp16 storage dtype restored
    return (out, lse) if return_lse else out


def fmha(qkv, *, segment_ids=None, causal: bool = True,
         sm_scale: float | None = None, dropout_p: float = 0.0,
         dropout_seed=None):
    """``apex.contrib.fmha.FMHAFun`` equivalent: packed (B, S, 3, H, D)
    QKV, varlen via ``segment_ids`` instead of cu_seqlens. No seqlen-512 or
    head-dim-64 cap — the flash kernel serves all sizes. ``dropout_p``
    is the reference's in-kernel probability dropout (seeded, fused)."""
    q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
    out = flash_attention(q, k, v, causal=causal, segment_ids=segment_ids,
                          sm_scale=sm_scale, dropout_p=dropout_p,
                          dropout_seed=dropout_seed)
    return out.transpose(0, 2, 1, 3)
