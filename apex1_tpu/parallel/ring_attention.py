"""Ring attention — context parallelism for long sequences over ICI.

The reference has NO long-context attention mechanism (SURVEY.md §5.7:
``apex/contrib/fmha`` caps seqlen at 512; Megatron SP shards LN/dropout
activations only). Its closest pattern is the spatial-parallel halo
exchange (``apex/contrib/bottleneck/halo_exchangers.py :: HaloExchangerNccl``
— activation-domain decomposition with neighbor transfers), which this
module generalizes to attention: shard the SEQUENCE over a mesh axis and
rotate K/V shards around the ring with ``jax.lax.ppermute`` (ICI
neighbor transfers), merging partial-attention results with the
numerically-stable logsumexp merge.

Per ring step each device computes flash attention of its local Q shard
against the visiting K/V shard (`apex1_tpu.ops.attention.flash_attention`
with traced global offsets for the causal mask), yielding ``(out_t,
lse_t)``; partials combine exactly:

    lse   = logaddexp(lse_a, lse_b)
    out   = out_a·exp(lse_a − lse) + out_b·exp(lse_b − lse)

**Double-buffered schedule** (the ``apex.parallel.DDP`` bucketed-overlap
optimization restated for ICI): the ppermute that fetches the K/V shard
for step t+1 is issued BEFORE ``attend(shard t)`` runs, so the attention
dots of step t have no data dependence on the in-flight transfer and
XLA's async collectives (``collective-permute-start``/``-done``) hide
the ICI latency behind the MXU work. Two K/V buffers are live per step
(the one being attended and the one in flight) — that is the double
buffer. The property is PINNED on optimized HLO text by
`apex1_tpu.testing.hlo_probe` (tools/aot_check.py probes the v5e
executables; a serialized rotate→attend loop fails the probe).

Fully-masked (future, under causal) visiting shards are skipped with
``lax.cond`` — their transfer still rides the ring but their FLOPs are
not spent. The backward is a ``jax.custom_vjp``: its own double-buffered
ring with the INVERTED permutation, reusing the flash kernels'
lse-residual backward per visiting shard (global-statistics trick: each
per-shard backward is evaluated with the FINAL merged ``(out, lse)``,
which makes the per-shard cotangents exact without storing any per-step
statistics). dK/dV partial sums ride the ring back to their owning
device alongside the shards themselves. Pass ``use_custom_vjp=False``
to fall back to XLA's transpose of the forward scan (the pre-overlap
behavior for the backward; forward stays double-buffered).

`ring_attention_serial` retains the original rotate-first-then-attend
schedule (every transfer exposed) as the parity anchor in tests.

Use inside ``jax.shard_map`` with the sequence dimension sharded over
``axis_name``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from apex1_tpu.ops._common import NEG_INF, use_pallas
from apex1_tpu.ops._common import vary as _vary
from apex1_tpu.ops.attention import flash_attention
from apex1_tpu.ops.stochastic import attn_keep_mask


def _axis_size(axis_name) -> int:
    return jax.lax.axis_size(axis_name)


def _merge(out_a, lse_a, out_b, lse_b):
    """Exact combine of two normalized partial attentions (fp32 stats)."""
    lse = jnp.logaddexp(lse_a, lse_b)
    w_a = jnp.exp(lse_a - lse)[..., None]
    w_b = jnp.exp(lse_b - lse)[..., None]
    return out_a * w_a + out_b.astype(out_a.dtype) * w_b, lse


def _ring_fwd_loop(q, k, v, qseg, axis_name, causal, sm_scale, has_segs,
                   block_q, block_k, dropout_p=0.0, seed=None,
                   skip_masked=True):
    """Double-buffered forward ring. Returns (out_fp32, lse).

    Schedule: the ppermute for the NEXT visiting shard is issued before
    the current shard is attended (no data dependence between them), so
    all n−1 neighbor transfers overlap the n attends. Attend/merge order
    is identical to the serialized schedule — forward numerics are
    bit-for-bit the same; only the permutes' dataflow changes.

    ``dropout_p``/``seed``: in-kernel probability dropout — every shard
    step passes its TRUE global offsets so the counter-based mask is
    keyed on global positions: shards draw disjoint streams and the mask
    is invariant to the visiting order (serial and overlapped schedules
    drop identical weights). ``seed`` must be replicated over the ring.
    ``skip_masked=False`` disables the causal lax.cond shard skip (the
    fully-masked attend runs and merges a NEG_INF partial — numerically
    identical); a path with no caller but its parity test (ROADMAP D4).
    """
    n = _axis_size(axis_name)
    B, Hq, Sq, _ = q.shape
    Sk = k.shape[2]
    # axis_index only when the causal mask (or the dropout counter,
    # which keys on global positions) consumes it: a dead partition-id
    # chain in the custom_vjp jaxpr breaks XLA sharding propagation
    # (consumer-less partition-id is UNIMPLEMENTED there)
    needs_offs = causal or dropout_p > 0.0
    if needs_offs:
        idx = jax.lax.axis_index(axis_name)
        q_off = idx * Sq
    perm = [(i, (i + 1) % n) for i in range(n)]

    out = _vary(jnp.zeros(q.shape, jnp.promote_types(q.dtype, jnp.float32)),
                axis_name)
    lse = _vary(jnp.full((B, Hq, Sq), NEG_INF, jnp.float32), axis_name)

    def attend(k_cur, v_cur, kseg_cur, t, out, lse):
        # offsets are consumed only by the causal mask / dropout
        # counter; computing them unconditionally would leave a dead
        # partition-id chain in the custom_vjp jaxpr (not DCE'd before
        # XLA sharding propagation, which then fails on the
        # consumer-less partition-id)
        if needs_offs:
            src = (idx - t) % n       # who this K/V shard belongs to
            k_off = src * Sk
            qo, ko = q_off, k_off
        else:
            qo = ko = 0

        def run(_):
            return flash_attention(
                q, k_cur, v_cur, causal=causal,
                segment_ids=(qseg, kseg_cur) if has_segs else None,
                sm_scale=sm_scale, q_offset=qo, k_offset=ko,
                block_q=block_q, block_k=block_k, return_lse=True,
                dropout_p=dropout_p, dropout_seed=seed)

        def skip(_):
            return (_vary(jnp.zeros(q.shape, q.dtype), axis_name),
                    _vary(jnp.full((B, Hq, Sq), NEG_INF, jnp.float32),
                          axis_name))

        if causal and skip_masked:
            # visiting shard strictly in the future → fully masked
            out_t, lse_t = jax.lax.cond(k_off > q_off + Sq - 1, skip, run,
                                        None)
        else:
            out_t, lse_t = run(None)
        return _merge(out, lse, out_t, lse_t)

    kseg0 = qseg if has_segs else jnp.zeros((), jnp.int32)
    if n == 1:
        return attend(k, v, kseg0, 0, out, lse)

    # prologue: issue the transfer for step 1 BEFORE attending the local
    # shard — attend(t=0) has no data dependence on it, so the transfer
    # flies behind the first attend's dots
    k_cur = jax.lax.ppermute(k, axis_name, perm)
    v_cur = jax.lax.ppermute(v, axis_name, perm)
    kseg_cur = (jax.lax.ppermute(kseg0, axis_name, perm) if has_segs
                else kseg0)
    out, lse = attend(k, v, kseg0, 0, out, lse)

    def step(carry, t):
        # issue the transfer for shard t+1, THEN attend shard t: the
        # dots consume only the carry (double buffer), never this
        # step's permute — the overlap property hlo_probe pins
        k_cur, v_cur, kseg_cur, out, lse = carry
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        kseg_nxt = (jax.lax.ppermute(kseg_cur, axis_name, perm)
                    if has_segs else kseg_cur)
        out, lse = attend(k_cur, v_cur, kseg_cur, t, out, lse)
        return (k_nxt, v_nxt, kseg_nxt, out, lse), None

    if n > 2:
        (k_cur, v_cur, kseg_cur, out, lse), _ = jax.lax.scan(
            step, (k_cur, v_cur, kseg_cur, out, lse), jnp.arange(1, n - 1))
    # epilogue: last visiting shard — no transfer left to issue, so the
    # ring does exactly n−1 permutes, all overlapped
    return attend(k_cur, v_cur, kseg_cur, n - 1, out, lse)


def _resolve_scale(q, sm_scale):
    return (1.0 / float(np.sqrt(q.shape[-1]))
            if sm_scale is None else float(sm_scale))


def _step_grads_pallas(q, k_cur, v_cur, qseg, kseg_cur, q_off, k_off, out,
                       lse, do, scale, causal, has_segs, block_q, block_k,
                       dropout_p=0.0, seed=None):
    """One visiting shard's (dq_t, dk_t, dv_t) via the flash backward
    kernels, evaluated with the FINAL merged (out, lse): p_t =
    exp(s_t − lse_global) is each key's true global softmax weight, so
    the per-shard cotangents are exact (the same lse-residual backward
    the single-shard flash custom VJP runs, with dlse = 0 since the
    ring consumes lse internally)."""
    from apex1_tpu.ops.attention import _auto_blocks, _flash_bwd_impl

    block_q, block_k = _auto_blocks(q.shape[3], block_q, block_k, q.dtype,
                                    k_cur.shape[2])
    dummy = jnp.zeros((1, 1), jnp.int32)
    sd = (jnp.asarray(seed, jnp.int32) if dropout_p > 0.0
          else jnp.zeros((), jnp.int32))
    res = (q, k_cur, v_cur,
           qseg if has_segs else dummy,
           kseg_cur if has_segs else dummy,
           q_off, k_off, sd, out, lse)
    cts = (do, jnp.zeros(lse.shape, jnp.float32))
    # cast=False: dk/dv stay in the kernels' native fp32 so the ring
    # accumulation is exact (dq is q.dtype — the dq kernel's output
    # dtype, same per-shard precision as single-shard flash). With
    # dropout the backward kernels recompute the mask from (seed,
    # global offsets) — identical to what the forward shard drew.
    grads, _ = _flash_bwd_impl(scale, causal, has_segs, block_q, block_k,
                               res, cts, cast=False, dropout_p=dropout_p)
    return grads[0], grads[1], grads[2]


def _step_grads_xla(q, k_cur, v_cur, qseg, kseg_cur, q_off, k_off, lse,
                    delta, do, scale, causal, has_segs, dropout_p=0.0,
                    seed=None):
    """XLA-composite per-shard backward (CPU/GPU gold): same math as
    `_step_grads_pallas` with the local S×S score block materialized."""
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k_cur.shape[1], k_cur.shape[2]
    group = Hq // Hkv
    kr, vr = k_cur, v_cur
    if group > 1:
        kr = jnp.repeat(k_cur, group, axis=1)
        vr = jnp.repeat(v_cur, group, axis=1)
    qf = q.astype(jnp.float32)
    kf = kr.astype(jnp.float32)
    vf = vr.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf,
                   preferred_element_type=jnp.float32) * scale
    row = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
    mask = jnp.ones((B, 1, Sq, Sk), bool)
    if causal:
        mask = mask & ((col + k_off) <= (row + q_off))[None, None]
    if has_segs:
        mask = mask & (qseg[:, None, :, None] == kseg_cur[:, None, None, :])
    # lse is the GLOBAL logsumexp; rows with no valid keys carry the
    # NEG_INF sentinel — their exp overflows but the mask zeroes p
    p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
    if dropout_p > 0.0:
        keep = attn_keep_mask(seed, B, Hq, row + q_off, col + k_off,
                              dropout_p)
        inv = 1.0 / (1.0 - dropout_p)
        p_av = jnp.where(keep, p * inv, 0.0)   # dv sees DROPPED probs
    else:
        p_av = p
    dv_full = jnp.einsum("bhqk,bhqd->bhkd", p_av, dof)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf)
    if dropout_p > 0.0:
        dp = jnp.where(keep, dp * inv, 0.0)
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
    dk_full = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
    if group > 1:
        dk_full = dk_full.reshape(B, Hkv, group, Sk, D).sum(axis=2)
        dv_full = dv_full.reshape(B, Hkv, group, Sk, D).sum(axis=2)
    return dq, dk_full, dv_full


def _ring_bwd_loop(q, k, v, qseg, out, lse, do, axis_name, causal,
                   sm_scale, has_segs, block_q, block_k, dropout_p=0.0,
                   seed=None, skip_masked=True):
    """Double-buffered backward ring over the INVERTED permutation.

    Shards flow backward (device i sends to i−1), so this device visits
    shards idx+1, idx+2, …, idx−1 in that order; the local shard's
    grads are computed in the prologue (overlapping the first hop) and
    folded in at the end. Travelling dK/dV accumulators hop alongside
    the shard they belong to and arrive home after n−1 hops — every
    transfer overlaps a per-shard flash backward.
    """
    n = _axis_size(axis_name)
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    # offsets exist only for the causal mask / dropout counter — see
    # _ring_fwd_loop on why a dead partition-id chain must not be traced
    needs_offs = causal or dropout_p > 0.0
    if needs_offs:
        idx = jax.lax.axis_index(axis_name)
        q_off = idx * Sq
    else:
        q_off = 0
    scale = _resolve_scale(q, sm_scale)
    inv = [(i, (i - 1) % n) for i in range(n)]
    pallas = use_pallas()
    # δ_i = Σ_d do·out — shared by every per-shard backward
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)

    def step_grads(k_cur, v_cur, kseg_cur, src):
        """fp32 (dq_t, dk_t, dv_t) for one visiting shard. ``src`` is the
        shard's owner (consumed by the causal mask only; 0 off-causal).
        fp32 so the cond branches agree and the dk/dv ring accumulation
        stays exact (the Pallas path hands its dk/dv over uncast via
        ``cast=False``; dq contributions carry the dq kernel's q.dtype
        precision, as in single-shard flash)."""
        k_off = src * Sk

        def run(_):
            if pallas:
                g = _step_grads_pallas(
                    q, k_cur, v_cur, qseg, kseg_cur, q_off, k_off, out,
                    lse, do, scale, causal, has_segs, block_q, block_k,
                    dropout_p=dropout_p, seed=seed)
            else:
                g = _step_grads_xla(
                    q, k_cur, v_cur, qseg, kseg_cur, q_off, k_off, lse,
                    delta, do, scale, causal, has_segs,
                    dropout_p=dropout_p, seed=seed)
            return tuple(t.astype(jnp.float32) for t in g)

        def skip(_):
            z = lambda shape: _vary(jnp.zeros(shape, jnp.float32),
                                    axis_name)
            return (z(q.shape), z(k.shape), z(v.shape))

        if causal and skip_masked:
            # visiting shard strictly in the future → zero cotangents;
            # the cond skips the FLOPs, the transfer still rides
            return jax.lax.cond(k_off > q_off + Sq - 1, skip, run, None)
        return run(None)

    kseg0 = qseg if has_segs else jnp.zeros((), jnp.int32)
    f32 = jnp.float32
    dq_own, dk_own, dv_own = step_grads(k, v, kseg0,
                                        idx if needs_offs else 0)
    dq = dq_own.astype(f32)
    dk_own = dk_own.astype(f32)
    dv_own = dv_own.astype(f32)
    if n == 1:
        return dq, dk_own, dv_own

    # prologue hop (issued before the local backward above in dataflow —
    # the local grads have no dependence on it)
    k_cur = jax.lax.ppermute(k, axis_name, inv)
    v_cur = jax.lax.ppermute(v, axis_name, inv)
    kseg_cur = (jax.lax.ppermute(kseg0, axis_name, inv) if has_segs
                else kseg0)
    zeros = lambda: _vary(jnp.zeros((B, Hkv, Sk, D), f32), axis_name)
    # travelling accumulators + one-step-delayed "pending" contributions:
    # each hop ships acc+pend where BOTH are carry values, so no permute
    # in the loop body depends on this step's backward kernels — XLA can
    # schedule every collective-permute-start before the dots and every
    # -done after them (the hlo_probe-pinned property; an add-then-hop
    # accumulator would chain the dk/dv transfer behind the compute and
    # the TPU scheduler then refuses to hoist ANY of the step's
    # permutes). Cost: one extra seed/return hop per buffer (n instead
    # of n−1), fully overlapped — latency hiding is first-order at 16k,
    # the ~1/(n−1) extra ICI bytes are not.
    dk_acc, dv_acc = zeros(), zeros()
    dk_pend, dv_pend = zeros(), zeros()

    def body(carry, t):
        (k_cur, v_cur, kseg_cur, dk_acc, dv_acc, dk_pend, dv_pend,
         dq) = carry
        # hop the accumulator completed through this device last step,
        # and prefetch shard t+1 — all carry-only dependences
        dk_acc = jax.lax.ppermute(dk_acc + dk_pend, axis_name, inv)
        dv_acc = jax.lax.ppermute(dv_acc + dv_pend, axis_name, inv)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, inv)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, inv)
        kseg_nxt = (jax.lax.ppermute(kseg_cur, axis_name, inv)
                    if has_segs else kseg_cur)
        src = (idx + 1 + t) % n if needs_offs else 0
        dq_t, dk_pend, dv_pend = step_grads(k_cur, v_cur, kseg_cur, src)
        dq = dq + dq_t.astype(f32)
        return (k_nxt, v_nxt, kseg_nxt, dk_acc, dv_acc, dk_pend,
                dv_pend, dq), None

    (_, _, _, dk_acc, dv_acc, dk_pend, dv_pend, dq), _ = jax.lax.scan(
        body,
        (k_cur, v_cur, kseg_cur, dk_acc, dv_acc, dk_pend, dv_pend, dq),
        jnp.arange(0, n - 1))
    # final hop carries the last pending contribution to each shard's
    # owner, where the prologue's local term folds in (order-free adds)
    dk = jax.lax.ppermute(dk_acc + dk_pend, axis_name, inv) + dk_own
    dv = jax.lax.ppermute(dv_acc + dv_pend, axis_name, inv) + dv_own
    return dq, dk, dv


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _ring(q, k, v, qseg, seed, axis_name, causal, sm_scale, has_segs,
          block_q, block_k, dropout_p, skip_masked):
    out, _ = _ring_fwd_loop(q, k, v, qseg, axis_name, causal, sm_scale,
                            has_segs, block_q, block_k,
                            dropout_p=dropout_p, seed=seed,
                            skip_masked=skip_masked)
    return out.astype(q.dtype)


def _ring_fwd_rule(q, k, v, qseg, seed, axis_name, causal, sm_scale,
                   has_segs, block_q, block_k, dropout_p, skip_masked):
    out, lse = _ring_fwd_loop(q, k, v, qseg, axis_name, causal, sm_scale,
                              has_segs, block_q, block_k,
                              dropout_p=dropout_p, seed=seed,
                              skip_masked=skip_masked)
    out = out.astype(q.dtype)
    return out, (q, k, v, qseg, seed, out, lse)


def _ring_bwd_rule(axis_name, causal, sm_scale, has_segs, block_q, block_k,
                   dropout_p, skip_masked, res, do):
    q, k, v, qseg, seed, out, lse = res
    dq, dk, dv = _ring_bwd_loop(q, k, v, qseg, out, lse, do, axis_name,
                                causal, sm_scale, has_segs, block_q,
                                block_k, dropout_p=dropout_p, seed=seed,
                                skip_masked=skip_masked)
    f0 = np.zeros(jnp.shape(qseg), dtype=jax.dtypes.float0)
    f0s = np.zeros(jnp.shape(seed), dtype=jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            f0, f0s)


_ring.defvjp(_ring_fwd_rule, _ring_bwd_rule)


def ring_attention(q, k, v, axis_name, *, causal: bool = False,
                   sm_scale: float | None = None, segment_ids=None,
                   block_q: int | None = None, block_k: int | None = None,
                   use_custom_vjp: bool = True, dropout_p: float = 0.0,
                   dropout_seed=None, skip_masked: bool = True):
    """Attention over a sequence sharded on mesh axis ``axis_name``.

    ``q``: local shard (B, Hq, S_local, D); ``k``/``v``: (B, Hkv, S_local,
    D). The global sequence is ``ring_size * S_local``, laid out in
    axis-index order. ``segment_ids``: local (B, S_local) shard of the
    global segment ids (rides the ring alongside K/V). Returns the local
    output shard (B, Hq, S_local, D).

    The schedule is double-buffered: each ring step issues the ppermute
    for the NEXT K/V shard before attending the current one, so the ICI
    transfer hides behind the attention dots (forward AND backward; the
    property is pinned on optimized HLO by `testing.hlo_probe`).
    ``use_custom_vjp=False`` reverts the backward to XLA's transpose of
    the forward scan (serialized transfers) — kept for parity tests and
    as an escape hatch; forward numerics are identical either way.
    ``dropout_p``/``dropout_seed``: in-kernel attention-probability
    dropout (`ops.attention.flash_attention`); the seed must be
    REPLICATED over the ring (every device passes the same int32) — the
    counter-based mask keys on each shard's global k-offset, so shards
    draw disjoint streams and serial/overlapped schedules drop
    identical weights. ``skip_masked=False`` disables the causal
    lax.cond shard skip (no caller but its parity test, ROADMAP D4;
    numerics identical).
    """
    sm_scale = None if sm_scale is None else float(sm_scale)
    dropout_p = float(dropout_p)
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 needs an explicit int32 "
                         "dropout_seed (replicated over the ring)")
    seed = (jnp.asarray(dropout_seed, jnp.int32) if dropout_p > 0.0
            else jnp.zeros((), jnp.int32))
    has_segs = segment_ids is not None
    qseg = (segment_ids if has_segs
            else jnp.zeros((1, 1), jnp.int32))
    if use_custom_vjp:
        return _ring(q, k, v, qseg, seed, axis_name, causal, sm_scale,
                     has_segs, block_q, block_k, dropout_p, skip_masked)
    out, _ = _ring_fwd_loop(q, k, v, qseg, axis_name, causal, sm_scale,
                            has_segs, block_q, block_k,
                            dropout_p=dropout_p, seed=seed,
                            skip_masked=skip_masked)
    return out.astype(q.dtype)


def ring_attention_serial(q, k, v, axis_name, *, causal: bool = False,
                          sm_scale: float | None = None, segment_ids=None,
                          block_q: int | None = None,
                          block_k: int | None = None,
                          dropout_p: float = 0.0, dropout_seed=None,
                          skip_masked: bool = True):
    """The ORIGINAL serialized schedule — rotate first, then attend, so
    every one of the n−1 ICI transfers is exposed (the attend consumes
    the permute it just issued). Retained as the parity anchor for the
    double-buffered rewrite and the hlo_probe negative control (this
    loop body must FAIL the overlap probe). Backward is XLA's transpose
    of the scan. Numerics are identical to `ring_attention` (same
    attend/merge order)."""
    n = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, Hq, Sq, _ = q.shape
    Sk = k.shape[2]
    q_off = idx * Sq
    perm = [(i, (i + 1) % n) for i in range(n)]
    has_segs = segment_ids is not None
    qseg = segment_ids
    dropout_p = float(dropout_p)
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 needs an explicit int32 "
                         "dropout_seed (replicated over the ring)")

    out0 = _vary(jnp.zeros(q.shape, jnp.promote_types(q.dtype,
                                                      jnp.float32)),
                 axis_name)
    lse0 = _vary(jnp.full((B, Hq, Sq), NEG_INF, jnp.float32), axis_name)

    def attend(k_cur, v_cur, kseg_cur, t, out, lse):
        src = (idx - t) % n           # who this K/V shard belongs to
        k_off = src * Sk

        def run(_):
            return flash_attention(
                q, k_cur, v_cur, causal=causal,
                segment_ids=(qseg, kseg_cur) if has_segs else None,
                sm_scale=sm_scale, q_offset=q_off, k_offset=k_off,
                block_q=block_q, block_k=block_k, return_lse=True,
                dropout_p=dropout_p, dropout_seed=dropout_seed)

        def skip(_):
            return (_vary(jnp.zeros(q.shape, q.dtype), axis_name),
                    _vary(jnp.full((B, Hq, Sq), NEG_INF, jnp.float32),
                          axis_name))

        if causal and skip_masked:
            # visiting shard strictly in the future → fully masked
            out_t, lse_t = jax.lax.cond(k_off > q_off + Sq - 1, skip, run,
                                        None)
        else:
            out_t, lse_t = run(None)
        return _merge(out, lse, out_t, lse_t)

    def step(carry, t):
        # rotate first, then attend: the attend CONSUMES this step's
        # permute, so the transfer latency is fully exposed
        k_cur, v_cur, kseg_cur, out, lse = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        if has_segs:
            kseg_cur = jax.lax.ppermute(kseg_cur, axis_name, perm)
        out, lse = attend(k_cur, v_cur, kseg_cur, t, out, lse)
        return (k_cur, v_cur, kseg_cur, out, lse), None

    kseg0 = qseg if has_segs else jnp.zeros((), jnp.int32)
    out, lse = attend(k, v, kseg0, 0, out0, lse0)  # local shard, no comm
    if n > 1:
        (_, _, _, out, lse), _ = jax.lax.scan(
            step, (k, v, kseg0, out, lse), jnp.arange(1, n))
    return out.astype(q.dtype)
