"""Fused computation-collective Pallas forms — ROADMAP item 3.

PR 4 overlapped the Megatron-SP boundary collectives at the XLA schedule
level (`transformer.tensor_parallel.mappings.all_gather_matmul` /
`matmul_reduce_scatter`: chunk-pipelined ppermute rings whose transfers
have no data dependence into the per-chunk dots). The collective still
runs *beside* the compute, bounded by what the scheduler will overlap.
This module moves the boundary INTO the kernels (arxiv 2305.06942's
fused computation-collective operations; the epilogue-fusion playbook of
2502.17728), in three forms:

- **`fused_matmul_reduce_scatter` / `fused_all_gather_matmul`** — the SP
  boundary matmuls with the per-chunk dot running in a Pallas kernel
  (`_chunk_matmul`) instead of an XLA dot. The ring schedule and the
  travelling-accumulator adds are bit-for-bit PR 4's (same hops, same
  add order — the carry-add must precede the hop it feeds, so it stays
  an XLA op on purpose; see the dataflow note below), which is what
  makes the fused forms bitwise-pinnable against their decomposed
  counterparts on the CPU mesh. The kernel is the execution-tested tile
  loop that the RDMA form below extends.
- **`fused_matmul_reduce_scatter(..., impl="rdma")`** — the paper-shape
  kernel: ONE `pallas_call` whose grid walks the ring steps, computing
  the partial dot for chunk t+1 while the epilogue's
  `make_async_remote_copy` ships the travelling fp32 accumulator for
  chunk t to the downstream neighbor. No XLA collective exists in the
  program at all. Compiled-TPU only (inter-chip DMA has no interpret
  lowering on this jax); numerics are gated by the AOT Mosaic compile
  (`tools/aot_check.py`) and UNVERIFIED on silicon until the next
  hardware window — opt-in, never the default.
- **`all_gather_flash_attention`** — ring/context attention where the
  partial-result MERGE rides the flash kernel's final-key-block epilogue
  instead of a per-step XLA read-modify-write of the (B, H, S, D) output
  (`_agf_kernel`: the standard flash forward extended with carried
  (out, lse) operands). The K/V gather hops keep PR 4's double-buffered
  schedule (probe-pinned); the backward reuses
  `parallel.ring_attention`'s inverted-permutation ring. Bitwise equal
  to `ring_attention` on the CPU mesh by construction (same attend math,
  same merge formula, same order).
- **`fused_vocab_parallel_merge`** — the vocab-parallel `linear_xent`
  cross-shard merge with the per-shard stats PACKED into one kernel
  output by the final vocab tile (`ops.linear_xent.shard_stats_packed`)
  and the pmax/psum ladder collapsed from four collectives to two (one
  pmax + ONE packed psum). Bitwise equal to the decomposed
  `_vp_merge` path (packed psum reduces each lane independently).

**Dataflow note (why the travelling-accumulator add is NOT in the
kernel on the ppermute path):** the reduce-scatter hop at step t ships
``acc_t + pend_t`` where ``acc_t`` arrives from step t−1's hop. Any
schedule that hops a kernel-produced sum one step late pairs a stale
accumulator with a fresh partial and sums the wrong chunks (verified by
simulation); computing the sum inside the step's dot kernel would make
the hop wait on the whole kernel. The add therefore stays a carry-only
XLA add at the body top — PR 4's form, whose overlap hlo_probe pins —
and the add-in-epilogue design is exactly what the RDMA kernel is for
(inside one kernel the grid sequencing, not the XLA scheduler, provides
the overlap).

Every executable form here keeps a bitwise-parity pin against its
decomposed PR 4 counterpart on the CPU mesh (interpret AND
XLA-composite paths, `tests/test_fused_collective.py`), a dependence-
mode `testing.hlo_probe` pin in tier-1, and an async-mode probe +
Mosaic-lowering gate in `tools/aot_check.py`. No wall-clock A/B has
run on chips (ROADMAP D4, S10).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex1_tpu.core.mesh import AXIS_TP
from apex1_tpu.ops._common import (NEG_INF, interpret_mode, kernel_call,
                                   out_struct, pad_to, to_mosaic, use_pallas)
from apex1_tpu.ops._common import vary as _vary

_LANES = 128


def _axis_size(axis_name):
    return jax.lax.axis_size(axis_name)


def _axis_index(axis_name):
    return jax.lax.axis_index(axis_name)


def _chunk(x, seq_dim, start, size):
    return jax.lax.dynamic_slice_in_dim(x, start, size, axis=seq_dim)


# ---------------------------------------------------------------------------
# chunk matmul kernel — the tile loop shared by the ppermute ring forms
# and (as its grid body) the RDMA kernel
# ---------------------------------------------------------------------------

def _cm_whole_kernel(x_ref, w_ref, o_ref):
    # ONE dot over the full operands with jnp.dot's dimension numbers:
    # in interpret mode this is literally the same dot_general the
    # decomposed loop's jnp.dot lowers to — the bitwise-parity anchor
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[...],
        (((x_ref.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _cm_tile_kernel(x_ref, w_ref, o_ref):
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _cm_blocks(Kp, block_m, block_n, dtype):
    """(block_m, block_n) for the tiled chunk matmul: explicit > tuning
    table (`fused_collective_matmul`, keyed on the padded depth Kp) >
    heuristic (256 x 512, halved while the registry VMEM model says the
    frame exceeds the generation's budget)."""
    if block_m is not None and block_n is not None:
        return block_m, block_n
    from apex1_tpu import tuning
    tuned = tuning.lookup("fused_collective_matmul", {"Kp": Kp},
                          dtype) or {}
    bm = block_m or tuned.get("block_m")
    bn = block_n or tuned.get("block_n")
    if bm is None or bn is None:
        from apex1_tpu.core.capability import vmem_budget
        from apex1_tpu.tuning.registry import SPECS
        cand_m, cand_n = bm or 256, bn or 512
        es = np.dtype(dtype).itemsize
        check = SPECS["fused_collective_matmul"].check
        while cand_m > 16:
            ok, _ = check({"block_m": cand_m, "block_n": cand_n},
                          {"Kp": Kp}, es, vmem_budget())
            if ok:
                break
            cand_m, cand_n = max(16, cand_m // 2), max(128, cand_n // 2)
        bm, bn = cand_m, cand_n
    return bm, bn


def _chunk_matmul(rows, w, block_m=None, block_n=None):
    """``rows @ w`` (fp32 accumulate/result) as a Pallas kernel.

    ``rows`` (..., K), ``w`` (K, N). With unresolved blocks in interpret
    mode the kernel is ONE whole-operand tile whose dot_general is
    bit-identical to ``jnp.dot(rows, w, preferred_element_type=f32)`` —
    the anchor for the fused-vs-decomposed bitwise pins. The compiled
    path (and interpret with explicit blocks, for grid-logic tests)
    tiles (M, N) with K untiled, so each output tile is one MXU dot and
    no cross-grid accumulation is needed.
    """
    if interpret_mode() and block_m is None and block_n is None:
        out_shape = rows.shape[:-1] + (w.shape[-1],)
        return kernel_call(
            _cm_whole_kernel,
            name="chunk_matmul_whole",
            out_shape=out_struct(out_shape, jnp.float32, rows, w),
            interpret=True,
        )(rows, w)
    rows, w = to_mosaic(rows, w)
    lead = rows.shape[:-1]
    K = rows.shape[-1]
    N = w.shape[-1]
    x2 = rows.reshape(-1, K)
    Kp = max(_LANES, ((K + _LANES - 1) // _LANES) * _LANES)
    bm, bn = _cm_blocks(Kp, block_m, block_n, rows.dtype)
    bm = min(bm, max(16, ((x2.shape[0] + 15) // 16) * 16))
    bn = min(bn, max(_LANES, ((N + _LANES - 1) // _LANES) * _LANES))
    xp, _ = pad_to(x2, 0, bm)
    xp, _ = pad_to(xp, 1, _LANES)
    wp, _ = pad_to(w, 0, _LANES)
    wp, _ = pad_to(wp, 1, bn)
    n_m, n_n = xp.shape[0] // bm, wp.shape[1] // bn
    out = kernel_call(
        _cm_tile_kernel,
        name="chunk_matmul",
        grid=(n_m, n_n),
        in_specs=[pl.BlockSpec((bm, xp.shape[1]), lambda i, j: (i, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((wp.shape[0], bn), lambda i, j: (0, j),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=out_struct((xp.shape[0], wp.shape[1]), jnp.float32,
                             xp, wp),
        interpret=interpret_mode(),
    )(xp, wp)
    return out[:x2.shape[0], :N].reshape(lead + (N,))


def _part_dot(rows, w, block_m, block_n):
    """One chunk partial product: the Pallas chunk kernel on the Pallas
    path, the decomposed loop's own jnp.dot on the XLA path — both fp32."""
    if use_pallas():
        return _chunk_matmul(rows, w, block_m, block_n)
    return jnp.dot(rows, w, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# fused matmul -> reduce-scatter (ppermute ring form)
# ---------------------------------------------------------------------------

def _fused_mrs_loop(x, w, axis_name, seq_dim, block_m, block_n):
    """PR 4's `mappings._mrs_loop` dataflow with the per-chunk dot in the
    Pallas chunk kernel: hop ships ``acc + pend`` (both carries, add at
    body top — see the module dataflow note), the kernel's dot lands in
    the carry untouched, n hops total (one zero-valued seed hop). Chunk
    summation order is identical to the decomposed form, so the result
    is bitwise the same wherever the kernel's dot is (interpret mode /
    the XLA path)."""
    n = _axis_size(axis_name)
    S = x.shape[seq_dim]
    if S % n:
        raise ValueError(f"seq dim {seq_dim} size {S} not divisible by "
                         f"ring size {n}")
    chunk = S // n

    def part(c):
        return _part_dot(_chunk(x, seq_dim, c * chunk, chunk), w,
                         block_m, block_n)

    if n == 1:
        return part(0)
    idx = _axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    shape = list(x.shape)
    shape[seq_dim] = chunk
    shape[-1] = w.shape[-1]
    acc = _vary(jnp.zeros(tuple(shape), jnp.float32), axis_name)
    pend = _vary(jnp.zeros(tuple(shape), jnp.float32), axis_name)

    def step(carry, t):
        acc, pend = carry
        acc = jax.lax.ppermute(acc + pend, axis_name, perm)
        pend = part((idx - 1 - t) % n)
        return (acc, pend), None

    (acc, pend), _ = jax.lax.scan(step, (acc, pend), jnp.arange(0, n))
    return acc + pend


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def fused_matmul_reduce_scatter(x, w, axis_name=AXIS_TP, seq_dim=0,
                                block_m=None, block_n=None):
    """``psum_scatter(x @ w, seq_dim)`` with the reduce-scatter
    decomposed into the PR 4 travelling-accumulator ppermute ring and
    the per-chunk dot fused into a Pallas kernel (`_chunk_matmul`).

    Bitwise equal to `mappings.matmul_reduce_scatter` on the CPU mesh
    (both dispatch paths); the custom VJP routes dx through
    `fused_all_gather_matmul` (the all-gather dual). Returns this rank's
    sequence chunk in fp32, like the decomposed form. For the
    single-kernel RDMA form see `matmul_reduce_scatter_rdma`.
    """
    return _fused_mrs_loop(x, w, axis_name, seq_dim, block_m, block_n)


def _fused_mrs_fwd(x, w, axis_name, seq_dim, block_m, block_n):
    return _fused_mrs_loop(x, w, axis_name, seq_dim, block_m,
                           block_n), (x, w)


def _fused_mrs_bwd(axis_name, seq_dim, block_m, block_n, res, g):
    x, w = res
    # dx through the all-gather dual (overlapped, fused); dw contracts
    # the re-gathered cotangent — the same shape as the decomposed VJP
    dx = fused_all_gather_matmul(g, jnp.swapaxes(w, 0, 1), axis_name,
                                 seq_dim, block_m, block_n)
    gg = jax.lax.all_gather(g, axis_name, axis=seq_dim, tiled=True)
    dw = jnp.matmul(x.reshape(-1, x.shape[-1]).T,
                    gg.reshape(-1, gg.shape[-1]),
                    preferred_element_type=jnp.float32)
    return dx.astype(x.dtype), dw.astype(w.dtype)


fused_matmul_reduce_scatter.defvjp(_fused_mrs_fwd, _fused_mrs_bwd)


# ---------------------------------------------------------------------------
# fused all-gather -> matmul (ppermute ring form) + its serialized
# negative control
# ---------------------------------------------------------------------------

def _fused_agm_loop(x, w, axis_name, seq_dim, block_m, block_n,
                    serialize=False):
    """PR 4's `mappings._agm_loop` with the per-chunk dot in the Pallas
    chunk kernel; prologue + n−2 in-loop hops, each issued before the
    dot that overlaps it. ``serialize=True`` is the rotate-THEN-dot
    schedule (the dot consumes this step's permute) — the falsifiable
    negative control for the overlap probes and the A/B baseline."""
    n = _axis_size(axis_name)
    chunk = x.shape[seq_dim]

    def dot(c):
        return _part_dot(c, w, block_m, block_n)

    if n == 1:
        return dot(x)
    idx = _axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    out_shape = list(x.shape)
    out_shape[seq_dim] = chunk * n
    out_shape[-1] = w.shape[-1]
    y = _vary(jnp.zeros(tuple(out_shape), jnp.float32), axis_name)

    def place(y, part, src):
        return jax.lax.dynamic_update_slice_in_dim(
            y, part, src * chunk, axis=seq_dim)

    if serialize:
        y = place(y, dot(x), idx)

        def sstep(carry, t):
            cur, y = carry
            cur = jax.lax.ppermute(cur, axis_name, perm)
            y = place(y, dot(cur), (idx - t) % n)
            return (cur, y), None

        (_, y), _ = jax.lax.scan(sstep, (x, y), jnp.arange(1, n))
        return y

    cur = jax.lax.ppermute(x, axis_name, perm)
    y = place(y, dot(x), idx)

    def step(carry, t):
        cur, y = carry
        nxt = jax.lax.ppermute(cur, axis_name, perm)
        y = place(y, dot(cur), (idx - t) % n)
        return (nxt, y), None

    if n > 2:
        (cur, y), _ = jax.lax.scan(step, (cur, y), jnp.arange(1, n - 1))
    return place(y, dot(cur), (idx - (n - 1)) % n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def fused_all_gather_matmul(x, w, axis_name=AXIS_TP, seq_dim=0,
                            block_m=None, block_n=None):
    """``all_gather(x, seq_dim) @ w`` over the PR 4 chunk-pipelined
    ppermute ring with the per-chunk dot fused into a Pallas kernel.
    Bitwise equal to `mappings.all_gather_matmul` on the CPU mesh; the
    custom VJP routes dx through `fused_matmul_reduce_scatter` (its
    reduce-scatter dual). fp32 result."""
    return _fused_agm_loop(x, w, axis_name, seq_dim, block_m, block_n)


def _fused_agm_fwd(x, w, axis_name, seq_dim, block_m, block_n):
    return _fused_agm_loop(x, w, axis_name, seq_dim, block_m,
                           block_n), (x, w)


def _fused_agm_bwd(axis_name, seq_dim, block_m, block_n, res, g):
    x, w = res
    dx = fused_matmul_reduce_scatter(g, jnp.swapaxes(w, 0, 1), axis_name,
                                     seq_dim, block_m, block_n)
    gx = jax.lax.all_gather(x, axis_name, axis=seq_dim, tiled=True)
    dw = jnp.matmul(gx.reshape(-1, gx.shape[-1]).T,
                    g.reshape(-1, g.shape[-1]),
                    preferred_element_type=jnp.float32)
    return dx.astype(x.dtype), dw.astype(w.dtype)


fused_all_gather_matmul.defvjp(_fused_agm_fwd, _fused_agm_bwd)


def fused_all_gather_matmul_serial(x, w, axis_name=AXIS_TP, seq_dim=0,
                                   block_m=None, block_n=None):
    """Serialized rotate-then-dot all-gather matmul: every chunk dot
    consumes the permute issued in the same step, so ALL n−1 transfers
    are exposed. Retained as the falsifiable negative control for the
    overlap probes (dependence mode in tier-1, async mode in the AOT
    gate). Numerics match the overlapped form (same dots, same
    placement order)."""
    return _fused_agm_loop(x, w, axis_name, seq_dim, block_m, block_n,
                           serialize=True)


# ---------------------------------------------------------------------------
# all-gather-fused flash attention: the ring merge rides the kernel's
# final-key-block epilogue
# ---------------------------------------------------------------------------

def _agf_kernel(q_ref, k_ref, v_ref, qo_ref, ko_ref, *rest,
                scale, causal, true_sq, true_sk, has_segs, n_k):
    """The flash forward's tile update (`ops.attention._attend_tile`, the
    SAME function `_fwd_kernel` runs; no bias/dropout operands) on the
    grid (b, h, qi, ki), extended with carried (prev_outᵀ fp32, prev_lse)
    inputs: the final key block's epilogue performs
    `parallel.ring_attention._merge` in VMEM instead of a per-ring-step
    XLA read-modify-write of the full (B, H, S, D) output in HBM. One
    tile function and the merge formula op for op — the parity contract
    of the fused form (a tile the plain kernel runs without a mask, or
    with the scale folded into q, holds the same values: the mask is all
    true there and the scale a power of two). Like the plain kernel it
    works on the TRANSPOSED tile: the accumulator, the carried output
    and the merged one are outᵀ (Dp, bq), the statistics (1, bq) rows."""
    from apex1_tpu.ops.attention import _attend_tile, _mask_for
    rest = list(rest)
    qseg_ref, kseg_ref = (rest.pop(0), rest.pop(0)) if has_segs \
        else (None, None)
    po_ref, pl_ref, o_ref, lse_ref, acc, m_scr, l_scr = rest
    qi, ki = pl.program_id(2), pl.program_id(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(ki == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    def compute():
        mask = _mask_for(qi, ki, bq, bk, causal=causal, true_sq=true_sq,
                         true_sk=true_sk, q_off=qo_ref[0, 0],
                         k_off=ko_ref[0, 0],
                         qseg=qseg_ref[0, 0] if has_segs else None,
                         kseg=kseg_ref[0] if has_segs else None,
                         transposed=True)
        _attend_tile(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], acc, m_scr,
                     l_scr, scale=scale, mask=mask)

    if causal:
        pl.when((ki * bk + ko_ref[0, 0])
                <= (qi * bq + bq - 1 + qo_ref[0, 0]))(compute)
    else:
        compute()

    @pl.when(ki == n_k - 1)
    def _():
        # this shard's (out_t, lse_t) exactly as the plain flash kernel
        # emits them (incl. the q.dtype round-trip the decomposed ring's
        # flash output makes), then `_merge` op for op
        l = l_scr[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_t = (acc[...] / safe).astype(q_ref.dtype)
        lse_t = jnp.where(l > 0.0, m_scr[...] + jnp.log(safe), NEG_INF)
        prev_lse = pl_ref[0, 0, 0]
        lse_new = jnp.logaddexp(prev_lse, lse_t)
        w_a = jnp.exp(prev_lse - lse_new)
        w_b = jnp.exp(lse_t - lse_new)
        o_ref[0, 0] = po_ref[0, 0] * w_a + o_t.astype(jnp.float32) * w_b
        lse_ref[0, 0, 0] = lse_new


def _agf_blocks(D, block_q, block_k, dtype, seq):
    """explicit > tuning table (`fused_ag_flash`) > the flash-attention
    resolution chain (its table, then the analytic heuristic)."""
    from apex1_tpu import tuning
    from apex1_tpu.ops.attention import _auto_blocks
    Dp = max(_LANES, ((D + _LANES - 1) // _LANES) * _LANES)
    if block_q is None or block_k is None:
        tuned = tuning.lookup("fused_ag_flash",
                              {"Dp": Dp, "Sb": tuning.seq_bucket(seq)},
                              dtype) or {}
        block_q = block_q or tuned.get("block_q")
        block_k = block_k or tuned.get("block_k")
    return _auto_blocks(D, block_q, block_k, dtype, seq)


def _agf_call(q, k, v, qseg, kseg, q_off, k_off, prev_out, prev_lse,
              scale, causal, has_segs, block_q, block_k):
    """One ring step: attend the visiting K/V shard AND fold the result
    into the carried (outᵀ, lse) — one pallas_call. ``prev_out`` and the
    returned output are TRANSPOSED, (B, Hq, D, Sq), as the kernel's
    accumulator lies."""
    from apex1_tpu.ops.attention import (_common_specs, _off_arrays,
                                         _prep, _stat_rows)
    q, k, v = to_mosaic(q, k, v)
    qp, kp, vp, qs, ks, g = _prep(q, k, v, qseg, kseg, has_segs,
                                  block_q, block_k)
    q_spec, kv_spec, stat_spec, off_spec, qseg_spec, kseg_spec = \
        _common_specs(g, transposed=True)
    po, _ = pad_to(prev_out, 2, _LANES)
    po, _ = pad_to(po, 3, g["bq"])
    plse = _stat_rows(prev_lse, g, NEG_INF)
    pout_spec = pl.BlockSpec((1, 1, g["Dp"], g["bq"]),
                             lambda b, h, qi, ki: (b, h, 0, qi),
                             memory_space=pltpu.VMEM)
    in_specs = [q_spec, kv_spec, kv_spec, off_spec, off_spec]
    args = [qp, kp, vp, *_off_arrays(q_off, k_off)]
    if has_segs:
        in_specs += [qseg_spec, kseg_spec]
        args += [qs[1], ks[0]]
    in_specs += [pout_spec, stat_spec]
    args += [po, plse]
    Sqp = g["n_q"] * g["bq"]
    out_p, lse_p = kernel_call(
        functools.partial(_agf_kernel, scale=scale, causal=causal,
                          true_sq=g["Sq"], true_sk=g["Sk"],
                          has_segs=has_segs, n_k=g["n_k"]),
        name="ring_flash_fold",
        grid=(g["B"], g["Hq"], g["n_q"], g["n_k"]),
        in_specs=in_specs,
        out_specs=(pout_spec, stat_spec),
        out_shape=(
            out_struct((g["B"], g["Hq"], g["Dp"], Sqp), jnp.float32,
                       qp, kp, vp, po, plse),
            out_struct((g["B"], g["Hq"], g["n_q"], 1, g["bq"]),
                       jnp.float32, qp, kp, vp, po, plse)),
        scratch_shapes=[
            pltpu.VMEM((g["Dp"], g["bq"]), jnp.float32),
            pltpu.VMEM((1, g["bq"]), jnp.float32),
            pltpu.VMEM((1, g["bq"]), jnp.float32)],
        interpret=interpret_mode(),
    )(*args)
    return (out_p[:, :, :g["D"], :g["Sq"]],
            lse_p.reshape(g["B"], g["Hq"], Sqp)[:, :, :g["Sq"]])


def _agf_fwd_loop(q, k, v, qseg, axis_name, causal, sm_scale, has_segs,
                  block_q, block_k):
    """Double-buffered K/V gather ring (PR 4's hop-before-attend
    schedule, hlo_probe-pinned) with the per-step merge fused into the
    flash kernel epilogue. Off the Pallas path this IS the decomposed
    ring (`parallel.ring_attention._ring_fwd_loop`) — bitwise by
    construction. Returns (out fp32, lse)."""
    from apex1_tpu.parallel.ring_attention import (_merge,
                                                   _ring_fwd_loop)
    if not use_pallas():
        return _ring_fwd_loop(q, k, v, qseg, axis_name, causal, sm_scale,
                              has_segs, block_q, block_k)
    n = _axis_size(axis_name)
    B, Hq, Sq, D = q.shape
    Sk = k.shape[2]
    scale = (1.0 / float(np.sqrt(D)) if sm_scale is None
             else float(sm_scale))
    block_q, block_k = _agf_blocks(D, block_q, block_k, q.dtype, Sk)
    if causal:
        idx = _axis_index(axis_name)
        q_off = idx * Sq
    perm = [(i, (i + 1) % n) for i in range(n)]
    # the carry is outᵀ (B, Hq, D, Sq), as the kernel's accumulator lies;
    # turned once, after the last shard
    out = _vary(jnp.zeros((B, Hq, D, Sq),
                          jnp.promote_types(q.dtype, jnp.float32)),
                axis_name)
    lse = _vary(jnp.full((B, Hq, Sq), NEG_INF, jnp.float32), axis_name)

    def attend(k_cur, v_cur, kseg_cur, t, out, lse):
        if causal:
            src = (idx - t) % n
            qo, ko = q_off, src * Sk
        else:
            qo = ko = 0

        def run(_):
            return _agf_call(q, k_cur, v_cur, qseg,
                             kseg_cur if has_segs else None, qo, ko,
                             out, lse, scale, causal, has_segs,
                             block_q, block_k)

        def skip(_):
            # the decomposed ring merges a (zeros, NEG_INF) partial for
            # fully-masked shards; replicate that exact merge (identity
            # up to fp edge cases like -0 + 0) instead of passing the
            # carry through, so the pin stays bitwise (`_merge` weights a
            # trailing head axis; outᵀ's queries are the last one)
            o, l = _merge(jnp.swapaxes(out, 2, 3), lse,
                          _vary(jnp.zeros(q.shape, q.dtype), axis_name),
                          _vary(jnp.full((B, Hq, Sq), NEG_INF,
                                         jnp.float32), axis_name))
            return jnp.swapaxes(o, 2, 3), l

        if causal:
            return jax.lax.cond(ko > qo + Sq - 1, skip, run, None)
        return run(None)

    def turned(out_lse):
        return jnp.swapaxes(out_lse[0], 2, 3), out_lse[1]

    kseg0 = qseg if has_segs else jnp.zeros((), jnp.int32)
    if n == 1:
        return turned(attend(k, v, kseg0, 0, out, lse))

    k_cur = jax.lax.ppermute(k, axis_name, perm)
    v_cur = jax.lax.ppermute(v, axis_name, perm)
    kseg_cur = (jax.lax.ppermute(kseg0, axis_name, perm) if has_segs
                else kseg0)
    out, lse = attend(k, v, kseg0, 0, out, lse)

    def step(carry, t):
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
    return turned(attend(k_cur, v_cur, kseg_cur, n - 1, out, lse))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _agf(q, k, v, qseg, axis_name, causal, sm_scale, has_segs, block_q,
         block_k):
    out, _ = _agf_fwd_loop(q, k, v, qseg, axis_name, causal, sm_scale,
                           has_segs, block_q, block_k)
    return out.astype(q.dtype)


def _agf_fwd_rule(q, k, v, qseg, axis_name, causal, sm_scale, has_segs,
                  block_q, block_k):
    out, lse = _agf_fwd_loop(q, k, v, qseg, axis_name, causal, sm_scale,
                             has_segs, block_q, block_k)
    out = out.astype(q.dtype)
    return out, (q, k, v, qseg, out, lse)


def _agf_bwd_rule(axis_name, causal, sm_scale, has_segs, block_q,
                  block_k, res, do):
    # the inverted-permutation double-buffered ring backward of PR 4,
    # unchanged: the fused forward saves the same (out, lse) residuals
    from apex1_tpu.parallel.ring_attention import _ring_bwd_loop
    q, k, v, qseg, out, lse = res
    dq, dk, dv = _ring_bwd_loop(q, k, v, qseg, out, lse, do, axis_name,
                                causal, sm_scale, has_segs, block_q,
                                block_k)
    f0 = np.zeros(jnp.shape(qseg), dtype=jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            f0)


_agf.defvjp(_agf_fwd_rule, _agf_bwd_rule)


def all_gather_flash_attention(q, k, v, axis_name, *,
                               causal: bool = False,
                               sm_scale: float | None = None,
                               segment_ids=None,
                               block_q: int | None = None,
                               block_k: int | None = None):
    """Ring/context flash attention with the K/V all-gather riding the
    kernel schedule: each ring step's shard hop is issued before the
    attend (PR 4's double-buffered schedule, hlo_probe-pinned) and the
    partial-result merge runs in the flash kernel's final-key-block
    epilogue instead of a per-step XLA read-modify-write of the full
    (B, H, S, D) output in HBM — at the 16k GQA shape that epilogue
    fusion removes n−1 full passes over the output per layer.

    Semantics (and, on the CPU mesh, bits) match
    `parallel.ring_attention`: ``q``/``k``/``v`` are local sequence
    shards over ``axis_name``; returns the local output shard. The
    backward is the same inverted-permutation ring as PR 4's custom
    VJP. Attention-probability dropout is NOT supported on this entry —
    use `parallel.ring_attention` for dropout-bearing training paths.
    """
    sm_scale = None if sm_scale is None else float(sm_scale)
    has_segs = segment_ids is not None
    qseg = (segment_ids if has_segs else jnp.zeros((1, 1), jnp.int32))
    return _agf(q, k, v, qseg, axis_name, causal, sm_scale, has_segs,
                block_q, block_k)


# ---------------------------------------------------------------------------
# vocab-parallel linear_xent merge: packed stats, two collectives
# ---------------------------------------------------------------------------

def fused_vocab_parallel_merge(stats, axis_name=AXIS_TP):
    """Cross-shard merge of PACKED per-shard online-softmax stats
    (``ops.linear_xent.shard_stats_packed``'s (T, 4) ``[m, l, tgt,
    sumx]``, emitted by the kernel's final vocab tile in one output
    stream instead of four): ONE pmax for the global max, then ONE psum
    of the (T, 3) pack ``[l·exp(m − gmax), tgt, sumx]`` — two
    collectives emitted where the decomposed `_vp_merge` ladder emits
    four (XLA's all-reduce combiner then merges the ladder's three
    psums into one variadic all-reduce: both forms COMPILE to two, on
    the CPU and for a described v5e — ROADMAP D4). Bitwise equal to
    the decomposed merge: an all-reduce sums each lane independently,
    so packing changes neither the reduction order nor a single bit
    (pinned by test_fused_collective). Returns (lse, tgt, sumx)."""
    m = stats[:, 0]
    gmax = jax.lax.pmax(m, axis_name)
    packed = jnp.stack([stats[:, 1] * jnp.exp(m - gmax),
                        stats[:, 2], stats[:, 3]], axis=-1)
    red = jax.lax.psum(packed, axis_name)
    return gmax + jnp.log(red[:, 0]), red[:, 1], red[:, 2]


# ---------------------------------------------------------------------------
# the paper-shape form: matmul -> reduce-scatter in ONE kernel, the
# epilogue shipping chunk t over ICI while the grid computes chunk t+1
# ---------------------------------------------------------------------------

_RDMA_COLLECTIVE_ID = 7  # arbitrary but stable; one fused collective
                         # kernel shape runs at a time in our programs


def _mrs_rdma_kernel(cs_ref, x_ref, w_ref, o_ref, acc_buf, send_buf,
                     send_sem, recv_sem, cap_sem, *, n, axis_name):
    """Reduce-scatter-in-the-matmul-epilogue (arxiv 2305.06942): grid
    step t computes this device's partial for chunk ``cs[t]`` on the
    MXU, folds in the travelling fp32 accumulator that arrived from the
    upstream neighbor during step t−1, and ships the sum downstream
    with `make_async_remote_copy` — the RDMA flies while grid step t+1's
    dot runs. Double-buffered recv/send slots with a credit semaphore
    (the downstream consumer returns a credit as it drains a slot) keep
    a fast producer from overwriting an unconsumed slot. n−1 transfers,
    none of them visible to XLA — the overlap is the grid's sequencing,
    not the scheduler's.

    Numerics are the ppermute form's by construction (same per-chunk
    partial order: upstream partials in ring order, own partial last),
    but this kernel cannot execute off-TPU (inter-chip DMA has no
    interpret lowering on this jax) — it is Mosaic-compile-gated by
    tools/aot_check.py and UNVERIFIED on silicon until the next
    hardware window. Keep it opt-in.
    """
    t = pl.program_id(0)
    my = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(my + 1, n)
    left = jax.lax.rem(my + n - 1, n)

    def dev(i):
        # MESH device id: full coordinate tuple over the canonical mesh
        # axes, the ring axis replaced by the neighbor index (all six
        # axes are bound inside shard_map over a make_mesh mesh)
        from apex1_tpu.core.mesh import MESH_AXES
        return tuple(i if a == axis_name else jax.lax.axis_index(a)
                     for a in MESH_AXES)

    @pl.when(t == 0)
    def _():
        # both neighbors' kernels must be live before any RDMA targets
        # their buffers
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id=dev(left))
        pltpu.semaphore_signal(barrier, inc=1, device_id=dev(right))
        pltpu.semaphore_wait(barrier, 2)

    # MXU work for chunk cs[t] (the x block spec already routed the
    # right rows here via the scalar-prefetch schedule)
    partial = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    slot = jax.lax.rem(t, 2)

    def send_desc(s):
        return pltpu.make_async_remote_copy(
            send_buf.at[s], acc_buf.at[s],
            send_sem.at[s], recv_sem.at[s],
            device_id=dev(right))

    @pl.when(t == 0)
    def _():
        send_buf[0] = partial

    @pl.when(t > 0)
    def _():
        # wait the accumulator the upstream neighbor shipped during
        # step t-1 and fold it into this chunk's partial (the fused
        # "epilogue add" the ppermute form cannot express)
        prev = jax.lax.rem(t + 1, 2)   # (t-1) % 2
        pltpu.make_async_remote_copy(
            send_buf.at[prev], acc_buf.at[prev],
            send_sem.at[prev], recv_sem.at[prev],
            device_id=dev(right)).wait_recv()

        ship = acc_buf[prev] + partial

        # return the drained slot's credit to upstream AFTER the
        # acc_buf[prev] read above (signalling first would let an
        # eager upstream DMA overwrite the slot mid-read), and ONLY if
        # upstream will reuse it (its steps 2..n-2) — t <= n-3 — so
        # every credit signal pairs with exactly one wait and the
        # semaphore is zero at kernel exit
        @pl.when(t < n - 2)
        def _():
            pltpu.semaphore_signal(cap_sem, inc=1, device_id=dev(left))

        @pl.when(t < n - 1)
        def _():
            # slot reuse (t >= 2): BEFORE overwriting send_buf[slot],
            # (a) the local t-2 DMA must have finished READING it
            # (send_sem), and (b) the downstream consumer must have
            # drained its previous payload (credit) — both waits must
            # precede the write, or a lagging neighbor reads a
            # half-overwritten slot
            @pl.when(t >= 2)
            def _():
                send_desc(slot).wait_send()
                pltpu.semaphore_wait(cap_sem, 1)
            send_buf[slot] = ship

        @pl.when(t == n - 1)
        def _():
            o_ref[...] = ship

    @pl.when(t < n - 1)
    def _():
        send_desc(slot).start()

    @pl.when(t == n - 1)
    def _():
        # drain: of the n-1 sends, the reuse waits above consumed n-3
        # send_sems (steps 2..n-2); the LAST TWO (steps n-3 and n-2 for
        # n > 2, step 0 alone for n == 2) are consumed here so every
        # DMA semaphore is zero at kernel exit
        send_desc(jax.lax.rem(t + 1, 2)).wait_send()

        @pl.when(n > 2)
        def _():
            send_desc(slot).wait_send()


def matmul_reduce_scatter_rdma(x, w, axis_name=AXIS_TP):
    """``psum_scatter(x @ w, 0)`` as ONE Pallas kernel with in-kernel
    ICI RDMA (see `_mrs_rdma_kernel`). ``x`` (S, K) 2-D with S/n a
    multiple of 16 and K, N multiples of 128 (pad at the call site —
    this entry is deliberately strict: it exists for the AOT gate, the
    A/B tool and the hardware window, not as a general dispatch
    target). Compiled-TPU only; raises off-TPU. Forward-only (no VJP):
    training paths use `fused_matmul_reduce_scatter`.

    VMEM sizing rule (established by the aot_check gate, enforced here
    and machine-checked by graftlint APX208): the kernel holds four
    fp32 chunk slots (2 recv + 2 send double buffers) beside the
    double-buffered x/w/out blocks — ``apex1_tpu.vmem_model.
    rdma_check`` is the ONE formula (shared with ``tuning.registry``'s
    gating and ``tools/aot_check.py``); chunk=512 x N=1024 measured
    RESOURCE_EXHAUSTED on v5e, 256 x 512 fits with margin. An
    over-budget shape raises here instead of dying in Mosaic with
    RESOURCE_EXHAUSTED mid-hardware-window.
    """
    if interpret_mode():
        raise NotImplementedError(
            "matmul_reduce_scatter_rdma is compiled-TPU only: "
            "inter-chip RDMA has no interpret lowering on this jax — "
            "use fused_matmul_reduce_scatter (the ppermute ring form) "
            "everywhere else")
    if x.ndim != 2:
        raise ValueError(f"x must be (S, K), got {x.shape}")
    n = _axis_size(axis_name)
    if n < 2:
        # the grid writes o_ref only at t > 0 and the drain waits a
        # send that never starts — on one device that is an in-kernel
        # HANG, not a wrong answer; fail loudly instead (the ppermute
        # forms handle n == 1 with a plain chunk dot)
        raise ValueError("matmul_reduce_scatter_rdma needs a ring of "
                         ">= 2 devices; use fused_matmul_reduce_scatter "
                         "for the single-device case")
    S, K = x.shape
    N = w.shape[-1]
    if S % n:
        raise ValueError(f"S={S} not divisible by ring size {n}")
    chunk = S // n
    if chunk % 16 or K % _LANES or N % _LANES:
        raise ValueError(
            f"rdma form needs chunk % 16 == 0 and K, N % 128 == 0; got "
            f"chunk={chunk}, K={K}, N={N} (pad at the call site)")
    x, w = to_mosaic(x, w)
    from apex1_tpu.vmem_model import budget_bytes, rdma_check
    fits, est = rdma_check(chunk, K, N, x.dtype.itemsize,
                           budget_bytes())
    if not fits:
        raise ValueError(
            f"rdma kernel frame ~{est / 2**20:.1f} MiB (4 fp32 chunk "
            f"slots + double-buffered x/w/out blocks, vmem_model."
            f"rdma_check) exceeds the VMEM planning budget "
            f"{budget_bytes() / 2**20:.1f} MiB — shrink chunk*N "
            f"(chunk=512 x N=1024 measured RESOURCE_EXHAUSTED on v5e)")
    idx = _axis_index(axis_name)
    # chunk visiting schedule, ring order: own chunk LAST (same
    # summation order as the ppermute form / a monolithic ring
    # reduce-scatter)
    cs = jnp.mod(idx - 1 - jnp.arange(n, dtype=jnp.int32), n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((chunk, K), lambda t, cs: (cs[t], 0)),
            pl.BlockSpec((K, N), lambda t, cs: (0, 0)),
        ],
        out_specs=pl.BlockSpec((chunk, N), lambda t, cs: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, N), jnp.float32),   # recv slots
            pltpu.VMEM((2, chunk, N), jnp.float32),   # send slots
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ])
    out = kernel_call(
        functools.partial(_mrs_rdma_kernel, n=n, axis_name=axis_name),
        name="matmul_reduce_scatter_rdma",
        grid_spec=grid_spec,
        out_shape=out_struct((chunk, N), jnp.float32, x, w),
        compiler_params=pltpu.CompilerParams(
            collective_id=_RDMA_COLLECTIVE_ID),
    )(cs, x, w)
    return out
