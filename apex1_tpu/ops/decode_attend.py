"""The serving step's attention as ONE kernel over the dense KV pool.

`serving.Engine`'s decode and verify steps run one batch forward with a
per-row cache index: lane ``b`` appends ``S`` new K/V rows at positions
``idx[b] .. idx[b] + S - 1`` and query ``j`` attends positions ``<=
idx[b] + j``. As XLA ops that is a select that rewrites every pool leaf
whole and a masked product that reads every position of every lane
(PERF.md, PR 26). :func:`decode_attend` does both in one Pallas call a
layer, on the pool where it lies:

- the two pool leaves are aliased input/outputs that stay in HBM
  (``memory_space=ANY``); the kernel moves blocks of ``DECODE_BLOCK``
  positions by its own double-buffered DMA, ``cdiv(idx + S, block)`` of
  them a lane, so a position past a lane's horizon is never read and a
  lane with ``idx < 0`` (an idle slot) costs one empty grid step;
- the new rows are selected into the block(s) that hold their positions
  once those are in VMEM, and only the aligned window of rows around
  them is written back (``W`` rows of the leaf, not the leaf);
- a leaf is stored ``(B, L, Hkv * D)``: one position's K (or V) for all
  heads is one contiguous row, a multiple of 128 lanes wide, so nothing
  is padded. Per-head scores come from one MXU product with the query
  laid out block-diagonal (``Hq * S`` rows by ``Hkv * D`` lanes, zero
  outside a row's own head); the output is masked to each row's head
  and summed over rows by a second, 0/1, product.

A WINDOW (``window=W``, a sliding-attention layer): query ``j`` attends
positions ``idx + j - W < p <= idx + j`` alone, and the leaf is a RING:
position ``p`` lies in row ``p mod L``, so a leaf of ``L >= W + S - 1``
rows (whole blocks) serves a lane of any depth. The kernel walks the
blocks of POSITIONS from the one that holds the window's first to the
horizon's, fetches block ``a`` from ring block ``a mod (L / block)``, and
never a block wholly below the window; the append and its aligned
write-back wrap with the block they lie in. A row whose position is above
the window's first has not been overwritten yet (``L >= W + S - 1``), and
every other row is masked, so the ring block that holds both ends of the
window is simply read twice. A lane shallower than the window reads from
block 0, as without one. Without ``window`` nothing of this is traced.

Operands enter the MXU in the pool's dtype (bfloat16 as configured)
with float32 accumulation; the softmax statistics are float32 and the
probabilities are rounded to the value dtype before P.V, as the
composite `ops.paged_decode.cache_attend` (the off-TPU path and the
parity gold) does.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex1_tpu.ops._common import (NEG_INF, interpret_mode, kernel_call,
                                   out_struct, pad_to, to_mosaic)

#: positions one DMA moves; the engine rounds its pool's length up to a
#: whole number of these (PERF.md §6, PR 29 has the chip readings)
DECODE_BLOCK = 128
#: query rows (``Hq * S``) the kernel takes; more go to the composite
MAX_ROWS = 256
_LANES = 128
#: K and V bytes the kernel keeps in flight, one constant whatever the
#: model: with one pair of 128 KB copies in flight (the two-buffer
#: schedule) rows of 512 lanes stand at 47 % of the HBM stream; the time a
#: block is flat from 0.75 MiB (three fetches) at rows of 512 lanes and
#: from 1.5 MiB at rows of 1024, where a block's own products set the pace
#: or the stream is full (docs/ops.md has the chip's sweep, PR 50)
FETCH_BYTES = 2 * 1024 * 1024


def fetch_depth(lanes: int, dtype, length: Optional[int] = None) -> int:
    """Fetches (one K and one V block each) the kernel keeps in flight
    over a pool whose rows are ``lanes`` wide: the blocks that make up
    `FETCH_BYTES`, or a quarter of `vmem_model.budget_bytes` where that
    is less, and two at least. From the row's bytes and the budget alone
    (the engine counts `kv_fetch_ahead` with it)."""
    from apex1_tpu.vmem_model import budget_bytes
    blk = DECODE_BLOCK if length is None else min(DECODE_BLOCK, length)
    pair = 2 * blk * lanes * jnp.dtype(dtype).itemsize
    return max(2, min(FETCH_BYTES, budget_bytes() // 4) // pair)


def _sublanes(dtype) -> int:
    """Rows of one VMEM tile of ``dtype`` (8 of 32 bits, packed below)."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def check_decode_geometry(length: int, lanes: int, rows: int, s: int,
                          dtype, window: Optional[int] = None):
    """Loud validation of a `decode_attend` geometry, at trace time and
    again by ``tools/aot_check``: whole blocks, whole tiles, and a VMEM
    frame under the shared `vmem_model` budget (a row narrower than a
    multiple of 128 lanes is padded by the chip, not refused; the K and V
    buffers are `fetch_depth`'s, one pair a fetch in flight); with a
    ``window``, a ring that holds the window and the call's new rows in
    at least two blocks. Returns ``(block, write-back rows, padded
    rows)``."""
    from apex1_tpu.vmem_model import CHECKS, budget_bytes
    sub = _sublanes(dtype)
    blk = min(DECODE_BLOCK, length)
    if length % blk or blk % sub:
        raise ValueError(
            f"decode_attend needs a pool of whole {DECODE_BLOCK}-position "
            f"blocks (or one block of whole {sub}-row tiles), got length "
            f"{length}: round the pool's length up")
    win = -(-(s + sub - 1) // sub) * sub
    if win > blk:
        raise ValueError(
            f"decode_attend appends at most {blk - sub + 1} rows a lane "
            f"to a block of {blk}, got {s}")
    # a leaf no longer than the window cannot wrap (it would forget what
    # is attended): it holds every position, as without a window
    if window is not None and (window < 1 or length > window and (
            length < window + s - 1 or length < 2 * blk)):
        raise ValueError(
            f"decode_attend over a ring of {length} rows: a window of "
            f"{window} positions and {s} new rows a lane need "
            f"{max(window + s - 1, 2 * blk)} (in whole blocks): an older "
            f"row would be overwritten while it is still attended")
    rp = -(-rows // 16) * 16
    fits, est = CHECKS["decode_attend"](
        {"block_l": blk, "depth": fetch_depth(lanes, dtype, length)},
        {"HD": lanes, "Rq": rp, "W": win},
        jnp.dtype(dtype).itemsize, budget_bytes())
    if not fits:
        raise ValueError(
            f"decode_attend geometry block={blk} HD={lanes} Rq={rp} needs "
            f"~{est} B of VMEM: over budget")
    return blk, win, rp


def _decode_attend_kernel(idx_ref, q_ref, kn_ref, vn_ref, kp_in, vp_in,
                          o_ref, kp_out, vp_out, kbuf, vbuf, acc, m_scr,
                          l_scr, rsem, wsem, cur=None, *, scale, S, G, Hkv,
                          D, blk, sub, win, window=None):
    """``cur`` (SMEM, three words that outlive a grid step) is the queue's
    state: the lane and the block of the next fetch to start, and the
    buffer of the next block to consume. Without it the schedule is the
    two-buffer one that empties at every lane's edge."""
    b = pl.program_id(0)
    idx = idx_ref[b]
    B = idx_ref.shape[0]
    depth = kbuf.shape[0]
    Rp, HD = acc.shape
    Hq = G * Hkv
    n_ring = kp_in.shape[1] // blk

    def window_first(ix):
        # the first block of POSITIONS a lane at ix walks: the one that
        # holds its window's first (None: block 0 of a plain leaf)
        if window is None:
            return None
        return jnp.maximum(ix - window + 1, 0) // blk

    def blocks(ix, first):
        # how many it walks: up to the horizon (a row past a plain
        # pool's end is dropped; a ring has no end)
        if window is None:
            return jnp.minimum((ix + S - 1) // blk + 1, n_ring)
        return (ix + S - 1) // blk + 1 - first

    first = window_first(idx)
    if window is None:
        # block i of the leaf holds positions i * blk ...
        at = lambda i, first=None: i
        base = lambda i: i * blk
    else:
        # block a of the blocks of positions lies in the ring's block a
        # mod n_ring
        at = lambda i, first=first: jax.lax.rem(first + i, n_ring)
        base = lambda i: (first + i) * blk     # block i's first position

    def fetch(i, slot, lane=b, first=first):
        rows = pl.ds(pl.multiple_of(at(i, first) * blk, blk), blk)
        return (pltpu.make_async_copy(kp_in.at[lane, rows, :],
                                      kbuf.at[slot], rsem.at[0, slot]),
                pltpu.make_async_copy(vp_in.at[lane, rows, :],
                                      vbuf.at[slot], rsem.at[1, slot]))

    def next_live(c):
        # the first lane from c on that has blocks to fetch (B: none)
        return jax.lax.while_loop(
            lambda c: (c < B) & (idx_ref[jnp.minimum(c, B - 1)] < 0),
            lambda c: c + 1, c)

    def issue(c, j, slot):
        """Start the queue's next fetch, block ``j`` of lane ``c``'s walk,
        into ``slot``, and step on: to the lane's next block, or to the
        next live lane's first. Past the last live lane (``c == B``)
        nothing is started. The lane, its depth and its walk are read
        HERE, from ``c`` alone: never the consuming lane's."""
        def start():
            first = window_first(idx_ref[c])
            for d in fetch(j, slot, c, first):
                d.start()
            last = j + 1 >= blocks(idx_ref[c], first)
            return (jax.lax.cond(last, lambda: next_live(c + 1), lambda: c),
                    jnp.where(last, 0, j + 1))
        return jax.lax.cond(c < B, start, lambda: (c, j))

    if cur is not None:
        @pl.when(b == 0)
        def _():
            # the queue's first depth - 1 fetches, whoever's they are
            c, j = jax.lax.fori_loop(
                0, depth - 1, lambda k, cj: issue(*cj, k),
                (next_live(jnp.int32(0)), jnp.int32(0)))
            cur[0], cur[1], cur[2] = c, j, jnp.int32(0)

    @pl.when(idx < 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def new_rows(i):
        # the aligned rows of block i that hold this lane's new ones
        r0 = jnp.maximum(idx - base(i), 0)
        return pl.multiple_of(jnp.minimum((r0 // sub) * sub, blk - win),
                              sub)

    def append(i, slot):
        ws = new_rows(i)
        rows = pl.ds(pl.multiple_of(at(i) * blk + ws, sub), win)
        return (pltpu.make_async_copy(kbuf.at[slot, pl.ds(ws, win), :],
                                      kp_out.at[b, rows, :], wsem.at[0]),
                pltpu.make_async_copy(vbuf.at[slot, pl.ds(ws, win), :],
                                      vp_out.at[b, rows, :], wsem.at[1]))

    def patch(i, slot):
        ws = new_rows(i)
        pos = base(i) + ws + jax.lax.broadcasted_iota(
            jnp.int32, (win, HD), 0)
        for buf, new in ((kbuf, kn_ref), (vbuf, vn_ref)):
            tile = buf[slot, pl.ds(ws, win), :]
            for j in range(S):
                tile = jnp.where(pos == idx + j, new[0, j], tile)
            buf[slot, pl.ds(ws, win), :] = tile

    def attend(i, slot):
        q = q_ref[0]                                       # (Rp, HD)
        k = kbuf[slot].astype(q.dtype)                     # (blk, HD)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # rows are (s, g, kv): query s of the chunk sees <= idx + s
        keep = base(i) + col <= idx + row // Hq
        if window is not None:
            keep &= base(i) + col > idx + row // Hq - window
        s = jnp.where(keep, s, NEG_INF)
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        e = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * corr + jnp.sum(e, axis=1, keepdims=True)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            e.astype(q.dtype), vbuf[slot].astype(q.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(idx >= 0)
    def _():
        n = blocks(idx, first)
        first_new = idx // blk                 # first block with a new row
        if window is not None:
            first_new -= first
        if cur is None:
            for c in fetch(0, 0):
                c.start()
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

        def consume(i, slot):
            # the lane's own arithmetic, in its own order, whatever the
            # schedule: the block lands, takes the new rows, hands their
            # aligned window back to the pool, and is attended; its buffer
            # is free for the queue once the write-back has left it
            for c in fetch(i, slot):
                c.wait()

            @pl.when(i >= first_new)
            def _():
                patch(i, slot)
                for c in append(i, slot):
                    c.start()

            attend(i, slot)

            @pl.when(i >= first_new)
            def _():
                for c in append(i, slot):
                    c.wait()

        if cur is None:
            def body(i, _):
                slot = jax.lax.rem(i, 2)

                @pl.when(i + 1 < n)
                def _():
                    for c in fetch(i + 1, 1 - slot):
                        c.start()

                consume(i, slot)

            jax.lax.fori_loop(0, n, body, None)
        else:
            def body(i, carry):
                # the fetch depth - 1 blocks on goes where the block
                # before this one was consumed, and written back from
                c, j, slot = carry
                c, j = issue(c, j, jnp.where(slot == 0, depth, slot) - 1)
                consume(i, slot)
                return c, j, jnp.where(slot == depth - 1, 0, slot + 1)

            cur[0], cur[1], cur[2] = jax.lax.fori_loop(
                0, n, body, (cur[0], cur[1], cur[2]))
        # each row keeps the lanes of its own head; a 0/1 product then
        # sums the Hkv rows of one (s, g) into one lane-dense row
        x = acc[...] / l_scr[:, :1]
        row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(lane // D == row % Hkv, x, 0.0).astype(o_ref.dtype)
        osel = jax.lax.broadcasted_iota(jnp.int32, (o_ref.shape[1], Rp), 0)
        rsel = jax.lax.broadcasted_iota(jnp.int32, (o_ref.shape[1], Rp), 1)
        sel = (rsel // Hkv == osel).astype(o_ref.dtype)
        o_ref[0] = jax.lax.dot_general(
            sel, x, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def decode_attend(q, k_new, v_new, k_pool, v_pool, idx, *,
                  sm_scale: Optional[float] = None,
                  window: Optional[int] = None):
    """Append and attend, one lane at its own depth: ``q`` (B, Hq, S,
    D) and ``k_new`` / ``v_new`` (B, Hkv, S, D) for the current tokens,
    the pool leaves (B, L, Hkv * D), ``idx`` (B,) each lane's write
    position (``< 0``: an idle lane, neither read nor written; its
    output rows are zero). Returns ``(attn (B, Hq, S, D), k_pool,
    v_pool)`` with rows ``idx[b] .. idx[b] + S - 1`` of every live lane
    replaced and nothing else touched; the pools are updated in place
    where the caller donates them. ``window``: query ``j`` sees the last
    ``window`` positions up to its own alone, and the leaves are rings
    (position ``p`` in row ``p mod L``: the module's text)."""
    _, Hq, S, D = q.shape
    Hkv = k_new.shape[1]
    _, L, HD = k_pool.shape
    if Hq % Hkv or HD != Hkv * D:
        raise ValueError(
            f"decode_attend: Hq={Hq}, Hkv={Hkv}, D={D} do not match a "
            f"pool row of {HD} lanes")
    geometry = check_decode_geometry(L, HD, Hq * S, S, k_pool.dtype,
                                     window)
    scale = (D ** -0.5) if sm_scale is None else sm_scale
    return _decode_attend(q, k_new, v_new, k_pool, v_pool,
                          jnp.asarray(idx, jnp.int32), scale=float(scale),
                          geometry=geometry, interpret=interpret_mode(),
                          depth=fetch_depth(HD, k_pool.dtype, L),
                          **({} if window is None
                             else {"window": int(window)}))


# a program's layers call this with the same shapes: jitted, they share
# one traced kernel and one lowering of it (24 of them took 10 s of a
# step executable's first call: PERF.md §6, PR 29)
@functools.partial(jax.jit, static_argnames=(
    "scale", "geometry", "interpret", "depth", "run_on", "window"))
def _decode_attend(q, k_new, v_new, k_pool, v_pool, idx, *, scale,
                   geometry, interpret, depth, run_on=True, window=None):
    """``depth`` fetches in flight in one queue over the call's lanes.
    ``run_on=False`` (depth 2, no caller but the parity checks): the
    schedule before the queue, two buffers emptied at every lane's edge,
    traced op for op as it was."""
    if not run_on and depth != 2:
        raise ValueError("the two-buffer schedule is two deep")
    B, Hq, S, D = q.shape
    Hkv = k_new.shape[1]
    HD = Hkv * D
    G = Hq // Hkv
    R = Hq * S
    blk, win, Rp = geometry
    SGp = -(-(S * G) // 16) * 16
    q, k_new, v_new = to_mosaic(q, k_new, v_new)
    # rows (s, g, kv), each with its query in the lanes of head kv
    qr = q.reshape(B, Hkv, G, S, D).transpose(0, 3, 2, 1, 4)
    qbd = jnp.einsum("bsgkd,kj->bsgkjd", qr, jnp.eye(Hkv, dtype=q.dtype))
    qbd, _ = pad_to(qbd.reshape(B, R, HD), 1, Rp)
    rows = lambda x: x.astype(k_pool.dtype).transpose(
        0, 2, 1, 3).reshape(B, S, 1, HD)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Rp, HD), lambda b, ix: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, 1, HD), lambda b, ix: (b, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, S, 1, HD), lambda b, ix: (b, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            any_spec, any_spec],
        out_specs=[
            pl.BlockSpec((1, SGp, HD), lambda b, ix: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            any_spec, any_spec],
        scratch_shapes=[
            pltpu.VMEM((depth, blk, HD), k_pool.dtype),
            pltpu.VMEM((depth, blk, HD), v_pool.dtype),
            pltpu.VMEM((Rp, HD), jnp.float32),
            pltpu.VMEM((Rp, _LANES), jnp.float32),
            pltpu.VMEM((Rp, _LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2, depth)),
            pltpu.SemaphoreType.DMA((2,)),
            *([pltpu.SMEM((3,), jnp.int32)] if run_on else [])],
    )
    out, k_pool, v_pool = kernel_call(
        functools.partial(_decode_attend_kernel, scale=scale, S=S, G=G,
                          Hkv=Hkv, D=D, blk=blk,
                          sub=_sublanes(k_pool.dtype), win=win,
                          **({} if window is None else {"window": window})),
        name="decode_attend",
        grid_spec=grid_spec,
        out_shape=[out_struct((B, SGp, HD), q.dtype, qbd, k_pool, v_pool),
                   out_struct(k_pool.shape, k_pool.dtype, k_pool),
                   out_struct(v_pool.shape, v_pool.dtype, v_pool)],
        input_output_aliases={4: 1, 5: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(idx, qbd, rows(k_new), rows(v_new), k_pool, v_pool)
    attn = out[:, :S * G].reshape(B, S, G, Hkv, D).transpose(0, 3, 2, 1, 4)
    return attn.reshape(B, Hq, S, D), k_pool, v_pool
