"""The grouped expert product of a sparse feed-forward layer: rows sorted
by expert, each expert's SwiGLU over its own rows.

    y_r = g_r * W2[e] (silu(W1[e]^T x_r) * (W3[e]^T x_r))     r in group e

``x`` (R, H) holds the rows of every (token, expert) pair a layer
computes, in GROUPS: expert ``e``'s rows are ``starts[e] ... starts[e] +
counts[e]``, every start a multiple of `ROW_TILE` (`transformer.moe.
expert_rows` lays them out so; the padding between groups exists in that
frame alone, never in a caller's activations). ``gains`` (R,) is each
row's weight in its token's mixture, 0 on a row that is padding.

Where the kernels run (``use_pallas()``) this is ONE Pallas call a layer,
`apex1_moe_experts`. At serving sizes a handful of rows meet each
expert and the product is bound by the weights' bytes, so the kernel is
built around streaming them: the grid walks the experts, and the width of
the hidden layer in ``block_f`` columns; an expert's three matrices are
the blocked operands, double-buffered by the pipeline, so expert ``e +
1``'s stream in while expert ``e`` multiplies; ``starts`` and ``counts``
are scalar-prefetched, and an expert with no rows names the block that is
already in VMEM, so it is neither fetched nor multiplied. The rows and the
result stay in VMEM for the whole call. An expert's rows go through the
MXU in PASSES of `PASS_ROWS` (the smallest that holds them all, then
whole passes of the largest): a pass costs the MXU about as long as it
takes to load the expert's weights into it whatever the rows, so one pass
an expert hides under the stream of the next expert's weights. A pass may
run past its group's end: what it writes there belongs to a LATER group,
which rewrites it, or to nobody; `PASS_SLACK` rows behind the last group
keep it inside the array.

Off the TPU, and under ``use_pallas()`` false, the same sums as a
composite: every expert's SwiGLU over all rows, kept where the row is the
expert's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex1_tpu.ops._common import (interpret_mode, kernel_call, out_struct,
                                   use_pallas)

#: a group starts on a multiple of this many rows: one packed bfloat16
#: register of sublanes
ROW_TILE = 16
#: rows of one pass through the MXU, smallest first
PASS_ROWS = (16, 32, 64, 128)
#: rows behind the last group that a pass may run over
PASS_SLACK = PASS_ROWS[-1] - ROW_TILE
#: the kernel's VMEM: two buffers of an expert's three matrices at the
#: published widths this repo serves (2048 x 1792, LFM2: 2 x 22 MB; 2048 x
#: 1024, Trinity-Mini: 2 x 12.6 MB, a half of each a grid step), the rows
#: and the result (a prefill chunk of 256 rows, top-8 over 16 held: 2432
#: rows of 2048, 10 MB each and 20 for the float32 sums)
_VMEM_LIMIT = 100 * 1024 * 1024


def padded_rows(n_pairs: int, n_experts: int) -> int:
    """Rows of the grouped frame that holds up to ``n_pairs`` rows in
    ``n_experts`` groups, however they fall: each group is padded to a
    whole `ROW_TILE` (at most one partial tile a group), and
    `PASS_SLACK` rows lie behind the last."""
    return (n_pairs // ROW_TILE + n_experts) * ROW_TILE + PASS_SLACK


def _composite(x, gains, w1, w3, w2, starts, counts):
    rows = jnp.arange(x.shape[0], dtype=jnp.int32)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(w1.shape[0]):
        mine = (rows >= starts[e]) & (rows < starts[e] + counts[e])
        h = jax.nn.silu(jnp.dot(x, w1[e], preferred_element_type=jnp.float32)
                        ) * jnp.dot(x, w3[e],
                                    preferred_element_type=jnp.float32)
        out = jnp.dot(h.astype(x.dtype), w2[e],
                      preferred_element_type=jnp.float32)
        y = jnp.where(mine[:, None], out, y)
    return (y * gains[:, None]).astype(x.dtype)


def _kernel(starts_ref, counts_ref, wsel_ref, fpin_ref, x_ref, g_ref, w1_ref,
            w3_ref, w2_ref, y_ref, *acc, n_f):
    del wsel_ref, fpin_ref            # the index maps read them
    e, f = pl.program_id(0), pl.program_id(1)
    start, count = starts_ref[e], counts_ref[e]

    @pl.when((e == 0) & (f == 0))
    def _():
        # a row that no pass writes is read all the same, by the 0/1
        # product that gathers the tokens' rows: 0 x what VMEM held
        y_ref[...] = jnp.zeros_like(y_ref)

    def one_pass(r0, rows):
        at = pl.ds(pl.multiple_of(r0, ROW_TILE), rows)
        x = x_ref[at, :]
        h = jax.nn.silu(jnp.dot(x, w1_ref[0],
                                preferred_element_type=jnp.float32)) \
            * jnp.dot(x, w3_ref[0], preferred_element_type=jnp.float32)
        out = jnp.dot(h.astype(x.dtype), w2_ref[0],
                      preferred_element_type=jnp.float32)
        if n_f == 1:
            y_ref[at, :] = (out * g_ref[at, :]).astype(y_ref.dtype)
            return
        acc_ref, = acc

        @pl.when(f == 0)
        def _():
            acc_ref[at, :] = out

        @pl.when(f > 0)
        def _():
            acc_ref[at, :] += out

        @pl.when(f == n_f - 1)
        def _():
            y_ref[at, :] = (acc_ref[at, :] * g_ref[at, :]).astype(
                y_ref.dtype)

    below = 0
    for rows in PASS_ROWS[:-1]:
        @pl.when((count > below) & (count <= rows))
        def _(rows=rows):
            one_pass(start, rows)
        below = rows
    big = PASS_ROWS[-1]

    @pl.when(count > below)
    def _():
        def body(i, carry):
            one_pass(start + i * big, big)
            return carry
        jax.lax.fori_loop(0, (count + big - 1) // big, body, 0)


def _resident(counts, n_f):
    """Which block of the weights each grid step names: its own expert's
    where the expert has rows; else the block that is in VMEM already (the
    last one of the touched expert before it), or, before the first
    touched expert, the block that one will ask for first. ``(wsel, fpin)``
    (E,) each: the expert, and the column block pinned (-1: the step's
    own)."""
    n = counts.shape[0]
    ids = jnp.arange(n, dtype=jnp.int32)
    touched = counts > 0
    before = jax.lax.cummax(jnp.where(touched, ids, -1))
    after = jax.lax.cummin(jnp.where(touched, ids, n), reverse=True)
    first = jnp.where(after < n, after, 0)
    wsel = jnp.where(touched, ids, jnp.where(before >= 0, before, first))
    fpin = jnp.where(touched, -1, jnp.where(before >= 0, n_f - 1, 0))
    return wsel.astype(jnp.int32), fpin.astype(jnp.int32)


# a program's layers call this with the same shapes: jitted, they share one
# traced kernel and one lowering of it (PERF.md §6, PR 29, `setup_s`)
@functools.partial(jax.jit, static_argnames=("block_f", "interpret"))
def _pallas(x, gains, w1, w3, w2, starts, counts, *, block_f, interpret):
    R, H = x.shape
    E, _, F = w1.shape
    n_f = F // block_f
    wsel, fpin = _resident(counts, n_f)

    def col(f, e, fpin):
        return jnp.where(fpin[e] < 0, f, fpin[e])

    whole = lambda shape: pl.BlockSpec(shape, lambda e, f, *_: (0, 0),
                                       memory_space=pltpu.VMEM)
    up = pl.BlockSpec((1, H, block_f),
                      lambda e, f, s, c, wsel, fpin: (wsel[e], 0,
                                                      col(f, e, fpin)),
                      memory_space=pltpu.VMEM)
    down = pl.BlockSpec((1, block_f, H),
                        lambda e, f, s, c, wsel, fpin: (wsel[e],
                                                        col(f, e, fpin), 0),
                        memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(E, n_f),
        in_specs=[whole((R, H)), whole((R, 1)), up, up, down],
        out_specs=whole((R, H)),
        scratch_shapes=([] if n_f == 1
                        else [pltpu.VMEM((R, H), jnp.float32)]),
    )
    return kernel_call(
        functools.partial(_kernel, n_f=n_f),
        name="moe_experts",
        grid_spec=grid_spec,
        out_shape=out_struct((R, H), x.dtype, x, w1),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(starts, counts, wsel, fpin, x, gains[:, None], w1, w3, w2)


def default_block_f(F: int) -> int:
    """Columns of the hidden layer a grid step takes: half of them where
    a half is whole lanes, else all. Two steps an expert halve what the
    pipeline's first fetch and last product leave uncovered and the
    buffers the weights take. On a v5e, 8 experts of 2048 x 1792 (PERF.md
    §6, PR 38): 12 rows an expert 261.8 / 260.5 / 261.5 us at 1792 / 896 /
    256 columns, no difference; groups of 0 to 130 rows 270.7 / 252.6 /
    251.1: a half wins where an expert's rows take several passes."""
    return F // 2 if F % 256 == 0 else F


def moe_experts(x, gains, w1, w3, w2, starts, counts, *, block_f=None):
    """``x`` (R, H) rows in groups, ``gains`` (R,) float32, ``w1`` / ``w3``
    (E, H, F) and ``w2`` (E, F, H) of ``x``'s dtype, ``starts`` / ``counts``
    (E,) int32: group ``e`` is the ``counts[e]`` rows from ``starts[e]``,
    a multiple of `ROW_TILE`, groups in order, and R at least the last
    group's end + `PASS_SLACK` (`padded_rows`). Returns (R, H) of ``x``'s
    dtype: a group's rows as above, every other row finite and multiplied
    by its gain (0 where the gains are)."""
    R, H = x.shape
    E, _, F = w1.shape
    if w3.shape != w1.shape or w2.shape != (E, F, H) or w1.shape[1] != H:
        raise ValueError(f"expert matrices {w1.shape}, {w3.shape}, "
                         f"{w2.shape} for rows of {H}")
    if R % ROW_TILE:
        raise ValueError(f"{R} rows: the frame is whole tiles of "
                         f"{ROW_TILE} (`padded_rows`)")
    gains = gains.astype(jnp.float32)
    starts, counts = starts.astype(jnp.int32), counts.astype(jnp.int32)
    if not use_pallas():
        return _composite(x, gains, w1, w3, w2, starts, counts)
    block_f = block_f or default_block_f(F)
    if F % block_f or (block_f != F and block_f % 128):
        raise ValueError(f"block_f {block_f} does not tile {F} in lanes")
    return _pallas(x, gains, w1, w3, w2, starts, counts, block_f=block_f,
                   interpret=interpret_mode())
