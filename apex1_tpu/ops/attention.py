"""Flash attention — Pallas TPU kernels.

Reference capability: ``apex/contrib/fmha/fmha.py :: FMHAFun`` (+
``apex/contrib/csrc/fmha/``, seqlen ≤ 512, head-dim 64, varlen via
cu_seqlens) and ``apex/contrib/multihead_attn`` (fused full-MHA blocks).
The reference kernels materialize (or tile) the full score matrix per CTA;
the TPU-native design is a flash/online-softmax kernel with NO seqlen cap:

- **forward**: VMEM scratch carries the running ``(max, sum, acc)`` across
  key tiles; saves only ``(out, logsumexp)`` — activation memory O(S·D),
  not O(S²).
- **backward**: recomputes probabilities from ``q·kᵀ`` and the saved
  logsumexp (the same recompute-instead-of-save trade the reference's
  xentropy kernel makes); two kernels — dq (key-innermost) and dk/dv
  (query-innermost accumulation).
- **two forms of each kernel, chosen by shape and operand** (`_kv_resident`,
  `_q_resident`; no option): RESIDENT — the (batch, head) row's whole K/V
  (forward, dq) or the GQA group's whole Q/dO rows (dk/dv) are ONE VMEM
  block fetched once a head, the grid is ``(B, H, tiles)`` and the pass
  over the other axis is a `fori_loop` in the kernel whose bounds
  (`_key_tiles`, `_query_tiles`; counted by `tile_plan`) stop at the
  causal diagonal: a tile above it is neither fetched nor stepped over,
  a tile wholly under it runs a body with no mask (INTERIOR), and only
  the tiles the diagonal or a padded edge crosses build one (MASKED).
  A whole square tile whose corner the diagonal passes through (what a
  causal call in aligned tiles crosses; `_on_diagonal`, a predicate on
  the tile indices and the offsets in SMEM) runs the DIAGONAL body
  instead: ``bq // DIAG_SUB`` static steps, each a trapezoid row of
  ``DIAG_SUB``-wide squares that stops at the diagonal, masked on its one
  diagonal square by a constant triangle; the squares above the diagonal,
  whose scores are all dead, are not computed. GRID — where
  the row does not fit VMEM, or with an additive bias (a bias tile is per
  (qi, ki) by nature), the other axis stays the innermost grid axis
  (TPU grid iteration is sequential, so scratch persists) and every
  computed tile is masked. With segment ids or dropout the resident loop
  runs the masked body on every tile (those operands are per tile too).
- **transposed tiles**: forward and dk/dv compute the score tile as
  (bk, bq), keys down the sublanes, so softmax's max and sum are plain
  vector ops and ``pᵀ·dO``/``dsᵀ·q`` take their left operand as it lies;
  dq keeps (bq, bk). Per-query statistics travel as dense (1, bq) rows.
- **scale**: a power-of-two ``sm_scale`` (exact in every dtype) is folded
  into a (rows, Dp) operand once a program; any other value multiplies
  the fp32 score tile as before (`_exact_scale`).
- **varlen**: ``segment_ids`` — positions in different segments never
  attend (≙ the reference fmha's cu_seqlens packed batches).
- **GQA/MQA**: ``k``/``v`` may have fewer heads than ``q`` (grouped by
  index-map arithmetic, no materialized repeat).
- **ring/context parallel**: traced ``q_offset``/``k_offset`` scalars (SMEM)
  shift the global positions used by the causal mask, and the op can return
  the per-shard ``lse`` so `apex1_tpu.parallel.ring_attention` can merge
  partial results around an ICI ring — differentiably (the custom VJP
  handles the lse cotangent: ∂lse/∂s = softmax(s) ⇒ ds += p·dlse).

- **two layouts, chosen by what the call hands over** (`flash_form`; no
  option): HEADS — `flash_attention`'s ``(B, H, S, D)`` operands, a block
  one head padded to 128 lanes; ROWS — `fmha`'s packed ``(B, S, 3, H, D)``
  array read WHERE THE QKV PRODUCT LEFT IT, as ``(B, S, 3·H·D)``: a block
  is ``(1, rows, 128)``, its lanes ``128 // D`` whole heads side by side,
  q, k and v three lane-block offsets into the one array, ``out`` written
  as the output projection reads it, dq, dk, dv into the lane ranges of
  ONE ``(B, S, 3·H·D)`` array, and δ = Σ dO·out made in the dq kernel.
  No pad, split, reshape, transpose or reduce in XLA. Inside a program the heads of a block take turns through the SAME
  tile bodies: the operand read once a program has the other heads'
  lanes zeroed (`_head_lanes`), so a contraction over the 128 lanes is
  one head's alone (exact zeros added: bitwise a head padded with
  zeros), and of a product's 128 output lanes (rows of outᵀ) the head's
  own are kept.

Shapes: ``q`` (B, Hq, Sq, D); ``k``/``v`` (B, Hkv, Sk, D), Hq % Hkv == 0.
Accumulation is fp32 regardless of input dtype (bf16 inputs feed the MXU
directly; only the running statistics are fp32) — matching the reference's
fp16-in/fp32-accumulate kernels.
"""

from __future__ import annotations

import functools
import math
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
               dropout_p, n_h, interp, transposed=False):
    """Attention-probability keep mask for the (qi, ki) score tile —
    counter-based on (seed, batch·n_h+head, GLOBAL q start, GLOBAL k
    start), so the mask is independent of grid iteration order and of
    ring-shard visiting order, and context-parallel shards (whose
    ``k_off`` differs) draw disjoint, shift-invariant streams. Forward
    and both backward kernels call this with identical arguments per
    tile — the recompute identity the custom VJPs rely on.
    ``transposed``: the SAME (bq, bk) draw turned over to (bk, bq), for
    the kernels whose tile has the keys down the sublanes."""
    keep = tile_keep_mask(
        (bq, bk), threshold_u32(dropout_p), sd_ref[0, 0], b * n_h + h,
        qi * bq + qo_ref[0, 0], ki * bk + ko_ref[0, 0], interp=interp)
    if transposed:   # through fp32: the transpose unit takes no bools
        keep = keep.astype(jnp.float32).T > 0.5
    return keep


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
    the step from 1024 halves peak usage for one extra grid level.

    The same tile is the resident form's (the loop inside the kernel,
    module docstring). A causal pass visits n_q·(n_q + 1)/2 of its n_q²
    tiles, (1 + 1/n_q)/2 of the square (0.75 at 512 of S = 1024, 0.625
    at 256), n_q of them on the diagonal; since PR 51 a diagonal tile
    COMPUTES (1 + 128/bq)/2 of itself (`DIAG_SUB`; 10 of 16 squares at
    512), so a 512 tile pass computes 0.5625 of the square. Smaller
    tiles waste less and run slower: every tile costs a fixed ~0.4 us
    of loop and pipeline fill, and every 128-row step of a diagonal tile
    a fixed cost of its own. On a v5e at 8 x 16 x 1024 x 64 bf16 the three
    kernels of a layer together take, rows layout (PERF.md §6, PR 51;
    in brackets the MASKED body on the diagonal tiles, what ran before):
    **1.63 ms at 512x512 (1.88)**, 2.30 at 256x256 (2.26: two steps of
    128 rows save a quarter of a small tile and cost as much), 2.15 at
    1024x1024 (the GRID form, one masked tile a head and no loop: the
    resident form's MASKED fallback holds four live (1024, 1024) fp32
    tiles a head and does not fit VMEM, so this tile has no diagonal
    body to take); heads layout 1.67 at 512x512 (1.82; PR 39: 2.06 at
    1024x1024, 2.28 at 256x512, 2.45 at 256x256): 512 stays. By the
    square of 128: an interior one ~0.025 ms a layer, a masked one
    ~0.031, a diagonal tile's ten 0.38 together where its sixteen
    masked were 0.50 (a step's fixed cost is what keeps it from 0.275).
    Whether the resident form is taken is NOT decided here but per call,
    from the padded lengths, the GQA group and the operands
    (`_kv_resident`, `_q_resident` over `vmem_model.flash_kv_row_check` /
    `flash_q_row_check`, the checks the tuning registry's
    `vmem_model.flash_check` prices a candidate with)."""
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
              qseg, kseg, transposed=False):
    """(bq, bk) validity mask for one score block — (bk, bq), keys down
    the sublanes, if ``transposed``. Padded rows/cols are invalid; causal
    compares GLOBAL positions (local + traced offset)."""
    shape, qa, ka = ((bk, bq), 1, 0) if transposed else ((bq, bk), 0, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, qa) + qi * bq
    col = jax.lax.broadcasted_iota(jnp.int32, shape, ka) + ki * bk
    mask = (col < true_sk) & (row < true_sq)
    if causal:
        mask &= (col + k_off) <= (row + q_off)
    if qseg is not None:
        # (bq,1) == (1,bk) broadcast; transposed (1,bq) == (bk,1)
        mask &= qseg == kseg
    return mask


# ---------------------------------------------------------------------------
# which tiles a pass visits, and which of them need a mask
# ---------------------------------------------------------------------------
# A (qi, ki) score tile is INTERIOR (every element live: wholly at or
# under the diagonal and inside both true lengths), MASKED (some live,
# some not: the diagonal or a padded edge crosses it) or NEVER VISITED
# (no live element). The two functions below give, for one query tile
# and for one key tile, the runs of each class in loop order. The
# resident kernels' loop bounds ARE these values (on traced offsets from
# SMEM) and `tile_plan` counts with them (on Python ints): one source.

def _static(*xs):
    return all(isinstance(x, (int, np.integer)) for x in xs)


def _mn(a, b):
    return min(a, b) if _static(a, b) else jnp.minimum(a, b)


def _mx(a, b):
    return max(a, b) if _static(a, b) else jnp.maximum(a, b)


def _sel(c, a, b):
    return (a if c else b) if isinstance(c, (bool, np.bool_)) \
        else jnp.where(c, a, b)


def _key_tiles(qi, bq, bk, true_sq, true_sk, q_off, k_off, causal):
    """``(n_int, n_vis)`` for query tile ``qi``: key tiles [0, n_int)
    are interior, [n_int, n_vis) masked, the rest never visited."""
    n_k, n_int = -(-true_sk // bk), true_sk // bk
    n_vis = n_k
    if causal:
        d = q_off - k_off       # row r sees the columns up to r + d
        n_vis = _mn(_mx(_mn(qi * bq + bq, true_sq) - 1 + d + bk, 0) // bk,
                    n_k)
        n_int = _mn(_mx(qi * bq + d + 1, 0) // bk, n_int)
    # a query tile that holds padded rows masks them in every key tile
    return _sel((qi + 1) * bq <= true_sq, n_int, 0), n_vis


def _query_tiles(ki, bq, bk, true_sq, true_sk, q_off, k_off, causal):
    """``(lo, a, b, n_q)`` for key tile ``ki``: query tiles [lo, a) are
    masked (the diagonal crosses them), [a, b) interior, [b, n_q) masked
    (the padded last rows); those under ``lo`` are never visited."""
    n_q, n_full = -(-true_sq // bq), true_sq // bq
    lo = a = 0
    if causal:
        c0 = ki * bk + k_off - q_off    # first row that sees column 0
        lo = _sel(c0 >= true_sq, n_q, _mx(c0, 0) // bq)
        a = _mn(_mx(c0 + bk - 1 + bq - 1, 0) // bq, n_q)
    # a key tile that holds padded columns masks them in every query tile
    a = _sel((ki + 1) * bk <= true_sk, a, n_q)
    return lo, a, _mx(a, n_full), n_q


# The three bodies a tile runs. DIAGONAL is MASKED's special case, taken
# where the call allows it statically (`_diag_sub`) and the tile at run
# time (`_on_diagonal`): both from what the kernel can see, no option.
INTERIOR, MASKED, DIAGONAL = "interior", "masked", "diagonal"

# Width of the squares the DIAGONAL body cuts its tile into: one lane
# tile. On a v5e a layer's three kernels at 8 x 16 x 1024 x 64 bf16 in
# 512 x 512 tiles take 1.632 ms at 128 and 1.647 at 256 (1.876 with no
# such body; the forward and dq a hair faster at 256, dk/dv at 128:
# docs/ops.md has the sweep, PERF.md §6, PR 51, the runs).
DIAG_SUB = 128


def _diag_sub(bq, bk, causal, plain=True):
    """The DIAGONAL body's square width for a call, 0 where it has none:
    causal, square tiles of whole ``DIAG_SUB`` squares, and ``plain`` (no
    segment ids, dropout or bias: operands that are per tile by nature
    keep the MASKED body)."""
    return DIAG_SUB if (causal and plain and bq == bk
                        and bq % DIAG_SUB == 0) else 0


def _on_diagonal(qi, ki, bq, bk, true_sq, true_sk, q_off, k_off):
    """Whether tile (qi, ki) is whole (no padded row or column) and the
    causal diagonal passes through its corner: row r of the tile sees
    columns 0..r of it, whatever the indices. Python ints (`tile_plan`)
    or the kernel's traced scalars, as `_key_tiles`."""
    return ((qi * bq + q_off - k_off == ki * bk)
            & ((qi + 1) * bq <= true_sq) & ((ki + 1) * bk <= true_sk))


def tile_plan(Sq, Sk, bq, bk, q_off=0, k_off=0, causal=True):
    """``(interior, masked, diagonal, never_visited)`` score tiles of one
    (batch, head) at true lengths ``Sq`` x ``Sk`` in ``bq`` x ``bk``
    tiles: what the resident kernels' loops run without a mask, with
    one, how many OF THE MASKED take the DIAGONAL body (`_diag_sub`,
    `_on_diagonal`), and what they do not run at all. Static counterpart
    of the in-kernel bounds and predicate (same functions)."""
    interior = masked = diagonal = 0
    diag = _diag_sub(bq, bk, causal) > 0
    for qi in range(-(-Sq // bq)):
        n_int, n_vis = _key_tiles(qi, bq, bk, Sq, Sk, q_off, k_off, causal)
        interior += n_int
        masked += n_vis - n_int
        diagonal += sum(
            bool(diag and _on_diagonal(qi, ki, bq, bk, Sq, Sk, q_off, k_off))
            for ki in range(n_int, n_vis))
    return interior, masked, diagonal, \
        -(-Sq // bq) * -(-Sk // bk) - interior - masked


def flash_form(Hq, Hkv, Sq, Sk, D, *, packed=False, has_bias=False,
               block_q=None, block_k=None, dtype=jnp.bfloat16):
    """The form a call takes, from what it can see and nothing else (no
    argument, option or environment variable picks it): ``layout``
    (``"rows"`` | ``"heads"``), ``heads_per_block``, ``resident`` (the
    forward's and dq's, dk/dv's) and ``blocks``. `fmha` and the custom
    VJPs decide with THIS function; a traced call says the same on the
    `obs` spine (counter ``flash/form``).

    ROWS, the packed array read where the qkv product left it (module
    docstring), wants: the packed array (``packed``: `fmha`; what hands
    over (B, H, S, D) has turned its arrays already), whole heads to a
    128-lane block (``128 % D == 0`` and the heads a multiple of
    ``128 // D``), as many K/V heads as query heads, no bias (its tile is
    per (qi, ki): the grid form, which the rows layout has not) and rows
    that fit VMEM for all three kernels at ``128 // D`` heads to a block
    (`vmem_model.flash_kv_row_check` / `flash_q_row_check`). Anything
    else runs the HEADS layout, `fmha` after turning its array."""
    block_q, block_k = _auto_blocks(D, block_q, block_k, dtype, Sk)
    es = jnp.dtype(dtype).itemsize
    n = _LANES // D if D <= _LANES and _LANES % D == 0 else 0
    if (packed and n and not has_bias and Hq == Hkv and Sq == Sk
            and Hq % n == 0):
        g = _sizes(1, Hq, Hkv, Sq, Sk, D, es, block_q, block_k, n)
        if _kv_resident(g) and _q_resident(g):
            return dict(layout="rows", heads_per_block=n,
                        resident=(True, True), blocks=(g["bq"], g["bk"]))
    g = _sizes(1, Hq, Hkv, Sq, Sk, D, es, block_q, block_k)
    return dict(layout="heads", heads_per_block=1,
                resident=(_kv_resident(g, has_bias),
                          _q_resident(g, has_bias)),
                blocks=(g["bq"], g["bk"]))


def _exact_scale(scale):
    """A power-of-two ``scale`` commutes with every rounding on the way
    (bf16 operand, fp32 product and sum), so it may leave the (bq, bk)
    score tile for a (rows, Dp) operand. Any other value would round the
    operand a second time: it stays on the score tile."""
    return scale > 0.0 and math.frexp(scale)[0] == 0.5


# A per-query statistic (lse; δ − dlse) lives in HBM as (B, Hq, n_q, 1, bq):
# a query tile's values one dense ROW along the lanes. As a (.., Sq, 1)
# column it would be tiled (8, 128) with ONE live lane: 128 times the
# bytes, 67 MB an array at the training cell's shapes where the values
# are 0.5 MB, and most of what the dq kernel fetched. The transposed
# forward and dk/dv tiles take the row as it is; dq turns it, once a
# program (a tile in the grid form), to the column its (bq, bk) tile
# broadcasts.

def _as_col(r):
    """(1, n) row -> (n, 1) column: a transpose of whole lane tiles, or,
    for a small or ragged tile, the diagonal picked out."""
    n = r.shape[1]
    if n % _LANES:
        eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
        return jnp.sum(jnp.where(eye, r, 0.0), axis=1, keepdims=True)
    return jnp.broadcast_to(r, (_LANES, n)).T[:, :1]


def _split_refs(rest, has_segs, has_bias, dropout_p):
    """The optional operands every kernel takes after its fixed ones, in
    order: seed (SMEM), (qseg, kseg), bias; then what is left."""
    rest = list(rest)
    sd_ref = rest.pop(0) if dropout_p > 0.0 else None
    qseg_ref, kseg_ref = (rest.pop(0), rest.pop(0)) if has_segs \
        else (None, None)
    bias_ref = rest.pop(0) if has_bias else None
    return sd_ref, qseg_ref, kseg_ref, bias_ref, rest


def _loop(lo, hi, body):
    jax.lax.fori_loop(lo, hi, lambda i, c: body(i), None)


def _rows(i, n):
    return pl.ds(pl.multiple_of(i * n, n), n)


def _tile_of(ref, i, n):
    """Rows [i·n, (i+1)·n) of a resident row's block, (1, 1, rows, Dp) or
    (1, rows, 128)."""
    return ref[(0,) * (len(ref.shape) - 2) + (_rows(i, n), slice(None))]


# The ROWS layout (module docstring): a (rows, 128) block holds ``heads``
# whole heads side by side in its lanes.

def _lane_head(shape, heads):
    """Which of a block's ``heads`` heads each lane of ``shape`` is."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1) \
        // (_LANES // heads)


def _head_lanes(x, heads):
    """``x`` once a head, the OTHER heads' lanes zeroed: a contraction
    over the 128 lanes against it is that head's alone, and what the
    zeros add is exact. Made once a program, of the operand read once a
    program. One head to a block: ``x`` itself."""
    if heads == 1:
        return (x,)
    lane = _lane_head(x.shape, heads)
    return tuple(jnp.where(lane == t, x, jnp.zeros_like(x))
                 for t in range(heads))


def _own_lanes(xs):
    """Of each head's (rows, 128) product its own lanes: one block."""
    out = xs[-1]
    if len(xs) > 1:
        lane = _lane_head(out.shape, len(xs))
        for t in range(len(xs) - 2, -1, -1):
            out = jnp.where(lane == t, xs[t], out)
    return out


def _edge_tile(tile, on):
    """Run ``tile(body)`` for a tile of a masked run: MASKED, or, where
    the call has a DIAGONAL body and the kernel sees the tile on the
    diagonal (``on``: `_on_diagonal` of traced scalars; None where the
    call has no such body), that."""
    if on is None:
        return tile(MASKED)
    jax.lax.cond(on, lambda: tile(DIAGONAL), lambda: tile(MASKED))


def _triangle(g, transposed=False):
    """The live scores of a ``g`` x ``g`` square that the diagonal halves,
    corner to corner: key <= query. One constant pattern whatever the
    tile, (queries, keys) or ``transposed``."""
    q, k = (1, 0) if transposed else (0, 1)
    return (jax.lax.broadcasted_iota(jnp.int32, (g, g), k)
            <= jax.lax.broadcasted_iota(jnp.int32, (g, g), q))


def _fill_dead(x, tri, fill, axis, last):
    """A DIAGONAL step's trapezoid row ``x``, whole ``g``-squares along
    ``axis``, with the dead scores of its ONE diagonal square (the
    ``last`` along the axis, or the first) set to ``fill``; the other
    squares are all live and pass as they are."""
    g = tri.shape[0]
    if x.shape[axis] == g:
        return jnp.where(tri, x, fill)
    a, b = jnp.split(x, [x.shape[axis] - g if last else g], axis)
    if last:
        b = jnp.where(tri, b, fill)
    else:
        a = jnp.where(tri, a, fill)
    return jnp.concatenate([a, b], axis)


def _over_key_tiles(tile, row, init, finish, *, block_k, qi, bq, bk, n_k,
                    true_sq, true_sk, q_off, k_off, causal, mask_all):
    """Drive ``tile(ki, body, *row())`` over query tile ``qi``'s key
    tiles for the forward and dq kernels. RESIDENT (``block_k`` given):
    two loops in the kernel, the interior run and the masked one
    (`_key_tiles`; ``mask_all``: segment ids or dropout, operands that
    are per tile by nature, mask every tile), a tile of the masked run
    taking the DIAGONAL body where it can (`_edge_tile`). GRID: this grid
    step's one tile, skipped when it lies wholly above the diagonal."""
    if block_k is not None:
        n_int, n_vis = _key_tiles(qi, bq, bk, true_sq, true_sk, q_off,
                                  k_off, causal)
        if mask_all:
            n_int = 0
        diag = _diag_sub(bq, bk, causal, not mask_all) > 0
        init()
        ops = row()
        _loop(0, n_int, lambda ki: tile(ki, INTERIOR, *ops))
        _loop(n_int, n_vis, lambda ki: _edge_tile(
            lambda body: tile(ki, body, *ops),
            _on_diagonal(qi, ki, bq, bk, true_sq, true_sk, q_off, k_off)
            if diag else None))
        finish()
        return
    ki = pl.program_id(3)
    pl.when(ki == 0)(init)
    if causal:
        # skip blocks entirely above the diagonal (no valid positions):
        # saves the strictly-upper-triangular ~half of the MXU work
        pl.when((ki * bk + k_off) <= (qi * bq + bq - 1 + q_off))(
            lambda: tile(ki, MASKED, *row()))
    else:
        tile(ki, MASKED, *row())
    pl.when(ki == n_k - 1)(finish)


def _attend_tile(q, k, v, acc, m_scr, l_scr, *, scale=None, bias=None,
                   mask=None, keep=None, dropout_p=0.0, own=None, tri=None):
    """Fold one score tile into the running (outᵀ, max, sum) of the
    online softmax. The tile is TRANSPOSED, (bk, bq) with the keys down
    the sublanes: a query's max and sum then run over sublanes and vregs
    (plain vector ops), where in (bq, bk) each is a reduction across the
    lanes, and those, not the matrix unit, set the forward's time at the
    training cell's shapes. ``acc`` is outᵀ (Dp, bq), ``m_scr`` and
    ``l_scr`` (1, bq) rows. ``mask=None`` is the INTERIOR body: no iota,
    compare or select (on an all-live tile they change nothing);
    ``scale=None`` means the caller folded it into ``q``. ``own``: the
    rows of ``vᵀ·eᵀ`` that are this head's (the ROWS layout, where ``v``
    holds several heads' lanes and ``acc`` is this head's rows of outᵀ).
    ``tri``: a DIAGONAL step (`_attend_diagonal`), the interior body on a
    trapezoid row whose last square alone holds dead scores; every query
    of it has a live key, so their ``e`` is exp's exact 0.0 unaided.
    The forward kernel and `ops.fused_collective._agf_kernel` both run
    THIS function, which is what keeps the fused ring equal to the
    decomposed one."""
    # native-dtype operands: bf16 inputs ride the MXU's bf16 path with
    # fp32 accumulation (an fp32 upcast before the dot would run the MXU
    # ~8x slower); running statistics stay fp32
    s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if scale is not None:
        s = s * scale
    if bias is not None:
        # additive logit bias (T5 rel-pos / arbitrary masks):
        # s = qk·scale + bias, matching scaled_masked_softmax
        s = s + bias.astype(jnp.float32).T
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    if tri is not None:
        s = _fill_dead(s, tri, NEG_INF, 0, last=True)
    m_prev = m_scr[...]                                       # (1, bq)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    e = jnp.exp(s - m_new)
    if mask is not None:
        e = jnp.where(mask, e, 0.0)
    l_scr[...] = l_scr[...] * corr + jnp.sum(e, axis=0, keepdims=True)
    if keep is not None:
        # dropout BETWEEN softmax and AV (the reference fmha fusion
        # point): the softmax denominator l accumulates the UNdropped
        # e, only the AV contribution is masked+rescaled, so (out, lse)
        # merge exactly across ring shards
        e = jnp.where(keep, e * (1.0 / (1.0 - dropout_p)), 0.0)
    old = acc[...] * corr
    pv = jax.lax.dot_general(                                 # vᵀ · eᵀ
        v, e.astype(v.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc[...] = old + (pv if own is None else pv[own])
    m_scr[...] = m_new


def _attend_diagonal(q, k, v, acc, m_scr, l_scr, *, tri, **kw):
    """`_attend_tile` for a tile on the diagonal (`_on_diagonal`), in
    static steps: query sub-block ``j`` (``g`` lanes of the transposed
    tile, of ``acc``, ``m_scr``, ``l_scr``) against the keys 0..(j+1)·g
    it can see and no others. A query's state is its lane's alone, so
    the steps are independent and none rescales another's."""
    g = tri.shape[0]
    for j in range(q.shape[0] // g):
        at, w = slice(j * g, (j + 1) * g), (j + 1) * g
        _attend_tile(q[at], k[:w], v[:w], acc.at[:, at], m_scr.at[:, at],
                     l_scr.at[:, at], tri=tri, **kw)


def _fwd_kernel(q_ref, k_ref, v_ref, qo_ref, ko_ref, *rest,
                scale, causal, true_sq, true_sk, has_segs, has_bias, n_k,
                block_k=None, dropout_p=0.0, n_h=0, interp=False, heads=0):
    """``block_k`` given: the RESIDENT form, grid (b, h, qi), the row's
    whole K and V in ``k_ref``/``v_ref`` and the pass over key tiles a
    loop in here (`_key_tiles`); else the GRID form, (b, h, qi, ki).
    Writes outᵀ, a (Dp, bq) block, and lse as a (1, bq) row.
    ``heads`` > 0: the ROWS layout (resident only), grid (b, j, qi) over
    lane blocks of ``heads`` whole heads; every tile is loaded and its
    mask built once and run for each head in turn, head ``t`` into its
    own rows of the one outᵀ accumulator, which is turned once, at the
    end, and written as (bq, 128) rows of ``out``."""
    sd_ref, qseg_ref, kseg_ref, bias_ref, rest = _split_refs(
        rest, has_segs, has_bias, dropout_p)
    o_ref, lse_ref, acc, m_scr, l_scr = rest
    resident = block_k is not None
    rows, n = heads > 0, max(heads, 1)
    lead = (0,) * (len(q_ref.shape) - 2)
    # program ids read out HERE: inside a `pl.when` or loop body the
    # primitive has no interpret-mode lowering
    b, h, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bq = q_ref.shape[-2]
    bk = block_k if resident else k_ref.shape[-2]
    q_off, k_off = qo_ref[0, 0], ko_ref[0, 0]
    fold = _exact_scale(scale)
    d = _LANES // n
    own = [slice(t * d, (t + 1) * d) if rows else None for t in range(n)]
    # head t's running (outᵀ rows, max, sum)
    state = [(acc.at[own[t], :], m_scr.at[t], l_scr.at[t]) if rows
             else (acc, m_scr, l_scr) for t in range(n)]

    def row():
        # what a tile takes from this program's query rows; read ONCE a
        # program in the resident form, per computed tile in the grid's
        return (_head_lanes(q_ref[lead] * scale if fold else q_ref[lead],
                            n),
                qseg_ref[0, 0] if has_segs else None)         # (1, bq)

    def init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    def tile(ki, body, qs, qseg):
        if resident:
            k, v = _tile_of(k_ref, ki, bk), _tile_of(v_ref, ki, bk)
            kseg = kseg_ref[0, _rows(ki, bk), :] if has_segs else None
        else:
            k, v = k_ref[0, 0], v_ref[0, 0]
            kseg = kseg_ref[0] if has_segs else None          # (bk, 1)
        if body is DIAGONAL:
            tri = _triangle(DIAG_SUB, transposed=True)
            for t in range(n):
                _attend_diagonal(qs[t], k, v, *state[t], tri=tri,
                                 scale=None if fold else scale, own=own[t])
            return
        mask = _mask_for(qi, ki, bq, bk, causal=causal, true_sq=true_sq,
                         true_sk=true_sk, q_off=q_off, k_off=k_off,
                         qseg=qseg, kseg=kseg, transposed=True) \
            if body is MASKED else None
        for t in range(n):
            keep = _keep_tile(sd_ref, qo_ref, ko_ref, qi, ki, bq, bk, b,
                              h * n + t if rows else h,
                              dropout_p=dropout_p, n_h=n_h, interp=interp,
                              transposed=True) if dropout_p > 0.0 else None
            _attend_tile(qs[t], k, v, *state[t],
                         scale=None if fold else scale,
                         bias=bias_ref[0, 0] if has_bias else None,
                         mask=mask, keep=keep, dropout_p=dropout_p,
                         own=own[t])

    def finish():
        for t in range(n):
            a, m, l_ = state[t]
            l = l_[...]
            safe = jnp.where(l > 0.0, l, 1.0)
            if rows:
                a[...] = a[...] / safe
            else:
                o_ref[0, 0] = (a[...] / safe).astype(o_ref.dtype)
            # finite NEG_INF sentinel for empty rows keeps ring merges exact
            lse_ref[0, t, 0] = jnp.where(l > 0.0, m[...] + jnp.log(safe),
                                         NEG_INF)
        if rows:
            o_ref[0] = acc[...].T.astype(o_ref.dtype)

    _over_key_tiles(tile, row, init, finish, block_k=block_k, qi=qi, bq=bq,
                    bk=bk, n_k=n_k, true_sq=true_sq, true_sk=true_sk,
                    q_off=q_off, k_off=k_off, causal=causal,
                    mask_all=has_segs or dropout_p > 0.0)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                   qo_ref, ko_ref, *rest,
                   scale, causal, true_sq, true_sk, has_segs, has_bias,
                   n_k, block_k=None, dropout_p=0.0, n_h=0, interp=False,
                   heads=0):
    """Resident (``block_k`` given) or grid form, and HEADS or ROWS
    layout (``heads`` > 0), as `_fwd_kernel`. ROWS: q AND dO are zeroed
    a head (both are contracted over the lanes, both read once a
    program), each head's ``ds·k`` keeps its own lanes and the block's
    heads share the one (bq, 128) accumulator; the dq block goes to the
    q lanes of a (B, S, 3·H·D) array that the dk/dv call completes. And
    δ = Σ dO·out is MADE here, once a program: ``dd_ref`` is the
    forward's ``out`` block, beside dO as it lies (in XLA a head's 64
    lanes summed out of a (B, S, H·D) array cost a float32 product
    written, a transposing copy and a reduce; here one turn of a
    (bq, 128) tile), written as the dense rows the dk/dv call reads."""
    sd_ref, qseg_ref, kseg_ref, bias_ref, rest = _split_refs(
        rest, has_segs, has_bias, dropout_p)
    rows, n = heads > 0, max(heads, 1)
    if rows:
        dq_ref, delta_ref, dq_acc = rest
    else:
        dq_ref, dq_acc = rest
    resident = block_k is not None
    lead = (0,) * (len(q_ref.shape) - 2)
    b, h, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bq = q_ref.shape[-2]
    bk = block_k if resident else k_ref.shape[-2]
    q_off, k_off = qo_ref[0, 0], ko_ref[0, 0]
    fold = _exact_scale(scale)

    def delta():
        # (dO·out)ᵀ: a head's δ is the sum down its own rows, one dense
        # (1, bq) row: what the dk/dv call reads, and `_as_col`'s input
        d = _LANES // n
        prod = (do_ref[0].astype(jnp.float32)
                * dd_ref[0].astype(jnp.float32)).T
        dds = tuple(jnp.sum(prod[t * d:(t + 1) * d], axis=0, keepdims=True)
                    for t in range(n))
        for t in range(n):
            delta_ref[0, t, 0] = dds[t]
        return dds

    def row():
        # folded: s = (q·scale)kᵀ here and dq = (Σ ds·k)·scale at the end
        return (_head_lanes(q_ref[lead] * scale if fold else q_ref[lead],
                            n),
                _head_lanes(do_ref[lead], n),
                tuple(_as_col(lse_ref[0, t, 0]) for t in range(n)),
                tuple(_as_col(dd) for dd in (
                    delta() if rows else [dd_ref[0, 0, 0]])),
                qseg_ref[0] if has_segs else None)

    def init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def tile(ki, body, qs, dos, lses, dds, qseg):
        if resident:
            k, v = _tile_of(k_ref, ki, bk), _tile_of(v_ref, ki, bk)
            kseg = kseg_ref[0, ki] if has_segs else None
        else:
            k, v = k_ref[0, 0], v_ref[0, 0]
            kseg = kseg_ref[0, 0] if has_segs else None

        def part(at, k, v, live):
            # query rows ``at`` of the tile against the keys ``k``, ``v``;
            # ``live``: what zeroes the dead scores' p, None where none is
            dqs = []
            for t in range(n):
                s = jax.lax.dot_general(qs[t][at], k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                if not fold:
                    s = s * scale
                if has_bias:
                    s = s + bias_ref[0, 0].astype(jnp.float32)
                p = jnp.exp(s - lses[t][at])
                if live is not None:
                    p = live(p)
                dp = jax.lax.dot_general(dos[t][at], v,
                                         (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                if dropout_p > 0.0:
                    # out = Σ drop∘softmax(s)·v with drop a CONSTANT mask ⇒
                    # ds = p·(drop·dp − δ + dlse): the recomputed mask
                    # scales only the dp term (δ already carries the dropped
                    # weights through do·out); dd is δ − dlse
                    keep = _keep_tile(sd_ref, qo_ref, ko_ref, qi, ki, bq, bk,
                                      b, h * n + t if rows else h,
                                      dropout_p=dropout_p, n_h=n_h,
                                      interp=interp)
                    dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_p)), 0.0)
                ds = p * (dp - dds[t][at])
                if not fold:
                    ds = ds * scale
                if t == n - 1:
                    acc = dq_acc[at]
                dqs.append(jax.lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            dq_acc[at] = acc + _own_lanes(dqs)

        if body is DIAGONAL:
            # query rows j against the keys 0..(j+1)·g they can see
            g = DIAG_SUB
            tri = _triangle(g)
            for j in range(bq // g):
                w = (j + 1) * g
                part(slice(j * g, w), k[:w], v[:w],
                     lambda p: _fill_dead(p, tri, 0.0, 1, last=True))
            return

        @functools.cache   # one mask a tile, built where a head first asks
        def mask():
            return _mask_for(qi, ki, bq, bk, causal=causal, true_sq=true_sq,
                             true_sk=true_sk, q_off=q_off, k_off=k_off,
                             qseg=qseg, kseg=kseg)

        part(..., k, v, (lambda p: jnp.where(mask(), p, 0.0))
             if body is MASKED else None)

    def finish():
        dq = dq_acc[...] * scale if fold else dq_acc[...]
        dq_ref[lead] = dq.astype(dq_ref.dtype)

    _over_key_tiles(tile, row, init, finish, block_k=block_k, qi=qi, bq=bq,
                    bk=bk, n_k=n_k, true_sq=true_sq, true_sk=true_sk,
                    q_off=q_off, k_off=k_off, causal=causal,
                    mask_all=has_segs or dropout_p > 0.0)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                    qo_ref, ko_ref, *rest,
                    scale, causal, true_sq, true_sk, has_segs, has_bias,
                    n_q, group, block_q=None, dropout_p=0.0, n_h=0,
                    interp=False, heads=0):
    """dk/dv of one key tile, accumulated over the GQA group's query
    heads and their query tiles in VMEM scratch and written ONCE at Hkv
    granularity — no (B, Hq, Sk, D) fp32 partials in HBM, each k/v tile
    fetched once a group. ``block_q`` given: the RESIDENT form, grid
    (b, hkv, ki), the group's whole Q, dO, lse and δ − dlse rows in their
    refs and the pass over (gi, qi) a loop in here (`_query_tiles`);
    else the GRID form, (b, hkv, ki, gi, qi).

    The score tile is computed TRANSPOSED, (bk, bq) with the keys down
    the sublanes: dv += pᵀ·dO and dk += dsᵀ·q then take their left
    operand as it lies (in (bq, bk) both would be turned in the
    transpose unit, every tile), and a query's lse and δ − dlse are
    the (1, bq) rows they are stored as.

    ``heads`` > 0: the ROWS layout (resident only), grid (b, j, ki, c)
    over lane blocks of ``heads`` whole heads. k AND v are zeroed a
    head, each head's ``pᵀ·dO`` and ``dsᵀ·q`` keep their own lanes.
    dk and dv are two lane ranges of ONE (B, S, 3·H·D) output and a
    call's output has one block a step, so a key tile takes two steps:
    ``c`` = 0 computes both and writes dk, ``c`` = 1 only hands the dv
    accumulator to the block the output's index map has moved to the v
    lanes; the operands' blocks at ``c`` = 1 are already the NEXT key
    tile's (`_dkv_specs`), fetched under this tile's work. The output
    IS the dq call's array (``rest[0]``, aliased, never read here): its
    q lanes are kept."""
    sd_ref, qseg_ref, kseg_ref, bias_ref, rest = _split_refs(
        rest, has_segs, has_bias, dropout_p)
    rows, n = heads > 0, max(heads, 1)
    if rows:
        _, dkv_ref, dk_acc, dv_acc = rest
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    resident = block_q is not None
    lead = (0,) * (len(k_ref.shape) - 2)
    b, hkv, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bq = block_q if resident else q_ref.shape[2]
    bk = k_ref.shape[-2]
    q_off, k_off = qo_ref[0, 0], ko_ref[0, 0]
    fold = _exact_scale(scale)
    nt = (((1,), (1,)), ((), ()))     # x · yᵀ
    nn = (((1,), (0,)), ((), ()))     # x · y

    def col():
        # folded: s = (k·scale)qᵀ here and dk = (Σ dsᵀ·q)·scale at the end
        return (_head_lanes(k_ref[lead] * scale if fold else k_ref[lead],
                            n),
                _head_lanes(v_ref[lead], n),
                kseg_ref[0] if has_segs else None)            # (bk, 1)

    def init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(gi, qi, body, ks, vs, kseg):
        if rows:
            at = (0, _rows(qi, bq), slice(None))
        elif resident:
            at = (0, gi, _rows(qi, bq), slice(None))
        else:
            at = (0, 0)
        if resident:
            qseg = qseg_ref[0, qi] if has_segs else None      # (1, bq)
        else:
            qseg = qseg_ref[0, 0] if has_segs else None
        q, do = q_ref[at], do_ref[at]

        def part(keys, qrs, q, do, live):
            # key rows ``keys`` of the tile against its queries ``qrs``
            # (where their statistics lie in a (1, bq) row; ``q``, ``do``
            # theirs: the columns of the transposed tile);
            # ``live``: what zeroes the dead scores' p, None where none is
            dvs, dks = [], []
            for t in range(n):
                # head t's statistics: its (1, bq) rows
                stat = (0, t if rows else gi, qi) if resident else (0, 0, 0)
                s = jax.lax.dot_general(ks[t][keys], q, nt,
                                        preferred_element_type=jnp.float32)
                if not fold:
                    s = s * scale
                if has_bias:
                    s = s + bias_ref[0, 0].astype(jnp.float32).T
                p = jnp.exp(s - lse_ref[stat + qrs])
                if live is not None:
                    p = live(p)
                dp = jax.lax.dot_general(vs[t][keys], do, nt,
                                         preferred_element_type=jnp.float32)
                p_av = p
                if dropout_p > 0.0:
                    # q head hkv·group + gi (ROWS: the block's head t) — the
                    # SAME salt, and the same (bq, bk) draw turned over, the
                    # forward used for this (b, h, qi, ki)
                    keep = _keep_tile(
                        sd_ref, qo_ref, ko_ref, qi, ki, bq, bk, b,
                        hkv * n + t if rows else hkv * group + gi,
                        dropout_p=dropout_p, n_h=n_h, interp=interp,
                        transposed=True)
                    inv = 1.0 / (1.0 - dropout_p)
                    p_av = jnp.where(keep, p * inv, 0.0)  # dv: DROPPED probs
                    dp = jnp.where(keep, dp * inv, 0.0)
                if t == n - 1:
                    dv = dv_acc[keys]
                dvs.append(jax.lax.dot_general(              # p_avᵀ · do
                    p_av.astype(do.dtype), do, nn,
                    preferred_element_type=jnp.float32))
                if t == n - 1:
                    dv_acc[keys] = dv + _own_lanes(dvs)
                ds = p * (dp - dd_ref[stat + qrs])              # δ − dlse
                if not fold:
                    ds = ds * scale
                if t == n - 1:
                    dk = dk_acc[keys]
                dks.append(jax.lax.dot_general(              # dsᵀ · q
                    ds.astype(q.dtype), q, nn,
                    preferred_element_type=jnp.float32))
            dk_acc[keys] = dk + _own_lanes(dks)

        if body is DIAGONAL:
            # key rows i against the queries i·g.. that can see them
            g = DIAG_SUB
            tri = _triangle(g, transposed=True)
            for i in range(bk // g):
                part(slice(i * g, (i + 1) * g),
                     (slice(None), slice(i * g, bq)), q[i * g:], do[i * g:],
                     lambda p: _fill_dead(p, tri, 0.0, 1, last=False))
            return

        @functools.cache   # one mask a tile, built where a head first asks
        def mask():
            return _mask_for(qi, ki, bq, bk, causal=causal, true_sq=true_sq,
                             true_sk=true_sk, q_off=q_off, k_off=k_off,
                             qseg=qseg, kseg=kseg, transposed=True)

        part(..., (), q, do, (lambda p: jnp.where(mask(), p, 0.0))
             if body is MASKED else None)

    def finish():
        dk = dk_acc[...] * scale if fold else dk_acc[...]
        if rows:    # dv waits in its accumulator for the step c = 1
            dkv_ref[0] = dk.astype(dkv_ref.dtype)
            return
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)

    if resident:
        def run():
            lo, a, b_, hi = _query_tiles(ki, bq, bk, true_sq, true_sk,
                                         q_off, k_off, causal)
            mask_all = has_segs or dropout_p > 0.0
            if mask_all:
                a = b_ = hi   # those operands are per tile by nature
            diag = _diag_sub(bq, bk, causal, not mask_all) > 0
            init()
            ops = col()

            def head(gi):
                # the diagonal crosses [lo, a): its corner tile DIAGONAL
                _loop(lo, a, lambda qi: _edge_tile(
                    lambda body: tile(gi, qi, body, *ops),
                    _on_diagonal(qi, ki, bq, bk, true_sq, true_sk, q_off,
                                 k_off) if diag else None))
                _loop(a, b_, lambda qi: tile(gi, qi, INTERIOR, *ops))
                _loop(b_, hi, lambda qi: tile(gi, qi, MASKED, *ops))

            _loop(0, group, head)
            finish()

        if not rows:
            return run()
        c = pl.program_id(3)
        pl.when(c == 0)(run)

        @pl.when(c == 1)
        def _():
            dkv_ref[0] = dv_acc[...].astype(dkv_ref.dtype)
        return
    gi, qi = pl.program_id(3), pl.program_id(4)
    pl.when((gi == 0) & (qi == 0))(init)
    if causal:
        pl.when((qi * bq + bq - 1 + q_off) >= (ki * bk + k_off))(
            lambda: tile(gi, qi, MASKED, *col()))
    else:
        tile(gi, qi, MASKED, *col())
    pl.when((gi == group - 1) & (qi == n_q - 1))(finish)


def _dbias_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
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
        qseg, kseg = qseg_ref[0], kseg_ref[0, 0]
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
        db_acc[...] += p * (dp - dd_ref[0, 0])

    if causal:
        pl.when((ki * bk + ko_ref[0, 0])
                <= (qi * bq + bq - 1 + qo_ref[0, 0]))(compute)
    else:
        compute()

    @pl.when(r == n_r - 1)
    def _():
        dbias_ref[0, 0] = db_acc[...].astype(dbias_ref.dtype)


def _sizes(B, Hq, Hkv, Sq, Sk, D, es, block_q, block_k, heads=0):
    """A call's sizes: true and tile sizes, tile counts, padded head
    width, element size; ``heads`` > 0: the ROWS layout with so many
    heads to a 128-lane block, 0 the HEADS layout (``per_block``: 1)."""
    bq, bk = _block(Sq, block_q), _block(Sk, block_k)
    return dict(B=B, Hq=Hq, Hkv=Hkv, group=Hq // Hkv, Sq=Sq, Sk=Sk, D=D,
                bq=bq, bk=bk, n_q=-(-Sq // bq), n_k=-(-Sk // bk),
                Dp=-(-D // _LANES) * _LANES, es=es, heads=heads,
                per_block=max(heads, 1))


def _geometry(q, k, block_q, block_k, heads=0):
    """`_sizes` from the operands' shapes alone: ``q``, ``k`` (B, H, S, D)
    or, ``heads`` > 0, ``q`` the packed (B, S, 3·H·D) array."""
    es = jnp.dtype(q.dtype).itemsize
    if heads:
        B, S, W3 = q.shape
        D = _LANES // heads
        return _sizes(B, W3 // (3 * D), W3 // (3 * D), S, S, D, es,
                      block_q, block_k, heads)
    B, Hq, Sq, D = q.shape
    return _sizes(B, Hq, k.shape[1], Sq, k.shape[2], D, es, block_q,
                  block_k)


def _prep(q, k, v, qseg, kseg, has_segs, block_q, block_k, heads=0):
    """Pad operands to block multiples; returns padded arrays + geometry.
    ROWS (``heads`` > 0): ``q`` is the packed array, padded along S only
    where a tile is ragged, and stands for k and v too."""
    g = _geometry(q, k, block_q, block_k, heads)
    B, bq, bk, n_q, n_k = g["B"], g["bq"], g["bk"], g["n_q"], g["n_k"]
    if heads:
        qp, _ = pad_to(q, 1, max(n_q * bq, n_k * bk))
        kp = vp = qp
    else:
        qp, _ = pad_to(q, 2, bq)
        qp, _ = pad_to(qp, 3, _LANES)
        kp, _ = pad_to(k, 2, bk)
        kp, _ = pad_to(kp, 3, _LANES)
        vp, _ = pad_to(v, 2, bk)
        vp, _ = pad_to(vp, 3, _LANES)
    if has_segs:
        # each side's ids as a COLUMN (B, S, 1) and as tile ROWS
        # (B, n, 1, block), a tile's ids one index of a LEADING axis (a
        # resident loop takes tile i by a traced index there, not by a
        # traced lane offset): forward and dq read (qs column, ks rows),
        # the transposed dk/dv tile (qs rows, ks column); 2-D tiles, no
        # in-kernel transpose; pad value -1 ≠ -2 so padded q never
        # matches padded k
        qs, _ = pad_to(qseg.astype(jnp.int32), 1, bq, value=-1)
        ks, _ = pad_to(kseg.astype(jnp.int32), 1, bk, value=-2)
        qs = (qs[:, :, None], qs.reshape(B, n_q, 1, bq))
        ks = (ks[:, :, None], ks.reshape(B, n_k, 1, bk))
    else:
        qs = ks = None
    return qp, kp, vp, qs, ks, g


def _kv_resident(g, has_bias=False):
    """Whether the forward and dq kernels take the RESIDENT form: the
    (batch, kv head) row's whole K and V one VMEM block, the pass over
    key tiles a loop in the kernel that stops at the diagonal. A choice
    by shape and operand alone: the row has to fit beside the tiles
    (`vmem_model.flash_kv_row_check`; S = 1024 at Dp 128 bf16 is 1 MB of
    rows, S = 16 384 is 16 MB and does not), and a bias tile is per
    (qi, ki) by nature (dbias has its own grid), so it keeps the grid."""
    from apex1_tpu.vmem_model import budget_bytes, flash_kv_row_check
    return not has_bias and flash_kv_row_check(
        {"block_q": g["bq"], "block_k": g["bk"]},
        {"Dp": g["Dp"], "Skp": g["n_k"] * g["bk"],
         "heads": g["per_block"]}, g["es"], budget_bytes())[0]


def _q_resident(g, has_bias=False):
    """The dk/dv kernel's RESIDENT form: the GQA group's whole Q, dO, lse,
    δ and dlse rows in VMEM and the pass over (gi, qi) a loop that starts
    at the diagonal (`vmem_model.flash_q_row_check`)."""
    from apex1_tpu.vmem_model import budget_bytes, flash_q_row_check
    return not has_bias and flash_q_row_check(
        {"block_q": g["bq"], "block_k": g["bk"]},
        {"Dp": g["Dp"], "Sqp": g["n_q"] * g["bq"], "group": g["group"],
         "heads": g["per_block"]}, g["es"], budget_bytes())[0]


def _vmem(blk, imap):
    return pl.BlockSpec(blk, imap, memory_space=pltpu.VMEM)


def _common_specs(g, resident=False, transposed=False):
    """Block specs shared by the fwd and dq kernels — grid (b, h, qi, ki),
    or (b, h, qi) with the row's K/V (and key segment ids) whole in the
    ``resident`` form. The segment ids as dq's (bq, bk) tile takes them,
    queries a column and keys a row, or, ``transposed`` (the forward's
    (bk, bq) tile), queries a row and keys a column (`_prep` makes both).
    ROWS layout (``g["heads"]`` > 0, resident): grid (b, j, qi) over the
    lane blocks of ONE packed array, ``kv_spec`` the pair (k's, v's)."""
    group, bq, bk, Dp = g["group"], g["bq"], g["bk"], g["Dp"]
    Skp, n_k = g["n_k"] * bk, g["n_k"]
    q_spec = _vmem((1, 1, bq, Dp), lambda b, h, qi, *_: (b, h, qi, 0))
    stat_spec = _vmem((1, 1, 1, 1, bq),
                      lambda b, h, qi, *_: (b, h, qi, 0, 0))
    if transposed:
        qseg_spec = _vmem((1, 1, 1, bq), lambda b, h, qi, *_: (b, qi, 0, 0))
    else:
        qseg_spec = _vmem((1, bq, 1), lambda b, h, qi, *_: (b, qi, 0))
    if resident:
        kv_spec = _vmem((1, 1, Skp, Dp),
                        lambda b, h, qi: (b, h // group, 0, 0))
        kseg_spec = (_vmem((1, Skp, 1), lambda b, h, qi: (b, 0, 0))
                     if transposed else
                     _vmem((1, n_k, 1, bk), lambda b, h, qi: (b, 0, 0, 0)))
    else:
        kv_spec = _vmem((1, 1, bk, Dp),
                        lambda b, h, qi, ki: (b, h // group, ki, 0))
        kseg_spec = (_vmem((1, bk, 1), lambda b, h, qi, ki: (b, ki, 0))
                     if transposed else
                     _vmem((1, 1, 1, bk), lambda b, h, qi, ki: (b, ki, 0, 0)))
    if g["heads"]:
        n, W = g["heads"], g["Hq"] // g["heads"]
        q_spec = _vmem((1, bq, _LANES), lambda b, j, qi: (b, qi, j))
        kv_spec = tuple(_vmem((1, Skp, _LANES),
                              lambda b, j, qi, at=at: (b, 0, at + j))
                        for at in (W, 2 * W))
        stat_spec = _vmem((1, n, 1, 1, bq),
                          lambda b, j, qi: (b, j, qi, 0, 0))
    off_spec = pl.BlockSpec((1, 1), lambda *_: (0, 0),
                            memory_space=pltpu.SMEM)
    return q_spec, kv_spec, stat_spec, off_spec, qseg_spec, kseg_spec


def _dkv_specs(g, resident=False):
    """Block specs for the dk/dv kernel — grid (b, hkv, ki, gi, qi): the
    q head is ``hkv * group + gi``; dk/dv blocks index (b, hkv, ki). In
    the ``resident`` form, grid (b, hkv, ki), the q-side blocks are the
    group's whole rows. The statistics are (B, Hq, n_q, 1, bq) arrays
    and the query segment ids (B, n_q, 1, bq): a query tile's values one
    ROW along the lanes (`_stat_rows`), as the transposed tile takes.
    ROWS layout (``g["heads"]`` > 0, resident): grid (b, j, ki, c) over
    the lane blocks of ONE packed array, ``kv_spec`` the pair (k's, v's);
    every operand's block at step (.., ki, c = 1) is the NEXT key tile's
    (``ahead``), the output's block (the caller's) is dk's then dv's."""
    group, bq, bk, Dp, n_q = g["group"], g["bq"], g["bk"], g["Dp"], g["n_q"]
    kv_spec = _vmem((1, 1, bk, Dp), lambda b, hkv, ki, *_: (b, hkv, ki, 0))
    kseg_spec = _vmem((1, bk, 1), lambda b, hkv, ki, *_: (b, ki, 0))
    if resident:
        q_spec = _vmem((1, group, n_q * bq, Dp),
                       lambda b, hkv, ki: (b, hkv, 0, 0))
        stat_spec = _vmem((1, group, n_q, 1, bq),
                          lambda b, hkv, ki: (b, hkv, 0, 0, 0))
        qseg_spec = _vmem((1, n_q, 1, bq),
                          lambda b, hkv, ki, *_: (b, 0, 0, 0))
    else:
        q_spec = _vmem(
            (1, 1, bq, Dp),
            lambda b, hkv, ki, gi, qi: (b, hkv * group + gi, qi, 0))
        stat_spec = _vmem(
            (1, 1, 1, 1, bq),
            lambda b, hkv, ki, gi, qi: (b, hkv * group + gi, qi, 0, 0))
        qseg_spec = _vmem((1, 1, 1, bq),
                          lambda b, hkv, ki, gi, qi: (b, qi, 0, 0))
    if g["heads"]:
        n, W, n_k = g["heads"], g["Hq"] // g["heads"], g["n_k"]
        last = g["B"] * W * n_k - 1

        def ahead(b, j, ki, c):
            # the step c = 1 reads no operand and is over at once: a
            # fetch it started for the next key tile's step would stand
            # exposed (2.1 ms a step of GPT-2 medium: PERF.md §6, PR 41).
            # Its blocks ARE the next tile's, so the long step c = 0
            # before it fetches them and nothing moves after it
            at = jnp.minimum((b * W + j) * n_k + ki + c, last)
            return at // (W * n_k), at // n_k % W, at % n_k

        def spec(blk, imap):
            return _vmem(blk, lambda *ids: imap(*ahead(*ids)))

        q_spec = spec((1, n_q * bq, _LANES), lambda b, j, ki: (b, 0, j))
        kv_spec = tuple(spec((1, bk, _LANES),
                             lambda b, j, ki, at=at: (b, ki, at + j))
                        for at in (W, 2 * W))
        stat_spec = spec((1, n, n_q, 1, bq),
                         lambda b, j, ki: (b, j, 0, 0, 0))
        qseg_spec = spec((1, n_q, 1, bq), lambda b, j, ki: (b, 0, 0, 0))
        kseg_spec = spec((1, bk, 1), lambda b, j, ki: (b, ki, 0))
    off_spec = pl.BlockSpec((1, 1), lambda *_: (0, 0),
                            memory_space=pltpu.SMEM)
    return q_spec, kv_spec, stat_spec, off_spec, qseg_spec, kseg_spec


def _stat_rows(x, g, value=0.0):
    """A (B, Hq, Sq) per-query statistic as the kernels take it: fp32,
    padded to whole query tiles, (B, Hq, n_q, 1, bq), a tile's values
    one dense row along the lanes."""
    xp, _ = pad_to(x.astype(jnp.float32), 2, g["bq"], value=value)
    return xp.reshape(*xp.shape[:2], g["n_q"], 1, g["bq"])


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
    return _flash_fwd_impl(q, k, v, qseg, kseg, q_off, k_off,
                           scale, causal, has_segs, block_q,
                           block_k, dropout_p=dropout_p, seed=seed)


def _drop_kw(dropout_p, g, interpret):
    """Kernel kwargs for the dropout path (none at p == 0)."""
    if dropout_p <= 0.0:
        return {}
    return dict(dropout_p=dropout_p, n_h=g["Hq"], interp=interpret)


def _flash_fwd_impl(q, k, v, qseg, kseg, q_off, k_off,
                    scale, causal, has_segs, block_q, block_k,
                    bias=None, dropout_p=0.0, seed=None):
    g = _geometry(q, k, block_q, block_k)
    return _fwd_call(q, k, v, qseg, kseg, q_off, k_off, seed, bias,
                     scale=scale, causal=causal, has_segs=has_segs,
                     block_q=block_q, block_k=block_k, dropout_p=dropout_p,
                     resident=_kv_resident(g, bias is not None),
                     interpret=interpret_mode())


# A model's layers call these with the same shapes: jitted, they share
# one traced kernel and one lowering of it (24 layers' kernels, each with
# its loops and two tile bodies, are seconds of a step executable's
# set-up otherwise). What a trace depends on besides shapes — the form
# and the interpreter — is a static argument, decided outside.
_STATIC = ("scale", "causal", "has_segs", "block_q", "block_k",
           "dropout_p", "resident", "interpret", "heads")


def _emit_form(g, resident, causal, plain):
    """The form a call took, said once where it is traced (these calls
    are jitted: once a shape and form) on the `obs` spine; with it the
    tiles a head's resident loop runs by body (`tile_plan` at offsets 0;
    not ``plain``, segment ids or dropout: every visited tile MASKED) and
    the DIAGONAL body's square width, 0 where the call has none."""
    from apex1_tpu.obs import spine
    interior, masked, diagonal, _ = tile_plan(
        g["Sq"], g["Sk"], g["bq"], g["bk"], causal=causal)
    sub = _diag_sub(g["bq"], g["bk"], causal, plain) if resident[0] else 0
    if not plain:
        interior, masked = 0, interior + masked
    spine.emit("counter", "flash/form", value=1,
               layout="rows" if g["heads"] else "heads",
               heads_per_block=g["per_block"],
               resident_kv=bool(resident[0]), resident_q=bool(resident[1]),
               block_q=g["bq"], block_k=g["bk"], diag_sub=sub,
               tiles_interior=interior, tiles_masked=masked,
               tiles_diagonal=diagonal if sub else 0)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(q, k, v, qseg, kseg, q_off, k_off, seed, bias, *, scale,
              causal, has_segs, block_q, block_k, dropout_p, resident,
              interpret, heads=0):
    """``heads`` > 0, the ROWS layout: ``q`` is the packed (B, S, 3·H·D)
    array, ``k`` and ``v`` None; returns ``out`` (B, S, H·D) and lse AS
    THE KERNELS KEEP IT, (B, H, n_q, 1, bq)."""
    qp, kp, vp, qs, ks, g = _prep(q, k, v, qseg, kseg, has_segs,
                                  block_q, block_k, heads)
    has_bias = bias is not None
    _emit_form(g, (resident, _q_resident(g, has_bias)), causal,
               not (has_segs or has_bias or dropout_p > 0.0))
    q_spec, kv_spec, stat_spec, off_spec, qseg_spec, kseg_spec = \
        _common_specs(g, resident, transposed=True)
    k_spec, v_spec = kv_spec if heads else (kv_spec, kv_spec)
    in_specs = [q_spec, k_spec, v_spec, off_spec, off_spec]
    args = [qp, kp, vp, *_off_arrays(q_off, k_off)]
    if dropout_p > 0.0:
        in_specs += [off_spec]
        args += [jnp.asarray(seed, jnp.int32).reshape(1, 1)]
    if has_segs:
        in_specs += [qseg_spec, kseg_spec]
        args += [qs[1], ks[0]]
    if has_bias:
        bp, Bb, Hb = _prep_bias(bias, g)
        in_specs += [_bias_spec(g, Bb, Hb)]
        args += [bp]
    Sqp = g["n_q"] * g["bq"]
    # the kernel's accumulator is outᵀ: written as it lies, (B, Hq, Dp, Sqp),
    # and turned by XLA with the slice it makes anyway; ROWS: turned once
    # a program in the kernel and written where the caller reads it
    out_p, lse_p = kernel_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          true_sq=g["Sq"], true_sk=g["Sk"],
                          has_segs=has_segs, has_bias=has_bias,
                          n_k=g["n_k"],
                          block_k=g["bk"] if resident else None,
                          heads=heads, **_drop_kw(dropout_p, g, interpret)),
        name="flash_fwd",
        grid=(g["B"], g["Hq"] // g["per_block"], g["n_q"])
        + (() if resident else (g["n_k"],)),
        in_specs=in_specs,
        out_specs=(q_spec if heads else
                   _vmem((1, 1, g["Dp"], g["bq"]),
                         lambda b, h, qi, *_: (b, h, 0, qi)), stat_spec),
        out_shape=(
            out_struct((g["B"], qp.shape[1], g["Hq"] * g["D"]) if heads
                       else (g["B"], g["Hq"], g["Dp"], Sqp), q.dtype,
                       qp, kp, vp),
            out_struct((g["B"], g["Hq"], g["n_q"], 1, g["bq"]),
                       jnp.float32, qp, kp, vp)),
        scratch_shapes=[pltpu.VMEM((g["Dp"], g["bq"]), jnp.float32)]
        + [pltpu.VMEM((heads, 1, g["bq"]) if heads else (1, g["bq"]),
                      jnp.float32)] * 2,
        interpret=interpret,
    )(*args)
    if heads:
        return out_p[:, :g["Sq"]], lse_p
    out = jnp.swapaxes(out_p[:, :, :g["D"], :g["Sq"]], 2, 3)
    lse = lse_p.reshape(g["B"], g["Hq"], Sqp)[:, :, :g["Sq"]]
    return out, lse


def _flash_fwd(q, k, v, qseg, kseg, q_off, k_off, seed,
               scale, causal, has_segs, block_q, block_k, dropout_p):
    out, lse = _flash_fwd_impl(q, k, v, qseg, kseg, q_off, k_off,
                               scale, causal, has_segs, block_q, block_k,
                               dropout_p=dropout_p, seed=seed)
    return (out, lse), (q, k, v, qseg, kseg, q_off, k_off, seed, out, lse)


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
    q, k, v, qseg, kseg, q_off, k_off, seed = res[:8]
    g = _geometry(q, k, block_q, block_k)
    has_bias = bias is not None
    dq, dk, dv, dbias = _bwd_call(
        res, cts, bias, scale=scale, causal=causal, has_segs=has_segs,
        block_q=block_q, block_k=block_k, dropout_p=dropout_p, cast=cast,
        resident=(_kv_resident(g, has_bias), _q_resident(g, has_bias)),
        interpret=interpret_mode())
    f0 = lambda x: np.zeros(jnp.shape(x), dtype=jax.dtypes.float0)
    grads = (dq, dk, dv,
             f0(qseg), f0(kseg), f0(q_off), f0(k_off), f0(seed))
    return grads, dbias


@functools.partial(jax.jit, static_argnames=_STATIC + ("cast",))
def _bwd_call(res, cts, bias, *, scale, causal, has_segs, block_q, block_k,
              dropout_p, cast, resident, interpret, heads=0):
    """(dq, dk, dv, dbias) by the dq, dk/dv and (with a bias) dbias
    kernels; ``resident`` is the pair (dq's form, dk/dv's). ``heads`` >
    0, the ROWS layout: ``res`` holds the packed array for q (None for k
    and v), ``out`` (B, S, H·D) and lse as the kernels keep it; dq, dk
    and dv come back as ONE (B, S, 3·H·D) array, in dq's place."""
    q, k, v, qseg, kseg, q_off, k_off, seed, out, lse = res
    dout, dlse = cts
    qp, kp, vp, qs, ks, g = _prep(q, k, v, qseg, kseg, has_segs,
                                  block_q, block_k, heads)
    Sqp = g["n_q"] * g["bq"]
    if heads:
        # δ is the dq kernel's to make, from dO and out as they lie
        dop, _ = pad_to(dout.astype(q.dtype), 1, qp.shape[1])
        outp, _ = pad_to(out, 1, qp.shape[1])
        stat_args = [lse, outp, *_off_arrays(q_off, k_off)]
    else:
        dop, _ = pad_to(dout.astype(q.dtype), 2, g["bq"])
        dop, _ = pad_to(dop, 3, g["Dp"])
        # δ_i = Σ_d dout·out — padded regions are zero so no masking needed
        delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)
        # ds = p·(dp − δ + dlse): the two row terms as one, δ − dlse (δ
        # itself where lse feeds nothing but the ring's merge, dlse = 0);
        # rows whose lse is the NEG_INF sentinel (no live key; the
        # padding) give p = 0
        stat_args = [_stat_rows(lse, g, NEG_INF),
                     _stat_rows(delta - dlse.astype(jnp.float32), g),
                     *_off_arrays(q_off, k_off)]
    n_seed = 0
    if dropout_p > 0.0:
        stat_args += [jnp.asarray(seed, jnp.int32).reshape(1, 1)]
        n_seed = 1  # one extra SMEM scalar operand per launch
    has_bias = bias is not None
    if has_bias:
        bp, Bb, Hb = _prep_bias(bias, g)
    kern = dict(scale=scale, causal=causal, true_sq=g["Sq"],
                true_sk=g["Sk"], has_segs=has_segs,
                **_drop_kw(dropout_p, g, interpret))

    # dq: grid (b, h, qi, ki), key axis innermost; resident: (b, h, qi);
    # ROWS: (b, j, qi), the block written to the q lanes of a fresh
    # (B, S, 3·H·D) array
    resident, q_resident = resident
    q_spec, kv_spec, stat_spec, off_spec, qseg_spec, kseg_spec = \
        _common_specs(g, resident)
    k_spec, v_spec = kv_spec if heads else (kv_spec, kv_spec)
    in_specs = [q_spec, k_spec, v_spec, q_spec, stat_spec,
                q_spec if heads else stat_spec, off_spec, off_spec]
    in_specs += [off_spec] * n_seed
    args = [qp, kp, vp, dop] + stat_args
    if has_segs:
        in_specs += [qseg_spec, kseg_spec]
        args += [qs[0], ks[1]]
    if has_bias:
        in_specs += [_bias_spec(g, Bb, Hb)]
        args += [bp]
    stats = out_struct((g["B"], g["Hq"], g["n_q"], 1, g["bq"]), jnp.float32,
                       qp, dop)
    dq = kernel_call(
        functools.partial(_bwd_dq_kernel, n_k=g["n_k"],
                          block_k=g["bk"] if resident else None,
                          has_bias=has_bias, heads=heads, **kern),
        name="flash_dq",
        grid=(g["B"], g["Hq"] // g["per_block"], g["n_q"])
        + (() if resident else (g["n_k"],)),
        in_specs=in_specs,
        out_specs=(q_spec, stat_spec) if heads else q_spec,
        out_shape=(out_struct(qp.shape, q.dtype, qp, dop), stats) if heads
        else out_struct((g["B"], g["Hq"], Sqp, g["Dp"]), q.dtype,
                        qp, kp, vp, dop),
        scratch_shapes=[pltpu.VMEM((g["bq"], g["Dp"]), jnp.float32)],
        interpret=interpret,
    )(*args)
    if heads:
        dq, stat_args[1] = dq
    else:
        dq = dq[:, :, :g["Sq"], :g["D"]]

    # dk/dv: grid (b, hkv, ki, gi, qi) — query axis innermost, GQA group
    # axis above it, so group accumulation happens in VMEM scratch and the
    # outputs are written at Hkv granularity (no Hq-sized fp32 partials);
    # resident: (b, hkv, ki), the same order as a loop in the kernel. The
    # kernel writes what the caller keeps: k.dtype where the caller would
    # cast (the accumulator's one rounding, made in VMEM), fp32 otherwise.
    # ROWS: (b, j, ki, c), dk (c = 0) and dv (c = 1) into the k and v
    # lanes of dq's array, handed over as an operand that stays in HBM
    # and is the output
    q_spec, kv_spec, stat_spec, off_spec, qseg_spec, kseg_spec = \
        _dkv_specs(g, q_resident)
    k_spec, v_spec = kv_spec if heads else (kv_spec, kv_spec)
    in_specs = [q_spec, k_spec, v_spec, q_spec, stat_spec, stat_spec,
                off_spec, off_spec]
    in_specs += [off_spec] * n_seed
    args = [qp, kp, vp, dop] + stat_args
    if has_segs:
        in_specs += [qseg_spec, kseg_spec]
        args += [qs[1], ks[0]]
    if has_bias:
        in_specs += [_bias_spec(g, Bb, Hb, dkv=True)]
        args += [bp]
    Skp = g["n_k"] * g["bk"]
    if heads:
        W = g["Hq"] // heads
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)]
        args += [dq]
        out_specs = _vmem((1, g["bk"], _LANES),
                          lambda b, j, ki, c: (b, ki, (1 + c) * W + j))
        out_shape = out_struct(qp.shape, q.dtype, qp, dop)
        grid = (g["B"], W, g["n_k"], 2)
        alias = {"input_output_aliases": {len(args) - 1: 0}}
    else:
        dk_dtype, dv_dtype = ((k.dtype, v.dtype) if cast
                              else (jnp.float32, jnp.float32))
        out_specs = (kv_spec, kv_spec)
        out_shape = (
            out_struct((g["B"], g["Hkv"], Skp, g["Dp"]), dk_dtype,
                       qp, kp, vp, dop),
            out_struct((g["B"], g["Hkv"], Skp, g["Dp"]), dv_dtype,
                       qp, kp, vp, dop))
        grid = (g["B"], g["Hkv"], g["n_k"]) + (
            () if q_resident else (g["group"], g["n_q"]))
        alias = {}
    dkv = kernel_call(
        functools.partial(_bwd_dkv_kernel, n_q=g["n_q"], group=g["group"],
                          block_q=g["bq"] if q_resident else None,
                          has_bias=has_bias, heads=heads, **kern),
        name="flash_dkv",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((g["bk"], g["Dp"]), jnp.float32),
                        pltpu.VMEM((g["bk"], g["Dp"]), jnp.float32)],
        interpret=interpret,
        **alias,
    )(*args)
    if heads:
        return dkv[:, :g["Sq"]], None, None, None
    dk, dv = dkv
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
        kseg_spec_b = spec4((1, 1, 1, g["bk"]),
                            lambda bb, hb, qi, ki, r: (bidx(bb, r), ki, 0, 0))
        bias_spec_b = spec4((1, 1, g["bq"], g["bk"]),
                            lambda bb, hb, qi, ki, r: (bb, hb, qi, ki))
        db_spec = spec4((1, 1, g["bq"], g["bk"]),
                        lambda bb, hb, qi, ki, r: (bb, hb, qi, ki))
        in_specs = [q_spec_b, kv_spec_b, kv_spec_b, q_spec_b, stat_spec_b,
                    stat_spec_b, off_spec_b, off_spec_b]
        in_specs += [off_spec_b] * n_seed
        # this pass alone takes the statistics as (bq, 1) columns
        args = [qp, kp, vp, dop] + [
            x.reshape(g["B"], g["Hq"], Sqp, 1) for x in stat_args[:2]] \
            + stat_args[2:]
        if has_segs:
            in_specs += [qseg_spec_b, kseg_spec_b]
            args += [qs[0], ks[1]]
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
            interpret=interpret,
        )(*args)
        dbias = dbias_p[:, :, :g["Sq"], :g["Sk"]]

    return dq.astype(q.dtype), dk, dv, dbias


def _flash_bwd(scale, causal, has_segs, block_q, block_k, dropout_p,
               res, cts):
    grads, _ = _flash_bwd_impl(scale, causal, has_segs, block_q, block_k,
                               res, cts, dropout_p=dropout_p)
    return grads


_flash.defvjp(_flash_fwd, _flash_bwd)


# The ROWS layout's entry (`fmha`; `flash_form` says when): the packed
# (B, S, 3·H·D) array in, ``out`` (B, S, H·D) back, and the gradient ONE
# array of the packed shape. Residuals are the array, ``out`` and lse as
# they lie: nothing padded or turned is kept.

@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(6, 13)))
def _flash_rows(qkv, qseg, kseg, q_off, k_off, seed,
                scale, causal, has_segs, block_q, block_k, dropout_p,
                heads):
    return _flash_rows_fwd(qkv, qseg, kseg, q_off, k_off, seed, scale,
                           causal, has_segs, block_q, block_k, dropout_p,
                           heads)[0]


def _flash_rows_fwd(qkv, qseg, kseg, q_off, k_off, seed,
                    scale, causal, has_segs, block_q, block_k, dropout_p,
                    heads):
    out, lse = _fwd_call(qkv, None, None, qseg, kseg, q_off, k_off, seed,
                         None, scale=scale, causal=causal,
                         has_segs=has_segs, block_q=block_q,
                         block_k=block_k, dropout_p=dropout_p,
                         resident=True, interpret=interpret_mode(),
                         heads=heads)
    return out, (qkv, None, None, qseg, kseg, q_off, k_off, seed, out, lse)


def _flash_rows_bwd(scale, causal, has_segs, block_q, block_k, dropout_p,
                    heads, res, dout):
    dqkv = _bwd_call(res, (dout, None), None, scale=scale, causal=causal,
                     has_segs=has_segs, block_q=block_q, block_k=block_k,
                     dropout_p=dropout_p, cast=True, resident=(True, True),
                     interpret=interpret_mode(), heads=heads)[0]
    f0 = lambda x: np.zeros(jnp.shape(x), dtype=jax.dtypes.float0)
    return (dqkv,) + tuple(f0(x) for x in res[3:8])


_flash_rows.defvjp(_flash_rows_fwd, _flash_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12, 13, 14))
def _flash_with_bias(q, k, v, bias, qseg, kseg, q_off, k_off, seed,
                     scale, causal, has_segs, block_q, block_k, dropout_p):
    return _flash_fwd_impl(q, k, v, qseg, kseg, q_off, k_off,
                           scale, causal, has_segs, block_q,
                           block_k, bias=bias, dropout_p=dropout_p,
                           seed=seed)


def _flash_with_bias_fwd(q, k, v, bias, qseg, kseg, q_off, k_off, seed,
                         scale, causal, has_segs, block_q, block_k,
                         dropout_p):
    out, lse = _flash_fwd_impl(q, k, v, qseg, kseg, q_off, k_off,
                               scale, causal, has_segs, block_q, block_k,
                               bias=bias, dropout_p=dropout_p, seed=seed)
    return (out, lse), (q, k, v, bias, qseg, kseg, q_off, k_off, seed,
                        out, lse)


def _flash_with_bias_bwd(scale, causal, has_segs, block_q, block_k,
                         dropout_p, res, cts):
    q, k, v, bias, qseg, kseg, q_off, k_off, seed, out, lse = res
    grads, dbias = _flash_bwd_impl(
        scale, causal, has_segs, block_q, block_k,
        (q, k, v, qseg, kseg, q_off, k_off, seed, out, lse), cts,
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


def _dropout_args(dropout_p, dropout_seed):
    """``(p, seed)`` as the kernels take them, or why not."""
    dropout_p = float(dropout_p)
    if not 0.0 <= dropout_p < 1.0:
        raise ValueError(f"dropout_p must be in [0, 1), got {dropout_p}")
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 needs an explicit int32 "
                         "dropout_seed (ops.stochastic.seed_from_key / "
                         "fold_seed at the call site)")
    return dropout_p, (jnp.asarray(dropout_seed, jnp.int32)
                       if dropout_p > 0.0 else jnp.zeros((), jnp.int32))


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
    dropout_p, seed = _dropout_args(dropout_p, dropout_seed)
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
         dropout_seed=None, q_offset=0, k_offset=0,
         block_q: int | None = None, block_k: int | None = None):
    """``apex.contrib.fmha.FMHAFun`` equivalent: packed (B, S, 3, H, D)
    QKV in, (B, S, H, D) out, varlen via ``segment_ids`` instead of
    cu_seqlens. No seqlen-512 or head-dim-64 cap — the flash kernel
    serves all sizes. ``dropout_p`` is the reference's in-kernel
    probability dropout (seeded, fused); ``q_offset``/``k_offset`` and
    ``block_q``/``block_k`` as `flash_attention`'s.

    The packed array is what a fused qkv projection leaves, (B, S, 3·H·D)
    seen as five axes, and where `flash_form` allows (whole heads to a
    128-lane block, rows that fit VMEM) the kernels read it AS IT LIES
    and write ``out`` as the output projection reads it — the ROWS
    layout: no split, pad or transpose in XLA, forward or backward, and
    the gradient one packed array. Otherwise the array is turned to
    (B, H, S, D) and `flash_attention` runs. Both reshapes around a call
    (to five axes and back) cancel in XLA."""
    B, S, _, H, D = qkv.shape
    io_dtype = qkv.dtype
    if use_pallas():
        packed = to_mosaic(qkv)
        bq, bk = _auto_blocks(D, block_q, block_k, packed.dtype, S)
        form = flash_form(H, H, S, S, D, packed=True, block_q=bq,
                          block_k=bk, dtype=packed.dtype)
        if form["layout"] == "rows":
            dropout_p, seed = _dropout_args(dropout_p, dropout_seed)
            has_segs, qseg, kseg = _norm_segments(segment_ids, S, S)
            dummy = jnp.zeros((1, 1), jnp.int32)
            out = _flash_rows(
                packed.reshape(B, S, 3 * H * D),
                qseg if has_segs else dummy, kseg if has_segs else dummy,
                q_offset, k_offset, seed,
                1.0 / float(np.sqrt(D)) if sm_scale is None
                else float(sm_scale), causal, has_segs, bq, bk, dropout_p,
                form["heads_per_block"])
            return out.reshape(B, S, H, D).astype(io_dtype)
    q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
    out = flash_attention(q, k, v, causal=causal, segment_ids=segment_ids,
                          sm_scale=sm_scale, dropout_p=dropout_p,
                          dropout_seed=dropout_seed, q_offset=q_offset,
                          k_offset=k_offset, block_q=block_q,
                          block_k=block_k)
    return out.transpose(0, 2, 1, 3)
