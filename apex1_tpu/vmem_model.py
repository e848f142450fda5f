"""The ONE per-kernel VMEM sizing model — shared by the tuning
registry, the graftlint kernel analyzer, and the AOT gate.

History: these formulas started life private to ``tuning.registry``
(gating table entries against ``core.capability.vmem_budget``), while
the RDMA reduce-scatter's sizing rule lived as prose in
``ops/fused_collective.matmul_reduce_scatter_rdma``'s docstring and a
comment beside ``tools/aot_check.py``'s compile gate. Three consumers,
three copies, zero machine checks. This module is the deduplication:

- ``tuning.registry`` builds its :class:`KernelSpec` ``check``
  callables from the ``*_check`` functions here (gating behavior pinned
  bit-identical to the pre-refactor formulas by
  ``tests/test_lint_kernels.py::TestVmemModelShared``);
- ``apex1_tpu.lint.kernels`` (graftlint APX208) prices statically
  evaluable ``pallas_call`` frames against ``budget_bytes`` — the gate
  that runs with NO jax and NO hardware;
- ``tools/aot_check.py`` sizes the RDMA gate shape through
  :func:`rdma_check` instead of restating the ``16·chunk·N`` bound in
  a comment.

Everything here is stdlib-only and jax-free: the lint CLI imports this
module through its stub-parent path (``tools/lint.py``), so nothing
below may import jax, numpy, or any ``apex1_tpu`` module that does.
The generation budgets come from ``core.capability`` (itself jax-free
at import; jax is touched only inside ``detect_generation``).

All models are GATING models, not performance models: coarse, monotone
in the block sizes, generous enough that every block shape the analytic
heuristics produce passes, tight enough that the shapes AOT analysis
showed OOMing do not.
"""

from __future__ import annotations

from typing import Mapping

#: fp32 scratch/statistics lanes — every row-stat scratch buffer is
#: (rows, 128) fp32 regardless of input dtype
LANES = 128
#: Pallas double-buffers every blocked operand
DB = 2


def budget_bytes(generation: str | None = None) -> int:
    """``core.capability.vmem_budget`` — re-exported here so every
    sizing consumer prices against the same figure. Off-TPU (and for
    the static analyzer, always) this is the conservative v5e planning
    budget."""
    from apex1_tpu.core.capability import vmem_budget
    return vmem_budget(generation)


def _flash_grid_frame(blocks, dims, es):
    """The GRID form's frame (key axis a grid axis): q/k/v/o blocks
    (double-buffered, input dtype), fp32 (acc, m, l) scratch, and the
    live fp32 score + exp tiles (bq, bk) the MXU step materializes in
    vregs/VMEM."""
    bq, bk = blocks["block_q"], blocks["block_k"]
    dp = dims["Dp"]
    return (DB * es * (bq * dp + 2 * bk * dp)      # q, k, v in
            + DB * es * bq * dp                    # o out
            + 4 * (bq * dp + 2 * bq * LANES)       # acc, m, l scratch
            + 2 * 4 * bq * bk)                     # s and e tiles


def flash_kv_row_check(blocks, dims, es, budget):
    """The RESIDENT form of the flash forward and dq kernels
    (`ops.attention._kv_resident`): the (batch, kv head) row's whole K
    and V (``Skp`` padded keys) one double-buffered block beside the
    tiles, priced at the dq kernel's frame, the larger of the two: q, dO
    in and dq out, the two fp32 statistics ((1, bq) rows, a sublane tile
    of 8 each, and the (bq, 128) columns they are turned into), the fp32
    accumulator, and the live fp32 s, p, dp and ds tiles. Where it does
    not fit the key axis stays a grid axis. ``dims["heads"]`` (default
    1): the ROWS layout's heads to a 128-lane block (`ops.attention.
    flash_form` decides with THIS function). The row, the blocks and the
    accumulator are the same bytes at any count (two heads of 64 lie
    where one padded head lay); what grows a head is its own zeroed
    copies of q and dO, its statistics and its live tiles, the heads of
    a block running through one loop body."""
    bq, bk = blocks["block_q"], blocks["block_k"]
    dp, n = dims["Dp"], dims.get("heads", 1)
    est = (DB * es * 2 * dims["Skp"] * dp          # the row's K and V
           + 3 * DB * es * bq * dp                 # q, dO in, dq out
           + (n - 1) * 2 * es * bq * dp            # q, dO zeroed a head
           + n * 2 * (DB * 4 * 8 * bq + 4 * bq * LANES)  # lse, delta - dlse
           + 4 * bq * dp                           # dq accumulator
           + n * 4 * 4 * bq * bk)                  # s, p, dp, ds tiles
    return est <= budget, est


def flash_q_row_check(blocks, dims, es, budget):
    """The RESIDENT form of the flash dk/dv kernel
    (`ops.attention._q_resident`): the GQA group's whole Q and dO rows
    (``Sqp`` padded queries a head) and their two fp32 statistics
    ((1, bq) rows along the lanes, a sublane tile of 8 each)
    double-buffered beside the k, v blocks in, the dk, dv blocks out
    (priced fp32), the two fp32 accumulators and the live s, p, dp and
    ds tiles. ``dims["heads"]`` as in `flash_kv_row_check`: a head more
    is its statistics' rows, its zeroed copies of k and v and its live
    tiles."""
    bq, bk = blocks["block_q"], blocks["block_k"]
    dp, n = dims["Dp"], dims.get("heads", 1)
    est = (DB * dims["group"] * dims["Sqp"]
           * (2 * es * dp + n * 2 * 4 * 8)         # Q, dO, lse, delta - dlse
           + 2 * DB * es * bk * dp                 # k, v in
           + (n - 1) * 2 * es * bk * dp            # k, v zeroed a head
           + 2 * DB * 4 * bk * dp                  # dk, dv out
           + 2 * 4 * bk * dp                       # dk, dv accumulators
           + n * 4 * 4 * bq * bk)                  # s, p, dp, ds tiles
    return est <= budget, est


def flash_check(blocks, dims, es, budget):
    """Flash attention frame at key length ``Sb``: the resident form's
    (`flash_kv_row_check`, which `ops.attention` takes wherever it
    fits: the same function decides there, ``dims["heads"]`` and all),
    else the grid form's."""
    if "Sb" in dims:     # an entry keyed on the head width alone: any length
        ok, est = flash_kv_row_check(blocks, {**dims, "Skp": dims["Sb"]},
                                     es, budget)
        if ok:
            return ok, est
    est = _flash_grid_frame(blocks, dims, es)
    return est <= budget, est


def row_check(n_passes):
    """Row-wise kernels (softmax/LN/xentropy/rope): ``n_passes``
    row-block operands of (br, lanes_p), double-buffered, priced fp32
    (compute is fp32 even for bf16 inputs)."""
    def check(blocks, dims, _es, budget):
        br = blocks["block_rows"]
        est = n_passes * DB * br * dims["lanes"] * 4
        return est <= budget, est
    return check


def linear_xent_check(blocks, dims, es, budget):
    """Fused LM-head CE: the binding constraint is the AOT-established
    accumulator bound (``ops/linear_xent._auto_blocks``): the fp32
    dx (bt, Hp) + dw (bv, Hp) accumulators must fit 3/4 of a quarter of
    the VMEM budget; the double-buffered operand blocks and the live
    (bt, bv) logit tile are additionally bounded by the full budget."""
    bt, bv = blocks["block_t"], blocks["block_v"]
    hp = dims["Hp"]
    acc = 4 * (bt + bv) * hp
    est = (acc + DB * es * (bt + bv) * hp + 2 * 4 * bt * bv)
    ok = est <= budget and acc <= (budget // 4) * 3 // 4
    return ok, est


def cm_check(blocks, dims, es, budget):
    """Fused-collective chunk matmul (`ops.fused_collective.
    _chunk_matmul`, the tile loop of the ppermute-ring and RDMA
    reduce-scatter forms): x (bm, Kp) and w (Kp, bn) operand blocks
    (double-buffered, input dtype) + the fp32 (bm, bn) output block.
    K is untiled by design (one MXU dot per output tile, no cross-grid
    accumulation), so Kp itself bounds the frame."""
    bm, bn = blocks["block_m"], blocks["block_n"]
    kp = dims["Kp"]
    est = DB * es * (bm * kp + kp * bn) + DB * 4 * bm * bn
    return est <= budget, est


def agf_check(blocks, dims, es, budget):
    """All-gather-fused flash attention (`ops.fused_collective.
    _agf_kernel`): the flash GRID frame (the visiting shard's K/V are
    never held whole: the kernel keeps the key axis on the grid at every
    length) plus the carried fp32 (prev_out, prev_lse) merge operands
    and the fp32 merged output block the epilogue writes (the plain
    kernel's output is input-dtype)."""
    est = _flash_grid_frame(blocks, dims, es)
    bq, dp = blocks["block_q"], dims["Dp"]
    extra = (DB * 4 * (bq * dp + bq * LANES)     # prev_out, prev_lse in
             + DB * 4 * bq * dp                  # merged fp32 out
             - DB * es * bq * dp)                # replaces q-dtype out
    est = est + extra
    return est <= budget, est


def paged_decode_check(blocks, dims, es, budget):
    """Paged ragged decode attention (`ops.paged_decode.paged_attend`):
    one (page, Dp) K page block + one V page block per grid step
    (double-buffered, CACHE dtype ``es`` — int8 pages are a quarter of
    the f32 frame, which is the capacity-tier point), the (Rq, Dp)
    query and output blocks, fp32 (acc, m, l) flash scratch, and the
    live fp32 (Rq, page) score + exp tiles."""
    p = blocks["page_p"]
    dp, rq = dims["Dp"], dims["Rq"]
    est = (DB * es * 2 * p * dp                    # k, v page blocks
           + DB * 4 * rq * dp                      # q block (fp32 path)
           + DB * 4 * rq * dp                      # o block
           + 4 * (rq * dp + 2 * rq * LANES)        # acc, m, l scratch
           + 2 * 4 * rq * p)                       # s and e tiles
    return est <= budget, est


def decode_attend_check(blocks, dims, es, budget):
    """Dense-pool decode attention (`ops.decode_attend.decode_attend`):
    the kernel's own K and V block buffers, one pair a fetch of its queue
    (``depth`` of them, `ops.decode_attend.fetch_depth`; ``block_l``
    positions of ``HD`` lanes, CACHE dtype ``es``; the pool itself stays
    in HBM), the block-diagonal query block and the output block
    (double-buffered by Pallas), the new K/V rows (each its own tile),
    fp32 (acc, m, l) scratch over ``Rq`` query rows, and the live fp32
    (Rq, block_l) score + exp tiles and (W, HD) append window. A window
    over a ring (a sliding-attention layer's leaf) changes WHICH blocks
    the kernel walks and masks, not what it holds: the same frame."""
    bl, depth = blocks["block_l"], blocks["depth"]
    hd, rq, w = dims["HD"], dims["Rq"], dims["W"]
    est = (2 * depth * es * bl * hd                # k, v block buffers
           + DB * es * rq * hd                     # q block
           + DB * es * rq * hd                     # o block (<= Rq rows)
           + 2 * DB * es * w * hd                  # new k, v rows
           + 4 * (rq * hd + 2 * rq * LANES)        # acc, m, l scratch
           + 2 * 4 * rq * bl                       # s and e tiles
           + 2 * es * w * hd)                      # append windows
    return est <= budget, est


def fused_sample_check(blocks, dims, _es, budget):
    """Fused sampling epilogue (`ops.paged_decode.fused_sample`): one
    (8, block_v) fp32 logits block (a sublane-aligned tile of rows,
    double-buffered) + the (8, LANES) key/token lanes, plus the live
    fp32/uint32 temporaries of the in-kernel threefry->gumbel pipeline
    (~6 block-width vectors: counter pair, two threefry lanes, bits,
    gumbel+logits)."""
    rows = 8                                       # sublane row tile
    bv = blocks["block_v"]
    est = (DB * 4 * rows * bv                      # logits block
           + 2 * DB * 4 * rows * LANES             # keys in, tokens out
           + 6 * 4 * rows * bv)                    # pipeline temporaries
    return est <= budget, est


def chunked_loss_check(blocks, dims, es, budget):
    """Chunked preference/distill losses (`ops.chunked_loss`): the
    streaming frame is one sublane row-tile of the per-chunk logits —
    (8, chunk_v) fp32, double-buffered — beside the (8, Hp) hidden rows
    feeding the chunk matmul and the (8, LANES) packed-stat lanes.
    The inner Pallas work is priced separately by ``linear_xent_check``
    (the chunk rides ``shard_stats_packed``); this model bounds the
    CHUNK choice itself so a mis-tuned chunk_v fails loudly at trace
    time instead of OOMing the recompute on silicon."""
    cv = blocks["chunk_v"]
    hp = dims["Hp"]
    rows = 8                                       # sublane row tile
    est = (DB * 4 * rows * cv                      # live chunk logit tile
           + DB * es * rows * hp                   # hidden rows in
           + 4 * rows * LANES)                     # packed stat lanes
    return est <= budget, est


def fused_swiglu_check(blocks, dims, es, budget):
    """Fused SwiGLU/GeGLU MLP (`ops.fused_dense.fused_glu`): x (bt, Hp)
    block + the two weight (Hp, bf) blocks (double-buffered, input
    dtype), the (bt, bf) output block, and the two live fp32 (bt, bf)
    gate/up tiles the elementwise glu consumes before the cast."""
    bt, bf = blocks["block_t"], blocks["block_f"]
    hp = dims["Hp"]
    est = (DB * es * (bt * hp + 2 * hp * bf)       # x, w_gate, w_up in
           + DB * es * bt * bf                     # out block
           + 2 * 4 * bt * bf)                      # fp32 g and u tiles
    return est <= budget, est


def lora_epilogue_check(blocks, dims, es, budget):
    """Multi-tenant LoRA decode epilogue (`ops.lora_epilogue.lora_delta`):
    per grid step one gathered A page (sublane-padded (8, Hp)) and one
    B page vocab tile (8, block_v), both double-buffered in page dtype,
    beside the (8, Hp) hidden row, the (8, block_v) delta output block
    and its fp32 accumulator scratch. Rank is a GRID axis (pages stream
    one at a time through the block-table gather), so it never enters
    the frame — only Hp and block_v do."""
    bv = blocks["block_v"]
    hp = dims["Hp"]
    rows = 8                                       # sublane row tile
    est = (DB * es * rows * hp                     # A page block
           + DB * es * rows * bv                   # B page vocab tile
           + DB * es * rows * hp                   # hidden row in
           + DB * es * rows * bv                   # delta out block
           + 4 * rows * bv)                        # fp32 accumulator
    return est <= budget, est


def int8_check(blocks, dims, _es, budget):
    """int8 decode GEMM at the kernel's worst-case row count (T <= 1024,
    ``ops/quantized._aligned_for_kernel``): bf16 x block, int8 w block
    (double-buffered), fp32 out block + scales."""
    bn, bk = blocks["block_n"], blocks["block_k"]
    t = 1024
    est = (DB * (t * bk * 2 + bn * bk * 1 + bn * 4) + t * bn * 4)
    return est <= budget, est


# ---------------------------------------------------------------------------
# the RDMA reduce-scatter sizing rule — previously comment-only
# ---------------------------------------------------------------------------

def rdma_slot_bytes(chunk: int, n_cols: int) -> int:
    """The four fp32 chunk slots (2 recv + 2 send double buffers) of
    ``ops.fused_collective._mrs_rdma_kernel``: ``16 * chunk * N``
    bytes — the bound PR 9's review established from the measured
    RESOURCE_EXHAUSTED at chunk=512, N=1024 on v5e."""
    return 4 * 4 * chunk * n_cols


def rdma_check(chunk: int, k: int, n_cols: int, es: int,
               budget: int) -> tuple[bool, int]:
    """Full static frame of the RDMA matmul->reduce-scatter kernel:
    the four fp32 chunk slots beside the double-buffered x (chunk, K)
    and w (K, N) operand blocks and the fp32 (chunk, N) output block.
    At the v5e budget this reproduces both gate data points: (256,
    1024, 512) bf16 fits with margin (~6 MiB), (512, 1024, 1024) does
    not (measured RESOURCE_EXHAUSTED)."""
    est = (rdma_slot_bytes(chunk, n_cols)
           + DB * es * (chunk * k + k * n_cols)   # x, w operand blocks
           + DB * 4 * chunk * n_cols)             # fp32 out block
    return est <= budget, est


#: the registry-facing name -> check table; ``tuning.registry`` builds
#: its SPECS from this, and the analyzer uses it to price kernels it can
#: match to a registered spec.
CHECKS: dict[str, object] = {
    "flash_attention": flash_check,
    "fused_softmax": row_check(3),       # y, dy, dx row blocks
    "layer_norm": row_check(5),          # x, dy, dx + dg/db acc
    "rope": row_check(6),                # x1, x2, cos, sin, o1, o2
    "xentropy": row_check(2),            # x in, dx out (stats are
                                         # (br, 1) noise)
    "bias_dropout_add": row_check(4),    # x, residual, out (+ dy/dx in
                                         # bwd); mask is PRNG-recomputed,
                                         # never stored
    "linear_xent": linear_xent_check,
    "fused_collective_matmul": cm_check,
    "fused_ag_flash": agf_check,
    "int8_matmul": int8_check,
    "paged_decode": paged_decode_check,
    "decode_attend": decode_attend_check,
    "fused_sample": fused_sample_check,
    "chunked_loss": chunked_loss_check,
    "fused_swiglu": fused_swiglu_check,
    "lora_epilogue": lora_epilogue_check,
}


def static_frame_bytes(block_bytes: Mapping[str, int] | None = None, *,
                       operand_bytes: int = 0,
                       scratch_bytes: int = 0) -> int:
    """Generic lower-bound frame for a ``pallas_call`` the analyzer can
    price without a registered spec: double-buffered blocked operands
    plus (single-buffered) scratch. A LOWER bound by construction —
    anything unpriceable contributes zero — so exceeding the budget is
    proof, not heuristic."""
    return DB * operand_bytes + scratch_bytes
