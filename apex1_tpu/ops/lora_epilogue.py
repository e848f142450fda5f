"""Multi-tenant LoRA decode epilogue — paged adapters in the LM-head matmul.

One deployed base model, many tenants: each tenant's low-rank adapter
(A (H, r), B (r, V), scale pre-folded into B) is stored as ``r`` PAGES in
a pool beside the KV pool (`serving.lora.LoraAdapterStore`, page-granular
alloc reused from `serving.kv_pool`), and each serving slot carries a
rank-length BLOCK-TABLE row of page ids — exactly the `ops.paged_decode`
indirection, scalar-prefetched so Mosaic pipelines the gathers.

The delta this module computes is

    delta[n] = Σ_j (h[n] · A_pages[bt[n, j]]) * B_pages[bt[n, j]]

i.e. ``(h @ A) @ B`` with the rank dimension streamed page-by-page, fused
into the decode step as a logits EPILOGUE (`serving.engine` adds it to the
base head matmul) instead of a separate gather + two-matmul pass per
tenant (arXiv 2502.17728's operation-fusion argument).  Page 0 is the
pool's zero page, so a slot with no adapter (all-zero block-table row)
contributes an exactly-zero delta — LoRA-off slots ride the same
executable with no retrace and the engine keeps its two-executable gate.

Grid is (rows, vocab tiles, rank): rank is a GRID axis, not a VMEM frame
dim, so the per-step footprint is one A page + one (8-sublane) B vocab
tile regardless of rank — priced by ``vmem_model.lora_epilogue_check``
and validated loudly by `check_lora_geometry` (the
`paged_decode.check_paged_geometry` contract).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex1_tpu.ops._common import (interpret_mode, kernel_call, out_struct,
                                   pad_to, to_mosaic, use_pallas)

_LANES = 128


def _ceil_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def check_lora_geometry(rank: int, hidden: int, vocab: int,
                        block_v: int, *, es: int = 4) -> int:
    """Validate LoRA-epilogue geometry LOUDLY at trace time: a bad rank
    or vocab tile raises with the priced VMEM estimate instead of
    falling back silently (`paged_decode.check_paged_geometry`)."""
    if rank < 1:
        raise ValueError(f"lora_epilogue: rank={rank} must be >= 1")
    if block_v < _LANES or block_v % _LANES:
        raise ValueError(
            f"lora_epilogue: block_v={block_v} must be a multiple of "
            f"{_LANES} (vocab tiles are lane-aligned)")
    from apex1_tpu.vmem_model import CHECKS, budget_bytes
    hp = _ceil_to(hidden, _LANES)
    vp = _ceil_to(vocab, _LANES)
    ok, est = CHECKS["lora_epilogue"](
        {"block_v": block_v}, {"Hp": hp, "Vp": vp}, es, budget_bytes())
    if not ok:
        raise ValueError(
            f"lora_epilogue: block_v={block_v} (Hp={hp}, Vp={vp}) prices "
            f"at ~{est} B of VMEM > budget {budget_bytes()} B; shrink "
            f"block_v or re-tune (tools/tune_kernels.py)")
    return block_v


def _auto_block_v(hidden, vocab, block_v, dtype):
    """Explicit > tuning table > shrink-to-fit heuristic (docs/ops.md)."""
    es = jnp.dtype(dtype).itemsize
    if block_v is not None:
        return check_lora_geometry(1, hidden, vocab, int(block_v), es=es)
    hp = _ceil_to(hidden, _LANES)
    vp = _ceil_to(vocab, _LANES)
    from apex1_tpu import tuning
    hit = tuning.lookup("lora_epilogue", {"Hp": hp, "Vp": vp}, dtype)
    if hit is not None:
        try:
            return check_lora_geometry(1, hidden, vocab,
                                       int(hit["block_v"]), es=es)
        except (KeyError, ValueError):
            pass  # fail-safe: stale table entries fall back to heuristic
    from apex1_tpu.vmem_model import CHECKS, budget_bytes
    bv = min(2048, vp)
    while bv > _LANES and not CHECKS["lora_epilogue"](
            {"block_v": bv}, {"Hp": hp, "Vp": vp}, es, budget_bytes())[0]:
        bv //= 2
    return check_lora_geometry(1, hidden, vocab, bv, es=es)


def _lora_delta_ref(h, a_pages, b_pages, block_table):
    """Composite gold: gather the pages dense, then the two rank matmuls.
    Row-independent by construction — row n touches only bt[n] — which is
    what makes mixed-tenant batches bitwise equal to solo runs."""
    a = a_pages[block_table]                         # (N, R, H)
    b = b_pages[block_table]                         # (N, R, V)
    coef = jnp.einsum("nh,nrh->nr", h.astype(jnp.float32),
                      a.astype(jnp.float32),
                      preferred_element_type=jnp.float32)
    return jnp.einsum("nr,nrv->nv", coef, b.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def _lora_kernel(bt_ref, h_ref, a_ref, b_ref, o_ref, acc, *, n_r):
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    hv = h_ref[0].astype(jnp.float32)                # (1, Hp)
    av = a_ref[0].astype(jnp.float32)                # (1, Hp) — page r
    coef = jnp.sum(hv * av)                          # h[n] · A[:, j]
    bv = b_ref[0].astype(jnp.float32)                # (1, bv) — page r
    acc[...] += coef * jnp.broadcast_to(bv, acc.shape)

    @pl.when(r == n_r - 1)
    def _():
        o_ref[0] = acc[:1, :]


def lora_delta(h, a_pages, b_pages, block_table, *, block_v=None):
    """Per-row paged LoRA logit delta: ``h`` (N, H) hidden rows,
    ``a_pages`` (P, H) / ``b_pages`` (P, V) the adapter page pools,
    ``block_table`` (N, R) int32 page ids (page 0 = zero page ⇒ exact
    0.0 delta for adapterless rows).  Returns (N, V) fp32."""
    N, H = h.shape
    R = block_table.shape[1]
    V = b_pages.shape[1]
    if not use_pallas():
        return _lora_delta_ref(h, a_pages, b_pages, block_table)
    bv = _auto_block_v(H, V, block_v, h.dtype)
    check_lora_geometry(R, H, V, bv, es=jnp.dtype(h.dtype).itemsize)
    hm, am, bm = to_mosaic(h, a_pages, b_pages)
    hp, _ = pad_to(hm, 1, _LANES)
    ap, _ = pad_to(am, 1, _LANES)
    bp, _ = pad_to(bm, 1, bv)
    Hp = hp.shape[1]
    Vp = bp.shape[1]
    # singleton sublane dim: Mosaic wants the last two block dims
    # (8, 128)-divisible OR equal to the array dims — a (1, Hp) block on
    # a (P, Hp) array is neither, but (1, 1, Hp) on (P, 1, Hp) is
    hp = hp.reshape(N, 1, Hp)
    ap = ap.reshape(-1, 1, Hp)
    bp = bp.reshape(-1, 1, Vp)
    btf = block_table.reshape(-1).astype(jnp.int32)  # scalar-prefetched

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N, Vp // bv, R),
        in_specs=[
            pl.BlockSpec((1, 1, Hp), lambda n, v, r, bt: (n, 0, 0)),
            pl.BlockSpec((1, 1, Hp),
                         lambda n, v, r, bt: (bt[n * R + r], 0, 0)),
            pl.BlockSpec((1, 1, bv),
                         lambda n, v, r, bt: (bt[n * R + r], 0, v)),
        ],
        out_specs=pl.BlockSpec((1, 1, bv), lambda n, v, r, bt: (n, 0, v)),
        scratch_shapes=[pltpu.VMEM((8, bv), jnp.float32)],
    )
    out = kernel_call(
        functools.partial(_lora_kernel, n_r=R),
        name="lora_delta",
        grid_spec=grid_spec,
        out_shape=out_struct((N, 1, Vp), jnp.float32, hm, am, bm),
        interpret=interpret_mode(),
    )(btf, hp, ap, bp)
    return out[:, 0, :V]
