"""Paged ragged decode attention over KV-pool pages + fused sampling.

The serving engine's decode step was XLA-composed attention over DENSE
per-slot KV lanes: every token paid full-``max_len`` attention reads, a
separate dequant pass on the int8 cache tier, and a host round trip for
sampling. This module is the kernel-shaped answer (ROADMAP item 5; the
op-fusion results in PAPERS.md 2502.17728 are the motivating numbers):

- :func:`cache_attend` — the decode/chunk attention composite, extracted
  from ``models.generate.cached_attention`` so the dense reference path
  and the paged path share ONE implementation (bit-identical logits on
  the CPU proxy is a structural property, not a test accident).
- :class:`PagedCache` + :func:`paged_update_attend` — the per-layer
  cache entry the models thread opaquely: K/V live in a shared PAGE
  pool ``(num_pages, Hkv, page, D)`` addressed through a per-row block
  table, so prefix pages are shared by reference (no copy-on-admit) and
  the decode working set is proportional to actual lengths.
- :func:`paged_attend` — the Pallas kernel: grid ``(N, Hkv, pages)``
  with the page axis innermost; each step DMAs ONE page block selected
  by the scalar-prefetched block table (``PrefetchScalarGridSpec`` —
  the index map reads ``bt[n·T + t]``, so the gather IS the pipeline),
  dequantizes int8/bf16 pages to f32 in-register (the ``cache_dtype``
  tier stops paying a separate dequant op), and folds an online-softmax
  flash update across pages. Pages past a row's horizon are skipped
  entirely (``pl.when`` on the traced length — the RAGGED part).
- :func:`fused_sample` — the sampling epilogue: logits → vocab mask →
  temperature → counter-keyed gumbel draw → argmax, one kernel per row
  batch. The in-kernel PRNG re-derives jax's exact
  threefry-2x32 stream (`_uniform_bits` — pinned bitwise against
  ``jax.random`` in ``tests/test_paged_decode.py``), so the kernel
  emits the SAME token ids as ``fold_in(key(seed), pos)`` +
  ``jax.random.categorical`` — the per-request counter-PRNG contract
  (resubmission idempotency, speculative exact-match accept) survives
  the fusion verbatim.

Dispatch follows `ops._common`: XLA composite on CPU/GPU (the parity
gold — tier-1 pins the serving engine's paged path bit-identical to the
dense path through it), Pallas on TPU (interpret-mode tested here).
What the CPU proxy does NOT measure is documented in
``docs/paged_decode.md``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex1_tpu.ops._common import (NEG_INF, interpret_mode, kernel_call,
                                   out_struct, pad_to, use_pallas)

_LANES = 128
_SUBLANES = 8
_TINY = np.float32(np.finfo(np.float32).tiny)


# ---- shared attention composite (the ONE decode-attention math) --------


def cache_attend(q, k_all, v_all, cache_index, *,
                 sm_scale: Optional[float] = None, bias=None,
                 valid_start=None, window: Optional[int] = None):
    """Masked composite attention of (B, Hq, S, D) queries against a
    FULL cache in its stored form (B, S_max, Hkv * D), one position's
    heads side by side in a row (`models.generate.init_cache`) — the
    decode/chunk-decode math of ``models.generate.cached_attention``,
    factored out so the paged path attends through the SAME ops (gather
    pages → dense → here) and token parity with the dense engine is
    bit-exact by construction. ``cache_index`` may be a scalar (the
    dense path) or a per-row (B,) vector (the paged batch path — rows
    at different depths). Query j sees cache slots <= index + j.

    ``window`` (a sliding-attention layer): query j sees the positions
    ``index + j - window < p <= index + j`` alone, and the cache is a
    RING: position ``p`` lies in row ``p mod S_max``, so row ``r`` holds
    the newest position up to the call's last (``index + S - 1``) that is
    ``r`` modulo ``S_max``. A cache that never wrapped (``S_max`` above
    every position) is the case in which that is ``r`` itself."""
    B, Hq, S, D = q.shape
    S_max = k_all.shape[1]
    Hkv = k_all.shape[2] // D
    idx = jnp.asarray(cache_index, jnp.int32)
    scale = (D ** -0.5) if sm_scale is None else sm_scale
    # GQA without materializing a repeated cache: group the q heads onto
    # the kv-head axis and contract against the cache as-is (a repeated
    # (B, Hq, S_max, D) copy would multiply the decode loop's memory
    # traffic by the group factor)
    group = Hq // Hkv
    qg = q.reshape(B, Hkv, group, S, D)
    k_all = k_all.reshape(B, S_max, Hkv, D)
    v_all = v_all.reshape(B, S_max, Hkv, D)
    scores = jnp.einsum("bhgsd,bkhd->bhgsk", qg, k_all,
                        preferred_element_type=jnp.float32) * scale
    if bias is None:
        scores_b = scores
    else:
        scores_b = scores + bias.astype(jnp.float32).reshape(
            bias.shape[0], Hkv, group, S, -1)
    pos = jnp.arange(S_max)
    # per-query horizon: query j sees cache slots <= idx + j (S == 1
    # decode reduces to pos <= idx)
    if idx.ndim == 0:
        horizon = idx + jnp.arange(S)[None, None, None, :, None]
    else:
        horizon = (idx.reshape(B, 1, 1, 1, 1)
                   + jnp.arange(S)[None, None, None, :, None])
    if window is not None:
        # what each ring row holds, by the call's last position
        last = (idx + (S - 1)).reshape(-1, 1)               # (1 or B, 1)
        pos = (last - (last - pos[None, :]) % S_max)[:, None, None, None, :]
        keep = (pos <= horizon) & (pos > horizon - window) & (pos >= 0)
    else:
        keep = pos[None, None, None, None, :] <= horizon
    if valid_start is not None:
        keep = keep & (pos[None, None, None, None, :]
                       >= valid_start.reshape(B, 1, 1, 1, 1))
    scores_b = jnp.where(keep, scores_b, NEG_INF)
    probs = jax.nn.softmax(scores_b, axis=-1).astype(q.dtype)
    attn = jnp.einsum("bhgsk,bkhd->bhgsd", probs, v_all)
    return attn.reshape(B, Hq, S, D)


# ---- sampling (shared pipeline + fused kernel) -------------------------


def _temperature_top_k(logits, temperature, top_k, vocab_size):
    """Shared temperature + top-k masking over (..., V) fp32 logits
    (the padded-vocab tail must already be NEG_INF-masked)."""
    logits = logits / temperature
    if top_k is not None:
        if top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        # clamp to the VALID vocab: a larger top_k would (a) raise an
        # opaque trace-time IndexError past the full width and (b) pick
        # a NEG_INF masked-tail entry as the kth threshold, silently
        # disabling truncation (ADVICE r3)
        eff_v = logits.shape[-1]
        if vocab_size is not None and vocab_size < eff_v:
            eff_v = vocab_size
        k = min(int(top_k), eff_v)
        kth = jnp.sort(logits, axis=-1)[..., -k][..., None]
        logits = jnp.where(logits >= kth, logits, NEG_INF)
    return logits


def sample_token(logits, rng, *, temperature: float = 0.0,
                 top_k: Optional[int] = None,
                 vocab_size: Optional[int] = None):
    """One sampling step from (B, V) logits. ``temperature == 0`` =
    greedy argmax; otherwise softmax sampling, optionally truncated to the
    ``top_k`` highest-probability tokens. ``vocab_size`` masks padded
    vocab tail (GPT-2's padded_vocab)."""
    logits = logits.astype(jnp.float32)
    if vocab_size is not None and vocab_size < logits.shape[-1]:
        mask = jnp.arange(logits.shape[-1]) < vocab_size
        logits = jnp.where(mask, logits, NEG_INF)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = _temperature_top_k(logits, temperature, top_k, vocab_size)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def _threefry2x32(k1, k2, x0, x1):
    """The 20-round threefry-2x32 block as pure uint32 jnp ops — runs
    identically inside a Pallas body and in plain XLA. Reproduces
    ``jax._src.prng.threefry2x32`` op-for-op (key schedule,
    rotation constants, round-group injections); the bitwise match
    against ``jax.random`` is pinned in ``tests/test_paged_decode.py``
    (a silent divergence here would break the serving engine's
    counter-seed resubmission contract, not just perf)."""
    rotations = ((13, 15, 26, 6), (17, 29, 16, 24))
    ks = (k1, k2, k1 ^ k2 ^ np.uint32(0x1BD11BDA))
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        for r in rotations[i % 2]:
            x0 = x0 + x1
            x1 = (x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))
            x1 = x1 ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def _uniform_bits(k1, k2, col, n: int,
                  partitionable: Optional[bool] = None):
    """The uint32 draw at flat position ``col`` of an n-element
    ``jax.random`` uniform over key (k1, k2), for EITHER threefry
    stream (``partitionable`` defaults to the live
    ``jax_threefry_partitionable`` config — True by default on the
    installed jax; the kernel must match whichever stream the
    composite engine draws from):

    - partitionable: per-position 64-bit counter split into uint32
      halves — position ``col`` is the pair (0, col) for any n < 2^32,
      output ``y0 ^ y1``. Trivially position-wise.
    - original: counts = iota(n) (zero-padded to even), split in
      halves, one threefry-2x32 pass. Each lane recomputes its
      half-pair partner (2x the threefry work, fully vectorized) so
      the whole draw is position-wise and fuses into the kernel."""
    if partitionable is None:
        partitionable = bool(jax.config.jax_threefry_partitionable)
    if partitionable:
        y0, y1 = _threefry2x32(k1, k2, jnp.zeros_like(col).astype(
            jnp.uint32), col.astype(jnp.uint32))
        return y0 ^ y1
    odd = n % 2
    h = (n + odd) // 2
    lo = col < h
    a_idx = jnp.where(lo, col, col - h)
    b_idx = a_idx + h
    aval = a_idx.astype(jnp.uint32)
    if odd:
        # the odd count is zero-PADDED before the split, so the last
        # second-half lane's counter is the pad zero, not its index
        bval = jnp.where(b_idx == n, 0, b_idx).astype(jnp.uint32)
    else:
        bval = b_idx.astype(jnp.uint32)
    y0, y1 = _threefry2x32(k1, k2, aval, bval)
    return jnp.where(lo, y0, y1)


def _bits_to_gumbel(bits):
    """uint32 → standard gumbel, op-for-op jax's
    ``_uniform``/``_gumbel`` pipeline (mantissa fill to [1, 2), subtract
    1, affine to [tiny, 1), −log(−log(u)))."""
    fb = (bits >> np.uint32(9)) | np.uint32(0x3F800000)
    u = jax.lax.bitcast_convert_type(fb, jnp.float32) - np.float32(1.0)
    u = u * np.float32(np.float32(1.0) - _TINY) + _TINY
    u = jnp.maximum(_TINY, u)
    return -jnp.log(-jnp.log(u))


def _row_keys(seeds, positions):
    """(R, 2) uint32 key data for ``fold_in(key(seed), position)`` per
    row — derived through jax.random itself (tiny per-row scalar work;
    reusing the canonical implementation removes any reimplementation
    risk from the key-derivation half of the contract)."""

    def one(s, p):
        return jax.random.key_data(
            jax.random.fold_in(jax.random.key(s), p))

    return jax.vmap(one)(jnp.asarray(seeds, jnp.int32),
                         jnp.asarray(positions, jnp.int32))


def _fused_sample_kernel(key_ref, lg_ref, o_ref, m_scr, i_scr, *, n,
                         v_eff, temperature, scale_in_kernel, greedy,
                         bv, total):
    t = pl.program_id(1)
    T = pl.num_programs(1)

    @pl.when(t == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        i_scr[...] = jnp.full_like(i_scr, total)

    lg = lg_ref[...].astype(jnp.float32)        # (_SUBLANES, bv)
    col = t * bv + jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1)
    if scale_in_kernel:
        lg = jnp.where(col < v_eff, lg, NEG_INF)
        if not greedy:
            lg = lg / temperature
    if greedy:
        vals = lg
    else:
        # per-row keys broadcast down the vocab lanes; row-pad keys are
        # zeros drawing over NEG_INF logits — argmax 0, sliced away
        k1 = key_ref[:, 0:1].astype(jnp.uint32)
        k2 = key_ref[:, 1:2].astype(jnp.uint32)
        g = _bits_to_gumbel(_uniform_bits(k1, k2, col, n))
        vals = g + lg
    # first-index-of-max == jnp.argmax, via max + masked-min (Mosaic has
    # no direct argmax reduction, and no INTEGER reductions at all — the
    # index min runs in f32, exact for any index < 2^24, far past any
    # vocab). f32 max is exact, so the running (max, first-index) fold
    # across vocab blocks is bitwise the single-block argmax whatever
    # block_v splits the row into.
    bm = jnp.max(vals, axis=-1, keepdims=True)
    bi = jnp.min(jnp.where(vals == bm, col.astype(jnp.float32),
                           jnp.float32(total)),
                 axis=-1, keepdims=True).astype(jnp.int32)
    m_prev, i_prev = m_scr[:, :1], i_scr[:, :1]
    new_i = jnp.where(bm > m_prev, bi,
                      jnp.where(bm == m_prev,
                                jnp.minimum(i_prev, bi), i_prev))
    m_scr[...] = jnp.broadcast_to(jnp.maximum(m_prev, bm), m_scr.shape)
    i_scr[...] = jnp.broadcast_to(new_i, i_scr.shape)

    @pl.when(t == T - 1)
    def _():
        o_ref[...] = i_scr[...]


def _fused_sample_ref(logits, seeds, positions, *, temperature, top_k,
                      vocab_size):
    """The composite: per-row ``fold_in(key(seed), pos)`` +
    `sample_token` — literally the dense engine's sampling ops under
    one vmap, so the CPU paged path emits bit-identical tokens."""

    def one(lg, s, p):
        key = jax.random.fold_in(jax.random.key(s), p)
        return sample_token(lg[None], key, temperature=temperature,
                            top_k=top_k, vocab_size=vocab_size)[0]

    return jax.vmap(one)(logits, jnp.asarray(seeds, jnp.int32),
                         jnp.asarray(positions, jnp.int32))


def fused_sample(logits, seeds, positions, *, temperature: float = 0.0,
                 top_k: Optional[int] = None,
                 vocab_size: Optional[int] = None,
                 block_v: Optional[int] = None):
    """Counter-keyed sampling over (R, V) logits rows: row r draws with
    ``fold_in(key(seeds[r]), positions[r])`` — `sample_token` semantics,
    per-row seeds. On the Pallas path the whole epilogue (vocab mask,
    temperature, gumbel draw, argmax) runs in ONE kernel per row batch
    and only the (R,) token ids leave the device — the fused sampling
    epilogue of the paged decode step. ``top_k`` keeps its sort outside
    the kernel (the reference `_temperature_top_k` pipeline runs first;
    the kernel then draws from the pre-truncated logits). ``block_v``
    tiles the vocab axis (None = tuning-table winner for the padded
    vocab, else one full-row block); any split is bitwise-equivalent —
    the in-kernel fold is an exact f32 (max, first-index) reduction."""
    R, V = logits.shape
    seeds = jnp.asarray(seeds, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    if not use_pallas():
        return _fused_sample_ref(logits, seeds, positions,
                                 temperature=temperature, top_k=top_k,
                                 vocab_size=vocab_size)
    lg = logits.astype(jnp.float32)
    v_eff = V if (vocab_size is None or vocab_size >= V) else int(
        vocab_size)
    greedy = temperature == 0.0
    scale_in_kernel = top_k is None
    if not scale_in_kernel:
        # sort-based truncation stays in XLA; mask + scale ride along so
        # the kernel sees exactly the reference's post-pipeline logits
        lg = jnp.where(jnp.arange(V) < v_eff, lg, NEG_INF)
        if not greedy:
            lg = _temperature_top_k(lg, temperature, top_k, vocab_size)
    # sublane-aligned row blocks (Mosaic requires 8x128-tileable block
    # shapes): rows pad with NEG_INF logits + zero keys, sliced away
    lgp, _ = pad_to(lg, 1, _LANES, value=NEG_INF)
    lgp, _ = pad_to(lgp, 0, _SUBLANES, value=NEG_INF)
    Rp, Vp = lgp.shape
    if block_v is None:
        from apex1_tpu import tuning
        tuned = tuning.lookup("fused_sample", {"Vp": Vp}, jnp.float32)
        block_v = int(tuned["block_v"]) if tuned else Vp
    bv = max(_LANES, min(-(-int(block_v) // _LANES) * _LANES, Vp))
    lgp, _ = pad_to(lgp, 1, bv, value=NEG_INF)   # grid tiles exactly
    Vp2 = lgp.shape[1]
    keys = jax.lax.bitcast_convert_type(
        _row_keys(seeds, positions), jnp.int32)
    keysp = jnp.zeros((Rp, _LANES), jnp.int32).at[:R, :2].set(keys)
    out = kernel_call(
        functools.partial(_fused_sample_kernel, n=V, v_eff=v_eff,
                          temperature=temperature,
                          scale_in_kernel=scale_in_kernel,
                          greedy=greedy, bv=bv, total=Vp2),
        name="fused_sample",
        grid=(Rp // _SUBLANES, Vp2 // bv),
        in_specs=[pl.BlockSpec((_SUBLANES, _LANES),
                               lambda b, t: (b, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((_SUBLANES, bv), lambda b, t: (b, t),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((_SUBLANES, _LANES), lambda b, t: (b, 0),
                               memory_space=pltpu.VMEM),
        out_shape=out_struct((Rp, _LANES), jnp.int32, lgp),
        scratch_shapes=[pltpu.VMEM((_SUBLANES, _LANES), jnp.float32),
                        pltpu.VMEM((_SUBLANES, _LANES), jnp.int32)],
        interpret=interpret_mode(),
    )(keysp, lgp)
    return out[:R, 0]


# ---- page pytree plumbing ----------------------------------------------


def gather_pages(pages, block_table, total_len: int):
    """Assemble dense lanes (N, total_len, Hkv * D), the dense cache's
    stored form, from a page pool (num_pages, Hkv, page, D) through an
    (N, T) block table — the composite read path (and the CPU engine's
    bridge onto the UNCHANGED dense reference executables: gather →
    reference ops → scatter)."""
    g = jnp.take(pages, jnp.asarray(block_table, jnp.int32), axis=0)
    N, T, Hkv, P, D = g.shape
    g = g.transpose(0, 1, 3, 2, 4)               # (N, T, P, Hkv, D)
    return g.reshape(N, T * P, Hkv * D)[:, :total_len, :]


def scatter_pages(pages, block_table, values, start):
    """Write ``values`` into the page pool at positions ``[start, start
    + W)`` per row (page-spanning windows handled by position-wise
    scatter — no page-alignment requirement): (N, Hkv, W, D) as a model
    hands its new K/V, or (N, W, Hkv * D) rows cut from a dense lane.
    Rows whose block-table entries are the trash page (id 0, freed
    slots) write harmless garbage there; page 0 is never attended."""
    Hkv, P, D = pages.shape[1:]
    T = block_table.shape[1]
    if values.ndim == 3:
        vals = values.reshape(values.shape[0], -1, Hkv, D)
    else:
        vals = jnp.swapaxes(values, 1, 2)        # (N, W, Hkv, D)
    W = vals.shape[1]
    start = jnp.asarray(start, jnp.int32).reshape(-1)
    pos = start[:, None] + jnp.arange(W, dtype=jnp.int32)[None]
    pid = jnp.take_along_axis(jnp.asarray(block_table, jnp.int32),
                              jnp.clip(pos // P, 0, T - 1), axis=1)
    off = pos % P
    return pages.at[pid, :, off, :].set(vals.astype(pages.dtype))


@jax.tree_util.register_pytree_node_class
class PagedCache:
    """One layer's paged KV cache entry: K/V page pools plus the block
    table that maps (row, page-slot) → pool page. Threads through the
    models' ``cache[f"layer{i}"]`` slot opaquely — `cached_attention`
    detects it and routes to :func:`paged_update_attend`. ``length`` is
    the STATIC dense-equivalent lane length (attention mask geometry);
    the block table rides as a pytree child shared (by reference)
    across every layer's entry."""

    def __init__(self, k_pages, v_pages, block_table, length: int):
        self.k_pages = k_pages
        self.v_pages = v_pages
        self.block_table = block_table
        self.length = int(length)

    def tree_flatten(self):
        return ((self.k_pages, self.v_pages, self.block_table),
                (self.length,))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def paged_update_attend(q, k_new, v_new, pc: PagedCache, cache_index, *,
                        sm_scale: Optional[float] = None,
                        chunk_decode: bool = False):
    """The paged counterpart of dense ``cached_attention``: scatter the
    new tokens' K/V into their pages (dtype cast = the int8 tier's
    quantized write, unchanged), then attend the updated pages —
    composite gather + :func:`cache_attend` off-TPU (the parity gold),
    the :func:`paged_attend` kernel on TPU. ``cache_index`` may be a
    scalar or per-row (B,) vector. Returns (attn, new PagedCache)."""
    B, Hq, S, D = q.shape
    if S > 1 and not chunk_decode:
        raise ValueError(
            "PagedCache prefill must use chunk_decode=True (the paged "
            "pipeline has no flash-prefill mode; an empty cache at "
            "index 0 is the chunk mode's degenerate case)")
    idx = jnp.asarray(cache_index, jnp.int32)
    idx = jnp.broadcast_to(idx.reshape(-1)[:1] if idx.ndim == 0
                           else idx, (B,))
    kp = scatter_pages(pc.k_pages, pc.block_table, k_new, idx)
    vp = scatter_pages(pc.v_pages, pc.block_table, v_new, idx)
    new = PagedCache(kp, vp, pc.block_table, pc.length)
    attn = paged_attend(q, kp, vp, pc.block_table, idx,
                        sm_scale=sm_scale, total_len=pc.length)
    return attn, new


# ---- the paged ragged attention kernel ---------------------------------


def _paged_attn_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                       acc, m_scr, l_scr, *, scale, S, P, T, n_rows):
    n, t = pl.program_id(0), pl.program_id(2)

    @pl.when(t == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    idx = len_ref[n]

    def compute():
        # fused dequant: int8/bf16 pages convert to f32 on the VMEM
        # tile, inside the same kernel that consumes them — the
        # cache_dtype tier's separate dequant op is gone
        q = q_ref[0, 0].astype(jnp.float32)              # (Rq, Dp)
        k = k_ref[0, 0].astype(jnp.float32)              # (P, Dp)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # rows are (g, s) pairs of the GQA group: query s of the chunk
        # sees global positions <= idx + s; padded rows stay empty
        keep = ((row < n_rows)
                & (t * P + col <= idx + row % S))
        s = jnp.where(keep, s, NEG_INF)
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        e = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * corr + jnp.sum(e, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            e, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    # ragged skip: pages wholly past this row's horizon (idx + S - 1)
    # are never read — per-token work tracks ACTUAL depth, not max_len
    pl.when(t * P <= idx + S - 1)(compute)

    @pl.when(t == T - 1)
    def _():
        l = l_scr[:, :1]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc[...] / safe).astype(o_ref.dtype)


def check_paged_geometry(page: int, head_dim: int, group: int, s: int):
    """Loud validation of a paged-kernel geometry: sublane-aligned page,
    VMEM-budget fit under the shared `vmem_model` formula. Raised at
    trace time on the kernel path and re-checked by ``tools/aot_check``
    for every engine-configured shape (including the int8 and bf16
    cache dtypes) — an unregistered/unfittable shape fails loudly, it
    never silently falls back."""
    from apex1_tpu.vmem_model import CHECKS, budget_bytes
    if page % 8 != 0 or page < 8:
        raise ValueError(
            f"paged_decode needs a sublane-aligned page size (multiple "
            f"of 8), got {page} — set EngineConfig.page_size")
    dp = max(_LANES, ((head_dim + _LANES - 1) // _LANES) * _LANES)
    rq = max(8, ((group * s + 7) // 8) * 8)
    fits, est = CHECKS["paged_decode"]({"page_p": page},
                                      {"Dp": dp, "Rq": rq}, 4,
                                      budget_bytes())
    if not fits:
        raise ValueError(
            f"paged_decode geometry page={page} Dp={dp} Rq={rq} needs "
            f"~{est} B of VMEM — over budget; shrink page_size")
    return dp, rq


def paged_attend(q, k_pages, v_pages, block_table, lengths, *,
                 sm_scale: Optional[float] = None,
                 total_len: Optional[int] = None):
    """Ragged paged decode attention: (N, Hq, S, D) queries against
    (num_pages, Hkv, page, D) K/V pools through an (N, T) block table,
    each row masked to its own ``lengths[n] + j`` horizon. Composite
    path gathers dense lanes and runs :func:`cache_attend` (bitwise the
    dense engine's math); Pallas path streams pages via
    scalar-prefetched block-table indices with int8 dequant fused
    in-kernel."""
    N, Hq, S, D = q.shape
    num_pages, Hkv, P, _ = k_pages.shape
    T = block_table.shape[1]
    if Hq % Hkv != 0:
        raise ValueError(f"Hq={Hq} must be a multiple of Hkv={Hkv}")
    lengths = jnp.asarray(lengths, jnp.int32).reshape(-1)
    L = T * P if total_len is None else int(total_len)
    if not use_pallas():
        k_all = gather_pages(k_pages, block_table, L)
        v_all = gather_pages(v_pages, block_table, L)
        return cache_attend(q, k_all, v_all, lengths, sm_scale=sm_scale)
    G = Hq // Hkv
    scale = (D ** -0.5) if sm_scale is None else sm_scale
    Dp, Rqp = check_paged_geometry(P, D, G, S)
    qv = q.reshape(N, Hkv, G * S, D)
    qv, _ = pad_to(qv, 2, Rqp)
    qv, _ = pad_to(qv, 3, Dp)
    kp, _ = pad_to(k_pages, 3, Dp)
    vp, _ = pad_to(v_pages, 3, Dp)
    btf = jnp.asarray(block_table, jnp.int32).reshape(-1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N, Hkv, T),
        in_specs=[
            pl.BlockSpec((1, 1, Rqp, Dp),
                         lambda n, h, t, bt, ln: (n, h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, P, Dp),
                         lambda n, h, t, bt, ln: (bt[n * T + t], h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, P, Dp),
                         lambda n, h, t, bt, ln: (bt[n * T + t], h, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, Rqp, Dp),
                               lambda n, h, t, bt, ln: (n, h, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((Rqp, Dp), jnp.float32),
            pltpu.VMEM((Rqp, _LANES), jnp.float32),
            pltpu.VMEM((Rqp, _LANES), jnp.float32)],
    )
    out = kernel_call(
        functools.partial(_paged_attn_kernel, scale=scale, S=S, P=P,
                          T=T, n_rows=G * S),
        name="paged_attend",
        grid_spec=grid_spec,
        out_shape=out_struct((N, Hkv, Rqp, Dp), q.dtype, qv, kp, vp),
        interpret=interpret_mode(),
    )(btf, lengths, qv, kp, vp)
    return out[:, :, :G * S, :D].reshape(N, Hq, S, D)


# ---- the parity drill (check_all's paged gate) --------------------------


def _drill():
    """Standalone paged-vs-reference parity drill — `check_all.sh`'s
    `== paged parity drill ==` step. Forces the Pallas kernels (CPU =
    interpret mode; on a real TPU the same drill exercises actual
    Mosaic) against the XLA-composed reference on ragged pools in BOTH
    cache dtypes, decode AND verify shapes, and the fused sampler at
    every tier-1 temperature with a non-trivial ``block_v`` split.
    Attention compares at the suite's f32 tolerance (flash fold vs
    composite softmax differ at the ulp, by construction); TOKENS are
    exact equality — the same contract tier-1 pins through the engine
    (`tests/test_paged_decode.py`)."""
    from apex1_tpu.ops._common import force_impl

    rng = np.random.default_rng(0)
    N, Hq, Hkv, D, P, T = 4, 8, 2, 64, 16, 6
    n_pg = 1 + N * T
    bt = jnp.asarray(
        np.arange(1, n_pg, dtype=np.int32).reshape(N, T))
    lens = jnp.asarray([1, P - 1, P + 3, T * P - 6], dtype=jnp.int32)
    q1 = jnp.asarray(rng.standard_normal((N, Hq, 1, D)), jnp.float32)
    S_v = 5
    qv = jnp.asarray(rng.standard_normal((N, Hq, S_v, D)), jnp.float32)
    raw = rng.standard_normal((2, n_pg, Hkv, P, D))
    for name, cast in (
            ("bf16", lambda a: jnp.asarray(a, jnp.bfloat16)),
            ("int8", lambda a: jnp.asarray(
                np.clip(np.round(a * 30.0), -127, 127), jnp.int8))):
        kp, vp = cast(raw[0]), cast(raw[1])
        for tag, q, ln in (("decode", q1, lens),
                           ("verify", qv, lens)):
            with force_impl("xla"):
                ref = paged_attend(q, kp, vp, bt, ln, total_len=T * P)
            with force_impl("pallas"):
                ker = paged_attend(q, kp, vp, bt, ln, total_len=T * P)
            np.testing.assert_allclose(
                np.asarray(ker, np.float32), np.asarray(ref, np.float32),
                rtol=1e-5, atol=1e-6)
            print(f"paged_attend {name} {tag}: kernel == reference OK")
    R, V = 8, 1024
    lg = jnp.asarray(rng.standard_normal((R, V)), jnp.float32)
    seeds = jnp.asarray(rng.integers(0, 2**31 - 1, R), jnp.int32)
    pos = jnp.asarray(rng.integers(0, 4096, R), jnp.int32)
    for temp in (0.0, 0.7, 1.3):
        with force_impl("xla"):
            ref = fused_sample(lg, seeds, pos, temperature=temp,
                               vocab_size=V - 175)
        with force_impl("pallas"):
            ker = fused_sample(lg, seeds, pos, temperature=temp,
                               vocab_size=V - 175, block_v=256)
        np.testing.assert_array_equal(np.asarray(ker), np.asarray(ref))
        print(f"fused_sample T={temp} block_v=256: tokens == "
              f"composite OK")
    print("paged parity drill PASSED")


if __name__ == "__main__":
    import sys

    if "--drill" in sys.argv:
        _drill()
    else:
        sys.exit("usage: python -m apex1_tpu.ops.paged_decode --drill")
