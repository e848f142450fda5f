"""Autoregressive generation with a functional KV cache (beyond-reference:
the reference accelerates training only; a complete framework needs the
sampling loop its users run after fine-tuning).

TPU-first design: the cache is an explicit pytree threaded through the
model (no mutable state), so the whole decode loop is ONE ``lax.scan``
inside ONE ``jit`` — token steps never return to the host, and the cache
update is an in-place ``dynamic_update_slice`` XLA aliases into the donated
carry. Prefill runs the normal flash-attention forward (filling the cache
in one pass); each decode step attends over the static-shape cache with a
position mask (S_max is static; no dynamic shapes on the MXU path).

Supported: `models.gpt2.GPT2` and `models.llama.Llama` (GQA included) via
``cache=``/``cache_index=`` on their ``__call__`` (drive with
:func:`generate` below), and `models.t5.T5` seq2seq via
:func:`t5_generate` (encode once; cached decoder self-attention with the
rel-pos bias row at the current index).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from apex1_tpu.obs.regions import region
from apex1_tpu.ops import NEG_INF
from apex1_tpu.ops._common import mosaic_dtype, use_pallas
from apex1_tpu.ops.attention import flash_attention
from apex1_tpu.ops.decode_attend import MAX_ROWS, decode_attend
# the decode-attention composite and the sampling pipeline are owned by
# ops.paged_decode so the paged serving path and this dense reference
# path share ONE implementation (token parity is structural, not tested
# into existence); re-exported here as the documented public surface
from apex1_tpu.ops.paged_decode import (PagedCache,  # noqa: F401
                                        _temperature_top_k, cache_attend,
                                        paged_update_attend, sample_token)


def init_cache(num_layers: int, batch: int, num_kv_heads: int,
               max_len: int, head_dim: int, dtype=jnp.bfloat16, *,
               page_form: bool = False):
    """Zeroed per-layer KV cache: {"layer{i}": {"k","v": (B, S_max,
    Hkv * D)}} — one position's K (or V) for all heads is one
    contiguous row, as it leaves the QKV projection, and the minor
    dimension is lane-dense where Hkv * D is a multiple of 128 (a head
    of 64 is not padded to 128 lanes). This is the ONE place that
    chooses the dense entry's stored form; `cache_write`,
    `ops.paged_decode.cache_attend` and `ops.decode_attend` are the
    three that read or write positions in it, `cache_len` says how many
    it holds, and everything else treats a leaf as opaque with the
    batch (the serving pool's slot) on axis 0.

    ``page_form=True`` is the paged pool's own form instead, (pages,
    Hkv, page, D), which `ops.paged_decode` addresses by block table
    (``batch`` pages of ``max_len`` positions)."""
    shape = ((batch, num_kv_heads, max_len, head_dim) if page_form
             else (batch, max_len, num_kv_heads * head_dim))
    one = lambda: {"k": jnp.zeros(shape, dtype),
                   "v": jnp.zeros(shape, dtype)}
    return {f"layer{i}": one() for i in range(num_layers)}


def cache_len(cache) -> int:
    """Positions a dense cache pytree (or one leaf of it) holds: read off
    a K/V leaf, since a tree may hold entries of another kind beside them
    (a recurrent state has no positions: `granite_hybrid_decoder`), and
    off the longest of them (a ring holds a window: `afmoe_decoder`)."""
    leaves = jax.tree_util.tree_flatten_with_path(cache)[0]
    kv = [x for path, x in leaves
          if path and getattr(path[-1], "key", None) in ("k", "v")]
    # the LONGEST: a sliding-attention layer's leaf is a ring, shorter
    # than the positions the tree serves (`models.afmoe`)
    return max(x.shape[1] for x in (kv or [x for _, x in leaves]))


def cached_attention(q, k_new, v_new, cache, cache_index, *,
                     sm_scale: Optional[float] = None, bias=None,
                     segment_ids=None, valid_start=None,
                     chunk_decode: bool = False,
                     window: Optional[int] = None):
    """Attention through the KV cache. ``q``/``k_new``/``v_new``:
    (B, H, S, D)/(B, Hkv, S, D) for the CURRENT tokens; ``cache`` holds
    (B, S_max, Hkv * D) (`init_cache`); ``cache_index`` is the (traced) write position:
    a scalar (one position for the batch — `generate`, beam search,
    prefill) or a (B,) vector (one per row — the serving engine's
    decode / verify step, rows at different depths; a row whose index
    is negative has nothing to do: no K/V is appended to it, nothing of
    it is read by the kernel, and its output is not meaningful); see
    `cache_write`. What the call can see in its input chooses the
    path: a rank-1 index where the kernels run (``use_pallas()``), with
    at most ``MAX_ROWS`` query rows and neither ``bias`` nor
    ``valid_start``, is `ops.decode_attend`'s one kernel; a scalar
    index, a prefill chunk or a CPU takes the composite below.

    - Prefill (S > 1): must start from an empty cache at index 0 — runs
      the causal flash kernel over the current tokens (with ``bias``
      riding its additive-bias operand — T5's rel-pos path stays
      O(S·D)) and writes them into the cache.
    - Decode (S == 1): composite matvec attention over the cache, masked
      to positions ≤ cache_index (static S_max — no dynamic shapes).

    ``bias``: additive logit bias. For prefill, shaped over the CURRENT
    tokens (1, H, S, S) (causality comes from the kernel's causal flag,
    not the bias); for decode, the query row vs all cache slots
    (1, H, 1, S_max).

    RAGGED batches (left-padded prompts of different lengths — see
    ``generate(prompt_lens=...)``): ``segment_ids`` (B, S) rides the
    flash kernel's varlen operand at prefill so pad and real tokens
    never attend across; ``valid_start`` (B,) masks decode attention to
    cache slots ≥ each row's first real position (the left-pad K/V slots
    are garbage by construction).

    ``chunk_decode=True`` is the third mode (speculative-decoding
    verify): S > 1 NEW tokens against a NON-empty cache — query j
    attends cache positions ≤ cache_index + j (history + causal within
    the chunk), via the composite path with a per-query mask. S == 1
    decode is the chunk_decode special case. An EMPTY cache at
    ``cache_index == 0`` is also legal here (the horizon mask reduces to
    plain causal prefill) — this is the FIXED-SHAPE chunked-prefill mode
    `apex1_tpu.serving`'s engine rides: one (1, C) chunk executable
    serves every prompt length (pad the tail chunk on the RIGHT; query
    j never reaches a pad slot k > cache_index + j, and the next write
    overwrites the pad K/V before any query can see it).

    ``window`` (a sliding-attention layer): query j attends the last
    ``window`` positions up to its own alone, and the entry is a RING:
    position ``p`` lies in row ``p mod L``, so ``L >= window + S - 1``
    rows serve any depth (`ops.decode_attend`, `cache_attend`; an entry
    no longer than the window holds every position and never wraps). Every
    call then attends THROUGH the cache (``chunk_decode``'s path), a
    prefill from an empty cache too: the flash kernels have no window.

    Returns (attn (B, H, S, D), new_cache_entry).
    """
    with region("attn"):
        B, Hq, S, D = q.shape
        Hkv = k_new.shape[1]
        if isinstance(cache, PagedCache):
            # paged serving tier: K/V live in a shared page pool addressed
            # through the entry's block table; bias/segment_ids/valid_start
            # have no paged consumers (serving prompts are right-padded)
            if (bias is not None or segment_ids is not None
                    or valid_start is not None):
                raise ValueError(
                    "PagedCache attention does not support bias/"
                    "segment_ids/valid_start")
            return paged_update_attend(q, k_new, v_new, cache, cache_index,
                                       sm_scale=sm_scale,
                                       chunk_decode=chunk_decode)
        idx = jnp.asarray(cache_index, jnp.int32)
        ring = {}
        if window is not None:
            if bias is not None or segment_ids is not None \
                    or valid_start is not None:
                raise ValueError("a window takes neither bias, "
                                 "segment_ids nor valid_start")
            if window < cache["k"].shape[1] < window + S - 1:
                raise ValueError(
                    f"a ring of {cache['k'].shape[1]} rows cannot take {S} "
                    f"new positions under a window of {window}: rows still "
                    f"attended would be overwritten")
            ring, chunk_decode = {"window": int(window)}, True
        if (idx.ndim == 1 and use_pallas() and Hq * S <= MAX_ROWS
                and (S == 1 or chunk_decode) and bias is None
                and valid_start is None
                and mosaic_dtype(cache["k"].dtype) == cache["k"].dtype):
            # the serving engine's step: rows at their own depths. One
            # kernel appends each lane's rows where they lie and reads the
            # lane to its horizon and no further (`ops.decode_attend`)
            attn, k_all, v_all = decode_attend(q, k_new, v_new, cache["k"],
                                               cache["v"], idx,
                                               sm_scale=sm_scale, **ring)
            return attn, {"k": k_all, "v": v_all}
        k_all = cache_write(cache["k"], k_new, idx, ring=bool(ring))
        v_all = cache_write(cache["v"], v_new, idx, ring=bool(ring))
        new_entry = {"k": k_all, "v": v_all}
        if S > 1 and not chunk_decode:
            # prefill attends only over the CURRENT tokens — valid only from
            # an empty cache. Fail fast on a concrete nonzero index (the
            # common prefill call passes a Python 0); a traced nonzero index
            # remains the documented precondition (ADVICE r3).
            if isinstance(cache_index, int) and cache_index != 0:
                raise ValueError(
                    f"cached_attention prefill (S={S} > 1) requires an "
                    f"empty cache at cache_index 0, got {cache_index} — it "
                    f"attends only over the new tokens, so a non-empty "
                    f"cache would be silently ignored")
            # prefill is always autoregressive; with bias the flash kernel's
            # additive-bias operand keeps this O(S·D) too
            attn = flash_attention(q, k_new, v_new, causal=True,
                                   sm_scale=sm_scale, bias=bias,
                                   segment_ids=segment_ids)
            return attn, new_entry
        attn = cache_attend(q, k_all, v_all, idx, sm_scale=sm_scale,
                            bias=bias, valid_start=valid_start, **ring)
        return attn, new_entry


def cache_write(cache, new, cache_index, ring: bool = False):
    """``cache`` (B, S_max, Hkv * D) with ``new`` (B, Hkv, S, D), as a
    model hands its K or V, written at ``cache_index``; the index's
    RANK chooses how.

    A scalar (one position for the whole batch) is one
    ``dynamic_update_slice``: S contiguous rows touched, in place in a
    donated carry. A (B,) vector puts row b's chunk at ``idx[b] ..
    idx[b] + S - 1``, and ``idx[b] < 0`` leaves row b as it is; there a
    batched ``dynamic_update_slice`` would be a scatter, which XLA
    expands on TPU into a loop of B one-row updates between two layout
    copies of the whole cache. Selecting by position instead is
    elementwise, so it fuses into the attention that reads the cache
    next. It rewrites every position, which is why the scalar case does
    not take it and why, where the kernels run, the engine's step does
    not either (`ops.decode_attend` writes the rows alone). A position
    past ``S_max`` is dropped there, not clamped onto earlier rows.

    ``ring``: position ``p`` lies in row ``p mod S_max`` (a
    sliding-attention layer's entry), so a chunk may wrap: either rank
    then selects by position, the chunk rolled into its rows (a ring is
    a window long, and a prefill chunk rewrites one lane's)."""
    idx = jnp.asarray(cache_index, jnp.int32)
    B, _, S, _ = new.shape
    new = new.astype(cache.dtype).transpose(0, 2, 1, 3).reshape(B, S, -1)
    L = cache.shape[1]
    if ring and idx.ndim == 0:
        if S > L:
            raise ValueError(f"{S} new positions into a ring of {L} rows")
        rolled = jnp.roll(jnp.pad(new, ((0, 0), (0, L - S), (0, 0))),
                          idx % L, axis=1)
        here = (jnp.arange(L, dtype=jnp.int32) - idx) % L < S
        return jnp.where(here[None, :, None], rolled, cache)
    if idx.ndim == 0:
        return jax.lax.dynamic_update_slice(cache, new, (0, idx, 0))
    pos = jnp.arange(L, dtype=jnp.int32)
    if ring:
        rel = jnp.where(idx[:, None] >= 0,
                        (pos[None, :] - idx[:, None]) % L, -1)[:, :, None]
    else:
        rel = jnp.where(idx[:, None] >= 0, pos[None, :] - idx[:, None],
                        -1)[:, :, None]                 # (B, S_max, 1)
    for j in range(S):
        cache = jnp.where(rel == j, new[:, j:j + 1], cache)
    return cache


def last_real_logits(logits, lengths):
    """(B, S, V) chunk logits → (B, V) at each row's LAST REAL token
    (index ``lengths[b] - 1``). The gather behind fixed-shape prefill:
    `apex1_tpu.serving`'s engine pads every prompt's tail chunk up to
    the chunk width, so the logit to sample the first token from sits
    at a per-row TRACED index, not at ``[:, -1]`` — one executable
    serves every prompt length without re-jitting per call."""
    idx = (jnp.asarray(lengths, jnp.int32) - 1).reshape(-1, 1, 1)
    return jnp.take_along_axis(logits, idx, axis=1)[:, 0]


def generate(apply_fn: Callable, params, prompt_tokens, *,
             max_new_tokens: int, cache,
             temperature: float = 0.0, top_k: Optional[int] = None,
             rng=None, eos_id: Optional[int] = None, pad_id: int = 0,
             vocab_size: Optional[int] = None, prompt_lens=None,
             cache_start: int = 0, return_cache: bool = False):
    """Prefill + single-dispatch decode loop.

    ``apply_fn(params, tokens, cache, cache_index) -> (logits, cache)``
    — the model's cached forward (see `models.gpt2`/`models.llama`
    ``cache=`` support). ``cache`` must be sized >= prompt_len +
    max_new_tokens. Returns (B, max_new_tokens) generated ids; sequences
    that emit ``eos_id`` are padded with ``pad_id`` afterwards.

    RAGGED batches: pass ``prompt_lens`` (B,) with ``prompt_tokens``
    right-padded to a common S0. TPU-first shape discipline — instead of
    per-row cache indices (`cached_attention` takes them, but then
    rewrites the whole cache each step where a scalar index touches one
    row of it), rows are
    LEFT-aligned once up front so every row's last real token sits at
    S0−1: the cache write index stays one scalar, decode steps stay one
    ``dynamic_update_slice``, and the pad prefix is masked out by the
    flash kernel's ``segment_ids`` at prefill and a per-row
    ``valid_start`` at decode (garbage pad K/V slots are never read).
    Each row's positions count from ITS OWN start (RoPE/learned
    positions see 0..len−1), so short rows decode exactly as if they
    were alone. Requires an ``apply_fn`` with the
    ``positions``/``segment_ids``/``valid_start`` kwargs
    (`gpt2_decoder`/`llama_decoder` provide them).

    PREFIX CACHING: ``cache_start > 0`` continues from a cache already
    holding that many positions — a shared system-prompt prefix
    prefilled ONCE via ``apply_fn(params, prefix, cache, 0)``, or the
    cache a previous ``generate(..., return_cache=True)`` handed back.
    ``prompt_tokens`` are the NEW tokens appended after it. The
    continuation prefill rides the chunk-decode attention mode (new
    tokens attend the cached prefix + their own causal prefix), so the
    shared prefix is never re-computed. Not combinable with
    ``prompt_lens``.

    ``return_cache=True`` returns ``(tokens, cache)`` — the cache after
    the final decode step. The FINAL sampled token is never fed back
    through the model, so its K/V is absent: the cache holds
    ``cache_start + S0 + max_new_tokens - 1`` positions, and a
    continuation must pass ``cache_start=cache_start + S0 +
    max_new_tokens - 1`` with the final emitted token as the FIRST
    token of its continuation prompt (see
    ``test_chained_generate_via_return_cache``). Continuing at
    ``+ max_new_tokens`` instead would leave a zero-K/V slot that
    chunk-decode attention still attends and silently drop the last
    token from context. Not combinable with ``prompt_lens``: a
    ragged-produced cache carries garbage left-pad K/V the
    continuation would attend (loud ValueError).

    The decode loop is a ``lax.scan`` — jit the whole call (e.g.
    ``jax.jit(functools.partial(generate, apply_fn, max_new_tokens=...,
    ...))``) for one-dispatch generation.
    """
    B, S0 = prompt_tokens.shape
    if rng is None:
        rng = jax.random.key(0)
    s_max = cache_len(cache)
    if s_max < cache_start + S0 + max_new_tokens:
        # dynamic_update_slice CLAMPS out-of-range writes: an undersized
        # cache would repeatedly overwrite its last slot and silently
        # diverge — the exact hazard speculative_generate also guards
        raise ValueError(
            f"cache holds {s_max} positions but this call needs "
            f"cache_start + prompt + max_new_tokens = "
            f"{cache_start + S0 + max_new_tokens}")
    kw = {}
    lens = None
    if return_cache and prompt_lens is not None:
        # the continuation API (cache_start, scalar positions) has no
        # channel for per-row valid_start/lens, so a ragged-produced
        # cache would be continued attending its garbage left-pad K/V
        # slots with uniformly-shifted RoPE positions — silently wrong
        # tokens for every short row. Refuse loudly (docs/serving.md
        # composition matrix: ragged x prefix-cache-production is an
        # unsupported cell).
        raise ValueError(
            "return_cache and prompt_lens cannot be combined — the "
            "returned cache's left-pad slots hold garbage K/V that a "
            "cache_start continuation would attend; produce "
            "continuation caches from dense (non-ragged) prompts")
    if cache_start:
        if prompt_lens is not None:
            raise ValueError(
                "cache_start (prefix caching) and prompt_lens (ragged "
                "batches) cannot be combined — left-aligned rows would "
                "shear against the shared cached prefix")
        kw = dict(chunk_decode=True)
    elif prompt_lens is not None:
        prompt_tokens, kw, pad = _ragged_align(prompt_tokens, prompt_lens)
        lens = S0 - pad
    logits, cache = apply_fn(params, prompt_tokens, cache, cache_start,
                             **kw)
    rng, sub = jax.random.split(rng)
    nxt = sample_token(logits[:, -1], sub, temperature=temperature,
                       top_k=top_k, vocab_size=vocab_size)
    done = jnp.zeros((B,), bool) if eos_id is None else (nxt == eos_id)

    def body(carry, _):
        tok, idx, cache, rng, done = carry
        if lens is None:
            dkw = {}
        else:
            # per-row positions continue each row's own count; the scalar
            # cache index keeps advancing uniformly past S0
            dkw = dict(positions=(lens + (idx - S0))[:, None],
                       valid_start=S0 - lens)
        logits, cache = apply_fn(params, tok[:, None], cache, idx, **dkw)
        rng, sub = jax.random.split(rng)
        new = sample_token(logits[:, -1], sub, temperature=temperature,
                           top_k=top_k, vocab_size=vocab_size)
        new = jnp.where(done, pad_id, new)
        if eos_id is not None:
            done = done | (new == eos_id)
        return (new, idx + 1, cache, rng, done), new

    (_, _, cache, _, _), rest = jax.lax.scan(
        body, (nxt, jnp.asarray(cache_start + S0, jnp.int32), cache, rng,
               done),
        None, length=max_new_tokens - 1)
    toks = jnp.concatenate([nxt[:, None], rest.T], axis=1)
    return (toks, cache) if return_cache else toks


def _ragged_align(prompt_tokens, prompt_lens):
    """LEFT-align a right-padded ragged batch and build the prefill
    masking kwargs — the shared mechanics behind ``prompt_lens`` in
    :func:`generate` AND :func:`speculative_generate` (contract
    documented on `generate`). Returns ``(aligned_tokens, prefill_kw,
    pad)`` where ``pad`` (B,) is each row's left-pad width (== its
    decode-time ``valid_start``)."""
    B, S0 = prompt_tokens.shape
    try:  # fail fast on concrete out-of-range lengths (a traced
        # lens skips the check); pad/position math below silently
        # scrambles the row otherwise
        lv = np.asarray(prompt_lens)
    except Exception:
        lv = None
    if lv is not None and ((lv < 1).any() or (lv > S0).any()):
        raise ValueError(
            f"prompt_lens must lie in [1, {S0}] (the padded prompt "
            f"width), got {lv.tolist()}")
    lens = jnp.asarray(prompt_lens, jnp.int32)
    pad = S0 - lens                             # left-pad widths (B,)
    # left-align: row b shifts right by pad_b (one gather); the
    # wrapped-in entries land in the pad region and are masked
    gidx = (jnp.arange(S0)[None, :] - pad[:, None]) % S0
    aligned = jnp.take_along_axis(prompt_tokens, gidx, axis=1)
    # pad slots get segment -1, the repo-wide padding convention
    # (`pack_documents`, xentropy's `label >= 0`): the flash kernel's
    # equality mask only needs "different from the real segment", but
    # MoE routing masks tokens with `segment_ids >= 0` — a 0-valued pad
    # would be ROUTED and claim expert capacity, silently perturbing
    # other rows' tokens (review r5)
    kw = dict(
        positions=jnp.maximum(
            jnp.arange(S0)[None, :] - pad[:, None], 0),
        segment_ids=jnp.where(
            jnp.arange(S0)[None, :] >= pad[:, None], 1, -1
        ).astype(jnp.int32),
        valid_start=pad)
    return aligned, kw, pad


def counter_sample(logits, seed, positions, *, temperature: float = 0.0,
                   top_k: Optional[int] = None,
                   vocab_size: Optional[int] = None):
    """Counter-keyed sampling over an (S, V) logits chunk: the token at
    output position ``positions[j]`` is drawn with
    ``fold_in(key(seed), positions[j])`` — the per-request counter-PRNG
    contract (`docs/serving.md` § Per-request sampling seeds) as ONE
    shared function. The serving engine's speculative verify executable
    samples the target's canonical stream through this, which is what
    makes a draft/verify round emit tokens BIT-IDENTICAL to plain
    step-decode of the same (params, prompt, seed) at any temperature —
    and therefore resubmission-safe and hedging-compatible. ``seed`` and
    ``positions`` (S,) may be traced."""
    seed = jnp.asarray(seed, jnp.int32)

    def one(lg, p):
        key = jax.random.fold_in(jax.random.key(seed), p)
        return sample_token(lg[None], key, temperature=temperature,
                            top_k=top_k, vocab_size=vocab_size)[0]

    return jax.vmap(one)(logits, jnp.asarray(positions, jnp.int32))


def _masked_probs(logits, *, temperature: float, top_k: Optional[int],
                  vocab_size: Optional[int]):
    """The probability distribution `sample_token` samples from: fp32,
    padded-vocab tail masked, then the SHARED `_temperature_top_k`
    pipeline (one implementation — a fix to the masking reaches both
    the sampler and the speculative accept rule). (..., V) logits."""
    lg = logits.astype(jnp.float32)
    V = lg.shape[-1]
    if vocab_size is not None and vocab_size < V:
        lg = jnp.where(jnp.arange(V) < vocab_size, lg, NEG_INF)
    return jax.nn.softmax(
        _temperature_top_k(lg, temperature, top_k, vocab_size), axis=-1)


def _speculative_accept(p, q, drafts, key):
    """One round of the speculative-sampling accept/resample rule
    (Leviathan et al. 2023; Chen et al. 2023): accept draft ``x_j`` with
    probability ``min(1, p_j(x_j) / q_j(x_j))``; at the first rejection
    emit a sample of the residual ``norm(max(p_j − q_j, 0))``; if all K
    accepted emit a bonus sample of ``p_K``. The emitted sequence is
    distributed EXACTLY as ancestral sampling from ``p``.

    ``p``: (K+1, V) target probs, ``q``: (K, V) draft probs, ``drafts``:
    (K,) proposed tokens. Returns ``(a, correction)`` — the accepted
    count and the token to emit at position ``a``.
    """
    K = drafts.shape[0]
    key_u, key_c = jax.random.split(key)
    j = jnp.arange(K)
    p_at = p[j, drafts]                               # p_j(x_j)
    q_at = jnp.maximum(q[j, drafts], 1e-30)           # x_j ~ q_j => > 0
    accept = jax.random.uniform(key_u, (K,)) < jnp.minimum(
        1.0, p_at / q_at)
    a = jnp.sum(jnp.cumprod(accept.astype(jnp.int32)))
    p_row = p[a]                                      # (V,) row a<=K
    q_row = jnp.where(a == K, 0.0, q[jnp.minimum(a, K - 1)])
    r = jnp.maximum(p_row - q_row, 0.0)               # residual (bonus:
    s = jnp.sum(r)                                    #  q_row=0 => p_K)
    r = jnp.where(s > 0, r / jnp.maximum(s, 1e-30), p_row)
    corr = jax.random.categorical(
        key_c, jnp.where(r > 0, jnp.log(jnp.maximum(r, 1e-30)),
                         NEG_INF)).astype(jnp.int32)
    return a, corr


def speculative_generate(target_fn, target_params, draft_fn, draft_params,
                         prompt_tokens, *, max_new_tokens: int,
                         target_cache, draft_cache, num_draft: int = 4,
                         temperature: float = 0.0,
                         top_k: Optional[int] = None, rng=None,
                         eos_id: Optional[int] = None, pad_id: int = 0,
                         vocab_size: Optional[int] = None,
                         prompt_lens=None):
    """Speculative decoding: a cheap DRAFT model proposes ``num_draft``
    tokens autoregressively; the TARGET model scores all of them in ONE
    chunk-verify forward (``chunk_decode=True`` — K+1 new tokens against
    its cache, causal within the chunk); the longest accepted prefix
    plus one correction token are emitted per round. The draft only
    changes how many target forwards it takes (1 per ~(accepted+1)
    tokens instead of 1 per token; decode is HBM-bound, so fewer target
    weight streams ≈ proportional speedup when the draft is much
    smaller).

    - ``temperature == 0`` (default): GREEDY — accept while the draft
      matches the target's argmax; output is TOKEN-IDENTICAL to plain
      greedy decoding of the target alone.
    - ``temperature > 0``: SPECULATIVE SAMPLING — drafts are sampled
      from the draft's (temperature/top-k) distribution and accepted by
      the `_speculative_accept` rejection rule, so the emitted sequence
      is distributed EXACTLY as ancestral sampling from the target's
      (temperature/top-k) distribution; with draft == target the
      acceptance ratio is 1 up to chunk-verify-vs-step-decode numerics
      (~1e-4 rel on logits), so essentially every proposal is
      accepted.

    TPU-first shape discipline: every round is fixed-size (K draft
    steps + one (K+1)-token verify); per-row acceptance raggedness lives
    in a ``lax.while_loop`` carried per row under ``jax.vmap`` (the
    batching rule runs until every row finishes, masking finished rows)
    — one dispatch, no host round-trips, static shapes throughout.

    ``target_fn``/``draft_fn`` take the `llama_decoder`/`gpt2_decoder`
    apply contract (incl. the ``chunk_decode`` kwarg). Caches must be
    sized >= prompt_len + max_new_tokens + num_draft + 1 (rejected
    speculative entries briefly occupy the tail before being
    overwritten).

    RAGGED batches: pass ``prompt_lens`` (B,) with ``prompt_tokens``
    right-padded to a common S0 — the same left-align contract as
    :func:`generate` (rows realigned once; per-row positions and
    ``valid_start`` thread through BOTH models' draft steps and the
    chunk-verify, so each row speculates exactly as if it were alone).
    The draft and target see identical alignment, so acceptance
    statistics are unaffected by padding.

    The draft is ANY apply_fn with the decoder contract — including the
    int8 `models.quant_decode` decoders (an int8 draft under a bf16
    target changes only acceptance rates at temperature > 0; at
    temperature 0 the output stays token-identical to the target's own
    greedy decode, whatever the draft).

    Returns (tokens (B, max_new_tokens), target_forwards (B,)) — the
    second output counts verify rounds per row (+1 prefill is implied),
    the observable the speedup comes from.
    """
    B, S0 = prompt_tokens.shape
    K = int(num_draft)
    if K < 1:
        raise ValueError(f"num_draft must be >= 1, got {K}")
    for nm, c in (("target_cache", target_cache),
                  ("draft_cache", draft_cache)):
        s_max = cache_len(c)
        if s_max < S0 + max_new_tokens + K + 1:
            # dynamic_update_slice CLAMPS out-of-range writes — an
            # undersized cache would silently overwrite earlier K/V and
            # diverge from target-only greedy; fail at trace time
            raise ValueError(
                f"{nm} holds {s_max} positions but speculative decoding "
                f"needs >= prompt + max_new_tokens + num_draft + 1 = "
                f"{S0 + max_new_tokens + K + 1} (rejected speculative "
                f"entries briefly occupy the tail)")

    sampled = temperature != 0.0
    if rng is None:
        rng = jax.random.key(0)

    def greedy(logits):
        # sample_token's temperature-0 path: fp32 + padded-vocab mask +
        # argmax (rng unused)
        return sample_token(logits, None, vocab_size=vocab_size)

    def probs(logits):
        return _masked_probs(logits, temperature=temperature,
                             top_k=top_k, vocab_size=vocab_size)

    # prefill both models at batch B (ordinary flash prefill); ragged
    # rows are left-aligned ONCE and both models see the same alignment
    pad = None
    pre_kw = {}
    if prompt_lens is not None:
        prompt_tokens, pre_kw, pad = _ragged_align(prompt_tokens,
                                                   prompt_lens)
    logits_t, target_cache = target_fn(target_params, prompt_tokens,
                                       target_cache, 0, **pre_kw)
    _, draft_cache = draft_fn(draft_params, prompt_tokens, draft_cache, 0,
                              **pre_kw)
    rng, sub = jax.random.split(rng)
    t0 = sample_token(logits_t[:, -1], sub, temperature=temperature,
                      top_k=top_k, vocab_size=vocab_size)
    row_keys = jax.random.split(rng, B)

    def row_loop(t0_row, cache_t_row, cache_d_row, row_key,
                 pad_row=None):
        buf0 = jnp.full((max_new_tokens,), pad_id, jnp.int32)
        buf0 = buf0.at[0].set(t0_row)
        done0 = (jnp.asarray(False) if eos_id is None
                 else (t0_row == eos_id))

        def cond(carry):
            _, count, _, _, done, _, _, _, _ = carry
            return (count < max_new_tokens) & ~done

        def body(carry):
            (buf, count, last, idx, done, cache_t, cache_d, rounds,
             key) = carry
            key, key_d, key_a = jax.random.split(key, 3)

            def dstep(c, step_key):
                tok, dc, di = c
                # ragged rows: the token at cache slot di is the row's
                # (di - pad_row)-th token; left-pad K/V slots stay masked
                dkw = ({} if pad_row is None else dict(
                    positions=(di - pad_row).reshape(1, 1),
                    valid_start=pad_row.reshape(1)))
                lg, dc = draft_fn(draft_params, tok.reshape(1, 1),
                                  jax.tree_util.tree_map(
                                      lambda x: x[None], dc), di, **dkw)
                dc = jax.tree_util.tree_map(lambda x: x[0], dc)
                if sampled:
                    q_row = probs(lg[0, -1])
                    nxt = jax.random.categorical(
                        step_key, jnp.where(
                            q_row > 0, jnp.log(jnp.maximum(q_row, 1e-30)),
                            NEG_INF)).astype(jnp.int32)
                else:
                    # greedy never divides by temperature=0 and carries
                    # no (V,)-sized scan output
                    q_row = jnp.zeros((lg.shape[-1],), jnp.float32)
                    nxt = greedy(lg[0, -1])
                return (nxt, dc, di + 1), (nxt, q_row)

            # K+1 steps, not K: the last step feeds drafts[K-1] so its
            # K/V lands in the draft cache (slot idx+K). Without it an
            # all-accept round left that slot permanently zero yet
            # attended, silently collapsing later acceptance rates (the
            # extra draft forward is the cheap model — the premise of
            # speculation)
            (_, cache_d, _), (drafts_ext, q_ext) = jax.lax.scan(
                dstep, (last, cache_d, idx),
                jax.random.split(key_d, K + 1))
            drafts = drafts_ext[:K]

            verify = jnp.concatenate([last[None], drafts])   # (K+1,)
            vkw = ({} if pad_row is None else dict(
                positions=(idx - pad_row
                           + jnp.arange(K + 1)).reshape(1, K + 1),
                valid_start=pad_row.reshape(1)))
            lg_t, cache_t = target_fn(
                target_params, verify[None],
                jax.tree_util.tree_map(lambda x: x[None], cache_t), idx,
                chunk_decode=True, **vkw)
            cache_t = jax.tree_util.tree_map(lambda x: x[0], cache_t)

            j = jnp.arange(K + 1)
            if sampled:
                a, corr = _speculative_accept(probs(lg_t[0]), q_ext[:K],
                                              drafts, key_a)
                toks = jnp.where(
                    j < a, jnp.concatenate([drafts, drafts[-1:]]),
                    corr)
            else:
                tgt_next = greedy(lg_t[0])                   # (K+1,)
                matches = (tgt_next[:K] == drafts).astype(jnp.int32)
                a = jnp.sum(jnp.cumprod(matches))  # leading agreements
                toks = jnp.where(
                    j < a, jnp.concatenate([drafts, drafts[-1:]]),
                    tgt_next)
            keep = (j <= a) & (count + j < max_new_tokens)
            if eos_id is not None:
                prior_eos = jnp.cumsum(
                    (toks == eos_id).astype(jnp.int32)) - (
                        toks == eos_id).astype(jnp.int32)
                keep = keep & (prior_eos == 0)
            # one scatter: invalid lanes are routed out of range and
            # dropped (kept indices are distinct, so no overlap)
            buf = buf.at[jnp.where(keep, count + j, max_new_tokens)].set(
                toks, mode="drop")
            n_emit = jnp.sum(keep.astype(jnp.int32))
            count = count + n_emit
            if eos_id is not None:
                done = done | jnp.any((toks == eos_id) & keep)
            last = toks[a]
            idx = idx + a + 1
            return (buf, count, last, idx, done, cache_t, cache_d,
                    rounds + 1, key)

        init = (buf0, jnp.asarray(1, jnp.int32), t0_row,
                jnp.asarray(S0, jnp.int32), done0, cache_t_row,
                cache_d_row, jnp.asarray(0, jnp.int32), row_key)
        buf, _, _, _, _, _, _, rounds, _ = jax.lax.while_loop(cond, body,
                                                              init)
        return buf, rounds

    if pad is None:
        return jax.vmap(row_loop)(t0, target_cache, draft_cache, row_keys)
    return jax.vmap(row_loop)(t0, target_cache, draft_cache, row_keys,
                              pad)

def beam_search(apply_fn: Callable, params, prompt_tokens, *,
                max_new_tokens: int, cache, num_beams: int = 4,
                length_penalty: float = 0.0,
                eos_id: Optional[int] = None, pad_id: int = 0,
                vocab_size: Optional[int] = None):
    """Beam search over the same cached decode step as :func:`generate`.

    TPU-first shape discipline: beams ride the batch axis — the cache
    and every decode step run at batch B·K (``cache`` must be built for
    batch ``B * num_beams``), and each step's beam reorder is one
    gather over that axis (XLA fuses it into the cache update). Prefill
    runs ONCE at batch B (the first B cache lanes) and the filled cache
    is tiled K-fold; the first expansion then takes the per-batch top-K
    tokens from that single distribution, one per lane.

    Scoring: sum of token log-probs over the VALID vocab (``vocab_size``
    masks padded-vocab logits BEFORE the softmax, as `sample_token`
    does). With ``length_penalty`` > 0, candidates compete at EVERY
    step on GNMT length-normalized scores ``sum / length**penalty``
    (length counts each beam's tokens up to and including its
    ``eos_id``), so a short finished hypothesis is never pruned by a
    longer unfinished one merely for having fewer summed terms; the
    carried scores stay unnormalized sums so accumulation is exact.
    ``length_penalty=0`` reduces to pure-sum ranking. Finished beams
    stop accumulating and pad with ``pad_id``. Returns
    (tokens (B, max_new_tokens), scores (B,)) for the best beam, scored
    by the same normalization.
    """
    B, S0 = prompt_tokens.shape
    K = num_beams

    def masked_logp(logits_row):
        lg = logits_row.astype(jnp.float32)
        if vocab_size is not None and vocab_size < lg.shape[-1]:
            lg = jnp.where(jnp.arange(lg.shape[-1]) < vocab_size, lg,
                           NEG_INF)
        return jax.nn.log_softmax(lg, -1)

    # prefill once at batch B on the cache's first B lanes, tile K-fold
    pre_cache = jax.tree_util.tree_map(lambda c: c[:B], cache)
    logits, pre_cache = apply_fn(params, prompt_tokens, pre_cache, 0)
    cache = jax.tree_util.tree_map(
        lambda c: jnp.repeat(c, K, axis=0), pre_cache)
    logp = masked_logp(logits[:, -1])                     # (B, V)
    V = logp.shape[-1]
    scores, nxt = jax.lax.top_k(logp, K)                  # (B, K)
    nxt = nxt.astype(jnp.int32)
    done = (jnp.zeros((B, K), bool) if eos_id is None
            else (nxt == eos_id))
    lens = jnp.ones((B, K), jnp.float32)

    # static-shape token buffer: the scan carries (B*K, max_new) and
    # writes one column per step (a growing concat would re-trace)
    toks_buf = jnp.full((B * K, max_new_tokens), pad_id, jnp.int32)
    toks_buf = toks_buf.at[:, 0].set(nxt.reshape(-1))

    def body(carry, t):
        nxt, idx, cache, scores, done, lens, buf = carry
        logits, cache = apply_fn(params, nxt.reshape(B * K, 1), cache,
                                 idx)
        logp = masked_logp(logits[:, -1]).reshape(B, K, V)
        # a finished beam proposes exactly one zero-score continuation
        # (pad) so its total never moves
        pad_row = jnp.where(jnp.arange(V) == pad_id, 0.0, NEG_INF)
        logp = jnp.where(done[..., None], pad_row, logp)
        cand = scores[..., None] + logp
        # rank on length-normalized scores (ADVICE r3: pure-sum in-beam
        # pruning under length_penalty > 0 let longer unfinished beams
        # evict shorter finished ones); carry the raw sums forward
        cand_len = (lens + jnp.where(done, 0.0, 1.0))[..., None]
        cand_rank = (cand / jnp.maximum(cand_len, 1.0) ** length_penalty
                     if length_penalty else cand)
        _, flat_idx = jax.lax.top_k(cand_rank.reshape(B, K * V), K)
        new_scores = jnp.take_along_axis(cand.reshape(B, K * V),
                                         flat_idx, axis=1)
        beam_src = flat_idx // V
        token = (flat_idx % V).astype(jnp.int32)
        gidx = (jnp.arange(B)[:, None] * K + beam_src).reshape(-1)
        cache = jax.tree_util.tree_map(lambda c: c[gidx], cache)
        done = jnp.take_along_axis(done, beam_src, axis=1)
        lens = jnp.take_along_axis(lens, beam_src, axis=1)
        buf = buf[gidx]
        # the emitted token counts toward length unless the beam had
        # already finished BEFORE this step (eos itself counts)
        lens = lens + jnp.where(done, 0.0, 1.0)
        if eos_id is not None:
            done = done | (token == eos_id)
        buf = jax.lax.dynamic_update_index_in_dim(
            buf, token.reshape(-1), t, axis=1)
        return (token, idx + 1, cache, new_scores, done, lens,
                buf), None

    (nxt, _, cache, scores, done, lens, toks_buf), _ = jax.lax.scan(
        body, (nxt, jnp.asarray(S0, jnp.int32), cache, scores, done,
               lens, toks_buf),
        jnp.arange(1, max_new_tokens))
    norm = scores / jnp.maximum(lens, 1.0) ** length_penalty
    best = jnp.argmax(norm, axis=1)                      # (B,)
    toks = toks_buf.reshape(B, K, -1)[jnp.arange(B), best]
    return toks, jnp.take_along_axis(norm, best[:, None], 1)[:, 0]


def _decoder(model, num_kv_heads: int, head_dim: int):
    """Shared (apply_fn, make_cache) builder: both models take the same
    ``positions``/``cache``/``cache_index`` kwargs, so the cached forward
    is one code path and only the cache geometry differs. The optional
    keyword-only args carry the RAGGED (left-padded) batch masking —
    ``generate(prompt_lens=...)`` supplies them; plain calls never do."""
    cfg = model.cfg

    def apply_fn(params, tokens, cache, cache_index, *, positions=None,
                 segment_ids=None, valid_start=None, chunk_decode=False,
                 return_hidden=False):
        B, S = tokens.shape
        if positions is None:
            # a scalar index, or one per row
            pos = (jnp.asarray(cache_index, jnp.int32)[..., None]
                   + jnp.arange(S))
            positions = jnp.broadcast_to(pos, (B, S))
        # return_hidden is forwarded only when asked: models without
        # the kwarg keep working, and the serving engine's LoRA
        # epilogue path gets the pre-head hidden states it recomputes
        # the head matmul from (gpt2 and llama both support it)
        kw = {"return_hidden": True} if return_hidden else {}
        out, new_cache = model.apply(
            {"params": params}, tokens, positions=positions,
            cache=cache, cache_index=cache_index,
            segment_ids=segment_ids, valid_start=valid_start,
            chunk_decode=chunk_decode, **kw)
        return out, new_cache

    def make_cache(batch: int, max_len: int, dtype=None, **form):
        return init_cache(cfg.num_layers, batch, num_kv_heads, max_len,
                          head_dim, dtype or cfg.policy.compute_dtype,
                          **form)

    return apply_fn, make_cache


def gpt2_decoder(model):
    """(apply_fn, make_cache) for `models.gpt2.GPT2`."""
    cfg = model.cfg
    return _decoder(model, cfg.num_heads, cfg.hidden_size // cfg.num_heads)


def granite_hybrid_decoder(model):
    """(apply_fn, make_cache) for `models.granite_hybrid.GraniteHybrid`:
    the model has no positions (``positions`` is taken and unused), and
    its cache tree holds two kinds of entry, one a layer by the model's
    own ``layer_types``: K/V for an attention layer, a recurrent state
    and a convolution's last inputs for a Mamba layer. ``n_real``
    (scalar, with a scalar ``cache_index``): the tokens of a right-padded
    run that are real; a state, unlike K/V, must not see the others."""
    from apex1_tpu.models.granite_hybrid import init_hybrid_cache

    def apply_fn(params, tokens, cache, cache_index, *, positions=None,
                 chunk_decode=False, n_real=None):
        del positions
        return model.apply({"params": params}, tokens, cache=cache,
                           cache_index=cache_index,
                           chunk_decode=chunk_decode, n_real=n_real)

    def make_cache(batch: int, max_len: int, dtype=None):
        return init_hybrid_cache(model.cfg, batch, max_len, dtype)

    return apply_fn, make_cache


def lfm2_moe_decoder(model):
    """(apply_fn, make_cache) for `models.lfm2.Lfm2Moe`: RoPE takes
    ``positions`` (None: from ``cache_index`` on); the cache tree holds,
    by the model's own ``layer_types``, K/V for an attention layer and the
    last inputs of a short convolution (a leaf without positions) for a
    conv layer; ``n_real`` (scalar, with a scalar ``cache_index``): the
    tokens of a right-padded run that are real: the others enter no
    convolution's state and are not routed. ``moe_counts=True`` adds a
    third result, (2,) int32: the (row, expert) pairs the sparse layers
    computed and the held experts they touched, summed over the layers;
    ``apply_fn.moe_expert_slots`` is the most the second can be, and tells
    `serving.Engine` that the counts are there to ask for."""
    from apex1_tpu.models.lfm2 import init_lfm2_cache

    def apply_fn(params, tokens, cache, cache_index, *, positions=None,
                 chunk_decode=False, n_real=None, moe_counts=False):
        return model.apply({"params": params}, tokens, positions=positions,
                           cache=cache, cache_index=cache_index,
                           chunk_decode=chunk_decode, n_real=n_real,
                           moe_counts=moe_counts)

    apply_fn.moe_expert_slots = model.cfg.moe_expert_slots

    def make_cache(batch: int, max_len: int, dtype=None):
        return init_lfm2_cache(model.cfg, batch, max_len, dtype)

    return apply_fn, make_cache


def afmoe_decoder(model, *, ring_slack: int = 256):
    """(apply_fn, make_cache) for `models.afmoe.Afmoe`: RoPE (the sliding
    layers' alone) takes ``positions`` (None: from ``cache_index`` on); the
    cache tree holds, by the model's own ``layer_types``, K/V leaves of TWO
    lengths: every position of a global layer, and a RING of
    ``sliding_window + ring_slack`` rows (whole blocks) of a sliding one,
    which nothing older than its window is ever read from. ``ring_slack``
    is the most one call may append to a ring (the engine's prefill chunk:
    `serving.Engine` refuses a longer one by the leaf shapes and
    ``apply_fn.sliding_window``). ``moe_counts`` and
    ``apply_fn.moe_expert_slots`` as `lfm2_moe_decoder` has them; ``n_real``
    (scalar, with a scalar ``cache_index``): the tokens of a right-padded
    run that are routed."""
    from apex1_tpu.models.afmoe import init_afmoe_cache

    def apply_fn(params, tokens, cache, cache_index, *, positions=None,
                 chunk_decode=False, n_real=None, moe_counts=False):
        return model.apply({"params": params}, tokens, positions=positions,
                           cache=cache, cache_index=cache_index,
                           chunk_decode=chunk_decode, n_real=n_real,
                           moe_counts=moe_counts)

    apply_fn.moe_expert_slots = model.cfg.moe_expert_slots
    apply_fn.sliding_window = model.cfg.sliding_window

    def make_cache(batch: int, max_len: int, dtype=None):
        return init_afmoe_cache(model.cfg, batch, max_len, dtype,
                                ring_slack=ring_slack)

    return apply_fn, make_cache


def t5_generate(model, params, enc_tokens, *, max_new_tokens: int,
                dec_start_id: int = 0, enc_pad_mask=None,
                temperature: float = 0.0, top_k: Optional[int] = None,
                rng=None, eos_id: Optional[int] = None, pad_id: int = 0,
                num_beams: int = 1, length_penalty: float = 0.0):
    """Seq2seq generation for `models.t5.T5`: encode once, then KV-cached
    decoder sampling seeded with ``dec_start_id`` (T5's decoder start =
    the pad token, id 0). Returns (B, max_new_tokens) ids. Decoder
    self-attention is cached; cross-attention recomputes K/V from the
    fixed memory each step (caching them per layer is a further
    optimization the adapter keeps out of the model).

    ``num_beams > 1`` switches to :func:`beam_search` (sampling args
    must be defaults — beam search is deterministic): the encoder still
    runs ONCE at batch B; its memory and ``enc_pad_mask`` are tiled
    K-fold for the beam lanes."""
    cfg = model.cfg
    K = num_beams
    if K > 1 and (temperature != 0.0 or top_k is not None):
        # validate BEFORE the encoder forward — a bad call must not pay
        # (or OOM on) a full encode first
        raise ValueError("beam search is deterministic — "
                         "temperature/top_k require num_beams=1")
    bound = model.bind({"params": params})
    memory = bound.encode(enc_tokens, enc_pad_mask)
    B = enc_tokens.shape[0]
    # beam lanes are b-major (b·K + k): prefill runs at batch B against
    # the UNtiled memory; decode steps run at B·K against the K-fold
    # tile (memory[:B] of the tile would be b0 repeated — wrong batch)
    memory_tiled = jnp.repeat(memory, K, axis=0) if K > 1 else memory
    mask_tiled = (jnp.repeat(enc_pad_mask, K, axis=0)
                  if K > 1 and enc_pad_mask is not None else enc_pad_mask)

    def apply_fn(params, tokens, cache, cache_index):
        pre = tokens.shape[0] == B
        mem = memory if pre else memory_tiled
        mask = enc_pad_mask if pre else mask_tiled
        return model.apply(
            {"params": params}, tokens, mem,
            enc_pad_mask=mask, cache=cache,
            cache_index=cache_index, method=model.decode)

    # 1 (start token) + max_new_tokens slots — generate() writes at
    # indices 0..prompt_len+max_new-2, but sizing to the documented
    # prompt_len + max_new_tokens contract keeps a slot of slack rather
    # than relying on the final token never being written back
    cache = init_cache(cfg.num_decoder_layers, B * K, cfg.num_heads,
                       1 + max_new_tokens, cfg.head_dim,
                       cfg.policy.compute_dtype)
    prompt = jnp.full((B, 1), dec_start_id, jnp.int32)
    if K > 1:
        toks, _ = beam_search(apply_fn, params, prompt,
                              max_new_tokens=max_new_tokens, cache=cache,
                              num_beams=K, length_penalty=length_penalty,
                              eos_id=eos_id, pad_id=pad_id)
        return toks
    return generate(apply_fn, params, prompt,
                    max_new_tokens=max_new_tokens, cache=cache,
                    temperature=temperature, top_k=top_k, rng=rng,
                    eos_id=eos_id, pad_id=pad_id)


def llama_decoder(model):
    """(apply_fn, make_cache) for `models.llama.Llama` (GQA-aware)."""
    cfg = model.cfg
    return _decoder(model, cfg.num_kv_heads, cfg.head_dim)
