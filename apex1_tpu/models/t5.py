"""T5 encoder-decoder — the model family behind the reference's
variable-shape pipeline machinery (SURVEY #55/#56: ``decoder_seq_length``,
``_communicate`` tensor-shape negotiation exist precisely so Megatron-style
enc-dec models can pipeline stages whose boundary tensors differ between
the encoder and decoder halves).

The reference has no model zoo; like `models.llama` this is a standalone
model built from the framework's fused ops:

- `ops.rms_norm` (Pallas) — T5's LayerNorm is RMSNorm (no mean/bias);
- `ops.flash_attention` (Pallas) with its additive-``bias`` operand for
  the bias-bearing self-attention (T5's learned relative-position bias
  rides the flash kernel — O(S·D) activations, dbias via the kernel's
  broadcast-accumulating backward pass — where the reference composes
  matmul + ``scaled_masked_softmax_cuda``, materializing O(S²); its
  fmha takes no bias at all) and for the bias-free cross-attention;
- `ops.linear_cross_entropy` for the (tied) LM head + CE.

T5-specific semantics kept faithful to the public architecture: pre-norm
blocks, NO attention scaling (folded into init), shared relative-position
bias per stack (bidirectional buckets in the encoder, unidirectional in
the decoder), tied embedding/LM-head with the d_model**-0.5 logit scale,
ReLU FFN (or gated-GELU, t5.1.1 style).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex1_tpu.core.policy import PrecisionPolicy, get_policy
from apex1_tpu.ops import (NEG_INF, linear_cross_entropy, rms_norm,
                           softmax_cross_entropy_loss)
from apex1_tpu.ops.attention import flash_attention
from apex1_tpu.transformer.tensor_parallel.random import checkpoint_policy


@dataclasses.dataclass(frozen=True)
class T5Config:
    vocab_size: int = 32128
    d_model: int = 512
    num_heads: int = 8
    head_dim: int = 64
    d_ff: int = 2048
    num_encoder_layers: int = 6
    num_decoder_layers: int = 6
    rel_pos_buckets: int = 32
    rel_pos_max_dist: int = 128
    norm_eps: float = 1e-6
    gated_act: bool = False      # True = gated-GELU (t5.1.1)
    tie_word_embeddings: bool = True
    remat: bool = False
    # jax.checkpoint_policies name; see models.llama.LlamaConfig
    remat_policy: str = "nothing_saveable"

    def __post_init__(self):
        checkpoint_policy(self.remat_policy)  # fail fast on a typo
        # the log-spaced bucket formula divides by
        # log(max_distance / max_exact) with max_exact = buckets//2
        # (//4 effective in the bidirectional encoder, which halves
        # num_buckets first) — max_dist <= max_exact makes the
        # denominator zero/negative and silently wraps garbage bucket
        # indices into the bias table (ADVICE r3); fail fast instead,
        # mirroring the remat_policy check above
        if self.rel_pos_max_dist <= self.rel_pos_buckets // 2:
            raise ValueError(
                f"rel_pos_max_dist ({self.rel_pos_max_dist}) must exceed "
                f"rel_pos_buckets // 2 ({self.rel_pos_buckets // 2}) — "
                f"the log-spaced tail of relative_position_bucket needs "
                f"max_distance > max_exact")
    policy: PrecisionPolicy = dataclasses.field(
        default_factory=lambda: get_policy("O0"))

    @staticmethod
    def t5_small(**kw) -> "T5Config":
        return T5Config(**kw)

    @staticmethod
    def t5_large(**kw) -> "T5Config":
        defaults = dict(d_model=1024, num_heads=16, head_dim=64,
                        d_ff=4096, num_encoder_layers=24,
                        num_decoder_layers=24)
        defaults.update(kw)
        return T5Config(**defaults)

    @staticmethod
    def tiny(**kw) -> "T5Config":
        defaults = dict(vocab_size=256, d_model=64, num_heads=4,
                        head_dim=16, d_ff=128, num_encoder_layers=2,
                        num_decoder_layers=2, rel_pos_buckets=8,
                        rel_pos_max_dist=16)
        defaults.update(kw)
        return T5Config(**defaults)


def relative_position_bucket(rel, *, bidirectional: bool,
                             num_buckets: int = 32,
                             max_distance: int = 128):
    """T5's log-spaced relative-position bucketing (public architecture).

    ``rel`` = memory_position − query_position, any integer array.
    Bidirectional stacks split buckets between past/future; unidirectional
    (decoder) buckets only the past and clamps the future to bucket 0.
    Buckets are exact up to num_buckets//2 and log-spaced beyond, saturating
    at ``max_distance``.
    """
    rel = jnp.asarray(rel, jnp.int32)
    ret = jnp.zeros_like(rel)
    if bidirectional:
        num_buckets //= 2
        ret = ret + (rel > 0).astype(jnp.int32) * num_buckets
        n = jnp.abs(rel)
    else:
        n = jnp.maximum(-rel, 0)
    max_exact = num_buckets // 2
    is_small = n < max_exact
    # avoid log(0): the large branch is only selected when n >= max_exact
    nf = jnp.maximum(n, 1).astype(jnp.float32)
    val_large = max_exact + (
        jnp.log(nf / max_exact)
        / jnp.log(max_distance / max_exact)
        * (num_buckets - max_exact)).astype(jnp.int32)
    val_large = jnp.minimum(val_large, num_buckets - 1)
    return ret + jnp.where(is_small, n, val_large)


class RelPosBias(nn.Module):
    """Learned per-head relative-position bias, shared by every layer of a
    stack (computed once from the stack's single bias table, as in public
    T5 where only the first block owns the table)."""

    cfg: T5Config
    bidirectional: bool

    @nn.compact
    def __call__(self, q_len: int, k_len: int, q_positions=None):
        """``q_positions``: optional traced (q_len,) global query
        positions — the KV-cached decode path asks for one bias row at
        the current cache index."""
        cfg = self.cfg
        table = self.param("rel_bias",
                           nn.initializers.normal(0.02),
                           (cfg.rel_pos_buckets, cfg.num_heads),
                           jnp.float32)
        if q_positions is None:
            q_positions = jnp.arange(q_len)
        qpos = q_positions[:, None]
        kpos = jnp.arange(k_len)[None, :]
        bucket = relative_position_bucket(
            kpos - qpos, bidirectional=self.bidirectional,
            num_buckets=cfg.rel_pos_buckets,
            max_distance=cfg.rel_pos_max_dist)
        bias = table[bucket]                      # (Sq, Sk, H)
        return bias.transpose(2, 0, 1)[None]      # (1, H, Sq, Sk)


def _causal_mask(sq: int, sk: int):
    q = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    k = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    return jnp.where(k > q, NEG_INF, 0.0)[None, None]    # (1, 1, Sq, Sk)


class T5Attention(nn.Module):
    """Self- or cross-attention, T5 form (no 1/sqrt(d) scale, no biases
    on the projections). Always the flash kernel: ``bias`` (rel-pos +
    folded causal, broadcast (1, H, Sq, Sk)) rides its additive-bias
    operand and ``kv_keep`` (a (B, Sk) bool key-padding mask) rides its
    ``segment_ids`` — never a materialized O(B·H·S²) mask."""

    cfg: T5Config

    @nn.compact
    def __call__(self, x, kv, bias=None, kv_keep=None, causal=False,
                 cache=None, cache_index=None):
        cfg = self.cfg
        dtype = cfg.policy.compute_dtype
        H, D = cfg.num_heads, cfg.head_dim
        if kv is None:           # self-attention
            kv = x
        B, Sq = x.shape[0], x.shape[1]
        Sk = kv.shape[1]
        if cache is not None and kv_keep is not None:
            raise NotImplementedError(
                "cached_attention has no key-padding channel — a silent "
                "drop would attend padded keys; mask upstream or extend "
                "the cache path")
        init = nn.initializers.normal(cfg.d_model ** -0.5)
        wq = self.param("wq", init, (cfg.d_model, H * D),
                        jnp.float32).astype(dtype)
        wk = self.param("wk", init, (cfg.d_model, H * D),
                        jnp.float32).astype(dtype)
        wv = self.param("wv", init, (cfg.d_model, H * D),
                        jnp.float32).astype(dtype)
        wo = self.param("wo", init, (H * D, cfg.d_model),
                        jnp.float32).astype(dtype)
        q = (x @ wq).reshape(B, Sq, H, D).transpose(0, 2, 1, 3)
        k = (kv @ wk).reshape(B, Sk, H, D).transpose(0, 2, 1, 3)
        v = (kv @ wv).reshape(B, Sk, H, D).transpose(0, 2, 1, 3)
        segs = None
        if kv_keep is not None:
            # key padding as segment ids: every query in segment 0,
            # padded keys in segment 1 — equality masking excludes them
            segs = (jnp.zeros((B, Sq), jnp.int32),
                    jnp.where(kv_keep, 0, 1).astype(jnp.int32))
        new_cache = None
        if cache is not None:
            from apex1_tpu.models.generate import cached_attention
            attn, new_cache = cached_attention(
                q, k, v, cache, cache_index, sm_scale=1.0, bias=bias)
        else:
            # bias (pure rel-pos) rides the flash kernel's additive-bias
            # operand — O(S·D) activations even for the bias-bearing
            # stacks (the kernel's dbias pass handles the rel-pos table
            # gradient) — and causality rides the kernel's causal flag,
            # keeping its above-diagonal block skip (~2x less MXU work
            # than folding the mask into the bias); on non-TPU backends
            # the same call dispatches to the biased XLA composite
            attn = flash_attention(q, k, v, causal=causal, sm_scale=1.0,
                                   bias=bias, segment_ids=segs)
        attn = attn.transpose(0, 2, 1, 3).reshape(B, Sq, H * D)
        out = attn @ wo
        return out if new_cache is None else (out, new_cache)


class T5FFN(nn.Module):
    cfg: T5Config

    @nn.compact
    def __call__(self, h):
        cfg = self.cfg
        dtype = cfg.policy.compute_dtype
        init = nn.initializers.normal(cfg.d_model ** -0.5)
        wo = self.param("wo", init, (cfg.d_ff, cfg.d_model),
                        jnp.float32).astype(dtype)
        if cfg.gated_act:
            wg = self.param("wi_0", init, (cfg.d_model, cfg.d_ff),
                            jnp.float32).astype(dtype)
            wu = self.param("wi_1", init, (cfg.d_model, cfg.d_ff),
                            jnp.float32).astype(dtype)
            y = jax.nn.gelu(h @ wg) * (h @ wu)
        else:
            wi = self.param("wi", init, (cfg.d_model, cfg.d_ff),
                            jnp.float32).astype(dtype)
            y = jax.nn.relu(h @ wi)
        return y @ wo


class T5Block(nn.Module):
    cfg: T5Config
    is_decoder: bool

    @nn.compact
    def __call__(self, x, bias, memory=None, kv_keep=None, cache=None,
                 cache_index=None):
        """``kv_keep`` (B, S_enc) bool: encoder key-padding — masks the
        encoder self-attention's keys and the decoder cross-attention's
        memory keys."""
        cfg = self.cfg
        dtype = cfg.policy.compute_dtype

        def norm(name, z):
            g = self.param(name, nn.initializers.ones, (cfg.d_model,),
                           jnp.float32)
            if not cfg.policy.keep_norms_fp32:
                g = g.astype(dtype)
            return rms_norm(z, g, eps=cfg.norm_eps).astype(dtype)

        h = T5Attention(cfg, name="self_attn")(
            norm("self_norm", x), None, bias=bias,
            kv_keep=None if self.is_decoder else kv_keep,
            causal=self.is_decoder,
            cache=cache, cache_index=cache_index)
        new_cache = None
        if cache is not None:
            h, new_cache = h
        x = x + h.astype(x.dtype)
        if self.is_decoder:
            h = T5Attention(cfg, name="cross_attn")(
                norm("cross_norm", x),
                memory.astype(dtype), kv_keep=kv_keep)
            x = x + h.astype(x.dtype)
        h = T5FFN(cfg, name="ffn")(norm("ffn_norm", x))
        out = x + h.astype(x.dtype)
        return out if new_cache is None else (out, new_cache)


class T5Stack(nn.Module):
    cfg: T5Config
    is_decoder: bool

    @nn.compact
    def __call__(self, x, memory=None, enc_pad_mask=None, cache=None,
                 cache_index=None):
        cfg = self.cfg
        S = x.shape[1]
        rel_pos = RelPosBias(cfg, bidirectional=not self.is_decoder,
                             name="rel_pos")
        if cache is not None and S == 1:
            # decode: one bias row at the current position vs all cache
            # slots (cached_attention masks slots > cache_index)
            from apex1_tpu.models.generate import cache_len
            S_max = cache_len(cache)
            bias = rel_pos(1, S_max,
                           q_positions=jnp.asarray([cache_index],
                                                   jnp.int32))
        else:
            # pure rel-pos bias: decoder causality rides the attention
            # kernel's causal flag (block-skip), not a folded mask
            bias = rel_pos(S, S)
        # enc_pad_mask stays a (B, S_enc) KEY mask end to end (the flash
        # kernel's segment_ids channel) — folding it into the additive
        # bias would batch-expand it to O(B·H·S²)
        n_layers = (cfg.num_decoder_layers if self.is_decoder
                    else cfg.num_encoder_layers)
        block = T5Block
        if cfg.remat and cache is None:
            block = nn.remat(T5Block, static_argnums=(),
                             policy=checkpoint_policy(cfg.remat_policy))
        new_cache = {}
        for i in range(n_layers):
            out = block(cfg, self.is_decoder, name=f"layer{i}")(
                x, bias, memory, enc_pad_mask,
                cache=None if cache is None else cache[f"layer{i}"],
                cache_index=cache_index)
            if cache is None:
                x = out
            else:
                x, new_cache[f"layer{i}"] = out
        g = self.param("final_norm", nn.initializers.ones,
                       (cfg.d_model,), jnp.float32)
        if not cfg.policy.keep_norms_fp32:
            g = g.astype(cfg.policy.compute_dtype)
        out = rms_norm(x, g, eps=cfg.norm_eps)
        return out if cache is None else (out, new_cache)


class T5(nn.Module):
    """Returns decoder logits (B, S_dec, vocab) with fp32 accumulation, or
    the pre-head hidden states with ``return_hidden=True`` (for the fused
    LM-head CE path)."""

    cfg: T5Config

    def setup(self):
        cfg = self.cfg
        self.shared = self.param("shared_embedding",
                                 nn.initializers.normal(1.0),
                                 (cfg.vocab_size, cfg.d_model),
                                 jnp.float32)
        self.encoder = T5Stack(cfg, is_decoder=False, name="encoder")
        self.decoder = T5Stack(cfg, is_decoder=True, name="decoder")
        if not cfg.tie_word_embeddings:
            self.lm_head = self.param("lm_head",
                                      nn.initializers.normal(0.02),
                                      (cfg.vocab_size, cfg.d_model),
                                      jnp.float32)

    def encode(self, enc_tokens, enc_pad_mask=None):
        dtype = self.cfg.policy.compute_dtype
        x = self.shared[enc_tokens].astype(dtype)
        return self.encoder(x, enc_pad_mask=enc_pad_mask)

    def decode(self, dec_tokens, memory, enc_pad_mask=None,
               return_hidden=False, cache=None, cache_index=None):
        """``cache``/``cache_index`` enable KV-cached decoding of the
        self-attention (see `models.generate.t5_generate`; cross-attention
        recomputes its K/V from the fixed memory each step). The return
        becomes ``(logits, new_cache)``."""
        cfg = self.cfg
        dtype = cfg.policy.compute_dtype
        y = self.shared[dec_tokens].astype(dtype)
        h = self.decoder(y, memory=memory, enc_pad_mask=enc_pad_mask,
                         cache=cache, cache_index=cache_index)
        new_cache = None
        if cache is not None:
            h, new_cache = h
        h = h.astype(dtype)
        if return_hidden:
            return h if cache is None else (h, new_cache)
        logits = jnp.einsum("bsh,vh->bsv", h, self.head_weight(),
                            preferred_element_type=jnp.float32)
        return logits if cache is None else (logits, new_cache)

    def head_weight(self):
        """(vocab, d_model) LM-head weight in compute dtype — tied form
        carries T5's d_model**-0.5 logit scale."""
        cfg = self.cfg
        dtype = cfg.policy.compute_dtype
        if cfg.tie_word_embeddings:
            return (self.shared * cfg.d_model ** -0.5).astype(dtype)
        return self.lm_head.astype(dtype)

    def __call__(self, enc_tokens, dec_tokens, enc_pad_mask=None,
                 return_hidden=False):
        memory = self.encode(enc_tokens, enc_pad_mask)
        return self.decode(dec_tokens, memory, enc_pad_mask,
                           return_hidden=return_hidden)


# TP rules (pattern: models.llama._TP_RULES — regex over flattened paths)
_TP_RULES = (
    (r"shared_embedding$", P("tp", None)),
    (r"lm_head$", P("tp", None)),
    (r"w[qkv]$", P(None, "tp")),
    (r"wo$", P("tp", None)),
    (r"wi(_[01])?$", P(None, "tp")),
    (r"rel_bias$", P()),
    (r".*norm$", P()),
)


def param_specs(params, *, rules=_TP_RULES, default=P()):
    from apex1_tpu.parallel.specs import specs_from_rules
    return specs_from_rules(params, rules, default=default)


def t5_loss_fn(model: T5, *, fuse_head: bool = True,
               label_pad_id: Optional[int] = None):
    """``loss_fn(params, enc_tokens, dec_tokens) -> scalar``: seq2seq CE,
    teacher-forced — position t predicts ``dec_tokens[t+1]``. Default path
    fuses the LM-head matmul into the CE kernel
    (``ops.linear_cross_entropy``); ``fuse_head=False`` materializes the
    logits (the parity gold). ``label_pad_id`` positions are excluded from
    the mean (≙ ``xentropy``'s padding_idx)."""

    def loss_fn(params, enc_tokens, dec_tokens, enc_pad_mask=None):
        bound = model.bind({"params": params})
        labels = dec_tokens[:, 1:]
        # pad-row zeroing happens inside the CE kernels (padding_idx —
        # zero loss AND grad in-lane); only the mean's denominator is
        # computed here
        if fuse_head:
            h = bound(enc_tokens, dec_tokens[:, :-1],
                      enc_pad_mask=enc_pad_mask, return_hidden=True)
            w = bound.head_weight()
            losses = linear_cross_entropy(h, w, labels,
                                          padding_idx=label_pad_id)
        else:
            logits = bound(enc_tokens, dec_tokens[:, :-1],
                           enc_pad_mask=enc_pad_mask)
            losses = softmax_cross_entropy_loss(
                logits.astype(jnp.float32), labels,
                padding_idx=label_pad_id)
        if label_pad_id is None:
            return jnp.mean(losses)
        keep = jnp.sum((labels != label_pad_id).astype(jnp.float32))
        return jnp.sum(losses) / jnp.maximum(keep, 1.0)

    return loss_fn
