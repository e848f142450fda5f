"""GPT-2 — BASELINE config 1 model ("GPT-2 125M, amp O1 + Adam").

The reference repo has no model zoo (apex bolts onto user models; its test
models live in ``apex/transformer/testing/standalone_gpt.py``). This is the
equivalent standalone model, built from this framework's fused ops:
FusedLayerNorm, scaled_upper_triang_masked_softmax, softmax_cross_entropy
— pre-LN transformer with learned positions, GELU MLP, weight-tied LM head.

Policy-aware: ``policy.compute_dtype`` drives activations/matmuls; norms and
softmax run fp32 when ``keep_norms_fp32``/``fp32_fragile_ops`` ask for it
(the O1 op-list semantics).
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex1_tpu.core.policy import PrecisionPolicy, get_policy
from apex1_tpu.obs.regions import region
from apex1_tpu.ops import (layer_norm, linear_cross_entropy,
                           scaled_upper_triang_masked_softmax,
                           softmax_cross_entropy_loss)
from apex1_tpu.ops.attention import fmha
from apex1_tpu.ops.stochastic import (fold_seed, fused_bias_dropout_add,
                                      seed_from_key)


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    hidden_size: int = 768
    mlp_ratio: int = 4
    dropout: float = 0.0
    use_flash: bool = True
    policy: PrecisionPolicy = dataclasses.field(
        default_factory=lambda: get_policy("O0"))

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded to a lane multiple (Megatron-style padding) so
        the LM-head matmul and CE tile cleanly onto the MXU; padded rows
        exist only in the embedding table, logits are sliced back."""
        return ((self.vocab_size + 127) // 128) * 128

    @staticmethod
    def gpt2_125m(**kw) -> "GPT2Config":
        return GPT2Config(**kw)

    @staticmethod
    def tiny(**kw) -> "GPT2Config":
        defaults = dict(vocab_size=256, max_seq_len=128, num_layers=2,
                        num_heads=4, hidden_size=128)
        defaults.update(kw)
        return GPT2Config(**defaults)


class Block(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, *, deterministic=True, segment_ids=None,
                 cache=None, cache_index=None, valid_start=None,
                 chunk_decode=False):
        cfg = self.cfg
        dtype = cfg.policy.compute_dtype
        h = cfg.hidden_size
        nh = cfg.num_heads
        hd = h // nh

        def norm(name, z):
            gamma = self.param(f"{name}_scale", nn.initializers.ones, (h,),
                               jnp.float32)
            beta = self.param(f"{name}_bias", nn.initializers.zeros, (h,),
                              jnp.float32)
            if not cfg.policy.keep_norms_fp32:
                gamma, beta = gamma.astype(dtype), beta.astype(dtype)
            return layer_norm(z, gamma, beta)

        # dropout (cfg.dropout > 0, training): attention-probability
        # dropout fused in the flash kernel + fused dropout-add residual
        # epilogues; one rng draw per block, per-site int32 streams via
        # fold_seed (the APX103-sanctioned idiom)
        active = cfg.dropout > 0.0 and not deterministic and cache is None
        if active and not cfg.use_flash:
            raise ValueError("dropout > 0 needs use_flash=True (the "
                             "composite path has no fused dropout)")
        seed = seed_from_key(self.make_rng("dropout")) if active else None

        # attention — flash kernel (O(S·D) memory; the materialized
        # scores + fused-softmax path is kept via use_flash=False for
        # the kernel-parity cross-check). Training (no cache, use_flash)
        # hands the qkv product's output to `fmha` AS IT LIES, (B, S, 3·h)
        # seen as (B, S, 3, heads, head width), and the result to `proj`:
        # where `ops.attention.flash_form` allows (two heads of 64 to a
        # 128-lane block; GPT-2's every size) no split, pad or transpose
        # runs in XLA, forward or backward, and the qkv product's
        # backward gets dq, dk, dv as one array. The cached and the
        # composite paths want (B, heads, S, head width) and turn q, k, v
        y = norm("ln1", x)
        with region("attn"):
            qkv = nn.Dense(3 * h, dtype=dtype, name="qkv")(y)
            B, S = x.shape[0], x.shape[1]
            new_cache = None
            if cache is None and cfg.use_flash:
                attn = fmha(
                    qkv.reshape(B, S, 3, nh, hd), causal=True,
                    segment_ids=segment_ids, sm_scale=1.0 / math.sqrt(hd),
                    dropout_p=cfg.dropout if active else 0.0,
                    dropout_seed=fold_seed(seed, 0) if active else None)
            else:
                q, k, v = jnp.split(qkv, 3, axis=-1)
                q = q.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)
                k = k.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)
                v = v.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)
                if cache is not None:
                    from apex1_tpu.models.generate import cached_attention
                    attn, new_cache = cached_attention(
                        q, k, v, cache, cache_index,
                        sm_scale=1.0 / math.sqrt(hd),
                        segment_ids=segment_ids, valid_start=valid_start,
                        chunk_decode=chunk_decode)
                else:
                    if segment_ids is not None:
                        raise ValueError(
                            "packed batches need use_flash=True")
                    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                                        preferred_element_type=jnp.float32)
                    probs = scaled_upper_triang_masked_softmax(
                        scores, scale=1.0 / math.sqrt(hd))
                    attn = jnp.einsum("bhqk,bhkd->bhqd",
                                      probs.astype(dtype), v)
                attn = attn.transpose(0, 2, 1, 3)
            attn = attn.reshape(B, S, h)
            proj = nn.Dense(h, dtype=dtype, name="proj")(attn)
            if active:
                # Megatron bias_dropout_add epilogue (pre-LN stack: no
                # norm after the add) — mask recomputed from the seed in
                # backward
                x = fused_bias_dropout_add(proj, x, p=cfg.dropout,
                                           seed=fold_seed(seed, 1))
            else:
                x = x + proj

        # MLP
        y = norm("ln2", x)
        with region("ffn"):
            y = nn.Dense(cfg.mlp_ratio * h, dtype=dtype, name="fc_in")(y)
            y = nn.gelu(y)
            y = nn.Dense(h, dtype=dtype, name="fc_out")(y)
            if active:
                out = fused_bias_dropout_add(y, x, p=cfg.dropout,
                                             seed=fold_seed(seed, 2))
            else:
                out = x + y
        return out if new_cache is None else (out, new_cache)


class GPT2(nn.Module):
    """Returns logits; `loss` computes the fused CE."""

    cfg: GPT2Config

    @nn.compact
    def __call__(self, tokens, *, deterministic=True, return_hidden=False,
                 segment_ids=None, positions=None, cache=None,
                 cache_index=None, valid_start=None,
                 chunk_decode=False):
        """``segment_ids``/(B, S) ``positions`` enable packed batches
        (≙ fmha cu_seqlens varlen; see `runtime.pack_documents`) — tokens
        attend within their segment, learned positions gather per row.

        ``cache``/``cache_index`` enable KV-cached decoding (see
        `models.generate`): the return becomes ``(logits, new_cache)``;
        prefill (S>1) must start from an empty cache at index 0. With a
        cache, ``segment_ids``/``valid_start`` carry the ragged
        left-padded-prompt masking (``generate(prompt_lens=...)``)."""
        cfg = self.cfg
        dtype = cfg.policy.compute_dtype
        B, S = tokens.shape
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (cfg.padded_vocab, cfg.hidden_size), jnp.float32)
        wpe = self.param("wpe", nn.initializers.normal(0.01),
                         (cfg.max_seq_len, cfg.hidden_size), jnp.float32)
        with region("embed"):
            if positions is None:
                pos_emb = wpe[:S].astype(dtype)[None]
            else:
                # out-of-range positions (e.g. runtime.pack_documents
                # chunking a long document without
                # restart_chunk_positions=True) must not silently clamp
                # under jit — fill with NaN so the loss goes non-finite
                # and the mistake is visible immediately
                pos_emb = jnp.take(wpe, positions, axis=0, mode="fill",
                                   fill_value=jnp.nan).astype(dtype)
            x = wte[tokens].astype(dtype) + pos_emb
        new_cache = {}
        for i in range(cfg.num_layers):
            out = Block(cfg, name=f"h{i}")(
                x, deterministic=deterministic, segment_ids=segment_ids,
                cache=None if cache is None else cache[f"layer{i}"],
                cache_index=cache_index, valid_start=valid_start,
                chunk_decode=chunk_decode)
            if cache is None:
                x = out
            else:
                x, new_cache[f"layer{i}"] = out
        gamma = self.param("lnf_scale", nn.initializers.ones,
                           (cfg.hidden_size,), jnp.float32)
        beta = self.param("lnf_bias", nn.initializers.zeros,
                          (cfg.hidden_size,), jnp.float32)
        x = layer_norm(x, gamma, beta)
        if return_hidden:
            # for the fused LM-head+CE path (ops.linear_cross_entropy):
            # the (B, S, V) logits never hit HBM. With a cache the
            # contract mirrors the logits return — the serving LoRA
            # epilogue replays the tied-head matmul itself so per-slot
            # adapter deltas can fuse in (llama does the same)
            h = x.astype(dtype)
            return h if cache is None else (h, new_cache)
        with region("head"):
            logits = jnp.einsum("bsh,vh->bsv", x.astype(dtype),
                                wte.astype(dtype),
                                preferred_element_type=jnp.float32)
        # returned over padded_vocab — slice-free; consumers mask with
        # num_classes=cfg.vocab_size (the CE kernel does it in-lane)
        return logits if cache is None else (logits, new_cache)


# Megatron-style TP sharding as path-regex rules (see parallel/specs.py):
# attention qkv + MLP fc_in are column-parallel (output dim sharded, bias
# sharded with it), proj + fc_out row-parallel (input dim sharded, bias
# replicated), embeddings vocab-sharded, positions/norms replicated.
_TP_RULES = (
    (r"wte$", P("tp", None)),
    (r"wpe$", P()),
    (r"(qkv|fc_in)/kernel$", P(None, "tp")),
    (r"(qkv|fc_in)/bias$", P("tp")),
    (r"(proj|fc_out)/kernel$", P("tp", None)),
    (r"(proj|fc_out)/bias$", P()),
)


def param_specs(params, *, rules=_TP_RULES, default=P()):
    """PartitionSpec tree for a GPT-2 param tree (TP over the ``tp`` mesh
    axis) — ≙ ``set_tensor_model_parallel_attributes`` as data."""
    from apex1_tpu.parallel.specs import specs_from_rules
    return specs_from_rules(params, rules, default=default)


def gpt2_loss_fn(model: GPT2, *, fuse_head: bool = True):
    """``loss_fn(params, tokens) -> scalar`` for `Amp.make_train_step`:
    next-token CE (fp32 inside the kernel — O1 FP32_FUNCS semantics).

    ``fuse_head=True`` (default) runs the tied LM head through
    ``ops.linear_cross_entropy`` — head matmul fused into the CE, no
    (B, S, V) logits in HBM. ``False`` keeps the materialized-logits path
    (the parity gold; also what inference uses).

    ``dropout_rng`` (a jax.random key) ACTIVATES the in-kernel dropout
    paths when ``cfg.dropout > 0`` — same contract as
    ``bert_pretrain_loss_fn``'s ``batch["dropout_rng"]``; it rides the
    batch tail positionally through ``Amp.make_train_step``
    (``step(state, tokens, None, None, rng)``). Without it the model
    runs deterministic regardless of ``cfg.dropout`` — passing a key
    with ``cfg.dropout == 0`` is therefore a config mistake and raises."""

    def loss_fn(params, tokens, segment_ids=None, positions=None,
                dropout_rng=None):
        if dropout_rng is not None and model.cfg.dropout == 0.0:
            raise ValueError("dropout_rng passed but cfg.dropout == 0 — "
                             "the key would be silently unused")
        kw = dict(segment_ids=segment_ids, positions=positions,
                  deterministic=dropout_rng is None,
                  rngs=(None if dropout_rng is None
                        else {"dropout": dropout_rng}))
        if fuse_head:
            h = model.apply({"params": params}, tokens, return_hidden=True,
                            **kw)
        else:
            logits = model.apply({"params": params}, tokens, **kw)
        with region("head"):
            if fuse_head:
                w = params["wte"].astype(h.dtype)
                losses = linear_cross_entropy(
                    h[:, :-1], w, tokens[:, 1:],
                    num_classes=model.cfg.vocab_size)
            else:
                losses = softmax_cross_entropy_loss(
                    logits[:, :-1].astype(jnp.float32), tokens[:, 1:],
                    num_classes=model.cfg.vocab_size)
            if segment_ids is not None:
                from apex1_tpu.ops import masked_next_token_mean
                return masked_next_token_mean(losses, segment_ids)
            return jnp.mean(losses)

    return loss_fn
