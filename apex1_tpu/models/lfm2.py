"""LFM2 with experts (`model_type: lfm2_moe`; the published
`LiquidAI/LFM2-8B-A1B` is 24 layers of 2048): a decoder whose layers mix
the sequence by a GATED SHORT CONVOLUTION, with a few grouped-query
attention layers among them, each followed by a SwiGLU feed-forward that
is dense in the first ``num_dense_layers`` layers and a mixture of experts
after. RMSNorm before each; tied head behind a final RMSNorm.

A layer is ``h = x + op(norm(x)); out = h + ffn(norm(h))``.

- *conv* (``conv_L_cache`` L = 3, no bias): ``[B, C, u] = W_in x`` (E ->
  3E); ``z = B * u``; ``c_t = sum_{j < L} w[j] * z_{t - (L - 1) + j}``
  (depthwise, causal, one L-tap filter a channel); ``y = W_out (C * c)``.
  Its state is the last L inputs ``z``: a cache leaf without positions.
- *full_attention*: q of ``num_attention_heads``, k and v of
  ``num_key_value_heads`` heads of ``hidden_size / num_attention_heads``,
  no bias; RMSNorm over each head's width on q and on k, THEN RoPE
  (``rope_theta``, the whole head rotated, halves paired); causal softmax
  of ``q . k / sqrt(d)``; ``W_o``.
- dense feed-forward: ``W2 (silu(W1 x) * (W3 x))`` of
  ``intermediate_size``.
- sparse feed-forward: ``num_experts`` experts, each a SwiGLU of
  ``moe_intermediate_size``; the router in float32, ``s = sigmoid(W_g
  x)``, the ``num_experts_per_tok`` experts CHOSEN by ``s + b``
  (``use_expert_bias``: a stored vector), the weights the UNBIASED ``s``
  at the chosen, divided by their sum (``norm_topk_prob``), times
  ``routed_scaling_factor``. No token is dropped
  (`transformer.moe.dropless_route`, `held_experts_mlp`).

``experts_held`` ``(first, count)`` makes the model ONE CHIP'S SHARE of an
expert-parallel deployment: the router keeps all ``num_experts`` outputs,
the expert leaves hold ``count`` experts, and a sparse layer adds the
part of the mixture that those give; what the experts held elsewhere would
add is left out (None: all of them are held, the whole model).

TWO per-layer lists drive the block, the cache and the parameter tree:
``layer_types`` (the mixer) and `ffn_kinds` (dense or sparse, from
``num_dense_layers``). A conv layer's cache entry is ``{"conv": (B, L,
E)}`` in the compute dtype, an attention layer's ``{"k", "v"}``
(`generate.init_cache`'s form). A cached call with a per-row index (the
engine's decode step) takes one token a row, and a row whose index is
negative is idle: its entries stay as they are and it is not routed. A
call with a scalar index takes a run of tokens of which the first
``n_real`` are real (a right-padded prefill chunk): the others neither
enter a convolution's state nor are routed.

Norm weights are named ``*scale``, and so are the convolution's taps
(``conv_tap_scale``): a weight generator that starts such leaves at 1 +
0.1 normal and everything else at 0.02 normal
(`benchmark/harness/builders.py`) then gives a convolution that passes its
input on (with taps at 0.02 a check of the logits would not see the
convolution). The router's bias is NOT so named (``expert_bias``): drawn
0.02 normal against scores whose spread is ~0.2 it changes the chosen
experts for about a third of the tokens and leaves every expert within
0.5 to 1.6 times the mean load, as a trained bias does, whose purpose is
to balance the load. Drawn with a spread of 0.1 it starves some experts
altogether (8 % of the held ones idle in a step of 96 rows, a different
8 % a seed) and the step's bytes follow the seed (PERF.md §6, PR 38).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex1_tpu.core.policy import PrecisionPolicy, get_policy
from apex1_tpu.obs.regions import region
from apex1_tpu.ops import apply_rotary_pos_emb, rms_norm, rope_tables
from apex1_tpu.ops.attention import flash_attention
from apex1_tpu.ops.ssm import causal_conv
from apex1_tpu.transformer.moe import (RouteConfig, dropless_route,
                                       held_experts_mlp)

CONV, ATTENTION = "conv", "full_attention"
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """The published keys, under their published names, and the share."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_dense_layers: int = 2
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    experts_held: Optional[Tuple[int, int]] = None
    policy: PrecisionPolicy = dataclasses.field(
        default_factory=lambda: get_policy("O0"))

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers or any(
                t not in (CONV, ATTENTION) for t in self.layer_types):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {CONV!r} or {ATTENTION!r}: {self.layer_types}")
        if self.conv_bias:
            raise ValueError("a convolution without bias is what this "
                             "model computes")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads \
                or self.head_dim % 2:
            raise ValueError("attention heads do not divide")
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held",
                               tuple(int(n) for n in self.experts_held))
        first, count = self.held.start, len(self.held)
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts} experts")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def held(self) -> range:
        """The expert ids whose matrices this model holds."""
        if self.experts_held is None:
            return range(self.num_experts)
        return range(self.experts_held[0], sum(self.experts_held))

    @property
    def ffn_kinds(self) -> Tuple[str, ...]:
        return tuple(DENSE if i < self.num_dense_layers else SPARSE
                     for i in range(self.num_hidden_layers))

    @property
    def route(self) -> RouteConfig:
        return RouteConfig(self.num_experts, self.num_experts_per_tok,
                           score="sigmoid", select_bias=self.use_expert_bias,
                           normalize=self.norm_topk_prob,
                           scale=self.routed_scaling_factor)

    @property
    def moe_expert_slots(self) -> int:
        """Held experts, summed over the sparse layers: what a step can
        touch at most."""
        return len(self.held) * self.ffn_kinds.count(SPARSE)

    @staticmethod
    def tiny(**kw) -> "Lfm2MoeConfig":
        """The published widths' ratios at hidden 128: one dense layer,
        then a period of (conv, conv, attention, conv) twice over, 8
        experts of which 2 a token."""
        defaults = dict(
            vocab_size=512, hidden_size=128, intermediate_size=448,
            moe_intermediate_size=128, num_hidden_layers=6,
            layer_types=(CONV, CONV, ATTENTION, CONV, CONV, ATTENTION),
            num_attention_heads=8, num_key_value_heads=2,
            num_dense_layers=1, num_experts=8, num_experts_per_tok=2)
        defaults.update(kw)
        return Lfm2MoeConfig(**defaults)


def _head_norm(x, g, eps):
    """RMSNorm over a head's width, float32: (..., D) by (D,)."""
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                                + eps) * g).astype(x.dtype)


class Lfm2MoeBlock(nn.Module):
    cfg: Lfm2MoeConfig
    kind: str
    ffn: str

    def _conv(self, h, cache, idx, n_real):
        cfg = self.cfg
        dtype = h.dtype
        E, L = cfg.hidden_size, cfg.conv_L_cache
        init = nn.initializers.normal(0.02)
        w_in = self.param("in_proj", init, (E, 3 * E),
                          jnp.float32).astype(dtype)
        taps = self.param("conv_tap_scale", nn.initializers.ones, (L, E),
                          jnp.float32)
        w_out = self.param("out_proj", init, (E, E),
                           jnp.float32).astype(dtype)
        gate_in, gate_out, u = jnp.split(h @ w_in, 3, axis=-1)
        z = gate_in * u
        per_row = idx is not None and idx.ndim == 1
        if per_row and h.shape[1] != 1:
            raise ValueError(
                "a convolution's state takes one token a row under a "
                f"per-row index, got {h.shape[1]}")
        state = (jnp.zeros((h.shape[0], L, E), dtype) if cache is None
                 else cache["conv"])
        c, new = causal_conv(z, taps, None, state, n_real)
        if per_row:                     # an idle row keeps its inputs
            new = jnp.where((idx >= 0)[:, None, None], new, state)
        return (gate_out * c) @ w_out, (None if cache is None
                                        else {"conv": new})

    def _attention(self, h, cache, cache_index, chunk_decode, cos, sin):
        cfg = self.cfg
        dtype = h.dtype
        E, Hq, Hkv, D = (cfg.hidden_size, cfg.num_attention_heads,
                         cfg.num_key_value_heads, cfg.head_dim)
        B, S = h.shape[:2]
        init = nn.initializers.normal(0.02)
        heads = lambda name, n: (h @ self.param(
            name, init, (E, n * D), jnp.float32).astype(dtype)).reshape(
                B, S, n, D)
        q, k, v = heads("wq", Hq), heads("wk", Hkv), heads("wv", Hkv)
        gq = self.param("q_norm_scale", nn.initializers.ones, (D,),
                        jnp.float32)
        gk = self.param("k_norm_scale", nn.initializers.ones, (D,),
                        jnp.float32)
        q = apply_rotary_pos_emb(_head_norm(q, gq, cfg.norm_eps), cos, sin)
        k = apply_rotary_pos_emb(_head_norm(k, gk, cfg.norm_eps), cos, sin)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        new_cache = None
        if cache is not None:
            from apex1_tpu.models.generate import cached_attention
            attn, new_cache = cached_attention(q, k, v, cache, cache_index,
                                               chunk_decode=chunk_decode)
        else:
            attn = flash_attention(q, k, v, causal=True)
        attn = attn.transpose(0, 2, 1, 3).reshape(B, S, Hq * D)
        wo = self.param("wo", init, (Hq * D, E), jnp.float32).astype(dtype)
        return attn.astype(dtype) @ wo, new_cache

    def _dense(self, h):
        cfg = self.cfg
        E, F = cfg.hidden_size, cfg.intermediate_size
        init = nn.initializers.normal(0.02)
        w = lambda name, shape: self.param(name, init, shape,
                                           jnp.float32).astype(h.dtype)
        return (jax.nn.silu(h @ w("w1", (E, F))) * (h @ w("w3", (E, F)))
                ) @ w("w2", (F, E))

    def _sparse(self, h, live):
        cfg = self.cfg
        E, F, n = cfg.hidden_size, cfg.moe_intermediate_size, len(cfg.held)
        init = nn.initializers.normal(0.02)
        gate = self.param("router", init, (E, cfg.num_experts), jnp.float32)
        bias = (self.param("expert_bias", nn.initializers.zeros,
                           (cfg.num_experts,), jnp.float32)
                if cfg.use_expert_bias else None)
        w = lambda name, shape: self.param(name, init, shape,
                                           jnp.float32).astype(h.dtype)
        x2 = h.reshape(-1, E)
        experts, weights = dropless_route(x2, gate, bias, cfg.route)
        y, counts = held_experts_mlp(
            x2, experts, weights, w("experts_w1", (n, E, F)),
            w("experts_w3", (n, E, F)), w("experts_w2", (n, F, E)),
            cfg.held, None if live is None else live.reshape(-1))
        return y.reshape(h.shape), counts

    @nn.compact
    def __call__(self, x, cos, sin, cache=None, cache_index=None,
                 chunk_decode=False, n_real=None):
        """``(out, new cache entry or None, counts (2,) or None)``: the
        pairs of (row, expert) a sparse layer computed and the held experts
        it touched."""
        cfg = self.cfg
        dtype = cfg.policy.compute_dtype

        def norm(name, z):
            g = self.param(name, nn.initializers.ones, (cfg.hidden_size,),
                           jnp.float32)
            if not cfg.policy.keep_norms_fp32:
                g = g.astype(dtype)
            return rms_norm(z, g, eps=cfg.norm_eps).astype(dtype)

        idx = None if cache is None else jnp.asarray(cache_index, jnp.int32)
        h = norm("operator_norm_scale", x)
        with region("attn" if self.kind == ATTENTION else "mixer"):
            if self.kind == ATTENTION:
                y, entry = self._attention(h, cache, cache_index,
                                           chunk_decode, cos, sin)
            else:
                y, entry = self._conv(h, cache, idx, n_real)
            x = x + y.astype(x.dtype)

        h = norm("ffn_norm_scale", x)
        with region("ffn"):
            if self.ffn == DENSE:
                return x + self._dense(h).astype(x.dtype), entry, None
            # who is routed: a live lane's one token, a run's real tokens
            B, S = x.shape[:2]
            live = None
            if idx is not None and idx.ndim == 1:
                live = jnp.broadcast_to((idx >= 0)[:, None], (B, S))
            elif n_real is not None:
                live = jnp.broadcast_to(jnp.arange(S)[None, :] < n_real,
                                        (B, S))
            y, counts = self._sparse(h, live)
            return x + y.astype(x.dtype), entry, counts


class Lfm2Moe(nn.Module):
    """Logits (B, S, vocab) in float32; with a cache, ``(logits, cache)``;
    with ``moe_counts`` a last element more: (2,) int32, the (row, expert)
    pairs the sparse layers computed here and the held experts they
    touched, summed over the layers."""

    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, tokens, *, positions=None, cache=None,
                 cache_index=None, chunk_decode=False, n_real=None,
                 moe_counts=False):
        """``cache`` / ``cache_index``: see `models.generate`; a scalar
        index takes a run of tokens of which the first ``n_real`` (None:
        all) are real, a per-row index one token a row (negative: an idle
        row). ``positions`` (B, S) or (S,): where RoPE puts each token;
        None: from ``cache_index`` on, or from 0 without a cache."""
        cfg = self.cfg
        dtype = cfg.policy.compute_dtype
        B, S = tokens.shape
        emb = self.param("embed", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        with region("embed"):
            x = emb[tokens].astype(dtype)
        with region("attn"):        # every attention layer's RoPE tables
            if positions is None:
                positions = jnp.arange(S, dtype=jnp.int32)
                if cache_index is not None:
                    start = jnp.asarray(cache_index, jnp.int32)
                    positions = (start[:, None] + positions if start.ndim
                                 else start + positions)
            cos, sin = rope_tables(jnp.reshape(positions, (-1,)),
                                   cfg.head_dim, base=cfg.rope_theta)
            if jnp.ndim(positions) == 2:
                cos, sin = (t.reshape(B, S, -1) for t in (cos, sin))
        new_cache = {}
        counts = jnp.zeros((2,), jnp.int32)
        for i, (kind, ffn) in enumerate(zip(cfg.layer_types,
                                            cfg.ffn_kinds)):
            x, entry, c = Lfm2MoeBlock(cfg, kind, ffn, name=f"layer{i}")(
                x, cos, sin, None if cache is None else cache[f"layer{i}"],
                cache_index, chunk_decode, n_real)
            new_cache[f"layer{i}"] = entry
            if c is not None:
                counts = counts + c
        g = self.param("final_norm_scale", nn.initializers.ones,
                       (cfg.hidden_size,), jnp.float32)
        if not cfg.policy.keep_norms_fp32:
            g = g.astype(dtype)
        x = rms_norm(x, g, eps=cfg.norm_eps).astype(dtype)
        with region("head"):
            logits = jnp.einsum("bsh,vh->bsv", x, emb.astype(dtype),
                                preferred_element_type=jnp.float32)
        out = (logits,) if cache is None else (logits, new_cache)
        if moe_counts:
            out = out + (counts,)
        return out[0] if len(out) == 1 else out


def init_lfm2_cache(cfg: Lfm2MoeConfig, batch: int, max_len: int,
                    dtype=None):
    """One entry a layer, by its kind: ``{"k", "v"}: (B, max_len, Hkv *
    D)`` in ``dtype`` (`generate.init_cache`'s form), or ``{"conv": (B, L,
    E)}``, the convolution's last L inputs, in the compute dtype.
    ``dtype`` (a pool's capacity tier) reaches K/V alone."""
    compute = cfg.policy.compute_dtype
    kv = (batch, max_len, cfg.num_key_value_heads * cfg.head_dim)

    def entry(kind):
        if kind == ATTENTION:
            return {"k": jnp.zeros(kv, dtype or compute),
                    "v": jnp.zeros(kv, dtype or compute)}
        return {"conv": jnp.zeros((batch, cfg.conv_L_cache,
                                   cfg.hidden_size), compute)}

    return {f"layer{i}": entry(kind)
            for i, kind in enumerate(cfg.layer_types)}
