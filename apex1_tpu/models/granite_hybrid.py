"""Granite 4.0-H (`model_type: granitemoehybrid`, here without experts):
a decoder whose layers are Mamba-2 state-space mixers, with a few
grouped-query attention layers among them, each followed by a SwiGLU MLP;
RMSNorm before each, no positions at all (`position_embedding_type:
nope`), and the family's four multipliers. The published
`ibm-granite/granite-4.0-h-micro` is 36 + 4 layers of 2048.

With ``h = E[tok] * embedding_multiplier``, layer i is ``h += r *
Mixer_i(RMSNorm(h))`` then ``h += r * MLP(RMSNorm(h))`` (``r`` the
residual multiplier), ``MLP(x) = W_out(silu(g) * u)`` with ``[g, u] = W_in
x``, and ``logits = RMSNorm(h) E^T / logits_scaling`` (tied).

- *attention*: q, k, v without bias or rotary, causal softmax of ``q . k *
  attention_multiplier`` (in place of 1 / sqrt(d)), ``W_o``.
- *mamba*: ``[z | xBC | dt] = W_in x``; ``xBC = silu(conv1d(xBC))``
  (depthwise, causal, kernel 4, with bias); ``x, B, C = split(xBC)``, x as
  heads, B and C one group for all heads; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; the recurrence of `ops.ssm`; ``y = RMSNorm(y *
  silu(z))`` over the whole inner width (gate first, then norm); ``W_out
  y``.

ONE ``layer_types`` list says which mixer a layer has and, with it, which
kind of cache entry: ``{"k", "v"}`` (`generate.init_cache`'s form) for an
attention layer, ``{"ssm", "conv"}`` for a Mamba layer: the recurrent
state, float32, in the form `ops.ssm.pack_state` stores it (``(B, heads /
k, state width, k * head width)``, ``k`` heads side by side on 128 lanes),
and the last three inputs of its convolution. `generate.granite_hybrid_decoder` makes the
tree; the serving engine pools it like any other, slot on axis 0. A cached
call with a per-row index (the engine's decode step) takes one token a
row and updates each live row's state where it lies (`ops.ssm.ssm_step`);
a call with a scalar index takes a run of tokens, of which the first
``n_real`` are real (a right-padded prefill chunk).

Norm weights are named ``*scale``, and so are the depthwise convolution's
taps (``conv_tap_scale``: a gain a channel on each of the last four
inputs), so that a weight generator which starts gains near 1 and
everything else near 0 (`benchmark/harness/builders.py`) gives a
convolution that passes its input on: with taps near 0 the mixer's ``x``,
``B`` and ``C`` are near 0, ``y`` falls under the gate norm's epsilon, and
the state-space layers add nothing that a check of the logits could see.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex1_tpu.core.policy import PrecisionPolicy, get_policy
from apex1_tpu.obs.regions import region
from apex1_tpu.ops import rms_norm
from apex1_tpu.ops.attention import flash_attention
from apex1_tpu.ops.ssm import (causal_conv, heads_a_row, pack_state,
                               ssd_chunk, ssm_step, unpack_state)

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The published keys, under their published names."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    mamba_d_conv: int = 4
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_n_heads: int = 64
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    policy: PrecisionPolicy = dataclasses.field(
        default_factory=lambda: get_policy("O0"))

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers or any(
                t not in (MAMBA, ATTENTION) for t in self.layer_types):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {MAMBA!r} or {ATTENTION!r}: {self.layer_types}")
        if self.mamba_n_groups != 1 or self.mamba_proj_bias:
            raise ValueError("one group of B and C and no projection bias "
                             "is what this model computes")
        if self.mamba_n_heads * self.mamba_d_head != self.d_inner:
            raise ValueError("mamba_n_heads * mamba_d_head != "
                             "mamba_expand * hidden_size")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("attention heads do not divide")

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def conv_dim(self) -> int:
        """The width the convolution runs over: x | B | C."""
        return self.d_inner + 2 * self.mamba_d_state

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def tiny(**kw) -> "GraniteHybridConfig":
        """The published widths' ratios at hidden 128: two periods of
        (mamba, attention, mamba)."""
        defaults = dict(
            vocab_size=512, hidden_size=128, intermediate_size=512,
            num_hidden_layers=6, layer_types=(MAMBA, ATTENTION, MAMBA) * 2,
            num_attention_heads=8, num_key_value_heads=2,
            attention_multiplier=0.0625, mamba_d_head=16, mamba_d_state=32,
            mamba_n_heads=16, mamba_chunk_size=16)
        defaults.update(kw)
        return GraniteHybridConfig(**defaults)


class GraniteHybridBlock(nn.Module):
    cfg: GraniteHybridConfig
    kind: str

    def _attention(self, h, cache, cache_index, chunk_decode):
        cfg = self.cfg
        dtype = h.dtype
        E, Hq, Hkv, D = (cfg.hidden_size, cfg.num_attention_heads,
                         cfg.num_key_value_heads, cfg.head_dim)
        B, S = h.shape[:2]
        init = nn.initializers.normal(0.02)
        heads = lambda name, n: (h @ self.param(
            name, init, (E, n * D), jnp.float32).astype(dtype)).reshape(
                B, S, n, D).transpose(0, 2, 1, 3)
        q, k, v = heads("wq", Hq), heads("wk", Hkv), heads("wv", Hkv)
        new_cache = None
        if cache is not None:
            from apex1_tpu.models.generate import cached_attention
            attn, new_cache = cached_attention(
                q, k, v, cache, cache_index,
                sm_scale=cfg.attention_multiplier,
                chunk_decode=chunk_decode)
        else:
            attn = flash_attention(q, k, v, causal=True,
                                   sm_scale=cfg.attention_multiplier)
        attn = attn.transpose(0, 2, 1, 3).reshape(B, S, Hq * D)
        wo = self.param("wo", init, (Hq * D, E), jnp.float32).astype(dtype)
        return attn.astype(dtype) @ wo, new_cache

    def _mamba(self, h, cache, cache_index, n_real):
        cfg = self.cfg
        dtype = h.dtype
        f32 = jnp.float32
        E, I, N = cfg.hidden_size, cfg.d_inner, cfg.mamba_d_state
        Hm, P, K = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_conv
        B, S = h.shape[:2]
        init = nn.initializers.normal(0.02)
        w_in = self.param("in_proj", init, (E, I + cfg.conv_dim + Hm), f32)
        conv_w = self.param("conv_tap_scale", nn.initializers.ones,
                            (K, cfg.conv_dim), f32)
        conv_b = (self.param("conv_b", nn.initializers.zeros,
                             (cfg.conv_dim,), f32)
                  if cfg.mamba_conv_bias else None)
        A = -jnp.exp(self.param("A_log", nn.initializers.zeros, (Hm,), f32))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (Hm,), f32)
        D = self.param("D", nn.initializers.ones, (Hm,), f32)
        g = self.param("gate_norm_scale", nn.initializers.ones, (I,), f32)
        w_out = self.param("out_proj", init, (I, E), f32).astype(dtype)

        z, xbc, dt = jnp.split(h @ w_in.astype(dtype),
                               [I, I + cfg.conv_dim], axis=-1)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
        idx = None if cache is None else jnp.asarray(cache_index, jnp.int32)
        per_row = idx is not None and idx.ndim == 1
        if per_row and S != 1:
            raise ValueError(
                "a recurrent state takes one token a row under a per-row "
                f"index, got {S}: it is no list of positions to verify "
                "and roll back")
        if cache is None:
            conv_state = jnp.zeros((B, K - 1, cfg.conv_dim), dtype)
            state = jnp.zeros((B, Hm, P, N), f32)
        else:
            conv_state, state = cache["conv"], cache["ssm"]   # as stored
        xbc, new_conv = causal_conv(xbc, conv_w, conv_b, conv_state, n_real)
        if per_row:                     # an idle row keeps its inputs
            new_conv = jnp.where((idx >= 0)[:, None, None], new_conv,
                                 conv_state)
        x, Bm, Cm = jnp.split(jax.nn.silu(xbc), [I, I + N], axis=-1)
        x = x.reshape(B, S, Hm, P)
        if S == 1 and cache is not None:
            y, state = ssm_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                                D, state, idx)
            y = y[:, None]
        else:
            y, state = ssd_chunk(
                x, dt, A, Bm, Cm, D,
                state if cache is None else unpack_state(state, P), n_real,
                chunk=cfg.mamba_chunk_size)
            state = None if cache is None else pack_state(state)
        y = y.reshape(B, S, I) * jax.nn.silu(z.astype(f32))
        y = rms_norm(y, g, eps=cfg.rms_norm_eps).astype(dtype)
        new_cache = None if cache is None else {"ssm": state,
                                                "conv": new_conv}
        return y @ w_out, new_cache

    @nn.compact
    def __call__(self, x, cache=None, cache_index=None, chunk_decode=False,
                 n_real=None):
        cfg = self.cfg
        dtype = cfg.policy.compute_dtype
        E, F = cfg.hidden_size, cfg.intermediate_size
        init = nn.initializers.normal(0.02)

        def norm(name, z):
            g = self.param(name, nn.initializers.ones, (E,), jnp.float32)
            if not cfg.policy.keep_norms_fp32:
                g = g.astype(dtype)
            return rms_norm(z, g, eps=cfg.rms_norm_eps).astype(dtype)

        h = norm("in_norm_scale", x)
        with region("attn" if self.kind == ATTENTION else "mixer"):
            if self.kind == ATTENTION:
                y, new_cache = self._attention(h, cache, cache_index,
                                               chunk_decode)
            else:
                y, new_cache = self._mamba(h, cache, cache_index, n_real)
            x = x + (cfg.residual_multiplier * y).astype(x.dtype)

        h = norm("post_norm_scale", x)
        with region("ffn"):
            w_in = self.param("mlp_in", init, (E, 2 * F),
                              jnp.float32).astype(dtype)
            w_out = self.param("mlp_out", init, (F, E),
                               jnp.float32).astype(dtype)
            gate, up = jnp.split(h @ w_in, 2, axis=-1)
            y = (jax.nn.silu(gate) * up) @ w_out
            return (x + (cfg.residual_multiplier * y).astype(x.dtype),
                    new_cache)


class GraniteHybrid(nn.Module):
    """Logits (B, S, vocab) in float32; with a cache, ``(logits, cache)``."""

    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, tokens, *, cache=None, cache_index=None,
                 chunk_decode=False, n_real=None):
        """``cache`` / ``cache_index``: see `models.generate`; a scalar
        index takes a run of tokens of which the first ``n_real`` (None:
        all) are real, a per-row index one token a row (negative: an idle
        row, whose cache entries are left as they are)."""
        cfg = self.cfg
        dtype = cfg.policy.compute_dtype
        emb = self.param("embed", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        with region("embed"):
            x = (emb[tokens] * cfg.embedding_multiplier).astype(dtype)
        new_cache = {}
        for i, kind in enumerate(cfg.layer_types):
            x, entry = GraniteHybridBlock(cfg, kind, name=f"layer{i}")(
                x, None if cache is None else cache[f"layer{i}"],
                cache_index, chunk_decode, n_real)
            new_cache[f"layer{i}"] = entry
        g = self.param("final_norm_scale", nn.initializers.ones,
                       (cfg.hidden_size,), jnp.float32)
        if not cfg.policy.keep_norms_fp32:
            g = g.astype(dtype)
        x = rms_norm(x, g, eps=cfg.rms_norm_eps).astype(dtype)
        with region("head"):
            logits = jnp.einsum("bsh,vh->bsv", x, emb.astype(dtype),
                                preferred_element_type=jnp.float32) \
                / cfg.logits_scaling
        return logits if cache is None else (logits, new_cache)


def init_hybrid_cache(cfg: GraniteHybridConfig, batch: int, max_len: int,
                      dtype=None):
    """One entry a layer, by its kind: ``{"k", "v"}: (B, max_len, Hkv *
    D)`` in ``dtype`` (`generate.init_cache`'s form), or ``{"ssm": (B,
    heads / k, state width, k * head width)`` float32
    (`ops.ssm.pack_state`'s form), ``"conv": (B, 3, conv width)}`` in the
    compute dtype. ``dtype`` (a pool's capacity tier)
    reaches K/V alone: a state is summed into at every token."""
    compute = cfg.policy.compute_dtype
    kv = (batch, max_len, cfg.num_key_value_heads * cfg.head_dim)

    def entry(kind):
        if kind == ATTENTION:
            return {"k": jnp.zeros(kv, dtype or compute),
                    "v": jnp.zeros(kv, dtype or compute)}
        k = heads_a_row(cfg.mamba_n_heads, cfg.mamba_d_head)
        return {"ssm": jnp.zeros((batch, cfg.mamba_n_heads // k,
                                  cfg.mamba_d_state, k * cfg.mamba_d_head),
                                 jnp.float32),
                "conv": jnp.zeros((batch, cfg.mamba_d_conv - 1,
                                   cfg.conv_dim), compute)}

    return {f"layer{i}": entry(kind)
            for i, kind in enumerate(cfg.layer_types)}
