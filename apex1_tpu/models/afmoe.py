"""AFMoE (`model_type: afmoe`; the published `arcee-ai/Trinity-Mini` is 32
layers of 2048, 128 experts of which 8 a token beside one shared expert):
a decoder whose attention layers alternate between a SLIDING WINDOW with
RoPE and, every fourth layer, GLOBAL causal attention with no positional
term at all; the attention's output passes a learned sigmoid GATE before
``W_o``; the feed-forward is a dense SwiGLU in the first
``num_dense_layers`` layers and a dropless mixture of small experts
beside a shared expert after. Four RMSNorms a layer; an untied head.

``h = sqrt(hidden_size) * E[tok]`` (``mup_enabled``). A layer is ``h +=
norm_b(attn(norm_a(h))); h += norm_d(ffn(norm_c(h)))``; ``logits = W_head
norm(h)``.

- *attention*: q of ``num_attention_heads``, k, v of
  ``num_key_value_heads`` heads of ``head_dim``, a gate of
  ``num_attention_heads * head_dim``, no bias; RMSNorm with a learned
  scale over each head's width on q and on k; on a ``sliding_attention``
  layer RoPE (``rope_theta``, the whole head, halves paired) and the mask
  ``0 <= t - s < sliding_window``; on a ``full_attention`` layer NO
  positions and the causal mask; softmax of ``q . k / sqrt(head_dim)``;
  ``W_o (heads * sigmoid(gate))``.
- dense feed-forward: ``W2 (silu(W1 x) * (W3 x))`` of
  ``intermediate_size``.
- sparse feed-forward: the router in float32, ``s = sigmoid(W_r x)``
  (``score_func``), the ``num_experts_per_tok`` experts CHOSEN by ``s +
  b`` (``expert_bias``, a stored vector: it chooses and never weighs), the
  weights the unbiased ``s`` at the chosen over their sum
  (``route_norm``) times ``route_scale``; each expert a SwiGLU of
  ``moe_intermediate_size``; beside them the shared expert, a SwiGLU of
  ``moe_intermediate_size * num_shared_experts`` over every token. No
  token is dropped (`transformer.moe.dropless_route`, `held_experts_mlp`,
  `shared_expert_mlp`).

``experts_held`` ``(first, count)`` makes the model ONE CHIP'S SHARE of an
expert-parallel deployment, as `models.lfm2` has it: the router keeps all
``num_experts`` outputs, the expert leaves hold ``count`` experts, a
sparse layer adds the shared expert and the part of the mixture that the
held experts give, and nothing stands in for the chips that hold the
others.

``layer_types`` drives the mask, the positions AND the kind of a layer's
cache entry. Both kinds are ``{"k", "v"}: (B, L, Hkv * D)``
(`generate.init_cache`'s form) and differ in ``L``: a ``full_attention``
layer's holds every position, ``L = max_len``; a ``sliding_attention``
layer's is a RING of ``sliding_window + ring_slack`` rows in whole
`DECODE_BLOCK`s (position ``p`` in row ``p mod L``), since nothing older
is ever attended: at the published sizes 2304 rows beside 8960. The slack
is what one call may append (`generate.cached_attention`: ``L >= window +
S - 1``).

What the published configuration can say and this model does not compute
is refused by name at construction: a group-limited choice (``n_group``,
``topk_group`` above 1), a tied head, scaled RoPE.

Norm weights are named ``*scale`` (the benchmark's weight generator starts
such leaves at 1 + 0.1 normal, all else at 0.02 normal); the router's
bias is NOT (``expert_bias``), for `models.lfm2`'s reason: drawn small it
changes the choice for a share of the tokens and starves no expert.

The uncached forward (a loss, a parity check) runs the causal flash
kernel where the window cannot bind (``S <= sliding_window``) and a
masked composite of (S, S) scores where it can: the flash kernels have no
window yet (ROADMAP R2).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex1_tpu.core.policy import PrecisionPolicy, get_policy
from apex1_tpu.models.lfm2 import _head_norm
from apex1_tpu.obs.regions import region
from apex1_tpu.ops import (NEG_INF, apply_rotary_pos_emb, rms_norm,
                           rope_tables)
from apex1_tpu.ops.attention import flash_attention
from apex1_tpu.ops.decode_attend import DECODE_BLOCK
from apex1_tpu.transformer.moe import (RouteConfig, dropless_route,
                                       held_experts_mlp, shared_expert_mlp)

SLIDING, GLOBAL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """The published keys, under their published names, and the share."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    num_dense_layers: int = 2
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    n_group: int = 1
    topk_group: int = 1
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    tie_word_embeddings: bool = False
    experts_held: Optional[Tuple[int, int]] = None
    policy: PrecisionPolicy = dataclasses.field(
        default_factory=lambda: get_policy("O0"))

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers or any(
                t not in (SLIDING, GLOBAL) for t in self.layer_types):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {SLIDING!r} or {GLOBAL!r}: {self.layer_types}")
        for key, want in (("n_group", 1), ("topk_group", 1),
                          ("tie_word_embeddings", False),
                          ("rope_scaling", None)):
            if getattr(self, key) != want:
                raise ValueError(
                    f"{key} = {getattr(self, key)!r}: this model computes "
                    f"only {want!r} (no group-limited choice, no tied "
                    f"head, no scaled RoPE)")
        if self.score_func not in ("sigmoid", "softmax"):
            raise ValueError(f"score_func {self.score_func!r}")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.head_dim % 2 or self.sliding_window < 1:
            raise ValueError("attention heads or window do not divide")
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held",
                               tuple(int(n) for n in self.experts_held))
        first, count = self.held.start, len(self.held)
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.num_experts} experts")

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
                           score=self.score_func, select_bias=True,
                           normalize=self.route_norm, scale=self.route_scale)

    @property
    def moe_expert_slots(self) -> int:
        """Held experts, summed over the sparse layers: what a step can
        touch at most."""
        return len(self.held) * self.ffn_kinds.count(SPARSE)

    @staticmethod
    def tiny(**kw) -> "AfmoeConfig":
        """The published ratios at hidden 128: one dense layer, the period
        (sliding, sliding, sliding, global) twice over less its first
        layer's twin, a window of 16, 16 experts of which 4 a token."""
        defaults = dict(
            vocab_size=512, hidden_size=128, intermediate_size=384,
            moe_intermediate_size=64, num_hidden_layers=6,
            layer_types=(SLIDING, SLIDING, GLOBAL, SLIDING, SLIDING,
                         GLOBAL),
            num_attention_heads=8, num_key_value_heads=2, head_dim=16,
            sliding_window=16, num_dense_layers=1, num_experts=16,
            num_experts_per_tok=4, route_scale=2.826)
        defaults.update(kw)
        return AfmoeConfig(**defaults)


def windowed_attention(q, k, v, window: int):
    """Uncached sliding-window attention, ``q`` (B, Hq, S, D), ``k`` /
    ``v`` (B, Hkv, S, D): query t sees ``0 <= t - s < window``. The
    causal flash kernel where the window cannot bind; else the masked
    composite, (S, S) scores a head."""
    B, Hq, S, D = q.shape
    if S <= window:
        return flash_attention(q, k, v, causal=True)
    Hkv = k.shape[1]
    qg = q.reshape(B, Hkv, Hq // Hkv, S, D)
    scores = jnp.einsum("bhgsd,bhtd->bhgst", qg, k,
                        preferred_element_type=jnp.float32) * D ** -0.5
    t, s = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    keep = (s <= t) & (t - s < window)
    probs = jax.nn.softmax(jnp.where(keep, scores, NEG_INF),
                           axis=-1).astype(q.dtype)
    return jnp.einsum("bhgst,bhtd->bhgsd", probs, v).reshape(B, Hq, S, D)


class AfmoeBlock(nn.Module):
    cfg: AfmoeConfig
    kind: str
    ffn: str

    def _attention(self, h, cache, cache_index, chunk_decode, cos, sin):
        cfg = self.cfg
        dtype = h.dtype
        E, Hq, Hkv, D = (cfg.hidden_size, cfg.num_attention_heads,
                         cfg.num_key_value_heads, cfg.head_dim)
        B, S = h.shape[:2]
        init = nn.initializers.normal(0.02)
        proj = lambda name, n: h @ self.param(
            name, init, (E, n * D), jnp.float32).astype(dtype)
        q = proj("wq", Hq).reshape(B, S, Hq, D)
        k = proj("wk", Hkv).reshape(B, S, Hkv, D)
        v = proj("wv", Hkv).reshape(B, S, Hkv, D)
        gate = proj("wgate", Hq)
        gq = self.param("q_norm_scale", nn.initializers.ones, (D,),
                        jnp.float32)
        gk = self.param("k_norm_scale", nn.initializers.ones, (D,),
                        jnp.float32)
        q = _head_norm(q, gq, cfg.rms_norm_eps)
        k = _head_norm(k, gk, cfg.rms_norm_eps)
        window = None
        if self.kind == SLIDING:     # a global layer has no positions
            window = cfg.sliding_window
            q = apply_rotary_pos_emb(q, cos, sin)
            k = apply_rotary_pos_emb(k, cos, sin)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        new_cache = None
        if cache is not None:
            from apex1_tpu.models.generate import cached_attention
            attn, new_cache = cached_attention(
                q, k, v, cache, cache_index, chunk_decode=chunk_decode,
                window=window)
        elif window is None:
            attn = flash_attention(q, k, v, causal=True)
        else:
            attn = windowed_attention(q, k, v, window)
        attn = attn.transpose(0, 2, 1, 3).reshape(B, S, Hq * D)
        wo = self.param("wo", init, (Hq * D, E), jnp.float32).astype(dtype)
        return (attn.astype(dtype) * jax.nn.sigmoid(gate)) @ wo, new_cache

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
        Fs = F * cfg.num_shared_experts
        init = nn.initializers.normal(0.02)
        gate = self.param("router", init, (E, cfg.num_experts), jnp.float32)
        bias = self.param("expert_bias", nn.initializers.zeros,
                          (cfg.num_experts,), jnp.float32)
        w = lambda name, shape: self.param(name, init, shape,
                                           jnp.float32).astype(h.dtype)
        x2 = h.reshape(-1, E)
        experts, weights = dropless_route(x2, gate, bias, cfg.route)
        y, counts = held_experts_mlp(
            x2, experts, weights, w("experts_w1", (n, E, F)),
            w("experts_w3", (n, E, F)), w("experts_w2", (n, F, E)),
            cfg.held, None if live is None else live.reshape(-1))
        if Fs:
            y = y + shared_expert_mlp(x2, w("shared_w1", (E, Fs)),
                                      w("shared_w3", (E, Fs)),
                                      w("shared_w2", (Fs, E)))
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
            return rms_norm(z, g, eps=cfg.rms_norm_eps).astype(dtype)

        with region("attn"):
            y, entry = self._attention(norm("input_norm_scale", x), cache,
                                       cache_index, chunk_decode, cos, sin)
            x = x + norm("post_attn_norm_scale", y).astype(x.dtype)

        with region("ffn"):
            h = norm("pre_ffn_norm_scale", x)
            counts = None
            if self.ffn == DENSE:
                y = self._dense(h)
            else:
                # who is routed: a live lane's one token, a run's real ones
                B, S = x.shape[:2]
                live = None
                if cache is not None and jnp.ndim(cache_index) == 1:
                    live = jnp.broadcast_to(
                        (jnp.asarray(cache_index) >= 0)[:, None], (B, S))
                elif n_real is not None:
                    live = jnp.broadcast_to(
                        jnp.arange(S)[None, :] < n_real, (B, S))
                y, counts = self._sparse(h, live)
            return (x + norm("post_ffn_norm_scale", y).astype(x.dtype),
                    entry, counts)


class Afmoe(nn.Module):
    """Logits (B, S, vocab) in float32; with a cache, ``(logits, cache)``;
    with ``moe_counts`` a last element more: (2,) int32, the (row, expert)
    pairs the sparse layers computed here and the held experts they
    touched, summed over the layers."""

    cfg: AfmoeConfig

    @nn.compact
    def __call__(self, tokens, *, positions=None, cache=None,
                 cache_index=None, chunk_decode=False, n_real=None,
                 moe_counts=False):
        """``cache`` / ``cache_index``: see `models.generate`; a scalar
        index takes a run of tokens of which the first ``n_real`` (None:
        all) are routed, a per-row index one token a row (negative: an
        idle row). ``positions`` (B, S) or (S,): where RoPE puts each
        token on the sliding layers; None: from ``cache_index`` on, or
        from 0 without a cache."""
        cfg = self.cfg
        dtype = cfg.policy.compute_dtype
        B, S = tokens.shape
        init = nn.initializers.normal(0.02)
        emb = self.param("embed", init, (cfg.vocab_size, cfg.hidden_size),
                         jnp.float32)
        with region("embed"):
            x = emb[tokens].astype(dtype)
            if cfg.mup_enabled:
                x = x * jnp.asarray(cfg.hidden_size ** 0.5, dtype)
        with region("attn"):        # every sliding layer's RoPE tables
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
            x, entry, c = AfmoeBlock(cfg, kind, ffn, name=f"layer{i}")(
                x, cos, sin, None if cache is None else cache[f"layer{i}"],
                cache_index, chunk_decode, n_real)
            new_cache[f"layer{i}"] = entry
            if c is not None:
                counts = counts + c
        g = self.param("final_norm_scale", nn.initializers.ones,
                       (cfg.hidden_size,), jnp.float32)
        if not cfg.policy.keep_norms_fp32:
            g = g.astype(dtype)
        x = rms_norm(x, g, eps=cfg.rms_norm_eps).astype(dtype)
        head = self.param("lm_head", init,
                          (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        with region("head"):
            logits = jnp.einsum("bsh,vh->bsv", x, head.astype(dtype),
                                preferred_element_type=jnp.float32)
        out = (logits,) if cache is None else (logits, new_cache)
        if moe_counts:
            out = out + (counts,)
        return out[0] if len(out) == 1 else out


def ring_rows(cfg: AfmoeConfig, max_len: int, ring_slack: int) -> int:
    """Rows of a sliding layer's cache entry beside ``max_len`` positions:
    the window and ``ring_slack`` rows for one call's appends, in whole
    `DECODE_BLOCK`s, and never more than the positions there are."""
    ring = -(-(cfg.sliding_window + ring_slack) // DECODE_BLOCK) \
        * DECODE_BLOCK
    return min(int(max_len), ring)


def init_afmoe_cache(cfg: AfmoeConfig, batch: int, max_len: int,
                     dtype=None, *, ring_slack: int = 256):
    """One ``{"k", "v"}: (B, L, Hkv * D)`` entry a layer in ``dtype``
    (`generate.init_cache`'s form): ``L = max_len`` on a global layer,
    `ring_rows` on a sliding one."""
    dtype = dtype or cfg.policy.compute_dtype
    lanes = cfg.num_key_value_heads * cfg.head_dim
    ring = ring_rows(cfg, max_len, ring_slack)

    def entry(kind):
        shape = (batch, max_len if kind == GLOBAL else ring, lanes)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    return {f"layer{i}": entry(kind)
            for i, kind in enumerate(cfg.layer_types)}
