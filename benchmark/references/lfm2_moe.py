"""Plain reference for LFM2 with experts (`model_type: lfm2_moe`;
LiquidAI/LFM2-8B-A1B and any size of the family): the published forward
pass in straightforward float32 `jax.numpy`, products under
`jax.lax.Precision.HIGHEST`. No kernel, no cache, no sort, and nothing
imported from the program under test.

Published description (`config.json` named in
`benchmark/configs/lfm2-8b-a1b.json`). ``h = E[tok]``. Layer i: ``h +=
Op_i(RMSNorm(h))``, then ``h += FFN_i(RMSNorm(h))``, epsilon ``norm_eps``;
``logits = RMSNorm(h) E^T`` (tied).

- *conv* layers (``conv_L_cache`` L, no bias): ``[B, C, u] = W_in x``; ``z
  = B * u``; ``c_t = sum_{j < L} w[j] * z_{t - (L - 1) + j}``, depthwise
  and causal, HERE AS L SHIFTED PRODUCTS over the whole row; ``y = W_out
  (C * c)``.
- *full_attention* layers: q of ``num_attention_heads``, k and v of
  ``num_key_value_heads`` heads, no bias; RMSNorm over each head's width
  on q and on k, then RoPE (``rope_theta``; the whole head, the first half
  paired with the second); causal softmax of ``q . k / sqrt(d)``; ``W_o``.
- the first ``num_dense_layers`` layers: ``W2 (silu(W1 x) * (W3 x))``.
- every later layer: ``s = sigmoid(W_g x)`` over ``num_experts``; the
  ``num_experts_per_tok`` experts with the largest ``s + b`` are chosen
  (``use_expert_bias``); a chosen expert's weight is its UNBIASED ``s``
  over the sum of the chosen ones' (``norm_topk_prob``), times
  ``routed_scaling_factor``; every other expert's weight is 0. **Every
  held expert is computed for every token and multiplied by its weight or
  by zero.** ``held`` ``[first, count]`` is the share of the experts whose
  matrices the tree holds (this chip's, of an expert-parallel deployment):
  the layer's result is the sum over THOSE experts, and what the others
  would add is left out, as in the program. The router is never rounded:
  it is float32 in the published model, so ``quant`` leaves it alone.

Departures, each forced by how the system under test stores a checkpoint:
the tree is read under the names the system gives its leaves (``embed``,
``final_norm_scale``, ``layer<i>/{operator_norm_scale, ffn_norm_scale}``
and, by the layer's kinds, ``{in_proj, conv_tap_scale, out_proj}`` or
``{wq, wk, wv, wo, q_norm_scale, k_norm_scale}``, and ``{w1, w3, w2}`` or
``{router, expert_bias, experts_w1, experts_w3, experts_w2}``);
matrices are stored input-major, the taps (L, channels) with the LAST tap
on the current input, the held experts' matrices stacked on a leading
axis. The weights come as stored (bfloat16 where served) and are upcast a
layer, and an expert, at a time; rows go through one at a time (`lax.map`)
and attention a group of heads at a time, so the scratch is one row's.

``quant`` is the lower-precision control of the benchmark's correctness
check: when given, every matrix product's operands are rounded through
that dtype before the float32 product (not the router's).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.references.lowprec import q as _q

#: the loss is a mean of row means: equal blocks of rows average exactly
BLOCKABLE = True
#: no leaf holds several published tensors side by side
LEAF_PARTS = {}

_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _mm(a, b, quant):
    return jnp.matmul(_q(a, quant), _q(b, quant), precision=_HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _swiglu(x, w1, w3, w2, quant):
    return _mm(jax.nn.silu(_mm(x, w1, quant)) * _mm(x, w3, quant), w2, quant)


def _conv(p, x, cfg, quant):
    """x (S, E), one row: the convolution as L shifted products."""
    S, L = x.shape[0], cfg["conv_L_cache"]
    gate_in, gate_out, u = jnp.split(_mm(x, p["in_proj"], quant), 3, axis=-1)
    z = jnp.pad(gate_in * u, ((L - 1, 0), (0, 0)))
    c = sum(p["conv_tap_scale"][j] * z[j:j + S] for j in range(L))
    return _mm(gate_out * c, p["out_proj"], quant)


def _rope(x, theta):
    """x (S, heads, d): the first half of a head paired with the second."""
    S, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d // 2, dtype=_F32) / (d // 2))
    ang = jnp.arange(S, dtype=_F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, cfg, quant):
    """x (S, E), one row; one group of query heads (those that share a
    K/V head) at a time, so the scores held are (S, S) times the group."""
    S = x.shape[0]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // hq
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    q = _mm(x, p["wq"], quant).reshape(S, hq, d)
    k = _mm(x, p["wk"], quant).reshape(S, hkv, d)
    v = _mm(x, p["wv"], quant).reshape(S, hkv, d)
    q = _rope(_rms(q, p["q_norm_scale"], eps), theta)
    k = _rope(_rms(k, p["k_norm_scale"], eps), theta)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def group(args):
        qg, kg, vg = args                      # (S, g, d), (S, d), (S, d)
        scores = jnp.einsum("sgd,td->gst", _q(qg, quant), _q(kg, quant),
                            precision=_HIGHEST) / jnp.sqrt(_F32(d))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("gst,td->sgd", _q(probs, quant), _q(vg, quant),
                          precision=_HIGHEST)

    out = jax.lax.map(group, (
        q.reshape(S, hkv, hq // hkv, d).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))      # (hkv, S, g, d)
    return _mm(out.transpose(1, 0, 2, 3).reshape(S, hq * d), p["wo"], quant)


def _mixture(p, x, cfg, quant):
    """x (S, E), one row: every held expert over every token."""
    n, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    first, count = cfg.get("held") or (0, n)
    s = jax.nn.sigmoid(jnp.matmul(x, p["router"].astype(_F32),
                                  precision=_HIGHEST))
    by = s + p["expert_bias"].astype(_F32) \
        if cfg["use_expert_bias"] else s
    # an expert is chosen where fewer than k of the token's (biased)
    # scores lie above its own
    chosen = jnp.sum(by[:, None, :] > by[:, :, None], axis=-1) < k
    w = jnp.where(chosen, s, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]

    def expert(y, e):
        up = lambda name: jax.lax.dynamic_index_in_dim(
            p[name], e, 0, keepdims=False).astype(_F32)
        out = _swiglu(x, up("experts_w1"), up("experts_w3"),
                      up("experts_w2"), quant)
        return y + jax.lax.dynamic_index_in_dim(
            w, first + e, 1, keepdims=True) * out, None

    return jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(count))[0]


def _row_hidden(params, tokens, cfg, quant):
    """(S,) tokens of one row -> (S, E) after the final norm."""
    eps = cfg["norm_eps"]
    up = lambda tree: jax.tree_util.tree_map(lambda a: a.astype(_F32), tree)
    h = params["embed"][tokens].astype(_F32)
    for i, kind in enumerate(cfg["layer_types"]):
        p = params[f"layer{i}"]
        # upcast a layer at a time, and of a sparse layer's experts one
        # at a time (`_mixture`): the weights come as stored
        small = up({k: v for k, v in p.items()
                    if not k.startswith("experts_w")})
        op = _attention if kind == "full_attention" else _conv
        h = h + op(small, _rms(h, small["operator_norm_scale"], eps), cfg,
                   quant)
        y = _rms(h, small["ffn_norm_scale"], eps)
        if i < cfg["num_dense_layers"]:
            h = h + _swiglu(y, small["w1"], small["w3"], small["w2"], quant)
        else:
            h = h + _mixture(p, y, cfg, quant)
    return _rms(h, params["final_norm_scale"].astype(_F32), eps)


def logits(params, tokens, cfg, quant=None, positions=None):
    """(B, S) int tokens -> float32 logits of the tied head: (B, S, vocab),
    or with ``positions`` (B, n) the (B, n, vocab) at those positions
    alone."""
    embed = params["embed"].astype(_F32)[:cfg["vocab_size"]]

    def row(args):
        toks, pos = args
        h = _row_hidden(params, toks, cfg, quant)
        if pos is not None:
            h = h[pos]
        return _mm(h, embed.T, quant)

    if positions is None:
        return jax.lax.map(lambda t: row((t, None)), tokens)
    return jax.lax.map(row, (tokens, positions))


def loss(params, batch, cfg, quant=None):
    """Mean next-token cross-entropy over every row and position of
    ``batch["tokens"]`` (B, S). No cell trains this family; the protocol
    asks every reference for it."""
    tokens = batch["tokens"]
    logp = jax.nn.log_softmax(logits(params, tokens, cfg, quant)[:, :-1],
                              axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)
