"""Plain reference for AFMoE (`model_type: afmoe`; arcee-ai/Trinity-Mini and
any size of the family): the published forward pass in straightforward
float32 `jax.numpy`, products under `jax.lax.Precision.HIGHEST`. No kernel,
no cache, no ring, no sort, and nothing imported from the program under
test.

Published description (`config.json` named in
`benchmark/configs/trinity-mini.json`; the terms the row has no key for
are from the family's public modelling code, Hugging Face `transformers`
`models/afmoe/modeling_afmoe.py`, and listed under the configuration's
`assumed`). ``h = sqrt(hidden_size) * E[tok]`` (``mup_enabled``). Layer i:
``h += RMSNorm_b(Attn_i(RMSNorm_a(h)))``, then ``h +=
RMSNorm_d(FFN_i(RMSNorm_c(h)))``, epsilon ``rms_norm_eps``; ``logits =
W_head RMSNorm(h)`` (untied).

- *attention*: q of ``num_attention_heads``, k and v of
  ``num_key_value_heads`` heads of ``head_dim``, a gate of
  ``num_attention_heads * head_dim``, no bias; RMSNorm with a learned
  scale over each head's width on q and on k. A ``sliding_attention``
  layer: RoPE (``rope_theta``; the whole head, the first half paired with
  the second) and the mask ``0 <= t - s < sliding_window``, HERE AS A MASK
  OVER THE FULL ROW. A ``full_attention`` layer: no positional term at
  all, and the causal mask. Softmax of ``q . k / sqrt(head_dim)``; ``out =
  W_o (heads * sigmoid(gate))``.
- the first ``num_dense_layers`` layers: ``W2 (silu(W1 x) * (W3 x))``.
- every later layer: ``s = sigmoid(W_r x)`` over ``num_experts``
  (``score_func``; with ``softmax``, the softmax); the
  ``num_experts_per_tok`` experts with the largest ``s + b`` are chosen
  (``b`` the stored ``expert_bias``); a chosen expert's weight is its
  UNBIASED ``s`` over the sum of the chosen ones' (``route_norm``), times
  ``route_scale``; every other expert's weight is 0. **Every held expert
  is computed for every token and multiplied by its weight or by zero.**
  Beside them the shared expert (a SwiGLU of ``moe_intermediate_size *
  num_shared_experts``) over every token, unweighted. ``held`` ``[first,
  count]`` is the share of the experts whose matrices the tree holds (this
  chip's, of an expert-parallel deployment): the layer's result is the
  shared expert plus the sum over THOSE experts, and what the others would
  add is left out, as in the program. The router is never rounded: it is
  float32 in the published model, so ``quant`` leaves it alone.

Departures from the published code. (1) The code adds 1e-20 to the sum it
divides the kept weights by; eight sigmoid scores never sum to 0, and it
is dropped. (2) The published model never holds a share of its experts:
``held`` is the deployment's. (3) Forced by how the system under test
stores a checkpoint: the tree is read under the names the system gives
its leaves (``embed``, ``lm_head``, ``final_norm_scale``,
``layer<i>/{input_norm_scale, post_attn_norm_scale, pre_ffn_norm_scale,
post_ffn_norm_scale, wq, wk, wv, wgate, wo, q_norm_scale, k_norm_scale}``
and ``{w1, w3, w2}`` or ``{router, expert_bias, experts_w1, experts_w3,
experts_w2, shared_w1, shared_w3, shared_w2}``); matrices are stored
input-major, the head (vocab, hidden), the held experts' matrices stacked
on a leading axis. The weights come as stored (bfloat16 where served) and
are upcast a layer, and an expert, at a time, the head a slab of the
vocabulary at a time; rows go through one at a time (`lax.map`) and
attention a group of heads and a block of queries at a time, so the
scratch is one row's.

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
#: queries of one block of attention; rows of the head's slab
_Q_BLOCK, _V_SLAB = 1024, 16384


def _mm(a, b, quant):
    return jnp.matmul(_q(a, quant), _q(b, quant), precision=_HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _swiglu(x, w1, w3, w2, quant):
    return _mm(jax.nn.silu(_mm(x, w1, quant)) * _mm(x, w3, quant), w2, quant)


def _parts(n: int, most: int) -> int:
    """The fewest equal parts of ``n`` of at most ``most`` each."""
    return next(k for k in range(1, n + 1) if n % k == 0 and n // k <= most)


def _rope(x, theta):
    """x (S, heads, d): the first half of a head paired with the second."""
    S, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d // 2, dtype=_F32) / (d // 2))
    ang = jnp.arange(S, dtype=_F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, cfg, sliding, quant):
    """x (S, E), one row; one group of query heads (those that share a
    K/V head) and one block of queries at a time, so the scores held are
    (block, S) times the group."""
    S = x.shape[0]
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = _rms(_mm(x, p["wq"], quant).reshape(S, hq, d), p["q_norm_scale"],
             eps)
    k = _rms(_mm(x, p["wk"], quant).reshape(S, hkv, d), p["k_norm_scale"],
             eps)
    v = _mm(x, p["wv"], quant).reshape(S, hkv, d)
    gate = _mm(x, p["wgate"], quant)
    if sliding:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    n_blocks = _parts(S, _Q_BLOCK)
    blk = S // n_blocks
    s_at = jnp.arange(S)

    def group(args):
        qg, kg, vg = args                      # (S, g, d), (S, d), (S, d)

        def block(args):
            qb, t0 = args                      # (blk, g, d), its first row
            t_at = t0 + jnp.arange(blk)
            keep = s_at[None, :] <= t_at[:, None]
            if sliding:
                keep &= t_at[:, None] - s_at[None, :] < cfg["sliding_window"]
            scores = jnp.einsum("sgd,td->gst", _q(qb, quant), _q(kg, quant),
                                precision=_HIGHEST) / jnp.sqrt(_F32(d))
            probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
            return jnp.einsum("gst,td->sgd", _q(probs, quant),
                              _q(vg, quant), precision=_HIGHEST)

        out = jax.lax.map(block, (qg.reshape(n_blocks, blk, *qg.shape[1:]),
                                  jnp.arange(n_blocks) * blk))
        return out.reshape(S, *qg.shape[1:])

    out = jax.lax.map(group, (
        q.reshape(S, hkv, hq // hkv, d).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))      # (hkv, S, g, d)
    out = out.transpose(1, 0, 2, 3).reshape(S, hq * d)
    return _mm(out * jax.nn.sigmoid(gate), p["wo"], quant)


def _mixture(p, small, x, cfg, quant):
    """x (S, E), one row: the shared expert, and every held expert over
    every token."""
    n, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    first, count = cfg.get("held") or (0, n)
    logit = jnp.matmul(x, small["router"], precision=_HIGHEST)
    s = jax.nn.sigmoid(logit) if cfg["score_func"] == "sigmoid" \
        else jax.nn.softmax(logit, -1)
    by = s + small["expert_bias"]
    # an expert is chosen where fewer than k of the token's (biased)
    # scores lie above its own
    chosen = jnp.sum(by[:, None, :] > by[:, :, None], axis=-1) < k
    w = jnp.where(chosen, s, 0.0)
    if cfg["route_norm"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * cfg["route_scale"]

    def expert(y, e):
        up = lambda name: jax.lax.dynamic_index_in_dim(
            p[name], e, 0, keepdims=False).astype(_F32)
        out = _swiglu(x, up("experts_w1"), up("experts_w3"),
                      up("experts_w2"), quant)
        return y + jax.lax.dynamic_index_in_dim(
            w, first + e, 1, keepdims=True) * out, None

    y = jnp.zeros_like(x)
    if cfg["num_shared_experts"]:
        y = _swiglu(x, small["shared_w1"], small["shared_w3"],
                    small["shared_w2"], quant)
    return jax.lax.scan(expert, y, jnp.arange(count))[0]


def _row_hidden(params, tokens, cfg, quant):
    """(S,) tokens of one row -> (S, E) after the final norm."""
    eps = cfg["rms_norm_eps"]
    up = lambda tree: jax.tree_util.tree_map(lambda a: a.astype(_F32), tree)
    h = params["embed"][tokens].astype(_F32)
    if cfg["mup_enabled"]:
        h = h * jnp.sqrt(_F32(cfg["hidden_size"]))
    for i, kind in enumerate(cfg["layer_types"]):
        p = params[f"layer{i}"]
        # upcast a layer at a time, and of a sparse layer's experts one
        # at a time (`_mixture`): the weights come as stored
        small = up({k: v for k, v in p.items()
                    if not k.startswith("experts_w")})
        y = _attention(small, _rms(h, small["input_norm_scale"], eps), cfg,
                       kind == "sliding_attention", quant)
        h = h + _rms(y, small["post_attn_norm_scale"], eps)
        x = _rms(h, small["pre_ffn_norm_scale"], eps)
        if i < cfg["num_dense_layers"]:
            y = _swiglu(x, small["w1"], small["w3"], small["w2"], quant)
        else:
            y = _mixture(p, small, x, cfg, quant)
        h = h + _rms(y, small["post_ffn_norm_scale"], eps)
    return _rms(h, params["final_norm_scale"].astype(_F32), eps)


def _head(h, head, quant):
    """(n, E) by the head as stored (vocab, E) -> (n, vocab), a slab of
    the vocabulary upcast at a time."""
    V, E = head.shape
    n_slabs = _parts(V, _V_SLAB)
    slab = V // n_slabs

    def one(out, i):
        w = jax.lax.dynamic_slice_in_dim(head, i * slab, slab, 0)
        return jax.lax.dynamic_update_slice_in_dim(
            out, _mm(h, w.astype(_F32).T, quant), i * slab, 1), None

    return jax.lax.scan(one, jnp.zeros((h.shape[0], V), _F32),
                        jnp.arange(n_slabs))[0]


def logits(params, tokens, cfg, quant=None, positions=None):
    """(B, S) int tokens -> float32 logits of the untied head: (B, S,
    vocab), or with ``positions`` (B, n) the (B, n, vocab) at those
    positions alone."""
    head = params["lm_head"][:cfg["vocab_size"]]

    def row(args):
        toks, pos = args
        h = _row_hidden(params, toks, cfg, quant)
        if pos is not None:
            h = h[pos]
        return _head(h, head, quant)

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
