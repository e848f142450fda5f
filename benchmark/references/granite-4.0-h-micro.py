"""Plain reference for Granite 4.0-H (ibm-granite/granite-4.0-h-micro and
any other size of the family without experts): the published forward pass
in straightforward float32 `jax.numpy`, matmuls under
`jax.lax.Precision.HIGHEST`. No kernels, no cache, no chunked form, and
nothing imported from the program under test.

Published description (`config.json` named in
`benchmark/configs/granite-4.0-h-micro.json`, `model_type`
`granitemoehybrid`; Dao & Gu 2024 for the Mamba-2 mixer). ``h = E[tok] *
embedding_multiplier``. For layer i: ``h += residual_multiplier *
Mixer_i(RMSNorm(h))``, then ``h += residual_multiplier * MLP(RMSNorm(h))``,
epsilon ``rms_norm_eps``. ``MLP(x) = W_out(silu(g) * u)``, ``[g, u] = W_in
x`` (gate first). ``logits = RMSNorm(h) E^T / logits_scaling`` (tied).

- *attention* layers: q of ``num_attention_heads``, k and v of
  ``num_key_value_heads`` heads, no bias, no rotary or other positions
  (`position_embedding_type: nope`), causal softmax of ``q . k *
  attention_multiplier``, ``W_o``.
- *mamba* layers: ``[z | xBC | dt] = W_in x``; ``xBC =
  silu(conv1d(xBC))``, depthwise, causal, kernel ``mamba_d_conv``, with
  bias; ``x, B, C = split(xBC)``, x as ``mamba_n_heads`` heads of
  ``mamba_d_head``, B and C one group of ``mamba_d_state`` shared by all
  heads; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, a scalar a
  head; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t =
  S_t C_t + D x_t``; ``y = RMSNorm(y * silu(z))`` over the whole inner
  width (gate first, then norm, one group); ``W_out y``. **The recurrence
  is computed token by token under `lax.scan`,** not in the chunked form
  the program's prefill uses (``mamba_chunk_size`` plays no part here).

Departures, each forced by how the system under test stores a checkpoint:
the parameter tree is read under the names the system gives its leaves
(``embed``, ``final_norm_scale``, ``layer<i>/{in_norm_scale,
post_norm_scale, mlp_in, mlp_out}`` and, by the layer's kind, ``{wq, wk, wv,
wo}`` or ``{in_proj, conv_tap_scale, conv_b, A_log, dt_bias, D,
gate_norm_scale, out_proj}``; matrices are stored input-major, the
convolution's weight ``conv_tap_scale`` as (kernel, channels) with its LAST
tap on the current input). The weights come as stored (bfloat16 where
served) and are upcast a layer at a time; rows go through one at a time
(`lax.map`), so the scratch is one row's.

``quant`` is the lower-precision control of the benchmark's correctness
check: when given, every product's operands (the matmuls', and the x, B and
C that enter the state's two products) are rounded through that dtype
before the float32 product. The state itself stays float32.
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


def _attention(p, x, cfg, quant):
    """x (S, E), one row."""
    S = x.shape[0]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // hq
    q = _mm(x, p["wq"], quant).reshape(S, hkv, hq // hkv, d)
    k = _mm(x, p["wk"], quant).reshape(S, hkv, d)
    v = _mm(x, p["wv"], quant).reshape(S, hkv, d)
    scores = jnp.einsum("skgd,tkd->kgst", _q(q, quant), _q(k, quant),
                        precision=_HIGHEST) * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("kgst,tkd->skgd", _q(probs, quant), _q(v, quant),
                     precision=_HIGHEST)
    return _mm(out.reshape(S, hq * d), p["wo"], quant)


def _mamba(p, x, cfg, quant):
    """x (S, E), one row: the recurrence one token at a time."""
    S = x.shape[0]
    n_heads, d_head = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, k = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    inner = n_heads * d_head
    z, xbc, dt = jnp.split(_mm(x, p["in_proj"], quant),
                           [inner, 2 * inner + 2 * n], axis=-1)
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_b"] + sum(
        p["conv_tap_scale"][j] * padded[j:j + S] for j in range(k)))
    xs, b, c = jnp.split(xbc, [inner, inner + n], axis=-1)
    xs = _q(xs, quant).reshape(S, n_heads, d_head)
    b, c = _q(b, quant), _q(c, quant)
    dt = jax.nn.softplus(dt + p["dt_bias"])                   # (S, heads)
    a = -jnp.exp(p["A_log"])

    def token(state, t):
        x_t, dt_t, b_t, c_t = t
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        y = jnp.sum(state * c_t[None, None, :], -1) + p["D"][:, None] * x_t
        return state, y

    _, y = jax.lax.scan(token, jnp.zeros((n_heads, d_head, n), _F32),
                        (xs, dt, b, c))
    y = _rms(y.reshape(S, inner) * jax.nn.silu(z), p["gate_norm_scale"],
             cfg["rms_norm_eps"])
    return _mm(y, p["out_proj"], quant)


def _row_hidden(params, tokens, cfg, quant):
    """(S,) tokens of one row -> (S, E) after the final norm."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = params["embed"][tokens].astype(_F32) * cfg["embedding_multiplier"]
    for i, kind in enumerate(cfg["layer_types"]):
        # upcast a layer at a time: the weights come as stored
        p = jax.tree_util.tree_map(lambda a: a.astype(_F32),
                                   params[f"layer{i}"])
        mixer = _attention if kind == "attention" else _mamba
        h = h + r * mixer(p, _rms(h, p["in_norm_scale"], eps), cfg, quant)
        y = _rms(h, p["post_norm_scale"], eps)
        gate, up = jnp.split(_mm(y, p["mlp_in"], quant), 2, axis=-1)
        h = h + r * _mm(jax.nn.silu(gate) * up, p["mlp_out"], quant)
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
        return _mm(h, embed.T, quant) / cfg["logits_scaling"]

    if positions is None:
        return jax.lax.map(lambda t: row((t, None)), tokens)
    return jax.lax.map(row, (tokens, positions))


def loss(params, batch, cfg, quant=None):
    """Mean next-token cross-entropy over every row and position of
    ``batch["tokens"]`` (B, S). No cell trains this family yet; the
    protocol asks every reference for it."""
    tokens = batch["tokens"]
    logp = jax.nn.log_softmax(logits(params, tokens, cfg, quant)[:, :-1],
                              axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)
