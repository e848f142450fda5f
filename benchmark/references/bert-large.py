"""Plain reference for BERT pre-training (google-bert/bert-large-uncased and
any other size of the family): the published encoder with its masked-LM
and next-sentence heads and their loss, in straightforward float32
`jax.numpy`, matmuls at ``Precision.HIGHEST``. No kernels, nothing imported
from the program under test.

Published description (Devlin et al. 2018; google-research/bert
`modeling.py`; the `config.json` named in `benchmark/configs/
bert-large.json`): word + position + token-type embeddings, layer norm;
``num_hidden_layers`` post-LN blocks (x = LN(x + attention(x)), x = LN(x +
ffn(x))) of bidirectional multi-head attention and a GELU feed-forward in
the tanh form `modeling.py` writes out; a pooler (tanh dense on the first
token) feeding the 2-way next-sentence head; a masked-LM head of dense,
GELU, layer norm, then the word embedding transposed plus a bias. Loss =
mean masked-LM cross-entropy over the masked positions + mean
next-sentence cross-entropy.

Departures: the parameter tree is read under the names the system under
test gives its leaves (``bert/{word,position,token_type}_embeddings``,
``bert/layer<i>/{qkv,attn_out,ffn_in,ffn_out}/{kernel,bias}``, the
``*_ln_{scale,bias}`` pairs, ``bert/pooler``, ``mlm_transform``,
``mlm_ln_*``, ``mlm_bias``, ``nsp``); layer-norm epsilon is 1e-5, the
system's, where the published file says 1e-12 (both far below the
variance of any row here); no padding (every row is full length), and
dropout 0 (the configuration file lists it).

``quant``: as in the GPT-2 reference, the lower-precision control.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.references.lowprec import q as _q

LN_EPS = 1e-5
IGNORE = -1


def _mm(a, b, quant):
    return jnp.matmul(_q(a, quant), _q(b, quant),
                      precision=jax.lax.Precision.HIGHEST)


def _dense(p, x, quant):
    return _mm(x, p["kernel"], quant) + p["bias"]


def _ln(x, scale, bias):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _layer(p, x, n_head, quant):
    B, S, E = x.shape
    D = E // n_head
    q, k, v = jnp.split(_dense(p["qkv"], x, quant), 3, axis=-1)

    def heads(t):
        return t.reshape(B, S, n_head, D).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    scores = jnp.einsum("bhqd,bhkd->bhqk", _q(q, quant), _q(k, quant),
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(D)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqk,bhkd->bhqd", _q(probs, quant), _q(v, quant),
                      precision=jax.lax.Precision.HIGHEST)
    attn = attn.transpose(0, 2, 1, 3).reshape(B, S, E)
    x = _ln(x + _dense(p["attn_out"], attn, quant),
            p["attn_ln_scale"], p["attn_ln_bias"])
    h = _dense(p["ffn_out"], _gelu(_dense(p["ffn_in"], x, quant)), quant)
    return _ln(x + h, p["ffn_ln_scale"], p["ffn_ln_bias"])


def _heads(params, tokens, cfg, quant):
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    enc = params["bert"]
    S = tokens.shape[1]
    x = (enc["word_embeddings"][tokens]
         + enc["position_embeddings"][:S][None]
         + enc["token_type_embeddings"][jnp.zeros_like(tokens)])
    x = _ln(x, enc["emb_ln_scale"], enc["emb_ln_bias"])
    # one scanned layer over the stacked weights, recomputed in backward
    # (see the GPT-2 reference for why)
    layers = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[enc[f"layer{i}"] for i in range(cfg["num_hidden_layers"])])
    layer = jax.checkpoint(lambda x, p: (
        _layer(p, x, cfg["num_attention_heads"], quant), None))
    x, _ = jax.lax.scan(layer, x, layers)
    pooled = jnp.tanh(_dense(enc["pooler"], x[:, 0], quant))
    nsp = _dense(params["nsp"], pooled, quant)
    h = _gelu(_dense(params["mlm_transform"], x, quant))
    h = _ln(h, params["mlm_ln_scale"], params["mlm_ln_bias"])
    mlm = _mm(h, enc["word_embeddings"].T, quant) + params["mlm_bias"]
    return mlm, nsp


def loss(params, batch, cfg, quant=None):
    """``batch``: tokens (B, S), mlm_labels (B, S; -1 where unmasked),
    nsp_labels (B,)."""
    mlm, nsp = _heads(params, batch["tokens"], cfg, quant)
    labels = batch["mlm_labels"]
    on = labels != IGNORE
    logp = jax.nn.log_softmax(mlm, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mlm_loss = jnp.sum(jnp.where(on, nll, 0.0)) / jnp.maximum(
        jnp.sum(on), 1)
    nsp_logp = jax.nn.log_softmax(nsp, axis=-1)
    nsp_loss = -jnp.mean(jnp.take_along_axis(
        nsp_logp, batch["nsp_labels"][:, None], axis=-1))
    return mlm_loss + nsp_loss
