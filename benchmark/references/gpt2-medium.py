"""Plain reference for GPT-2 (openai-community/gpt2-medium and any other
size of the family): the published forward pass and next-token loss in
straightforward float32 `jax.numpy`, matmuls under
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
batching tricks, and nothing imported from the program under test.

Published description (Radford et al. 2019; the `config.json` named in
`benchmark/configs/gpt2-medium.json`): learned token and position
embeddings; ``n_layer`` pre-LN blocks of causal multi-head attention and a
4x MLP with the tanh GELU (`gelu_new`); a final layer norm; the output head
tied to the token embedding; layer-norm epsilon 1e-5.

Departures, each forced by how the system under test stores a checkpoint:
the parameter tree is read under the names the system gives its leaves
(``wte``, ``wpe``, ``h<i>/{ln1,ln2}_{scale,bias}``, ``h<i>/{qkv,proj,fc_in,
fc_out}/{kernel,bias}``, ``lnf_{scale,bias}``), and ``wte`` may carry rows
beyond ``vocab_size`` (lane padding): they take no part in the loss or in
an argmax here. Dropout is 0 (the configuration file lists it).

``quant`` is the lower-precision control of the benchmark's correctness
check: when given, every matmul operand is rounded through that dtype
(e.g. ``float8_e4m3fn``, the step below the bfloat16 the configuration
states) before the float32 product.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.references.lowprec import q as _q

LN_EPS = 1e-5
#: the loss is a mean of row means: equal blocks of rows average exactly
BLOCKABLE = True
#: a leaf under a `qkv` node is the published W_q | W_k | W_v (or their
#: biases) side by side on its last axis: the check takes each one's norm
#: apart, because the key BIAS has no gradient at all (softmax cancels a
#: shift of every key) and Adam makes full-size steps of the rounding
#: noise there, which would swamp the query and value parts of the leaf
LEAF_PARTS = {"qkv": 3}


def _mm(a, b, quant):
    return jnp.matmul(_q(a, quant), _q(b, quant),
                      precision=jax.lax.Precision.HIGHEST)


def _ln(x, scale, bias):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(p, x, n_head, quant):
    B, S, H = x.shape
    D = H // n_head
    y = _ln(x, p["ln1_scale"], p["ln1_bias"])
    qkv = _mm(y, p["qkv"]["kernel"], quant) + p["qkv"]["bias"]
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(B, S, n_head, D).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    scores = jnp.einsum("bhqd,bhkd->bhqk", _q(q, quant), _q(k, quant),
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqk,bhkd->bhqd", _q(probs, quant), _q(v, quant),
                      precision=jax.lax.Precision.HIGHEST)
    attn = attn.transpose(0, 2, 1, 3).reshape(B, S, H)
    x = x + _mm(attn, p["proj"]["kernel"], quant) + p["proj"]["bias"]
    y = _ln(x, p["ln2_scale"], p["ln2_bias"])
    y = _gelu_new(_mm(y, p["fc_in"]["kernel"], quant) + p["fc_in"]["bias"])
    return x + _mm(y, p["fc_out"]["kernel"], quant) + p["fc_out"]["bias"]


def hidden(params, tokens, cfg, quant=None):
    """(B, S) int tokens -> (B, S, H) final-layer-norm output, float32."""
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    S = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][:S][None]
    # the layers are alike: one scanned block over their stacked weights
    # (a compiled program a twentieth the size of the unrolled loop, which
    # matters to a compile cache with a size limit), recomputed in the
    # backward pass so that a block of rows fits beside the weights
    layers = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[params[f"h{i}"] for i in range(cfg["n_layer"])])
    block = jax.checkpoint(
        lambda x, p: (_block(p, x, cfg["n_head"], quant), None))
    x, _ = jax.lax.scan(block, x, layers)
    return _ln(x, params["lnf_scale"], params["lnf_bias"])


def logits(params, tokens, cfg, quant=None):
    """(B, S, vocab_size) float32 logits of the tied head."""
    h = hidden(params, tokens, cfg, quant)
    wte = params["wte"].astype(jnp.float32)[:cfg["vocab_size"]]
    return _mm(h, wte.T, quant)


def loss(params, batch, cfg, quant=None):
    """Mean next-token cross-entropy over every row and position of
    ``batch["tokens"]`` (B, S)."""
    tokens = batch["tokens"]
    lg = logits(params, tokens, cfg, quant)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)
