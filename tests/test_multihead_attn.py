"""Fused MHA module tests — reference analogue:
``apex/contrib/test/multihead_attn/test_{self,encdec}_multihead_attn.py``
(gold = hand-rolled attention; norm_add variants; mask handling)."""

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

from apex1_tpu.contrib import (EncdecMultiheadAttn, SelfMultiheadAttn,
                               SoftmaxCrossEntropyLoss)

S, B, E, H = 24, 2, 32, 4


def _gold_self_attn(params, x, causal=False, mask=None):
    """Hand-rolled reference attention, (S,B,E) layout."""
    qkv = np.asarray(params["in_proj_weight"])
    wo = np.asarray(params["out_proj_weight"])
    x_ = np.asarray(x, np.float32)
    proj = x_ @ qkv
    q, k, v = np.split(proj, 3, axis=-1)
    D = E // H

    def heads(t):
        return t.reshape(S, B, H, D).transpose(1, 2, 0, 3)

    q, k, v = heads(q), heads(k), heads(v)
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    if causal:
        r, c = np.meshgrid(np.arange(S), np.arange(S), indexing="ij")
        s = np.where(c > r, -1e30, s)
    if mask is not None:
        s = s + np.asarray(mask, np.float32)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    ctx = np.einsum("bhqk,bhkd->bhqd", p, v)
    ctx = ctx.transpose(2, 0, 1, 3).reshape(S, B, E)
    return ctx @ wo


@pytest.mark.parametrize("causal", [False, True])
def test_self_attn_matches_gold(rng, causal):
    x = jnp.asarray(rng.normal(size=(S, B, E)), jnp.float32)
    m = SelfMultiheadAttn(embed_dim=E, num_heads=H)
    params = m.init(jax.random.key(0), x)["params"]
    out = m.apply({"params": params}, x, causal=causal, is_training=False)
    gold = _gold_self_attn(params, x, causal=causal)
    np.testing.assert_allclose(out, gold, rtol=1e-4, atol=1e-4)


def test_self_attn_additive_mask(rng):
    x = jnp.asarray(rng.normal(size=(S, B, E)), jnp.float32)
    mask = jnp.where(
        jnp.asarray(rng.random((B, 1, 1, S))) < 0.3, -1e30, 0.0)
    m = SelfMultiheadAttn(embed_dim=E, num_heads=H)
    params = m.init(jax.random.key(0), x)["params"]
    out = m.apply({"params": params}, x, attn_mask=mask, is_training=False)
    gold = _gold_self_attn(params, x, mask=mask)
    np.testing.assert_allclose(out, gold, rtol=1e-4, atol=1e-4)


def test_norm_add_residual(rng):
    x = jnp.asarray(rng.normal(size=(S, B, E)), jnp.float32)
    m = SelfMultiheadAttn(embed_dim=E, num_heads=H, include_norm_add=True)
    params = m.init(jax.random.key(0), x)["params"]
    out = m.apply({"params": params}, x, is_training=False)
    assert "lyr_nrm_gamma_weights" in params
    # zeroing the out-projection must leave exactly the residual
    params2 = dict(params)
    params2["out_proj_weight"] = jnp.zeros_like(params["out_proj_weight"])
    out2 = m.apply({"params": params2}, x, is_training=False)
    np.testing.assert_allclose(out2, x, rtol=1e-6, atol=1e-6)
    assert not np.allclose(out, x)


def test_separate_qkv_params(rng):
    x = jnp.asarray(rng.normal(size=(S, B, E)), jnp.float32)
    m = SelfMultiheadAttn(embed_dim=E, num_heads=H,
                          separate_qkv_params=True)
    params = m.init(jax.random.key(0), x)["params"]
    assert set(params) >= {"q_weight", "k_weight", "v_weight"}
    out = m.apply({"params": params}, x, is_training=False)
    assert out.shape == (S, B, E)


def test_dropout_path(rng):
    x = jnp.asarray(rng.normal(size=(S, B, E)), jnp.float32)
    m = SelfMultiheadAttn(embed_dim=E, num_heads=H, dropout=0.5)
    params = m.init({"params": jax.random.key(0),
                     "dropout": jax.random.key(1)}, x)["params"]
    o1 = m.apply({"params": params}, x, is_training=True,
                 rngs={"dropout": jax.random.key(2)})
    o2 = m.apply({"params": params}, x, is_training=True,
                 rngs={"dropout": jax.random.key(3)})
    o_eval = m.apply({"params": params}, x, is_training=False)
    assert not np.allclose(o1, o2)
    gold = _gold_self_attn(params, x)
    np.testing.assert_allclose(o_eval, gold, rtol=1e-4, atol=1e-4)


def test_encdec_attn(rng):
    Sk = 16
    q = jnp.asarray(rng.normal(size=(S, B, E)), jnp.float32)
    kv = jnp.asarray(rng.normal(size=(Sk, B, E)), jnp.float32)
    m = EncdecMultiheadAttn(embed_dim=E, num_heads=H)
    params = m.init(jax.random.key(0), q, kv)["params"]
    out = m.apply({"params": params}, q, kv, is_training=False)
    assert out.shape == (S, B, E)
    # gold
    wq = np.asarray(params["q_weight"])
    wkv = np.asarray(params["kv_weight"])
    wo = np.asarray(params["out_proj_weight"])
    D = E // H
    qh = (np.asarray(q) @ wq).reshape(S, B, H, D).transpose(1, 2, 0, 3)
    kvp = np.asarray(kv) @ wkv
    kh, vh = np.split(kvp, 2, axis=-1)
    kh = kh.reshape(Sk, B, H, D).transpose(1, 2, 0, 3)
    vh = vh.reshape(Sk, B, H, D).transpose(1, 2, 0, 3)
    s = np.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(D)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ctx = np.einsum("bhqk,bhkd->bhqd", p, vh)
    gold = ctx.transpose(2, 0, 1, 3).reshape(S, B, E) @ wo
    np.testing.assert_allclose(out, gold, rtol=1e-4, atol=1e-4)


def test_grads_flow(rng):
    x = jnp.asarray(rng.normal(size=(S, B, E)), jnp.float32)
    m = SelfMultiheadAttn(embed_dim=E, num_heads=H, include_norm_add=True)
    params = m.init(jax.random.key(0), x)["params"]

    def loss(p):
        return jnp.sum(jnp.square(
            m.apply({"params": p}, x, causal=True, is_training=False)))

    g = jax.grad(loss)(params)
    for leaf in jax.tree.leaves(g):
        assert np.all(np.isfinite(leaf))
        assert float(jnp.sum(jnp.abs(leaf))) > 0


def test_contrib_xentropy_api(rng):
    logits = jnp.asarray(rng.normal(size=(6, 50)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 50, (6,)), jnp.int32)
    loss = SoftmaxCrossEntropyLoss.apply(logits, labels, 0.1, None, True)
    assert loss.shape == (6,)
    crit = SoftmaxCrossEntropyLoss(smoothing=0.1)
    np.testing.assert_allclose(crit(logits, labels), loss)


# ---------------------------------------------------------------------------
# no-materialization probe: SelfMultiheadAttn(dropout>0) must stay on the
# flash kernel — NO O(S²) probability tensor in the traced program
# (the pre-PR-5 module fell back to the materialized composite whenever
# attention-probability dropout was active, degrading the fused
# capability on exactly the BERT-pretrain headline workload)
# ---------------------------------------------------------------------------

def _nonkernel_avals(jaxpr, out):
    """Every intermediate aval OUTSIDE pallas kernel bodies: kernel-
    internal tiles are VMEM-resident blocks (bounded by block_q/block_k),
    not HBM tensors — the probe asserts nothing S×S exists in HBM."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            out.append(var.aval)
        if eqn.primitive.name == "pallas_call":
            continue

        def visit(val):
            if isinstance(val, jax.extend.core.ClosedJaxpr):
                _nonkernel_avals(val.jaxpr, out)
            elif isinstance(val, jax.extend.core.Jaxpr):
                _nonkernel_avals(val, out)
            elif isinstance(val, (tuple, list)):
                for item in val:
                    visit(item)

        for val in eqn.params.values():
            visit(val)


def _probe_s2(fn, *args, seq):
    jaxpr = jax.make_jaxpr(fn)(*args)
    avals = []
    _nonkernel_avals(jaxpr.jaxpr, avals)
    return [a for a in avals
            if getattr(a, "ndim", 0) >= 2 and a.shape[-1] == seq
            and a.shape[-2] == seq]


def test_dropout_no_s2_materialization(rng):
    from apex1_tpu.ops._common import force_impl

    # S prime-ish and distinct from B/E/H so an S×S aval is unambiguous
    Sp = 72
    x = jnp.asarray(rng.normal(size=(Sp, B, E)), jnp.float32)
    m = SelfMultiheadAttn(embed_dim=E, num_heads=H, dropout=0.1)
    params = m.init({"params": jax.random.key(0),
                     "dropout": jax.random.key(1)}, x)["params"]

    def fwd(params, x):
        with force_impl("pallas"):
            return m.apply({"params": params}, x, is_training=True,
                           rngs={"dropout": jax.random.key(2)})

    assert _probe_s2(fwd, params, x, seq=Sp) == [], \
        "dropout>0 forward materialized an S×S tensor"

    def loss(params, x):
        return jnp.sum(fwd(params, x) ** 2)

    assert _probe_s2(jax.grad(loss), params, x, seq=Sp) == [], \
        "dropout>0 backward materialized an S×S tensor"

    # negative control — the probe must be falsifiable: the XLA
    # composite path DOES materialize S×S probabilities
    def fwd_xla(params, x):
        with force_impl("xla"):
            return m.apply({"params": params}, x, is_training=True,
                           rngs={"dropout": jax.random.key(2)})

    assert _probe_s2(fwd_xla, params, x, seq=Sp), \
        "probe failed to flag the materialized composite"


def test_dropout_stays_on_flash_with_mask_and_norm_add(rng):
    """The full SelfMHA feature set (additive mask + norm_add epilogue)
    composes with in-kernel dropout — still no S×S materialization."""
    from apex1_tpu.ops._common import force_impl

    Sp = 72
    x = jnp.asarray(rng.normal(size=(Sp, B, E)), jnp.float32)
    mask = jnp.asarray(rng.normal(size=(B, 1, 1, Sp)) < 0, jnp.float32)
    mask = mask * -1e9
    m = SelfMultiheadAttn(embed_dim=E, num_heads=H, dropout=0.1,
                          include_norm_add=True)
    params = m.init({"params": jax.random.key(0),
                     "dropout": jax.random.key(1)}, x)["params"]

    def fwd(params, x):
        with force_impl("pallas"):
            return m.apply({"params": params}, x, attn_mask=mask,
                           is_training=True,
                           rngs={"dropout": jax.random.key(2)})

    # the broadcast additive mask rides the kernel bias operand at
    # (B, 1, Sp, Sp)... which has head dim 1, not S — only a true
    # (.., Sp, Sp) PROBABILITY tensor (B, H, Sp, Sp) would trip probes
    # keyed on the last two dims; accept the (1-head) bias operand
    hits = _probe_s2(fwd, params, x, seq=Sp)
    assert all(a.ndim >= 3 and a.shape[-3] == 1 for a in hits), hits
