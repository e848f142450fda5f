"""The yardstick is itself tested: the plain float32 references against
`apex1_tpu.models` at a tiny size on the CPU (float32 policy, so the two
differ by rounding order only), and the reference optimizers against the
program's fused ones."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_testlib as lib
from benchmark.harness import builders, check

ROOT = lib.ROOT


def _params(b, seed=3):
    model = b.model("O0")
    return model, builders.make_params(b.param_shapes(model), seed,
                                       jnp.float32)


def test_gpt2_reference_matches_the_model_logits_and_loss():
    ref = lib.mf.load_reference("gpt2-medium", ROOT)
    b = builders.get(dict(lib.TINY_GPT2))
    model, params = _params(b)
    batch = b.make_batch(jax.random.key(1), 3, 48, {})
    want = model.apply({"params": params}, batch["tokens"])
    got = ref.logits(params, batch["tokens"], b.ref_cfg)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want)[..., :b.vocab_size],
        rtol=2e-4, atol=2e-5)
    loss_prog = b.loss_fn(model)(params, batch)
    loss_ref = ref.loss(params, batch, b.ref_cfg)
    assert abs(float(loss_prog) - float(loss_ref)) < 2e-5
    g_prog = jax.grad(b.loss_fn(model))(params, batch)
    g_ref = jax.grad(lambda p: ref.loss(p, batch, b.ref_cfg))(params)
    gap = check.worst_leaf_gap(np.asarray(check.leaf_norms(g_prog)),
                               np.asarray(check.leaf_norms(g_ref)))
    assert gap < 1e-3, gap


def test_bert_reference_matches_the_model_loss_and_gradient():
    ref = lib.mf.load_reference("bert-large", ROOT)
    b = builders.get(dict(lib.TINY_BERT))
    model, params = _params(b)
    batch = b.make_batch(jax.random.key(2), 4, 32, {"mask_share": 0.15})
    assert int((batch["mlm_labels"] >= 0).sum()) > 0
    loss_prog = b.loss_fn(model)(params, batch)
    loss_ref = ref.loss(params, batch, b.ref_cfg)
    assert abs(float(loss_prog) - float(loss_ref)) < 2e-5
    g_prog = jax.grad(b.loss_fn(model))(params, batch)
    g_ref = jax.grad(lambda p: ref.loss(p, batch, b.ref_cfg))(params)
    gap = check.worst_leaf_gap(np.asarray(check.leaf_norms(g_prog)),
                               np.asarray(check.leaf_norms(g_ref)))
    assert gap < 1e-3, gap


@pytest.mark.parametrize("spec", [
    {"name": "adam", "lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
     "weight_decay": 0.01},
    {"name": "lamb", "lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-6,
     "weight_decay": 0.01, "max_grad_norm": 1.0}])
def test_reference_optimizer_matches_the_fused_one(spec):
    import optax
    opt = lib.mf.load_module(
        f"{ROOT}/benchmark/references/optimizers.py", "ref_opt_test")
    rng = np.random.default_rng(0)
    params = {"a": jnp.asarray(rng.normal(size=(8, 16)), jnp.float32),
              "b": {"c": jnp.asarray(rng.normal(size=(16,)), jnp.float32)}}
    tx = builders.optimizer(spec)
    st_prog, st_ref = tx.init(params), opt.init(params)
    p_prog = p_ref = params
    kw = {k: v for k, v in spec.items() if k != "name"}
    for i in range(3):
        grads = jax.tree_util.tree_map(
            lambda p: jnp.asarray(rng.normal(size=p.shape) * 3, jnp.float32),
            params)
        upd, st_prog = tx.update(grads, st_prog, p_prog)
        p_prog = optax.apply_updates(p_prog, upd)
        p_ref, st_ref = opt.OPTIMIZERS[spec["name"]](p_ref, grads, st_ref,
                                                     **kw)
        if i == 0:
            g1 = opt.first_gradient(st_ref, spec["b1"])
            got = jax.tree_util.tree_map(lambda m: m / (1 - spec["b1"]),
                                         st_prog.exp_avg)
            for x, y in zip(jax.tree_util.tree_leaves(g1),
                            jax.tree_util.tree_leaves(got)):
                np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-7)
    for x, y in zip(jax.tree_util.tree_leaves(p_prog),
                    jax.tree_util.tree_leaves(p_ref)):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)


def test_worst_leaf_gap_is_a_gap_of_norms_against_leaf_or_median():
    ref = np.array([1.0, 2.0, 1e-9, 4.0])
    prog = np.array([1.1, 2.0, 2e-9, 4.0])
    # leaf 0: 0.1 / max(1.0, median 1.5) ; the all-but-zero leaf 2 is
    # measured against the median leaf, not against itself
    assert check.worst_leaf_gap(prog, ref) == pytest.approx(0.1 / 1.5)


def test_sample_always_holds_the_longest_finished_request():
    fin = [{"prompt": np.zeros(10 + i), "tokens": np.zeros(5)}
           for i in range(20)]
    fin[7]["tokens"] = np.zeros(50)
    for seed in range(5):
        s = check.pick_sample(fin, 4, seed)
        assert len(s) == 4 and any(x is fin[7] for x in s)
    a = check.pick_sample(fin, 4, 1)
    b = check.pick_sample(fin, 4, 1)
    assert [id(x) for x in a] == [id(x) for x in b]
    assert check.pick_sample([], 4, 0) == []
