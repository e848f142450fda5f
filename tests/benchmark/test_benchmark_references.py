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


# ---- the serving check holds the weights once ---------------------------


def _served(vocab, lens=(20, 33, 50), n_out=8, total=96):
    toks = np.random.default_rng(0).integers(0, vocab, total).astype(np.int32)
    return [{"prompt": toks[:n], "tokens": toks[n:n + n_out]} for n in lens]


class _Recording:
    """A reference without `positions`, noting the types it is handed."""

    def __init__(self, ref):
        self.ref, self.seen = ref, None

    def logits(self, params, tokens, cfg, quant=None):
        self.seen = {a.dtype for a in jax.tree_util.tree_leaves(params)}
        return self.ref.logits(params, tokens, cfg, quant)


@pytest.mark.parametrize("rows_per_call", [4, 2])
def test_serving_check_hands_the_weights_as_stored_and_reads_the_same(
        rows_per_call):
    """Through the GPT-2 reference, which takes no `positions`: the
    weights arrive in the served type, and the summary is that of the
    check that upcast them first (what `serve_gaps` did before PR 28),
    control included."""
    ref = _Recording(lib.mf.load_reference("gpt2-medium", ROOT))
    b = builders.get(dict(lib.TINY_GPT2))
    params = builders.make_params(b.param_shapes(b.model("O2")), 9,
                                  jnp.bfloat16)
    sample = _served(b.vocab_size)
    quant = check.control_quant(True)
    got = check.serve_gaps(ref, b.ref_cfg, params, sample, 96, 8, quant,
                           rows_per_call)
    assert ref.seen == {jnp.dtype(jnp.bfloat16)}
    before = check.serve_gaps(
        ref, b.ref_cfg, jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params), sample, 96, 8, quant,
        rows_per_call)
    assert ref.seen == {jnp.dtype(jnp.float32)}
    assert got == before
    assert got["n_tokens"] == 24 and got["n_requests"] == 3


@pytest.mark.parametrize("quant", [None, "control"])
def test_serving_check_asks_only_for_the_positions_it_compares(quant):
    """A reference with `positions` gets them, with the weights as stored,
    and the summary is the one that all the logits, gathered here, give."""
    c = lib.SMALL_DECODER
    params = builders.make_params(lib.decoder_shapes(c), 5, jnp.bfloat16)
    sample = _served(c["vocab"])
    quant = check.control_quant(quant)
    ref = lib.StandInReference(c, block=64)
    got = check.serve_gaps(ref, {}, params, sample, 96, 8, quant, 2)
    assert ref.asked and all(shape == (2, 8) for shape in ref.asked)
    assert ref.seen == {jnp.dtype(jnp.bfloat16)}
    want = check.serve_gaps(lib.StandInReference(c, 64).without_positions(),
                            {}, params, sample, 96, 8, quant, 2)
    assert got.keys() == want.keys()
    assert ("control_widest_gap" in got) == (quant is not None)
    for k in got:
        assert got[k] == pytest.approx(want[k], rel=1e-5), k


def _stored_type_logits(params, tokens, cfg, quant=None):
    return params["embed"][tokens] @ params["head"]        # bfloat16 x bfloat16


@pytest.mark.parametrize("how", ["logits_as_stored", "logits_cast_up",
                                 "product_inside_a_loop"])
def test_serving_check_refuses_a_reference_that_multiplies_as_stored(how):
    """The check hands over bfloat16 leaves and trusts nobody to upcast
    them: a reference whose sound path multiplies them as they are is
    refused before it computes a yardstick in the served precision,
    whether its logits come back bfloat16, are cast to float32 afterwards,
    or the product sits inside a mapped row."""
    import types
    c = lib.SMALL_DECODER
    params = builders.make_params(lib.decoder_shapes(c), 5, jnp.bfloat16)
    logits = {
        "logits_as_stored": _stored_type_logits,
        "logits_cast_up": lambda *a, **k: _stored_type_logits(
            *a, **k).astype(jnp.float32),
        "product_inside_a_loop": lambda p, toks, cfg, quant=None: jax.lax.map(
            lambda row: _stored_type_logits(p, row, cfg).astype(jnp.float32),
            toks)}[how]
    with pytest.raises(TypeError, match=r"dot_general\(bfloat16, bfloat16\)"):
        check.serve_gaps(types.SimpleNamespace(logits=logits), {}, params,
                         _served(c["vocab"]), 96, 8, None, 2)
    upcast = types.SimpleNamespace(
        logits=lambda p, toks, cfg, quant=None: p["embed"][toks].astype(
            jnp.float32) @ p["head"].astype(jnp.float32))
    assert check.serve_gaps(upcast, {}, params, _served(c["vocab"]), 96, 8,
                            None, 2)["n_tokens"] == 24
