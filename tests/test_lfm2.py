"""`models.lfm2` against the benchmark's plain reference
(`benchmark/references/lfm2_moe.py`: float32, no cache, no sort, every held
expert over every token) at a small size with the published widths' ratios
kept, through every path the model has: the full forward, prefill in
right-padded chunks then decoding through the cache, and `serving.Engine`
with requests joining and leaving; the whole model and one chip's share of
its experts. And the counts the engine's step hands out with its tokens
against a replayed routing, a lane's tokens not depending on its
neighbours, and the decode executable compiled for a described v5e at the
published widths."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex1_tpu.models.generate import generate, lfm2_moe_decoder
from apex1_tpu.models.lfm2 import Lfm2Moe, Lfm2MoeConfig
from apex1_tpu.ops.ssm import causal_conv
from apex1_tpu.serving.engine import (Engine, EngineConfig,
                                      recurrent_lane_bytes)
from apex1_tpu.transformer import moe as moe_lib
from benchmark.harness import manifest as mf

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = Lfm2MoeConfig.tiny()
SHARE = Lfm2MoeConfig.tiny(experts_held=(2, 4))
_REF_KEYS = ("vocab_size", "hidden_size", "layer_types",
             "num_attention_heads", "num_key_value_heads",
             "num_dense_layers", "num_experts", "num_experts_per_tok",
             "norm_topk_prob", "use_expert_bias", "routed_scaling_factor",
             "conv_L_cache", "norm_eps", "rope_theta")
#: float32 model against float32 reference: they differ by the order of
#: their sums (5e-6 to 9e-6 as read here, of logits whose spread is 1.1).
#: A router that ignores its bias moves them by 0.5, one that weighs by the
#: biased score by 0.1, a padded token in a convolution's state by 0.3
TOL = 5e-5


def ref_cfg(cfg):
    return dict({k: getattr(cfg, k) for k in _REF_KEYS},
                held=list(cfg.experts_held) if cfg.experts_held else None)


def make_params(model, seed=7):
    """Seeded: 0.1 * normal (the router's bias too), every `*scale` leaf
    (norm weights, taps) 1 + 0.1 * normal."""
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    out = []
    for (path, s), k in zip(leaves, keys):
        noise = 0.1 * jax.random.normal(k, s.shape, jnp.float32)
        name = str(getattr(path[-1], "key", path[-1]))
        out.append(1.0 + noise if name.endswith("scale") else noise)
    return jax.tree_util.tree_unflatten(tree, out)


def share_of(params, cfg):
    """The whole model's tree cut to the experts ``cfg`` holds."""
    lo, hi = cfg.held.start, cfg.held.stop
    return {name: ({k: (v[lo:hi] if k.startswith("experts_w") else v)
                    for k, v in layer.items()}
                   if isinstance(layer, dict) else layer)
            for name, layer in params.items()}


@pytest.fixture(scope="module")
def model():
    return Lfm2Moe(CFG)


@pytest.fixture(scope="module")
def params(model):
    return make_params(model)


@pytest.fixture(scope="module")
def reference():
    return mf.load_reference("lfm2_moe", ROOT)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(1), (2, 45), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def want(reference, params, tokens):
    return np.asarray(reference.logits(params, tokens, ref_cfg(CFG)))


def test_parameter_tree_and_cache_are_driven_by_the_two_lists(model, params):
    assert CFG.layer_types == ("conv", "conv", "full_attention") * 2
    assert CFG.ffn_kinds == ("dense",) + ("sparse",) * 5
    mixer = {"conv": {"in_proj", "conv_tap_scale", "out_proj"},
             "full_attention": {"wq", "wk", "wv", "wo", "q_norm_scale",
                                "k_norm_scale"}}
    ffn = {"dense": {"w1", "w3", "w2"},
           "sparse": {"router", "expert_bias", "experts_w1",
                      "experts_w3", "experts_w2"}}
    for i, (kind, f) in enumerate(zip(CFG.layer_types, CFG.ffn_kinds)):
        assert set(params[f"layer{i}"]) == {
            "operator_norm_scale", "ffn_norm_scale"} | mixer[kind] | ffn[f]
    assert params["layer0"]["in_proj"].shape == (128, 3 * 128)
    assert params["layer0"]["conv_tap_scale"].shape == (3, 128)
    assert params["layer2"]["q_norm_scale"].shape == (16,)
    assert params["layer1"]["router"].shape == (128, 8)
    assert params["layer1"]["experts_w1"].shape == (8, 128, 128)
    # the share: the router keeps its width, the leaves hold 4 experts
    shapes = jax.eval_shape(Lfm2Moe(SHARE).init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    assert shapes["layer1"]["router"].shape == (128, 8)
    assert shapes["layer1"]["experts_w2"].shape == (4, 128, 128)
    assert SHARE.moe_expert_slots == 4 * 5 and CFG.moe_expert_slots == 8 * 5
    _, make_cache = lfm2_moe_decoder(model)
    cache = make_cache(3, 40, jnp.int8)
    assert set(cache["layer2"]) == {"k", "v"}
    assert cache["layer2"]["k"].shape == (3, 40, 2 * 16)
    assert cache["layer2"]["k"].dtype == jnp.int8
    assert set(cache["layer0"]) == {"conv"}
    assert cache["layer0"]["conv"].shape == (3, 3, 128)
    assert recurrent_lane_bytes(make_cache) == 4 * 3 * 128 * 4
    with pytest.raises(ValueError, match="experts_held"):
        Lfm2MoeConfig.tiny(experts_held=(6, 4))
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeConfig.tiny(layer_types=("conv",) * 5)


@pytest.mark.parametrize("cfg", [CFG, SHARE], ids=["whole", "share"])
def test_full_forward_is_the_reference(cfg, params, tokens, reference):
    """The whole model, and one chip's share of its experts: the reference
    is given the same share and leaves the rest of the mixture out too."""
    p = share_of(params, cfg)
    want = np.asarray(reference.logits(p, tokens, ref_cfg(cfg)))
    got = Lfm2Moe(cfg).apply({"params": p}, tokens)
    assert 0.3 < want.std() < 3.0
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)


def test_a_share_is_not_the_whole(params, tokens, want):
    got = Lfm2Moe(SHARE).apply({"params": share_of(params, SHARE)}, tokens)
    assert np.abs(np.asarray(got) - want).max() > 1000 * TOL


def _chunked_prefill(apply_fn, params, cache, tokens, n, C, **fault):
    """Rows of ``tokens`` (their first ``n`` real) through right-padded
    chunks of ``C``, as `Engine.prefill` feeds them."""
    out, counts = [], np.zeros(2, np.int64)
    for c in range(0, -(-n // C) * C, C):
        seg = np.zeros((tokens.shape[0], C), np.int32)
        real = min(n - c, C)
        seg[:, :real] = tokens[:, c:c + real]
        lg, cache, cnt = apply_fn(params, seg, cache, c, chunk_decode=True,
                                  n_real=fault.get("n_real", real),
                                  moe_counts=True)
        out.append(lg[:, :real])
        counts += np.asarray(cnt)
    return jnp.concatenate(out, axis=1), cache, counts


def test_chunked_prefill_then_cached_decode_is_the_reference(
        model, params, tokens, want):
    """A prompt of 27 through chunks of 16 (the last one padded: `n_real`
    short of the chunk, a convolution's inputs carried across the
    boundary), then one token a step with a per-row index, as the
    engine's two executables run the model. Padded rows are not routed:
    the pairs counted are the real tokens'."""
    apply_fn, make_cache = lfm2_moe_decoder(model)
    n, C = 27, 16
    lg, cache, counts = _chunked_prefill(apply_fn, params,
                                         make_cache(2, 64),
                                         np.asarray(tokens), n, C)
    np.testing.assert_allclose(lg, want[:, :n], rtol=0, atol=TOL)
    assert counts[0] == 2 * n * CFG.num_experts_per_tok * 5
    idx = jnp.full((2,), n, jnp.int32)
    for t in range(n, tokens.shape[1]):
        lg, cache = apply_fn(params, tokens[:, t:t + 1], cache, idx,
                             chunk_decode=True)
        np.testing.assert_allclose(lg[:, 0], want[:, t], rtol=0, atol=TOL)
        idx = idx + 1


def test_faults_of_state_and_router_fail_the_tolerance(model, params, tokens,
                                                       want, monkeypatch):
    """What the tolerance is for: padding that enters a convolution's
    state, a state dropped between two chunks, a router that ignores its
    bias and one that weighs by the biased score each move the next logits
    far past it."""
    apply_fn, make_cache = lfm2_moe_decoder(model)
    toks = np.asarray(tokens)
    n, C = 27, 16

    def next_logits(cache):
        return apply_fn(params, tokens[:, n:n + 1], cache,
                        jnp.full((2,), n, jnp.int32),
                        chunk_decode=True)[0][:, 0]

    def prefilled(**fault):
        return _chunked_prefill(apply_fn, params, make_cache(2, 64), toks,
                                n, C, **fault)[1]

    np.testing.assert_allclose(next_logits(prefilled()), want[:, n],
                               atol=TOL)
    assert np.abs(next_logits(prefilled(n_real=None))
                  - want[:, n]).max() > 100 * TOL
    _, first, _ = _chunked_prefill(apply_fn, params, make_cache(2, 64), toks,
                                   C, C)
    dropped = {k: ({"conv": jnp.zeros_like(v["conv"])} if "conv" in v else v)
               for k, v in first.items()}
    seg = np.zeros((2, C), np.int32)
    seg[:, :n - C] = toks[:, C:n]
    _, dropped = apply_fn(params, seg, dropped, C, chunk_decode=True,
                          n_real=n - C)
    assert np.abs(next_logits(dropped) - want[:, n]).max() > 100 * TOL

    real = moe_lib.dropless_route

    def ignores_bias(x2, wg, bias, cfg):
        return real(x2, wg, None, moe_lib.dataclasses.replace(
            cfg, select_bias=False))

    def weighs_biased(x2, wg, bias, cfg):
        experts, _ = real(x2, wg, bias, cfg)
        s = jax.nn.sigmoid(x2.astype(jnp.float32) @ wg) + bias
        w = jnp.take_along_axis(s, experts, -1)
        return experts, w / w.sum(-1, keepdims=True)

    from apex1_tpu.models import lfm2
    for broken in (ignores_bias, weighs_biased):
        monkeypatch.setattr(lfm2, "dropless_route", broken)
        got = Lfm2Moe(CFG).apply({"params": params}, tokens)
        assert np.abs(np.asarray(got) - want).max() > 100 * TOL, broken
    monkeypatch.undo()


def test_conv_step_by_step_is_the_full_causal_product():
    """The convolution alone: a run of 21 inputs at once from an empty
    state; the same in chunks of 8 with the last padded (`n_real` 5), the
    state carried across both boundaries; and one input a step. All three
    are ``c_t = sum_j w[j] z[t - 2 + j]`` with zeros before the start,
    and the state is always the last three real inputs."""
    ks = jax.random.split(jax.random.key(5), 2)
    z = jax.random.normal(ks[0], (2, 21, 32))
    w = 1.0 + 0.1 * jax.random.normal(ks[1], (3, 32))
    zp = jnp.pad(z, ((0, 0), (2, 0), (0, 0)))
    want = sum(w[j] * zp[:, j:j + 21] for j in range(3))
    empty = jnp.zeros((2, 3, 32))
    got, state = causal_conv(z, w, None, empty)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_array_equal(state, z[:, -3:])
    outs, st = [], empty
    for c in range(0, 24, 8):
        seg = jnp.zeros((2, 8, 32)).at[:, :min(8, 21 - c)].set(
            z[:, c:c + 8])
        y, st = causal_conv(seg, w, None, st, min(8, 21 - c))
        outs.append(y[:, :min(8, 21 - c)])
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=1e-6)
    np.testing.assert_array_equal(st, z[:, -3:])
    outs, st = [], empty
    for t in range(21):
        y, st = causal_conv(z[:, t:t + 1], w, None, st)
        outs.append(y)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=1e-6)
    # a state of K - 1 rows (granite's) is the same filter
    got2, st2 = causal_conv(z, w, None, empty[:, :2])
    np.testing.assert_allclose(got2, want, atol=1e-6)
    np.testing.assert_array_equal(st2, z[:, -2:])


def test_an_idle_row_keeps_its_entries_and_is_not_routed(model, params,
                                                         tokens):
    apply_fn, make_cache = lfm2_moe_decoder(model)
    _, cache, _ = _chunked_prefill(apply_fn, params, make_cache(2, 64),
                                   np.asarray(tokens), 20, 16)
    _, after, counts = apply_fn(params, tokens[:, 20:21], cache,
                                jnp.asarray([20, -1], jnp.int32),
                                chunk_decode=True, moe_counts=True)
    for layer in ("layer0", "layer4"):
        np.testing.assert_array_equal(after[layer]["conv"][1],
                                      cache[layer]["conv"][1])
        assert not np.array_equal(after[layer]["conv"][0],
                                  cache[layer]["conv"][0])
    assert int(counts[0]) == CFG.num_experts_per_tok * 5    # one row's
    with pytest.raises(ValueError, match="one token a row"):
        apply_fn(params, jnp.zeros((2, 3), jnp.int32), make_cache(2, 32),
                 jnp.zeros((2,), jnp.int32), chunk_decode=True)


# ---- serving.Engine ------------------------------------------------------

ENGINE = dict(max_slots=3, max_len=96, prefill_chunk=16, eos_id=511,
              vocab_size=CFG.vocab_size, prefix_cache=False)


def _solo(model, params, prompt, n_out):
    apply_fn, make_cache = lfm2_moe_decoder(model)
    out = np.asarray(generate(apply_fn, params, prompt[None],
                              max_new_tokens=n_out, cache=make_cache(1, 96),
                              eos_id=511, vocab_size=CFG.vocab_size)[0])
    stop = np.flatnonzero(out == 511)
    return out[:stop[0] + 1] if stop.size else out


def _replayed_counts(model, params, prompt, produced):
    """What the decode steps of one request route: the request's tokens
    fed again one a step, alone, and the model's own counts summed. A
    step is launched for every token but the first (prefill's) and takes
    the token before it."""
    apply_fn, make_cache = lfm2_moe_decoder(model)
    _, cache = apply_fn(params, prompt[None], make_cache(1, 96), 0)
    total = np.zeros(2, np.int64)
    idx = len(prompt)
    for tok in produced[:-1]:
        _, cache, counts = apply_fn(params, jnp.asarray([[tok]]), cache,
                                    jnp.asarray([idx]), chunk_decode=True,
                                    moe_counts=True)
        total += np.asarray(counts)
        idx += 1
    return total


def test_engine_is_solo_generate_and_counts_what_it_routed():
    """Six requests over three slots, joining while others decode and
    leaving at their own lengths, on ONE CHIP'S SHARE of the experts.
    Every stream is the one `generate` gives that request alone (a lane's
    tokens do not depend on its neighbours: the layer is dropless), from
    two executables traced once; and the step spans' `moe_rows` are the
    pairs a replay of each request routes to the held experts. Touched
    experts are not additive over lanes: they are bounded by the slots a
    launch has and by its rows."""
    from apex1_tpu.obs import spine
    model = Lfm2Moe(SHARE)
    params = share_of(make_params(Lfm2Moe(CFG)), SHARE)
    eng = Engine(*lfm2_moe_decoder(model), params, EngineConfig(**ENGINE))
    assert eng._moe_slots == 20 and eng._moe_read
    assert eng._state_lane_bytes == 4 * 3 * 128 * 4
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 500, n).astype(np.int32)
               for n in (5, 16, 21, 33, 47, 9)]
    outs = [7, 12, 9, 15, 6, 11]
    t0 = spine.monotonic_ns()
    ids = [eng.submit(prompts[0], outs[0]), eng.submit(prompts[1], outs[1])]
    eng.step()
    eng.step()
    ids.append(eng.submit(prompts[2], outs[2]))
    eng.step()
    ids += [eng.submit(p, o) for p, o in zip(prompts[3:], outs[3:])]
    eng.run()
    want_rows, streams = 0, set()
    for rid, p, o in zip(ids, prompts, outs):
        got = eng.results[rid].tokens
        np.testing.assert_array_equal(got, _solo(model, params, p, o))
        streams.add(tuple(got))
        want_rows += _replayed_counts(model, params, p, got)[0]
    assert len(streams) == len(ids)
    assert eng.trace_counts == {"prefill": 1, "decode": 1}
    steps = [r for r in spine.snapshot()
             if r.name == "serving/step" and r.start_ns >= t0]
    total = lambda k: sum(sp.counts[k] for sp in steps)
    assert total("moe_rows") == want_rows > 0
    launches = total("moe_expert_slots") // 20
    assert total("moe_expert_slots") == 20 * launches
    assert 0 < total("moe_experts_touched") <= min(total("moe_rows"),
                                                   total("moe_expert_slots"))
    assert total("state_lanes") == sum(
        len(eng.results[r].tokens) - 1 for r in ids)


def test_a_lanes_tokens_do_not_depend_on_its_neighbours(model, params):
    """The same request served alone, and among five others that fill
    every lane: the same tokens. Under a capacity-routed layer the second
    could drop a pair that the first kept."""
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 500, 19).astype(np.int32)
    others = [rng.integers(0, 500, n).astype(np.int32)
              for n in (7, 30, 12, 25, 40)]

    def served(crowd):
        eng = Engine(*lfm2_moe_decoder(model), params,
                     EngineConfig(**ENGINE))
        for p in crowd[:2]:
            eng.submit(p, 14)
        rid = eng.submit(prompt, 10)
        for p in crowd[2:]:
            eng.submit(p, 9)
        eng.run()
        return eng.results[rid].tokens

    np.testing.assert_array_equal(served([]), served(others))


def test_a_decoder_without_experts_hands_out_no_counts():
    from apex1_tpu.models.generate import gpt2_decoder
    from apex1_tpu.models.gpt2 import GPT2, GPT2Config
    gpt2 = GPT2(GPT2Config.tiny())
    p = gpt2.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = Engine(*gpt2_decoder(gpt2), p, EngineConfig(
        max_slots=2, max_len=32, prefill_chunk=8, eos_id=3))
    assert eng._moe_slots == 0 and not eng._moe_read
    assert not any(k.startswith("moe_") for k in eng._tally)
    # the step's results are the four they were: no array more
    out = jax.eval_shape(eng._decode._jit, eng._packed.operands,
                         eng.kv.cache, eng._d_toks, eng._d_idxs,
                         eng._d_active, eng._d_seeds, eng._d_pos)
    assert len(out) == 4


def test_counts_are_read_where_tokens_are_read_step_by_step(model, params):
    """Without an `eos_id` the engine reads no token before a request
    retires, and asks for no counts: the step is the four results it
    was."""
    eng = Engine(*lfm2_moe_decoder(model), params,
                 EngineConfig(**dict(ENGINE, eos_id=None)))
    assert eng._moe_slots == 40 and not eng._moe_read
    rid = eng.submit(np.arange(9, dtype=np.int32), 5)
    eng.run()
    assert len(eng.results[rid].tokens) == 5


# ---- compiled for a described v5e ----------------------------------------

@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def mosaic(topo):
    """The real (non-interpret) kernels for the described chip, with the
    persistent cache off, as `tests/test_engine_aot.py` sets them."""
    import apex1_tpu.ops._common as common
    from apex1_tpu.core import capability
    from jax.experimental.compilation_cache import compilation_cache
    saved = (common.on_tpu, common.interpret_mode,
             jax.config.jax_enable_compilation_cache)
    common.on_tpu = lambda: True
    common.interpret_mode = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with capability.target_generation("v5e"):
        yield
    common.on_tpu, common.interpret_mode = saved[:2]
    jax.config.update("jax_enable_compilation_cache", saved[2])
    compilation_cache.reset_cache()


def test_decode_step_of_the_cell_compiles_for_a_v5e(topo, mosaic):
    """`Engine._decode` of `lfm2moe_serve_rollout` (the published widths,
    24 layers, 8 of 32 experts held, the whole vocabulary, bfloat16; 8
    slots of the cell's 96, so that the test holds a pool of 0.26 GB and
    not 3), compiled for a v5e: no loop; one `apex1_moe_experts` a sparse
    layer and one `apex1_decode_attend` an attention layer; the pool
    aliased to its donated input; the tokens and the two counts leave in
    one array; and the launch hands over the tree as it is (5.05 GB take
    a v5e 6.2 ms to stream: `serving.packing.launch_is_hidden`). A
    compile is not a chip run."""
    from jax.sharding import SingleDeviceSharding
    from benchmark.harness import builders
    man = mf.load_manifest(ROOT)
    cell = mf.find(man, "workloads", "lfm2moe_serve_rollout")
    cfg = mf.load_config(man, cell["config"], ROOT)
    traffic = mf.load_traffic(cell["traffic"], ROOT)
    s1 = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype,
                                           sharding=s1), tree)

    b = builders.get(cfg)
    big = b.model("O2")
    weights = place(jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
        b.param_shapes(big)))
    n_bytes = sum(2 * int(np.prod(w.shape))
                  for w in jax.tree_util.tree_leaves(weights))
    assert 5.04e9 < n_bytes < 5.07e9
    eng = Engine(*b.decoder(big), weights, EngineConfig(
        vocab_size=b.vocab_size, **dict(traffic["engine"], max_slots=8)))
    assert eng.kv.cache["layer2"]["k"].shape == (8, 2816, 512)
    assert eng.kv.cache["layer0"]["conv"].shape == (8, 3, 2048)
    assert eng._state_lane_bytes == 18 * 3 * 2048 * 2
    assert eng._moe_slots == 8 * 22 and eng._moe_read
    assert eng._packed.layout.groups == []
    # hidden: the loop keeps the one launch in flight it kept before
    assert eng._packed.layout.hidden and eng._depth == 1
    pool_bytes = eng.kv.pool_bytes()
    compiled = eng._decode.lower(
        weights, place(eng.kv.cache),
        *place((eng._d_toks, eng._d_idxs, eng._d_active, eng._d_seeds,
                eng._d_pos))).compile()
    del eng
    text = compiled.as_text()
    assert not re.findall(r" while\(", text)
    assert compiled.memory_analysis().alias_size_in_bytes >= 0.98 * pool_bytes
    kernels = re.findall(r'%(apex1_[a-z_]+)[.\d]* = [^\n]*custom-call', text)
    assert kernels.count("apex1_moe_experts") == 22
    assert kernels.count("apex1_decode_attend") == 6
    assert re.search(r"s32\[10\]", text)       # 8 tokens and 2 counts
