"""`models.granite_hybrid` against the benchmark's plain reference
(`benchmark/references/granite-4.0-h-micro.py`: float32, the recurrence
token by token) at a small size with the published widths' ratios kept,
through every path the model has: the full forward, prefill in
right-padded chunks then decoding through the cache, and `serving.Engine`
with requests joining and leaving. And what the engine refuses for a
decoder with recurrent state, and the decode executable compiled for a
described v5e at the published widths: a state leaf is touched by the
step kernel alone."""

import gc
import os
import re
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex1_tpu.models.generate import (cache_len, generate,
                                       granite_hybrid_decoder)
from apex1_tpu.models.granite_hybrid import (GraniteHybrid,
                                             GraniteHybridConfig)
from apex1_tpu.serving.engine import (Engine, EngineConfig,
                                      recurrent_lane_bytes)
from benchmark.harness import manifest as mf

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = GraniteHybridConfig.tiny(embedding_multiplier=1.0)
#: what the plain reference takes: the published keys
REF_CFG = {k: getattr(CFG, k) for k in (
    "vocab_size", "hidden_size", "layer_types", "num_attention_heads",
    "num_key_value_heads", "attention_multiplier", "embedding_multiplier",
    "residual_multiplier", "logits_scaling", "rms_norm_eps", "mamba_d_conv",
    "mamba_d_head", "mamba_d_state", "mamba_n_heads")}
#: float32 model against float32 reference: they differ by the order of
#: their sums (a chunk's products against one token at a time; the
#: softmax's blocks): 2e-6 to 3e-6 as read here, of logits whose spread is
#: 0.14. A state rounded to bfloat16 once moves the next logits by 9e-4,
#: forty times the limit; padding that advances the state by 0.6
TOL = 2e-5


@pytest.fixture(scope="module")
def model():
    return GraniteHybrid(CFG)


@pytest.fixture(scope="module")
def params(model):
    """Seeded: 0.1 * normal, norm weights and the convolution's taps
    (`*scale`) 1 + 0.1 * normal, so that every layer weighs in the logits
    at this width."""
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.key(7), len(leaves))
    out = []
    for (path, s), k in zip(leaves, keys):
        noise = 0.1 * jax.random.normal(k, s.shape, jnp.float32)
        name = str(getattr(path[-1], "key", path[-1]))
        out.append(1.0 + noise if name.endswith("scale") else noise)
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.fixture(scope="module")
def reference():
    return mf.load_reference("granite-4.0-h-micro", ROOT)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(1), (2, 45), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def want(reference, params, tokens):
    return np.asarray(reference.logits(params, tokens, REF_CFG))


def test_parameter_tree_and_cache_are_driven_by_layer_types(model, params):
    assert CFG.layer_types == ("mamba", "attention", "mamba") * 2
    assert set(params["layer1"]) == {"in_norm_scale", "post_norm_scale",
                                     "mlp_in", "mlp_out", "wq", "wk", "wv",
                                     "wo"}
    assert set(params["layer0"]) == {
        "in_norm_scale", "post_norm_scale", "mlp_in", "mlp_out", "in_proj",
        "conv_tap_scale", "conv_b", "A_log", "dt_bias", "D",
        "gate_norm_scale", "out_proj"}
    assert params["layer0"]["in_proj"].shape == (128, 256 + 320 + 16)
    _, make_cache = granite_hybrid_decoder(model)
    cache = make_cache(3, 40, jnp.int8)
    assert set(cache["layer1"]) == {"k", "v"}
    assert cache["layer1"]["k"].shape == (3, 40, 2 * 16)
    assert cache["layer1"]["k"].dtype == jnp.int8     # the capacity tier
    assert set(cache["layer0"]) == {"ssm", "conv"}
    # 16 heads of 16 by a state of 32, eight heads a row; never `dtype`
    assert cache["layer0"]["ssm"].shape == (3, 2, 32, 128)
    assert cache["layer0"]["ssm"].dtype == jnp.float32
    assert cache["layer0"]["conv"].shape == (3, 3, 256 + 2 * 32)
    # positions are read off a K/V leaf, though a state leaf comes first
    assert cache_len(cache) == 40
    assert cache_len(cache["layer1"]) == cache_len(cache["layer1"]["k"]) == 40
    # the recurrent leaves of one lane: 4 layers x (state + 3 inputs)
    assert recurrent_lane_bytes(make_cache) == 4 * (
        16 * 16 * 32 * 4 + 3 * 320 * 4)


def test_full_forward_is_the_reference(model, params, tokens, want):
    got = model.apply({"params": params}, tokens)
    assert 0.1 < want.std() < 1.0
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)


def _chunked_prefill(apply_fn, params, cache, tokens, n, C, **fault):
    """Rows of ``tokens`` (their first ``n`` real) through right-padded
    chunks of ``C``, as `Engine.prefill` feeds them."""
    out = []
    for c in range(0, -(-n // C) * C, C):
        seg = np.zeros((tokens.shape[0], C), np.int32)
        real = min(n - c, C)
        seg[:, :real] = tokens[:, c:c + real]
        lg, cache = apply_fn(params, seg, cache, c, chunk_decode=True,
                             n_real=fault.get("n_real", real))
        out.append(lg[:, :real])
    return jnp.concatenate(out, axis=1), cache


def test_chunked_prefill_then_cached_decode_is_the_reference(
        model, params, tokens, want):
    """A prompt of 27 through chunks of 16 (the last one padded), then one
    token a step with a per-row index, as the engine's two executables
    run the model."""
    apply_fn, make_cache = granite_hybrid_decoder(model)
    n, C = 27, 16
    lg, cache = _chunked_prefill(apply_fn, params, make_cache(2, 64),
                                 np.asarray(tokens), n, C)
    np.testing.assert_allclose(lg, want[:, :n], rtol=0, atol=TOL)
    idx = jnp.full((2,), n, jnp.int32)
    for t in range(n, tokens.shape[1]):
        lg, cache = apply_fn(params, tokens[:, t:t + 1], cache, idx,
                             chunk_decode=True)
        np.testing.assert_allclose(lg[:, 0], want[:, t], rtol=0, atol=TOL)
        idx = idx + 1


def test_an_idle_row_keeps_its_cache_entries(model, params, tokens):
    apply_fn, make_cache = granite_hybrid_decoder(model)
    _, cache = _chunked_prefill(apply_fn, params, make_cache(2, 64),
                                np.asarray(tokens), 20, 16)
    _, after = apply_fn(params, tokens[:, 20:21], cache,
                        jnp.asarray([20, -1], jnp.int32), chunk_decode=True)
    for layer in ("layer0", "layer2"):
        for leaf in ("ssm", "conv"):
            np.testing.assert_array_equal(after[layer][leaf][1],
                                          cache[layer][leaf][1])
            assert not np.array_equal(after[layer][leaf][0],
                                      cache[layer][leaf][0])


def test_state_faults_fail_the_tolerance(model, params, tokens, want):
    """What the tolerance is for: pad tokens that advance the state, a
    state dropped between two chunks, and a state kept in bfloat16 each
    move the next logits far past it."""
    apply_fn, make_cache = granite_hybrid_decoder(model)
    toks = np.asarray(tokens)
    n, C = 27, 16

    def next_logits(cache):
        return apply_fn(params, tokens[:, n:n + 1], cache,
                        jnp.full((2,), n, jnp.int32),
                        chunk_decode=True)[0][:, 0]

    _, sound = _chunked_prefill(apply_fn, params, make_cache(2, 64), toks,
                                n, C)
    np.testing.assert_allclose(next_logits(sound), want[:, n], atol=TOL)
    _, padded = _chunked_prefill(apply_fn, params, make_cache(2, 64), toks,
                                 n, C, n_real=None)
    assert np.abs(next_logits(padded) - want[:, n]).max() > 100 * TOL
    _, first = _chunked_prefill(apply_fn, params, make_cache(2, 64), toks,
                                C, C)
    dropped = {k: ({**v, "ssm": jnp.zeros_like(v["ssm"])} if "ssm" in v
                   else v) for k, v in first.items()}
    seg = np.zeros((2, C), np.int32)
    seg[:, :n - C] = toks[:, C:n]
    _, dropped = apply_fn(params, seg, dropped, C, chunk_decode=True,
                          n_real=n - C)
    assert np.abs(next_logits(dropped) - want[:, n]).max() > 100 * TOL
    rounded = {k: ({**v, "ssm": v["ssm"].astype(jnp.bfloat16).astype(
        jnp.float32)} if "ssm" in v else v) for k, v in sound.items()}
    assert np.abs(next_logits(rounded) - want[:, n]).max() > 20 * TOL


def test_a_run_of_tokens_under_a_per_row_index_is_refused(model, params):
    apply_fn, make_cache = granite_hybrid_decoder(model)
    with pytest.raises(ValueError, match="one token a row"):
        apply_fn(params, jnp.zeros((2, 3), jnp.int32), make_cache(2, 32),
                 jnp.zeros((2,), jnp.int32), chunk_decode=True)


# ---- serving.Engine ------------------------------------------------------

ENGINE = dict(max_slots=3, max_len=96, prefill_chunk=16, eos_id=511,
              vocab_size=CFG.vocab_size, prefix_cache=False)


def _solo(model, params, prompt, n_out):
    apply_fn, make_cache = granite_hybrid_decoder(model)
    out = np.asarray(generate(apply_fn, params, prompt[None],
                              max_new_tokens=n_out, cache=make_cache(1, 96),
                              eos_id=511, vocab_size=CFG.vocab_size)[0])
    stop = np.flatnonzero(out == 511)
    return out[:stop[0] + 1] if stop.size else out


def test_engine_with_staggered_joins_and_leaves_is_solo_generate(model,
                                                                 params):
    """Six requests over three slots, joining while others decode and
    leaving at their own lengths; prompts of 5 to 47 tokens against a
    chunk of 16 (none a multiple of it but one). Every stream is the one
    `generate` gives that request alone, from two executables traced once,
    and the step spans count the state they moved."""
    from apex1_tpu.obs import spine
    eng = Engine(*granite_hybrid_decoder(model), params,
                 EngineConfig(**ENGINE))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 500, n).astype(np.int32)
               for n in (5, 16, 21, 33, 47, 9)]
    outs = [7, 12, 9, 15, 6, 11]
    t0 = spine.monotonic_ns()      # the buffer is bounded: by time
    ids = [eng.submit(prompts[0], outs[0]), eng.submit(prompts[1], outs[1])]
    eng.step()
    eng.step()
    ids.append(eng.submit(prompts[2], outs[2]))
    eng.step()
    ids += [eng.submit(p, o) for p, o in zip(prompts[3:], outs[3:])]
    eng.run()
    streams = set()
    for rid, p, o in zip(ids, prompts, outs):
        got = eng.results[rid].tokens
        np.testing.assert_array_equal(got, _solo(model, params, p, o))
        streams.add(tuple(got))
    assert len(streams) == len(ids) and all(len(set(s)) > 2
                                            for s in streams)
    assert eng.trace_counts == {"prefill": 1, "decode": 1}
    steps = [r for r in spine.snapshot()
             if r.name == "serving/step" and r.start_ns >= t0]
    lanes = sum(sp.counts["state_lanes"] for sp in steps)
    # a token beyond a request's first is one lane-step
    assert lanes == sum(len(eng.results[r].tokens) - 1 for r in ids)
    assert sum(sp.counts["state_bytes"] for sp in steps) \
        == 2 * lanes * eng._state_lane_bytes
    assert all(0 <= sp.counts["state_lanes"] <= 3 for sp in steps)


def test_a_decoder_without_recurrent_state_counts_none():
    from apex1_tpu.models.generate import gpt2_decoder
    from apex1_tpu.models.gpt2 import GPT2, GPT2Config
    gpt2 = GPT2(GPT2Config.tiny())
    p = gpt2.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    dec = gpt2_decoder(gpt2)
    assert recurrent_lane_bytes(dec[1]) == 0
    eng = Engine(*dec, p, EngineConfig(max_slots=2, max_len=32,
                                       prefill_chunk=8))
    assert "state_lanes" not in eng._tally and "state_bytes" not in eng._tally


@pytest.mark.parametrize("asked,names", [
    (dict(prefix_cache=True), "prefix_cache=True"),
    (dict(num_draft=2), "num_draft > 0"),
    (dict(paged=True), "paged=True"),
    (dict(prefix_cache=True, num_draft=2, paged=True),
     "prefix_cache=True.*num_draft > 0.*paged=True")])
def test_what_a_recurrent_state_cannot_do_is_refused_at_construction(
        model, params, asked, names):
    cfg = EngineConfig(**dict(ENGINE, **asked))
    with pytest.raises(ValueError, match="recurrent state.*" + names):
        Engine(*granite_hybrid_decoder(model), params, cfg)


def test_an_explicit_prefix_is_snapshotted_at_its_share_point(model, params):
    """`submit(prefix=...)` with the radix cache off runs the prefix's own
    chunks, `n_real` and all, and snapshots the lane where they end: the
    state AT the share point, which a sharer installs. (The automatic
    registration, refused above, would snapshot after the whole prompt.)"""
    eng = Engine(*granite_hybrid_decoder(model), params,
                 EngineConfig(**ENGINE))
    rng = np.random.default_rng(3)
    prefix = tuple(int(t) for t in rng.integers(0, 500, 21))
    tails = [rng.integers(0, 500, n).astype(np.int32) for n in (6, 19)]
    ids = [eng.submit(t, 8, prefix=prefix) for t in tails]
    eng.run()
    assert eng.kv.get_prefix(prefix).hits == 2
    for rid, tail in zip(ids, tails):
        full = np.concatenate([np.asarray(prefix, np.int32), tail])
        np.testing.assert_array_equal(eng.results[rid].tokens,
                                      _solo(model, params, full, 8))


def test_an_engine_is_freed_with_its_last_reference(model, params):
    """No reference cycle holds an engine: its pool and its weights go
    when the last reference does, without the cycle collector. (The
    benchmark freezes the collector's generations before its window, then
    drops the engine and makes the weights again for its check: a cycle
    kept 10 GB of a 16 GB chip, PERF.md §6, PR 34.)"""
    eng = Engine(*granite_hybrid_decoder(model), params,
                 EngineConfig(**ENGINE))
    eng.submit(np.arange(20, dtype=np.int32), 4)
    eng.run()
    gc.collect()
    gc.freeze()
    try:
        ref = weakref.ref(eng)
        leaf = weakref.ref(eng.kv.cache["layer0"]["ssm"])
        del eng
        assert ref() is None and leaf() is None
    finally:
        gc.unfreeze()


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


INSTR_RE = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = "
    r"(\((?:[^=]|/\*index=\d+\*/)*?\)|\S+) ([\w\-]+)\(")


def test_decode_step_updates_a_state_leaf_by_the_kernel_alone(topo, mosaic):
    """`Engine._decode` of `granite4hm_serve_chat` (the published widths,
    40 layers, the whole vocabulary, bfloat16; 8 slots of the cell's 48,
    so that the test holds a pool of 0.65 GB and not 3.9), compiled for a
    v5e: no loop; every state leaf aliased to its donated input; and no
    instruction but the 36 `apex1_ssm_step` kernels, one a state-space
    layer, has a result of a state leaf's shape: nothing copies or
    rewrites one. The launch hands over the tree as it is, 466 leaves
    beside 80 of the pool and 5 control vectors: streaming 6.4 GB takes
    a v5e 7.8 ms, the launch lies under the step in flight
    (`serving.packing.launch_is_hidden`), and cutting stacks apart every
    step would only cost the device (PERF.md, PR 35: + 0.7 % on the
    step, + 1.5 % on the gap). A compile is not a chip run."""
    from jax.sharding import SingleDeviceSharding
    from benchmark.harness import builders
    man = mf.load_manifest(ROOT)
    cell = mf.find(man, "workloads", "granite4hm_serve_chat")
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
    eng = Engine(*b.decoder(big), weights, EngineConfig(
        vocab_size=b.vocab_size, **dict(traffic["engine"], max_slots=8)))
    leaf = eng.kv.cache["layer0"]["ssm"]
    assert leaf.shape == (8, 32, 128, 128) and leaf.dtype == jnp.float32
    assert eng.kv.cache["layer5"]["k"].shape == (8, 1280, 512)
    assert eng._state_lane_bytes == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert eng._packed.layout.groups == []
    # hidden: the loop keeps the one launch in flight it kept before
    assert eng._packed.layout.hidden and eng._depth == 1
    assert eng._n_operands["step"] == 466 + 80 + 5
    pool_bytes = eng.kv.pool_bytes()
    compiled = eng._decode.lower(
        weights, place(eng.kv.cache),
        *place((eng._d_toks, eng._d_idxs, eng._d_active, eng._d_seeds,
                eng._d_pos))).compile()
    del eng
    text = compiled.as_text()
    assert not re.findall(r" while\(", text)
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    shape = "f32[8,32,128,128]"
    makers = []
    for line in text.splitlines():
        m = INSTR_RE.match(line)
        if m and shape in m.group(2) and m.group(3) not in (
                "parameter", "tuple", "get-tuple-element", "bitcast"):
            makers.append(re.sub(r"\.\d+$", "", m.group(1)))
    assert makers == ["apex1_ssm_step"] * 36, makers
    assert len(re.findall(r"%apex1_decode_attend[.\d]* = ", text)) == 4
