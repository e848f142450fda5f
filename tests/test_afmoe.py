"""`models.afmoe` against the benchmark's plain reference
(`benchmark/references/afmoe.py`: float32, no cache, no ring, no sort, the
window a mask over the full row) at a small size with the published
pattern kept, through every path the model has: the full forward, prefill
in chunks then decoding through rings that wrap, and `serving.Engine` with
requests joining and leaving; the whole model and one chip's share of its
experts. The variants the comparison must catch (a window left out or one
position off, RoPE on a global layer, the gate left out, a bias that
weighs) each fail it. And the pool of two lengths of K/V leaf: what the
engine refuses with it, what its step spans count, and the cell's decode
executable compiled for a described v5e at the published widths."""

import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex1_tpu.models import afmoe
from apex1_tpu.models.afmoe import Afmoe, AfmoeConfig, init_afmoe_cache
from apex1_tpu.models.generate import afmoe_decoder, cache_len
from apex1_tpu.ops import _common
from apex1_tpu.ops.decode_attend import DECODE_BLOCK
from apex1_tpu.serving.engine import (Engine, EngineConfig, kv_leaf_rows,
                                      recurrent_lane_bytes)
from apex1_tpu.transformer import moe as moe_lib
from benchmark.harness import manifest as mf

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = AfmoeConfig.tiny()
SHARE = AfmoeConfig.tiny(experts_held=(4, 4))
_REF_KEYS = ("vocab_size", "hidden_size", "layer_types",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "sliding_window", "num_dense_layers", "num_experts",
             "num_experts_per_tok", "num_shared_experts", "score_func",
             "route_norm", "route_scale", "mup_enabled", "rms_norm_eps",
             "rope_theta")
#: float32 model against float32 reference: they differ by the order of
#: their sums (6e-6 as read here, of logits whose spread is 1.15); the
#: broken variants below move them by 1.3 to 4.5
TOL = 5e-5
BLK = DECODE_BLOCK


def ref_cfg(cfg):
    return dict({k: getattr(cfg, k) for k in _REF_KEYS},
                held=list(cfg.experts_held) if cfg.experts_held else None)


def make_params(model, seed=7):
    """Seeded: 0.1 * normal (the router's bias too), every `*scale` leaf
    1 + 0.1 * normal."""
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
    return Afmoe(CFG)


@pytest.fixture(scope="module")
def params(model):
    return make_params(model)


@pytest.fixture(scope="module")
def reference():
    return mf.load_reference("afmoe", ROOT)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(1), (2, 45), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def want(reference, params, tokens):
    return np.asarray(reference.logits(params, tokens, ref_cfg(CFG)))


def test_parameter_tree_and_cache_are_driven_by_the_two_lists(params):
    assert CFG.layer_types == ("sliding_attention", "sliding_attention",
                               "full_attention") * 2
    assert CFG.ffn_kinds == ("dense",) + ("sparse",) * 5
    attn = {"wq", "wk", "wv", "wgate", "wo", "q_norm_scale", "k_norm_scale",
            "input_norm_scale", "post_attn_norm_scale",
            "pre_ffn_norm_scale", "post_ffn_norm_scale"}
    ffn = {"dense": {"w1", "w3", "w2"},
           "sparse": {"router", "expert_bias", "experts_w1", "experts_w3",
                      "experts_w2", "shared_w1", "shared_w3", "shared_w2"}}
    for i, f in enumerate(CFG.ffn_kinds):
        assert set(params[f"layer{i}"]) == attn | ffn[f]
    assert set(params) == {"embed", "lm_head", "final_norm_scale"} | {
        f"layer{i}" for i in range(6)}
    assert params["layer1"]["router"].shape == (128, 16)
    assert params["layer1"]["experts_w1"].shape == (16, 128, 64)
    assert params["layer1"]["shared_w2"].shape == (64, 128)
    assert params["layer0"]["wgate"].shape == (128, 8 * 16)
    # a sliding layer's entry is a ring of window + slack rows in whole
    # blocks, a global layer's holds every position
    cache = init_afmoe_cache(CFG, 3, 640, ring_slack=100)
    rows = [cache[f"layer{i}"]["k"].shape[1] for i in range(6)]
    assert rows == [128, 128, 640] * 2
    assert cache["layer0"]["v"].shape == (3, 128, 2 * 16)
    assert cache_len(cache) == 640              # off the LONGEST leaf
    # where the positions are fewer than a ring, the ring is all of them
    assert init_afmoe_cache(CFG, 1, 64)["layer0"]["k"].shape == (1, 64, 32)
    apply_fn, make_cache = afmoe_decoder(Afmoe(CFG), ring_slack=100)
    assert recurrent_lane_bytes(make_cache) == 0         # no state leaf
    assert kv_leaf_rows(make_cache, 640) == rows
    assert apply_fn.sliding_window == 16
    assert apply_fn.moe_expert_slots == 5 * 16
    share = jax.eval_shape(Afmoe(SHARE).init, jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    assert share["layer1"]["experts_w1"].shape == (4, 128, 64)
    assert share["layer1"]["router"].shape == (128, 16)
    assert share["layer1"]["shared_w1"].shape == (128, 64)


@pytest.mark.parametrize("key,value", [
    ("n_group", 2), ("topk_group", 4), ("tie_word_embeddings", True),
    ("rope_scaling", {"type": "yarn"})])
def test_what_the_model_does_not_compute_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        AfmoeConfig.tiny(**{key: value})


@pytest.mark.parametrize("cfg", [CFG, SHARE], ids=["whole", "share"])
def test_forward_equals_the_plain_reference(cfg, params, reference, tokens):
    """45 tokens under a window of 16: the window binds on every sliding
    layer, and the global layers see the whole row without positions."""
    p = share_of(params, cfg)
    got = Afmoe(cfg).apply({"params": p}, tokens)
    want = reference.logits(p, tokens, ref_cfg(cfg))
    assert got.dtype == jnp.float32 and got.shape == (2, 45, 512)
    assert float(jnp.std(want)) > 0.5
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    at = jnp.asarray([[3, 44, 17], [0, 9, 30]])
    np.testing.assert_allclose(
        reference.logits(p, tokens, ref_cfg(cfg), positions=at),
        jnp.take_along_axis(want, at[..., None], axis=1), atol=1e-6)


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_layer(
        params, reference):
    """Eight chips of two experts each: the sum of what `held_experts_mlp`
    gives on each, plus the shared expert ONCE, is the uncut reference
    layer; the shared expert counted on every chip is not."""
    p = params["layer2"]
    x = jax.random.normal(jax.random.key(3), (24, 128), jnp.float32)
    want = reference._mixture(p, p, x, dict(ref_cfg(CFG), held=None), None)
    experts, weights = moe_lib.dropless_route(x, p["router"],
                                              p["expert_bias"], CFG.route)
    routed = sum(moe_lib.held_experts_mlp(
        x, experts, weights, p["experts_w1"][r:r + 2],
        p["experts_w3"][r:r + 2], p["experts_w2"][r:r + 2],
        range(r, r + 2))[0] for r in range(0, 16, 2))
    shared = moe_lib.shared_expert_mlp(x, p["shared_w1"], p["shared_w3"],
                                       p["shared_w2"])
    np.testing.assert_allclose(routed + shared, want, atol=2e-5, rtol=0)
    assert float(jnp.max(jnp.abs(routed + 8 * shared - want))) > 0.01


def _rope(x):
    from apex1_tpu.ops import apply_rotary_pos_emb, rope_tables
    cos, sin = rope_tables(jnp.arange(x.shape[2]), x.shape[3],
                           base=CFG.rope_theta)
    return apply_rotary_pos_emb(x.transpose(0, 2, 1, 3), cos,
                                sin).transpose(0, 2, 1, 3)


def _weighs_by_bias(real):
    def route(x2, wg, bias, cfg):
        experts, _ = real(x2, wg, bias, cfg)
        scores = jax.nn.sigmoid(jnp.dot(
            x2, wg, precision=jax.lax.Precision.HIGHEST)) + bias
        w = jnp.take_along_axis(scores, experts, axis=-1)
        return experts, w / jnp.sum(w, -1, keepdims=True) * cfg.scale
    return route


@pytest.mark.parametrize("broken", ["window_left_out", "window_one_off",
                                    "rope_on_a_global_layer",
                                    "gate_left_out", "bias_weighs"])
def test_a_broken_variant_fails_the_comparison(broken, params, tokens, want,
                                               monkeypatch):
    """Each is a way to get this family wrong that still runs. What it
    moves the logits by, as read here: a window left out 4.5, one position
    off 3.6, RoPE on the global layers 2.4, the gate left out 4.3, a bias
    that weighs 1.3 (0.1-normal biases, five times the harness's): with
    four norms a layer every layer's part is of the stream's own size, so
    nothing hides."""
    p = params
    if broken == "window_left_out":
        monkeypatch.setattr(afmoe, "windowed_attention",
                            lambda q, k, v, w: afmoe.flash_attention(
                                q, k, v, causal=True))
    elif broken == "window_one_off":
        real = afmoe.windowed_attention
        monkeypatch.setattr(afmoe, "windowed_attention",
                            lambda q, k, v, w: real(q, k, v, w + 1))
    elif broken == "rope_on_a_global_layer":
        # uncached, 45 tokens under a window of 16: the flash kernel is
        # the global layers' alone
        real = afmoe.flash_attention
        monkeypatch.setattr(afmoe, "flash_attention",
                            lambda q, k, v, causal: real(
                                _rope(q), _rope(k), v, causal=causal))
    elif broken == "gate_left_out":
        # a gate of 1/2 everywhere is no gate: the norm behind the
        # attention takes a constant factor out again
        p = {n: (dict(v, wgate=jnp.zeros_like(v["wgate"]))
                 if isinstance(v, dict) else v) for n, v in p.items()}
    else:
        monkeypatch.setattr(afmoe, "dropless_route",
                            _weighs_by_bias(afmoe.dropless_route))
    got = Afmoe(CFG).apply({"params": p}, tokens)
    assert float(np.max(np.abs(np.asarray(got) - want))) > 200 * TOL


@pytest.mark.parametrize("cfg", [CFG, SHARE], ids=["whole", "share"])
def test_chunked_prefill_then_decode_through_rings_that_wrap(
        cfg, params, reference):
    """The engine's two calls by hand: right-padded chunks of 32 at a
    scalar index, then one token a row at a per-row index, through rings
    of 128 rows under a window of 16, to position 300: every ring has
    wrapped twice. Every step's logits are the reference's full forward's
    at that position."""
    p = share_of(params, cfg)
    m = Afmoe(cfg)
    apply_fn, make_cache = afmoe_decoder(m, ring_slack=100)
    toks = np.asarray(jax.random.randint(jax.random.key(5), (300,), 0, 512))
    want = np.asarray(reference.logits(p, toks[None], ref_cfg(cfg)))[0]
    cache = make_cache(1, 384)
    assert cache["layer0"]["k"].shape[1] == 128
    n_pre, C = 270, 32
    pre = jax.jit(lambda c, t, i, n: apply_fn(
        p, t, c, i, chunk_decode=True, n_real=n))
    for at in range(0, n_pre, C):
        chunk = np.zeros((1, C), np.int32)
        real = min(C, n_pre - at)
        chunk[0, :real] = toks[at:at + real]
        logits, cache = pre(cache, chunk, jnp.int32(at), jnp.int32(real))
        np.testing.assert_allclose(logits[0, :real], want[at:at + real],
                                   atol=TOL, rtol=0)
    step = jax.jit(lambda c, t, i: apply_fn(
        p, t, c, i, positions=i[:, None], chunk_decode=True))
    for at in range(n_pre, 300):
        logits, cache = step(cache, toks[None, at:at + 1],
                             jnp.asarray([at], jnp.int32))
        np.testing.assert_allclose(logits[0, 0], want[at], atol=TOL, rtol=0)


ENGINE = dict(vocab_size=512, max_slots=3, max_len=640, prefill_chunk=32,
              eos_id=600, prefix_cache=False)


def _gaps(reference, p, cfg, prompt, served):
    """How far each served token's reference logit lies below the
    reference's best at its position: 0 where the engine chose it."""
    full = np.concatenate([prompt, served])
    at = np.arange(len(prompt) - 1, len(full) - 1)
    ref = np.asarray(reference.logits(p, full[None], ref_cfg(cfg),
                                      positions=at[None]))[0]
    return ref.max(-1) - ref[np.arange(len(at)), served]


def test_engine_serves_the_references_tokens_past_rings_that_wrap(
        params, reference):
    """Requests of 40 to 300 tokens join and leave a pool whose sliding
    layers keep rings of 128 rows; the longest is served to position 560,
    four times round its rings. Every served token is the float32
    reference's first choice at its position (or within the tolerance of
    it), from two executables traced once; the step spans' counts are a
    replay's."""
    from apex1_tpu.obs import spine
    model = Afmoe(SHARE)
    p = share_of(params, SHARE)
    eng = Engine(*afmoe_decoder(model, ring_slack=100), p,
                 EngineConfig(**ENGINE))
    assert eng._window == 16 and eng._moe_read
    assert eng._kv_rows == [128, 128, 768] * 2
    rows = {x.shape[1] for x in jax.tree_util.tree_leaves(eng.kv.cache)}
    assert rows == {128, 768}
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 500, n).astype(np.int32)
               for n in (300, 40, 131, 77)]
    outs = [260, 30, 50, 21]
    t0 = spine.monotonic_ns()
    ids = [eng.submit(prompts[0], outs[0]), eng.submit(prompts[1], outs[1])]
    eng.step()
    ids += [eng.submit(q, o) for q, o in zip(prompts[2:], outs[2:])]
    eng.run()
    assert eng.trace_counts == {"prefill": 1, "decode": 1}
    for rid, prompt, n in zip(ids, prompts, outs):
        got = np.asarray(eng.results[rid].tokens)
        assert len(got) == n
        assert _gaps(reference, p, SHARE, prompt, got).max() < TOL
    steps = [r for r in spine.snapshot()
             if r.name == "serving/step" and r.start_ns >= t0]
    total = lambda k: sum(sp.counts[k] for sp in steps)
    # a replay: token t of a request is launched at depth len(prompt) + t
    # - 1... the first decode launch appends at the prompt's length
    depths = [len(q) + t for q, n in zip(prompts, outs)
              for t in range(n - 1)]
    upto = sum(d // BLK + 1 for d in depths)
    below = sum(max(d - 16 + 1, 0) // BLK for d in depths)
    assert total("kv_layers") % 6 == 0 and total("kv_layers") > 0
    assert total("kv_blocks_read_window") == 4 * (upto - below)
    assert total("kv_blocks_read") == 2 * upto + 4 * (upto - below)
    launches = total("kv_layers") // 6
    assert total("kv_blocks_pool") == launches * 3 * (4 * 1 + 2 * 6)
    assert total("moe_rows") > 0


@pytest.mark.parametrize("asked,named", [
    (dict(prefix_cache=True), "prefix_cache=True"),
    (dict(num_draft=2), "num_draft > 0"),
    (dict(paged=True), "paged=True"),
    (dict(prefill_chunk=128), "prefill_chunk=128")])
def test_what_a_ring_cannot_serve_is_refused_at_construction(asked, named,
                                                             params):
    """A ring has forgotten what a shared prefix or a rolled-back draft
    would attend, the paged pool has no ring, and a chunk longer than the
    ring's slack (128 rows under a window of 16: 112) overwrites rows its
    own queries attend: each is refused by name."""
    with pytest.raises(ValueError, match="sliding-window rings") as e:
        Engine(*afmoe_decoder(Afmoe(CFG), ring_slack=100), params,
               EngineConfig(**dict(ENGINE, **asked)))
    assert named in str(e.value)
    others = {"prefix_cache=True", "num_draft > 0", "paged=True",
              "prefill_chunk="} - {named.split("128")[0]}
    assert not any(o in str(e.value) for o in others)


def test_a_pool_of_rings_needs_the_decoders_window(params):
    apply_fn, make_cache = afmoe_decoder(Afmoe(CFG), ring_slack=100)
    bare = lambda *a, **k: apply_fn(*a, **k)         # no sliding_window
    with pytest.raises(ValueError, match="apply_fn.sliding_window"):
        Engine(bare, make_cache, params, EngineConfig(**ENGINE))


def test_engine_under_the_kernel_serves_the_composites_tokens():
    """`force_impl("pallas")` runs `ops.decode_attend` in interpret mode in
    the step: one sliding layer over a ring of two blocks and one global
    layer; a prompt of 250 is prefilled by the composite and 14 tokens
    decoded across the ring's end at 256, beside a shallow lane and an
    idle one. The tokens are the composite engine's."""
    cfg = AfmoeConfig.tiny(
        num_hidden_layers=2, num_dense_layers=1,
        layer_types=("sliding_attention", "full_attention"))
    model = Afmoe(cfg)
    p = make_params(model, seed=3)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 500, n).astype(np.int32) for n in (250, 9)]

    def served():
        eng = Engine(*afmoe_decoder(model, ring_slack=200), p,
                     EngineConfig(**dict(ENGINE, max_len=384)))
        assert eng._kv_rows == [256, 512]
        ids = [eng.submit(prompts[0], 14), eng.submit(prompts[1], 6)]
        eng.run()
        return [np.asarray(eng.results[r].tokens) for r in ids]

    want = served()
    with _common.force_impl("pallas"):
        got = served()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_the_pool_at_the_published_lengths_and_what_a_step_reads():
    """The cell's pool by shapes alone: of a lane of 8704 + 255 positions
    (8960 in whole blocks), 8 global layers hold 8960 rows and 24 sliding
    layers rings of 2304, 260 MB where one length would be 587; and a live
    lane's sliding layers read at most 2048 / 128 + 2 blocks each,
    whatever its depth."""
    man = mf.load_manifest(ROOT)
    pub = mf.load_config(man, "trinity-mini", ROOT)
    cfg = AfmoeConfig.tiny(
        num_hidden_layers=32, layer_types=pub["layer_types"],
        sliding_window=pub["sliding_window"], num_dense_layers=2)
    apply_fn, make_cache = afmoe_decoder(Afmoe(cfg))
    rows = kv_leaf_rows(make_cache, 8960)
    assert sorted(set(rows)) == [2304, 8960]
    assert rows.count(2304) == 24 and rows.count(8960) == 8
    lane = sum(rows) * 2 * pub["num_key_value_heads"] * pub["head_dim"] * 2
    assert lane == (8 * 8960 + 24 * 2304) * 2048 == 260_046_848
    assert 32 * 8960 * 2048 == 587_202_560
    eng = types.SimpleNamespace(
        cfg=types.SimpleNamespace(max_slots=16), _window=2048,
        _kv_rows=rows, _lane_blocks=70, _ring_layers=24,
        _pool_blocks=16 * (8 * 70 + 24 * 18), _fetch_depth=8,
        _tally=dict.fromkeys(("kv_blocks_pool", "kv_blocks_read",
                              "kv_blocks_read_window", "kv_layers",
                              "kv_fetch_ahead"), 0))
    for depth in (0, 100, 2047, 2048, 2049, 2175, 4096, 4223, 8703):
        before = dict(eng._tally)
        eng._slots = [types.SimpleNamespace(depth=depth, in_batch=True),
                      None,
                      types.SimpleNamespace(depth=7000, in_batch=False)]
        Engine._count_kv_blocks(eng, 1)
        got = {k: v - before[k] for k, v in eng._tally.items()}
        assert got["kv_layers"] == 32
        assert got["kv_blocks_pool"] == 16 * (8 * 70 + 24 * 18)
        ring = got["kv_blocks_read_window"] // 24
        assert ring * 24 == got["kv_blocks_read_window"]
        assert ring <= 2048 // 128 + 2
        assert ring == depth // 128 + 1 - max(depth - 2047, 0) // 128
        assert got["kv_blocks_read"] == 8 * (depth // 128 + 1) + 24 * ring
        # the one live lane is lane 0: its own step starts its fetches
        assert got["kv_fetch_ahead"] == 0


def test_the_harnesss_draw_starves_no_expert_and_the_bias_chooses():
    """At the published router width (2048 inputs, 128 experts, top-8) with
    the benchmark's draw (router and bias 0.02 normal, rows of unit size):
    every one of the 16 held experts gets between a third and three times
    the mean load, and the bias changes the chosen eight for a visible
    share of the tokens."""
    ks = jax.random.split(jax.random.key(11), 3)
    x = jax.random.normal(ks[0], (4096, 2048), jnp.float32)
    wg = 0.02 * jax.random.normal(ks[1], (2048, 128), jnp.float32)
    bias = 0.02 * jax.random.normal(ks[2], (128,), jnp.float32)
    route = AfmoeConfig(layer_types=("full_attention",) * 32).route
    assert (route.num_experts, route.top_k, route.score, route.scale) == (
        128, 8, "sigmoid", 2.826)
    with_bias, w = moe_lib.dropless_route(x, wg, bias, route)
    np.testing.assert_allclose(jnp.sum(w, -1), 2.826, rtol=1e-5)
    load = np.bincount(np.asarray(with_bias).reshape(-1), minlength=128)
    mean = 4096 * 8 / 128
    assert load[:16].min() > mean / 3 and load[:16].max() < 3 * mean
    import dataclasses
    without, _ = moe_lib.dropless_route(
        x, wg, None, dataclasses.replace(route, select_bias=False))
    changed = np.mean([set(a) != set(b) for a, b in zip(
        np.asarray(with_bias), np.asarray(without))])
    assert 0.1 < changed < 0.9


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
    """`Engine._decode` of `trinitymini_serve_longctx` (the published
    widths, 32 layers, 16 of 128 experts held, the whole vocabulary,
    bfloat16; 2 slots of the cell's 16, so that the test describes a pool
    of 0.5 GB and not 4), compiled for a v5e: no loop; one
    `apex1_decode_attend` a layer, over rings of 2304 rows and leaves of
    8960, and one `apex1_moe_experts` a sparse layer; the pool aliased to
    its donated input; the tokens and the two counts leave in one array;
    the launch hands over the tree as it is (9.97 GB take a v5e 12 ms to
    stream: `serving.packing.launch_is_hidden`). A compile is not a chip
    run."""
    from jax.sharding import SingleDeviceSharding
    from benchmark.harness import builders
    man = mf.load_manifest(ROOT)
    cell = mf.find(man, "workloads", "trinitymini_serve_longctx")
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
    assert 9.96e9 < n_bytes < 9.98e9
    eng = Engine(*b.decoder(big), weights, EngineConfig(
        vocab_size=b.vocab_size, **dict(traffic["engine"], max_slots=2)))
    assert eng.kv.cache["layer0"]["k"].shape == (2, 2304, 512)
    assert eng.kv.cache["layer3"]["k"].shape == (2, 8960, 512)
    assert eng._state_lane_bytes == 0 and eng._window == 2048
    assert eng._moe_slots == 16 * 30 and eng._moe_read
    assert eng._packed.layout.hidden and eng._depth == 1
    assert eng.kv.lane_bytes() == 260_046_848
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
    assert kernels.count("apex1_moe_experts") == 30
    assert kernels.count("apex1_decode_attend") == 32
    assert re.search(r"s32\[4\]", text)        # 2 tokens and 2 counts
