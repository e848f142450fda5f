"""The dropless expert layer (`transformer.moe.dropless_route`,
`expert_rows`, `held_experts_mlp`) and its grouped product
(`ops.moe_experts`, `apex1_moe_experts`) against a mixture written out in
plain `jax.numpy`: every expert over every row, times its weight or zero.
The router's four properties each broken once and caught; the shares of
an expert-parallel deployment adding up to the whole layer; no row dropped
under any skew; the kernel in interpret mode against the composite; and
the kernel compiled for a described v5e at the published widths of
`lfm2-8b-a1b`. A compile is not a chip run."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex1_tpu.ops import force_impl
from apex1_tpu.ops.moe_experts import (PASS_ROWS, PASS_SLACK, ROW_TILE,
                                       moe_experts, padded_rows)
from apex1_tpu.transformer.moe import (RouteConfig, dropless_route,
                                       expert_rows, held_experts_mlp)

T, H, F, E, K = 45, 64, 256, 32, 4
ROUTE = RouteConfig(E, K, score="sigmoid", select_bias=True, normalize=True,
                    scale=1.5)
_HI = jax.lax.Precision.HIGHEST


@pytest.fixture(scope="module")
def w():
    ks = jax.random.split(jax.random.key(0), 6)
    n = jax.random.normal
    return {"x": n(ks[0], (T, H)), "gate": 0.3 * n(ks[1], (H, E)),
            "bias": 0.1 * n(ks[2], (E,)), "w1": 0.1 * n(ks[3], (E, H, F)),
            "w3": 0.1 * n(ks[4], (E, H, F)), "w2": 0.1 * n(ks[5], (E, F, H))}


def _weights_plain(w, cfg=ROUTE, *, by_bias=True, weigh_biased=False,
                   normalize=None, scale=None):
    """(T, E) mixture weights as the equations read, with no `top_k`: an
    expert is chosen where fewer than k scores lie above its own. The
    keywords break one property each."""
    s = jax.nn.sigmoid(jnp.dot(w["x"], w["gate"], precision=_HI))
    by = s + w["bias"] if by_bias else s
    chosen = jnp.sum(by[:, None, :] > by[:, :, None], axis=-1) < cfg.top_k
    g = jnp.where(chosen, by if weigh_biased else s, 0.0)
    if cfg.normalize if normalize is None else normalize:
        g = g / jnp.sum(g, -1, keepdims=True)
    return g * (cfg.scale if scale is None else scale)


def _mixture(w, g, held=range(E)):
    y = jnp.zeros((T, H))
    for e in held:
        out = jnp.dot(jax.nn.silu(jnp.dot(w["x"], w["w1"][e], precision=_HI))
                      * jnp.dot(w["x"], w["w3"][e], precision=_HI),
                      w["w2"][e], precision=_HI)
        y = y + g[:, e:e + 1] * out
    return y


def _layer(w, held=range(E), live=None, cfg=ROUTE):
    experts, weights = dropless_route(w["x"], w["gate"], w["bias"], cfg)
    sl = slice(held.start, held.stop)
    return held_experts_mlp(w["x"], experts, weights, w["w1"][sl],
                            w["w3"][sl], w["w2"][sl], held, live)


def test_route_is_the_equations(w):
    experts, weights = dropless_route(w["x"], w["gate"], w["bias"], ROUTE)
    assert experts.shape == weights.shape == (T, K)
    dense = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], experts].set(weights)
    np.testing.assert_allclose(dense, _weights_plain(w), atol=1e-6)
    # the bias really chooses: without it most rows keep another set
    plain, _ = dropless_route(w["x"], w["gate"], None,
                              RouteConfig(E, K, score="sigmoid"))
    moved = np.mean([set(a) != set(b) for a, b in
                     zip(np.asarray(experts), np.asarray(plain))])
    assert moved > 0.3
    np.testing.assert_allclose(weights.sum(-1), ROUTE.scale, rtol=1e-6)


@pytest.mark.parametrize("fault", [
    dict(by_bias=False), dict(weigh_biased=True), dict(normalize=False),
    dict(scale=1.0)], ids=["ignores_the_bias", "weighs_by_the_biased_score",
                           "does_not_normalise", "drops_the_scale"])
def test_each_property_of_the_router_is_caught_once_broken(w, fault):
    """The layer against the plain mixture is exact to 1e-5; with one
    property of the router broken in the plain mixture the two part by
    far more: a test that passes either way would test nothing."""
    y, _ = _layer(w)
    sound = _mixture(w, _weights_plain(w))
    assert float(jnp.abs(y - sound).max()) < 1e-5
    broken = _mixture(w, _weights_plain(w, **fault))
    assert float(jnp.abs(y - broken).max()) > 100 * 1e-5


def test_softmax_router_without_bias_or_normalisation(w):
    cfg = RouteConfig(E, 2, score="softmax", normalize=False, scale=1.0)
    experts, weights = dropless_route(w["x"], w["gate"], None, cfg)
    p = jax.nn.softmax(jnp.dot(w["x"], w["gate"], precision=_HI), -1)
    top = jnp.sort(p, -1)[:, ::-1][:, :2]
    np.testing.assert_allclose(weights, top, rtol=1e-6)
    with pytest.raises(ValueError, match="selection bias"):
        dropless_route(w["x"], w["gate"], w["bias"], cfg)
    with pytest.raises(ValueError, match="softmax or sigmoid"):
        RouteConfig(E, 2, score="tanh")


def test_the_four_shares_add_up_to_the_whole_layer(w):
    """32 experts, 8 a chip: what the four chips of the deployment each
    compute (their own experts' part of every token's mixture, nothing
    for the rest) sums to the uncut layer, and each part is the plain
    mixture over that share alone."""
    g = _weights_plain(w)
    whole = _mixture(w, g)
    parts, pairs = [], 0
    for rank in range(4):
        held = range(8 * rank, 8 * rank + 8)
        y, counts = _layer(w, held)
        np.testing.assert_allclose(y, _mixture(w, g, held), atol=1e-5)
        parts.append(y)
        pairs += int(counts[0])
    assert pairs == T * K                     # every pair, exactly once
    np.testing.assert_allclose(sum(parts), whole, atol=2e-5)
    uncut, counts = _layer(w)
    np.testing.assert_allclose(uncut, whole, atol=2e-5)
    assert int(counts[0]) == T * K
    assert float(jnp.abs(parts[0]).max()) > 0.01      # a share is not 0


def _frame(experts, held, live=None):
    return [np.asarray(a) for a in expert_rows(
        jnp.asarray(experts, jnp.int32), held, live)]


@pytest.mark.parametrize("case", ["all_to_one", "one_untouched", "ragged"])
def test_no_row_is_dropped_under_skew(case):
    """Every row to one expert; an expert with none; groups that are no
    multiple of a tile: each computed pair has a row of its own inside its
    expert's group, groups start on tiles in order, and the frame holds
    them with the slack a pass may run over."""
    n = 70
    held = range(4, 12)
    rng = np.random.default_rng(1)
    if case == "all_to_one":
        experts = np.tile(np.asarray([[6, 20, 21, 22]]), (n, 1))
    elif case == "one_untouched":
        experts = rng.choice([4, 5, 6, 8, 9, 10, 11], (n, 4))
    else:
        experts = np.stack([rng.permutation(16)[:4] for _ in range(n)])
    dest, starts, counts = _frame(experts, held)
    R = padded_rows(n * 4, len(held))
    here = (experts >= 4) & (experts < 12)
    assert counts.sum() == here.sum()
    np.testing.assert_array_equal(
        counts, [(experts == e).sum() for e in held])
    if case == "all_to_one":
        assert counts[2] == n and counts.sum() == n
    if case == "one_untouched":
        assert counts[3] == 0
    if case == "ragged":
        assert any(c % ROW_TILE for c in counts)
    assert (starts % ROW_TILE == 0).all()
    ends = starts + -(-counts // ROW_TILE) * ROW_TILE
    np.testing.assert_array_equal(starts[1:], ends[:-1])
    assert ends[-1] + PASS_SLACK <= R and R % ROW_TILE == 0
    assert (dest[~here] == -1).all()
    rows = dest[here]
    assert len(set(rows.tolist())) == rows.size          # one row a pair
    local = experts[here] - 4
    assert ((rows >= starts[local])
            & (rows < starts[local] + counts[local])).all()


def test_rows_that_are_not_live_are_not_routed(w):
    live = jnp.arange(T) % 3 != 0
    y, counts = _layer(w, range(8), live)
    g = _weights_plain(w) * live[:, None]
    np.testing.assert_allclose(y, _mixture(w, g, range(8)), atol=1e-5)
    assert float(jnp.abs(y[::3]).max()) == 0.0
    experts, _ = dropless_route(w["x"], w["gate"], w["bias"], ROUTE)
    assert int(counts[0]) == int(((experts < 8) & live[:, None]).sum())
    assert int(counts[1]) == len(set(np.asarray(
        experts)[np.asarray(live)].ravel().tolist()) & set(range(8)))


def test_a_rows_result_does_not_depend_on_its_neighbours(w):
    """Dropless: what a row gets is a function of the row alone, whoever
    shares the batch (a capacity-routed layer cannot say that)."""
    y, _ = _layer(w, range(8, 24))
    alone = dict(w, x=w["x"][7:8])
    experts, weights = dropless_route(alone["x"], w["gate"], w["bias"],
                                      ROUTE)
    one, _ = held_experts_mlp(alone["x"], experts, weights, w["w1"][8:24],
                              w["w3"][8:24], w["w2"][8:24], range(8, 24))
    np.testing.assert_allclose(one[0], y[7], atol=1e-6)


# ---- the kernel ------------------------------------------------------------

def _groups(counts, key=3):
    """A frame with groups of ``counts`` rows: random rows and gains in
    the groups, zero rows with a zero gain everywhere else."""
    counts = np.asarray(counts)
    n = len(counts)
    padded = -(-counts // ROW_TILE) * ROW_TILE
    starts = np.cumsum(padded) - padded
    R = padded_rows(int(counts.sum()), n)
    rows = np.arange(R)
    mine = np.zeros(R, bool)
    for s, c in zip(starts, counts):
        mine |= (rows >= s) & (rows < s + c)
    ks = jax.random.split(jax.random.key(key), 5)
    x = jnp.where(mine[:, None], jax.random.normal(ks[0], (R, H)), 0.0)
    gains = jnp.where(mine, jax.random.uniform(ks[1], (R,)) + 0.5, 0.0)
    mats = [0.1 * jax.random.normal(k, s) for k, s in zip(
        ks[2:], [(n, H, F), (n, H, F), (n, F, H)])]
    return (x, gains, *mats, jnp.asarray(starts, jnp.int32),
            jnp.asarray(counts, jnp.int32)), mine


@pytest.mark.parametrize("counts", [
    [12, 0, 3, 16, 17, 0, 1, 30], [0, 0, 0, 0], [0, 200, 0, 5],
    [33, 64, 65, 129]], ids=["serving", "none_touched", "one_hot_expert",
                             "every_pass_size"])
@pytest.mark.parametrize("block_f", [F, F // 2])
def test_kernel_in_interpret_mode_is_the_composite(counts, block_f):
    """Untouched experts (first, between and last), a group in every pass
    size, several whole passes of the largest, nothing at all: the kernel's
    rows are the composite's; a row outside every group is finite and,
    with its zero gain, 0."""
    args, mine = _groups(counts)
    assert max(counts) <= PASS_ROWS[-1] * 2
    with force_impl("xla"):
        want = moe_experts(*args)
    with force_impl("pallas"):
        got = moe_experts(*args, block_f=block_f)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert float(jnp.abs(got[~mine]).max() if (~mine).any() else 0) == 0.0
    if sum(counts):
        assert float(jnp.abs(want[mine]).max()) > 0.01


def test_kernel_refuses_a_frame_it_cannot_tile():
    args, _ = _groups([5, 5])
    with pytest.raises(ValueError, match="whole tiles"):
        moe_experts(args[0][:-3], args[1][:-3], *args[2:])
    with force_impl("pallas"), pytest.raises(ValueError, match="block_f"):
        moe_experts(*args, block_f=48)


# ---- compiled for a described v5e ------------------------------------------

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


@pytest.mark.parametrize("tokens", [96, 256], ids=["step", "prefill_chunk"])
def test_kernel_compiles_for_a_v5e_at_the_published_widths(topo, mosaic,
                                                           tokens):
    """`lfm2-8b-a1b`'s sparse layer on its chip: 8 experts of 2048 x 1792,
    top-4, the decode step's 96 rows and the prefill chunk's 256, bfloat16:
    Mosaic takes the kernel, blocks, scratch and the VMEM it asks for. A
    compile is not a chip run."""
    from jax.sharding import SingleDeviceSharding
    s1 = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=s1)
    h, f, n = 2048, 1792, 8
    R = padded_rows(tokens * 4, n)
    bf = jnp.bfloat16
    compiled = jax.jit(moe_experts).lower(
        sds((R, h), bf), sds((R,), jnp.float32), sds((n, h, f), bf),
        sds((n, h, f), bf), sds((n, f, h), bf), sds((n,), jnp.int32),
        sds((n,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%apex1_moe_experts" in text
