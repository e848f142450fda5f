"""Flash attention parity tests — Pallas kernel (interpret mode on the CPU
harness) vs the XLA composite gold, fwd + grads.

Reference test analogue: ``apex/contrib/test/fmha/test_fmha.py`` and
``apex/contrib/test/multihead_attn/*`` — hand-written python attention as
gold, per-kernel allclose at dtype tolerances (SURVEY.md §4.2.1).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex1_tpu.ops import force_impl
from apex1_tpu.ops.attention import flash_attention, fmha


def _qkv(rng, B=2, Hq=2, Hkv=None, Sq=48, Sk=None, D=16, dtype=jnp.float32):
    Hkv = Hq if Hkv is None else Hkv
    Sk = Sq if Sk is None else Sk
    q = jnp.asarray(rng.normal(size=(B, Hq, Sq, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, Sk, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, Sk, D)), dtype)
    return q, k, v


def _run(q, k, v, impl, **kw):
    with force_impl(impl):
        return flash_attention(q, k, v, **kw)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("gqa", [False, True])
def test_forward_parity(rng, causal, gqa):
    q, k, v = _qkv(rng, Hq=4, Hkv=2 if gqa else 4)
    got = _run(q, k, v, "pallas", causal=causal)
    want = _run(q, k, v, "xla", causal=causal)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_forward_parity_bf16(rng):
    q, k, v = _qkv(rng, dtype=jnp.bfloat16)
    got = _run(q, k, v, "pallas", causal=True).astype(jnp.float32)
    want = _run(q, k, v, "xla", causal=True).astype(jnp.float32)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_cross_attention_shapes(rng):
    q, k, v = _qkv(rng, Sq=24, Sk=56)
    got = _run(q, k, v, "pallas")
    want = _run(q, k, v, "xla")
    assert got.shape == (2, 2, 24, 16)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grad_parity(rng, causal):
    q, k, v = _qkv(rng, Sq=40)
    w = jnp.asarray(rng.normal(size=q.shape), jnp.float32)

    def loss(impl):
        def f(q, k, v):
            return jnp.sum(_run(q, k, v, impl, causal=causal) * w)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for g, gg in zip(loss("pallas"), loss("xla")):
        np.testing.assert_allclose(g, gg, rtol=1e-4, atol=1e-4)


def test_grad_parity_gqa(rng):
    q, k, v = _qkv(rng, Hq=4, Hkv=2)

    def grads(impl):
        def f(q, k, v):
            return jnp.sum(jnp.square(_run(q, k, v, impl, causal=True)))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for g, gg in zip(grads("pallas"), grads("xla")):
        np.testing.assert_allclose(g, gg, rtol=1e-4, atol=1e-4)


def test_segment_ids_varlen(rng):
    """Segments ≙ fmha's cu_seqlens: packed batch matches separate calls."""
    B, H, D = 1, 2, 16
    s1, s2 = 20, 28
    q, k, v = _qkv(rng, B=B, Hq=H, Sq=s1 + s2, D=D)
    seg = jnp.asarray([[0] * s1 + [1] * s2], jnp.int32)
    got = _run(q, k, v, "pallas", causal=True, segment_ids=seg)
    want = _run(q, k, v, "xla", causal=True, segment_ids=seg)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # piecewise reference: each segment attends only to itself
    for lo, hi in ((0, s1), (s1, s1 + s2)):
        piece = _run(q[:, :, lo:hi], k[:, :, lo:hi], v[:, :, lo:hi],
                     "xla", causal=True)
        np.testing.assert_allclose(got[:, :, lo:hi], piece,
                                   rtol=1e-5, atol=1e-5)


def test_segment_grad_parity(rng):
    q, k, v = _qkv(rng, B=2, Sq=32)
    seg = jnp.asarray(rng.integers(0, 3, size=(2, 32)), jnp.int32)
    seg = jnp.sort(seg, axis=1)

    def grads(impl):
        def f(q, k, v):
            return jnp.sum(jnp.square(
                _run(q, k, v, impl, segment_ids=seg)))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for g, gg in zip(grads("pallas"), grads("xla")):
        np.testing.assert_allclose(g, gg, rtol=1e-4, atol=1e-4)


def test_causal_offsets(rng):
    """Offsets shift the global causal positions (ring-attention blocks)."""
    S = 32
    q, k, v = _qkv(rng, B=1, Sq=S)
    # q shard holding global rows [32, 64), k shard holding cols [0, 32):
    # fully visible under causal → equals non-causal attention
    got = _run(q, k, v, "pallas", causal=True, q_offset=S, k_offset=0)
    want = _run(q, k, v, "xla", causal=False)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # k shard strictly in the future → fully masked, zero output, -inf lse
    out, lse = _run(q, k, v, "pallas", causal=True, q_offset=0, k_offset=S,
                    return_lse=True)
    np.testing.assert_allclose(out, jnp.zeros_like(out))
    assert np.all(np.asarray(lse) < -1e29)


def test_lse_and_its_grad(rng):
    """return_lse parity + the dlse VJP path (ring-merge differentiability)."""
    q, k, v = _qkv(rng, Sq=32)
    with force_impl("pallas"):
        out_p, lse_p = flash_attention(q, k, v, causal=True, return_lse=True)
    with force_impl("xla"):
        out_x, lse_x = flash_attention(q, k, v, causal=True, return_lse=True)
    np.testing.assert_allclose(lse_p, lse_x, rtol=1e-5, atol=1e-5)

    def loss(impl):
        def f(q, k, v):
            with force_impl(impl):
                out, lse = flash_attention(q, k, v, causal=True,
                                           return_lse=True)
            return jnp.sum(jnp.square(out)) + jnp.sum(jnp.sin(lse))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for g, gg in zip(loss("pallas"), loss("xla")):
        np.testing.assert_allclose(g, gg, rtol=1e-4, atol=1e-4)


def test_fmha_packed(rng):
    B, S, H, D = 2, 24, 2, 16
    qkv = jnp.asarray(rng.normal(size=(B, S, 3, H, D)), jnp.float32)
    with force_impl("pallas"):
        got = fmha(qkv, causal=True)
    with force_impl("xla"):
        want = fmha(qkv, causal=True)
    assert got.shape == (B, S, H, D)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("q_off,k_off", [(0, 0), (32, 0), (0, 32), (48, 16)])
def test_multiblock_causal_skip(rng, q_off, k_off):
    """Small explicit blocks force a multi-block grid so the causal
    block-skip predicate (fully-above-diagonal blocks bypassed) is
    exercised on every class of block: skipped, diagonal-partial, and
    fully-live — including shifted diagonals from ring-style offsets."""
    q, k, v = _qkv(rng, Sq=96, Sk=96)
    kw = dict(causal=True, q_offset=q_off, k_offset=k_off,
              block_q=16, block_k=32)

    def loss(impl):
        def f(q, k, v):
            with force_impl(impl):
                out = flash_attention(q, k, v, **kw)
            return jnp.sum(jnp.square(out.astype(jnp.float32)))
        return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)

    (lp, gp), (lx, gx) = loss("pallas"), loss("xla")
    np.testing.assert_allclose(lp, lx, rtol=1e-5)
    for a, b in zip(gp, gx):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


class TestAdditiveBias:
    """The flash kernel's additive-``bias`` operand (T5 rel-pos path):
    fwd and all four grads — including dbias through the dedicated
    broadcast-accumulating backward pass — must match the biased XLA
    composite for every broadcast layout."""

    @pytest.mark.parametrize("cfg", [
        dict(B=2, Hq=4, Hkv=4, Sq=48, Sk=48, Bb=1, Hb=4, causal=False),
        dict(B=2, Hq=4, Hkv=2, Sq=48, Sk=48, Bb=1, Hb=4, causal=True),
        dict(B=2, Hq=4, Hkv=4, Sq=40, Sk=56, Bb=2, Hb=4, causal=False),
        dict(B=1, Hq=2, Hkv=2, Sq=33, Sk=47, Bb=1, Hb=1, causal=False),
        dict(B=2, Hq=2, Hkv=2, Sq=96, Sk=96, Bb=1, Hb=2, causal=True,
             blocks=(16, 32)),  # multi-block grid + causal block skip
    ], ids=["full", "gqa-causal", "cross-batchbias", "ragged-bcast",
            "multiblock"])
    def test_grads_match_xla(self, rng, cfg):
        q, k, v = _qkv(rng, B=cfg["B"], Hq=cfg["Hq"], Hkv=cfg["Hkv"],
                       Sq=cfg["Sq"], Sk=cfg["Sk"], D=32)
        bias = jnp.asarray(
            rng.normal(size=(cfg["Bb"], cfg["Hb"], cfg["Sq"],
                             cfg["Sk"])), jnp.float32)
        kw = dict(causal=cfg["causal"], bias=bias)
        if "blocks" in cfg:
            kw.update(block_q=cfg["blocks"][0], block_k=cfg["blocks"][1])

        def loss(impl):
            def f(q, k, v, b):
                with force_impl(impl):
                    out = flash_attention(q, k, v, causal=cfg["causal"],
                                          bias=b,
                                          **({k_: v_ for k_, v_ in
                                              kw.items()
                                              if k_.startswith("block")}))
                return jnp.sum(jnp.square(out.astype(jnp.float32)))
            return jax.value_and_grad(f, argnums=(0, 1, 2, 3))(q, k, v,
                                                               bias)

        (lp, gp), (lx, gx) = loss("pallas"), loss("xla")
        np.testing.assert_allclose(lp, lx, rtol=1e-5)
        for name, a, b in zip(("dq", "dk", "dv", "dbias"), gp, gx):
            # dbias sums over batch x blocks: accumulation-order noise
            # ~1e-5 shows up at near-zero-gradient positions
            np.testing.assert_allclose(
                a, b, rtol=1e-4, atol=5e-5 if name == "dbias" else 1e-5,
                err_msg=name)

    def test_bias_with_segments(self, rng):
        """bias composes with varlen segment masking."""
        q, k, v = _qkv(rng, Sq=48)
        segs = jnp.asarray(
            np.repeat(np.arange(3), 16)[None].repeat(2, 0), jnp.int32)
        bias = jnp.asarray(rng.normal(size=(1, 2, 48, 48)), jnp.float32)

        def run(impl):
            def f(q, k, v, b):
                with force_impl(impl):
                    out = flash_attention(q, k, v, segment_ids=segs,
                                          bias=b)
                return jnp.sum(jnp.square(out.astype(jnp.float32)))
            return jax.value_and_grad(f, argnums=(0, 3))(q, k, v, bias)

        (lp, gp), (lx, gx) = run("pallas"), run("xla")
        np.testing.assert_allclose(lp, lx, rtol=1e-5)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    def test_bad_bias_shapes_raise(self, rng):
        q, k, v = _qkv(rng)
        with force_impl("pallas"):
            with pytest.raises(ValueError, match="bias"):
                flash_attention(q, k, v,
                                bias=jnp.zeros((3, 2, 48, 48)))
            with pytest.raises(ValueError, match="bias"):
                flash_attention(q, k, v, bias=jnp.zeros((48, 48)))


# ---------------------------------------------------------------------------
# the resident form: K/V (fwd, dq) or the group's Q rows (dkv) whole in VMEM,
# the pass over the other axis a loop in the kernel that stops at the diagonal
# ---------------------------------------------------------------------------

_OFFSETS = [(0, 0), (32, 0), (0, 32), (48, 16)]

# 80 keys in tiles of 32 (the last one padded), 80 queries in tiles of 16:
# with the offsets above every class of tile occurs: interior, crossed by
# the diagonal, last tile of a padded length, never visited
_TILE_CASES = {
    "base": dict(),
    "padded_rows": dict(Sq=72, Sk=96),
    "wide_q_tile": dict(block_q=32, block_k=16),
    "cross_lengths": dict(Sq=48, Sk=112),
    "gqa": dict(Hq=4, Hkv=2),
    "segments": dict(segs=True),
    "bias": dict(bias=True),
    "dropout": dict(dropout_p=0.25),
    "scale_pow2": dict(sm_scale=0.25),
    "scale_other": dict(sm_scale=0.3),
    "not_causal": dict(causal=False),
    "row_too_long": dict(budget=200_000),
    # tiles a multiple of the 128 lanes: dq turns the statistics' rows to
    # columns by a transpose, not by picking a diagonal
    "lane_tiles": dict(Sq=256, Sk=256, block_q=128, block_k=128),
}


def _grid_ranks(fn, *args):
    """Grid rank of every pallas_call under ``fn`` (custom VJPs opened)."""
    ranks = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                ranks.append(len(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return ranks


@pytest.mark.parametrize("q_off,k_off", _OFFSETS)
@pytest.mark.parametrize("case", sorted(_TILE_CASES))
def test_resident_tile_classes(rng, monkeypatch, case, q_off, k_off):
    """Forward and every gradient against the XLA composite over every
    class of tile the resident loop makes, times the ring's offsets."""
    c = dict(_TILE_CASES[case])
    Sq, Sk = c.pop("Sq", 80), c.pop("Sk", 80)
    q, k, v = _qkv(rng, Hq=c.pop("Hq", 2), Hkv=c.pop("Hkv", None), Sq=Sq,
                   Sk=Sk)
    kw = dict(causal=c.pop("causal", True), q_offset=q_off, k_offset=k_off,
              block_q=c.pop("block_q", 16), block_k=c.pop("block_k", 32))
    if c.pop("segs", False):
        kw["segment_ids"] = (jnp.asarray(np.arange(Sq) // 24)[None].repeat(2, 0),
                             jnp.asarray(np.arange(Sk) // 24)[None].repeat(2, 0))
    if "dropout_p" in c:
        kw.update(dropout_p=c.pop("dropout_p"), dropout_seed=1234)
    if "sm_scale" in c:
        kw["sm_scale"] = c.pop("sm_scale")
    bias = (jnp.asarray(rng.normal(size=(1, 2, Sq, Sk)), jnp.float32)
            if c.pop("bias", False) else None)
    if "budget" in c:
        # K/V rows (and the Q rows of dkv) no longer fit: the grid form
        import apex1_tpu.vmem_model as vm
        monkeypatch.setattr(vm, "budget_bytes", lambda *a: c["budget"])
    w = jnp.asarray(rng.normal(size=q.shape), jnp.float32)

    def loss(impl):
        def f(q, k, v, bias):
            with force_impl(impl):
                out, lse = flash_attention(q, k, v, bias=bias,
                                           return_lse=True, **kw)
            live = lse > -1e29     # empty rows carry the finite sentinel
            return (jnp.sum(out.astype(jnp.float32) * w)
                    + jnp.sum(jnp.where(live, lse, 0.0)))
        argnums = (0, 1, 2) + ((3,) if bias is not None else ())
        return jax.value_and_grad(f, argnums=argnums)(q, k, v, bias)

    (lp, gp), (lx, gx) = loss("pallas"), loss("xla")
    np.testing.assert_allclose(lp, lx, rtol=1e-5)
    for a, b in zip(gp, gx):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)

    # which form ran: fwd, dq, dkv (and dbias) by the rank of their grids
    with force_impl("pallas"):
        ranks = _grid_ranks(
            lambda q, k, v: jax.grad(
                lambda q, k, v: jnp.sum(flash_attention(
                    q, k, v, bias=bias, **kw)), argnums=(0, 1, 2))(q, k, v),
            q, k, v)
    grid_form = bias is not None or "budget" in c
    assert sorted(ranks) == ([4, 4, 5, 5] if bias is not None else
                             [4, 4, 5] if grid_form else [3, 3, 3])


def _brute_tiles(Sq, Sk, bq, bk, q_off, k_off, causal, diagonal=False):
    """{(qi, ki): 'interior' | 'masked' | 'never'} from the mask itself;
    ``diagonal``: instead the set of tiles whose mask IS the lower
    triangle, corner to corner (what the DIAGONAL body assumes)."""
    row = np.arange(-(-Sq // bq) * bq)[:, None]
    col = np.arange(-(-Sk // bk) * bk)[None, :]
    live = (row < Sq) & (col < Sk)
    if causal:
        live &= (col + k_off) <= (row + q_off)
    out, tril = {}, set()
    for qi in range(live.shape[0] // bq):
        for ki in range(live.shape[1] // bk):
            t = live[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            out[qi, ki] = ("interior" if t.all() else
                           "masked" if t.any() else "never")
            if bq == bk and (t == np.tri(bq, dtype=bool)).all():
                tril.add((qi, ki))
    return tril if diagonal else out


@pytest.mark.parametrize("q_off,k_off", _OFFSETS + [
    (0, 200), (200, 0), (256, 256), (512, 0), (576, 512)])
@pytest.mark.parametrize("Sq,Sk,bq,bk,causal", [
    (80, 80, 16, 32, True), (72, 96, 16, 32, True), (80, 80, 32, 16, True),
    (48, 112, 16, 32, True), (80, 80, 16, 32, False),
    (1024, 1024, 256, 256, True), (1024, 1024, 512, 512, True),
    (100, 60, 48, 16, True), (900, 900, 256, 256, True),
    (1024, 1024, 256, 256, False)])
def test_tile_plan_is_the_mask(Sq, Sk, bq, bk, causal, q_off, k_off):
    """The loop bounds the resident kernels run are exactly the tiles
    whose mask has a live element, and the unmasked ones exactly those
    whose mask is all live: by brute force from the mask. And the tiles
    `_on_diagonal` hands to the DIAGONAL body (in tiles of whole
    `DIAG_SUB` squares) are exactly those whose mask is the triangle."""
    from apex1_tpu.ops.attention import (DIAG_SUB, _key_tiles,
                                         _on_diagonal, _query_tiles,
                                         tile_plan)
    want = _brute_tiles(Sq, Sk, bq, bk, q_off, k_off, causal)
    n_q, n_k = -(-Sq // bq), -(-Sk // bk)
    args = (bq, bk, Sq, Sk, q_off, k_off, causal)
    by_q, by_k = {}, {}
    for qi in range(n_q):
        n_int, n_vis = _key_tiles(qi, *args)
        assert 0 <= n_int <= n_vis <= n_k
        for ki in range(n_k):
            by_q[qi, ki] = ("interior" if ki < n_int else
                            "masked" if ki < n_vis else "never")
    for ki in range(n_k):
        lo, a, b, hi = _query_tiles(ki, *args)
        assert 0 <= lo <= a <= b <= hi == n_q
        for qi in range(n_q):
            by_k[qi, ki] = ("never" if qi < lo else
                            "interior" if a <= qi < b else "masked")
    assert by_q == want
    assert by_k == want
    count = lambda cls: sum(1 for c in want.values() if c == cls)
    tril = _brute_tiles(Sq, Sk, bq, bk, q_off, k_off, causal, diagonal=True)
    assert tril <= {t for t, c in want.items() if c == "masked"}
    if bq == bk:    # the predicate is asked of square tiles only
        assert {t for t in want if causal and _on_diagonal(
            *t, bq, bk, Sq, Sk, q_off, k_off)} == tril
    assert tile_plan(Sq, Sk, bq, bk, q_off, k_off, causal) == (
        count("interior"), count("masked"),
        len(tril) if bq == bk and bq % DIAG_SUB == 0 else 0, count("never"))


def test_tile_plan_of_the_training_cell():
    """GPT-2 medium's call (S = 1024): the share of the square visited is
    (1 + 1/n_q) / 2, most visited tiles need no mask, and every masked
    one lies on the diagonal: (interior, masked, of which diagonal,
    never visited)."""
    from apex1_tpu.ops.attention import tile_plan
    assert tile_plan(1024, 1024, 512, 512) == (1, 2, 2, 1)
    assert tile_plan(1024, 1024, 256, 256) == (6, 4, 4, 6)
    assert tile_plan(1024, 1024, 128, 128) == (28, 8, 8, 28)
    assert tile_plan(1024, 1024, 512, 256) == (2, 4, 0, 2)


# ---------------------------------------------------------------------------
# the DIAGONAL body: a whole tile whose corner the diagonal passes through
# runs as a trapezoid of static 128-wide squares, masked on its diagonal ones
# ---------------------------------------------------------------------------

# what a case hands over, and ``takes``: whether the tiles of its masked
# runs take the DIAGONAL body ("all": every one; "none": not one at run
# time; "some": the whole ones do, the padded one not; "never": the call
# has no such body, statically)
_DIAG_CASES = {
    "rows_256": dict(layout="rows", b=256, S=512, takes="all"),
    "rows_512": dict(layout="rows", b=512, S=1024, takes="all"),
    "heads_256_gqa": dict(layout="heads", b=256, S=512, Hq=4, Hkv=2,
                          takes="all"),
    "heads_512_gqa": dict(layout="heads", b=512, S=1024, Hq=2, Hkv=1,
                          takes="all"),
    # a ring shard that attends itself: offsets equal, a tile's multiple
    "rows_256_aligned_offset": dict(layout="rows", b=256, S=512, q_off=256,
                                    k_off=256, takes="all"),
    "heads_256_aligned_offset": dict(layout="heads", b=256, S=512, Hq=2,
                                     Hkv=1, q_off=256, k_off=256,
                                     takes="all"),
    # the next shard's keys: a tile further down, still corner to corner
    "heads_256_offset_a_tile": dict(layout="heads", b=256, S=512, Hq=2,
                                    Hkv=1, q_off=256, k_off=0, takes="all"),
    "rows_256_misaligned_offset": dict(layout="rows", b=256, S=512,
                                       q_off=256 + 64, k_off=256,
                                       takes="none"),
    "heads_256_misaligned_offset": dict(layout="heads", b=256, S=512, Hq=2,
                                        Hkv=1, q_off=256 + 64, k_off=256,
                                        takes="none"),
    "rows_256_padded": dict(layout="rows", b=256, S=448, takes="some"),
    "heads_256_padded": dict(layout="heads", b=256, S=448, Hq=2, Hkv=1,
                             takes="some"),
    "rows_256_segments": dict(layout="rows", b=256, S=512, segs=True,
                              takes="never"),
    "heads_256_dropout": dict(layout="heads", b=256, S=512, Hq=2, Hkv=1,
                              dropout_p=0.25, takes="never"),
    "heads_256_wide_k": dict(layout="heads", b=256, bk=512, S=512, Hq=2,
                             Hkv=1, takes="never"),
}


def _conds_in_kernels(fn, *args):
    """How many `cond`s sit inside the pallas_calls under ``fn``."""
    found = []

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            kernel = inside or eqn.primitive.name == "pallas_call"
            found.append(kernel and eqn.primitive.name == "cond")
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, kernel)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
    return sum(found)


@pytest.mark.parametrize("case", sorted(_DIAG_CASES))
def test_diagonal_body(rng, monkeypatch, case):
    """The DIAGONAL body against the MASKED body on the same inputs and
    both against the XLA composite, forward and every gradient; and WHICH
    of the two a tile ran, by breaking the other: with `_mask_for` saying
    "nothing is live" a call whose masked runs are all diagonal tiles is
    still right, with `_fill_dead` killing the whole row a call none of
    whose tiles is on the diagonal is."""
    from apex1_tpu.ops import attention as A
    c = dict(_DIAG_CASES[case])
    layout, b, S, takes = c.pop("layout"), c.pop("b"), c.pop("S"), \
        c.pop("takes")
    kw = dict(causal=True, block_q=b, block_k=c.pop("bk", b),
              q_offset=c.pop("q_off", 0), k_offset=c.pop("k_off", 0))
    if c.pop("segs", False):
        kw["segment_ids"] = jnp.asarray(np.arange(S) // 200)[None]
    if "dropout_p" in c:
        kw.update(dropout_p=c.pop("dropout_p"), dropout_seed=7)
    if layout == "rows":
        args = (_packed(rng, 1, S, 2, 64),)
        call = lambda x: fmha(x, **kw)
        assert A.flash_form(2, 2, S, S, 64, packed=True, block_q=b,
                            block_k=b, dtype=jnp.float32)["layout"] == "rows"
    else:
        args = _qkv(rng, B=1, Hq=c.pop("Hq"), Hkv=c.pop("Hkv"), Sq=S, D=64)
        call = lambda q, k, v: flash_attention(q, k, v, **kw)
    assert not c
    with force_impl("xla"):
        w = jnp.asarray(rng.normal(size=jax.eval_shape(call, *args).shape),
                        jnp.float32)

    def run(impl, **broken):
        jax.clear_caches()      # the launches are jitted: a patched body
        with monkeypatch.context() as m:        # needs its own trace
            for name, fn in broken.items():
                m.setattr(A, name, fn)
            with force_impl(impl):
                out = jax.value_and_grad(
                    lambda *a: jnp.sum(call(*a).astype(jnp.float32) * w),
                    argnums=tuple(range(len(args))))(*args)
        jax.clear_caches()
        return out

    def same(a, b, rtol, atol):
        np.testing.assert_allclose(a[0], b[0], rtol=rtol)
        for x, y in zip(a[1], b[1]):
            np.testing.assert_allclose(x, y, rtol=rtol, atol=atol)

    got, gold = run("pallas"), run("xla")
    same(got, gold, 1e-4, 5e-5)
    # the parent's program: no call has a DIAGONAL body
    same(got, run("pallas", _diag_sub=lambda *a, **k: 0), 1e-5, 2e-6)
    plan = A.tile_plan(S, S, kw["block_q"], kw["block_k"], kw["q_offset"],
                       kw["k_offset"])
    with force_impl("pallas"):
        conds = _conds_in_kernels(jax.grad(
            lambda *a: jnp.sum(call(*a)), argnums=0), *args)
    # the rows layout's dk/dv has two of its own (its two steps a tile)
    conds -= 2 if layout == "rows" else 0
    if takes == "never":
        assert conds == 0
        return
    # one choice a masked run: the forward's, dq's and dk/dv's
    assert conds == 3
    assert plan[2] == dict(all=plan[1], none=0, some=1)[takes]
    if takes == "all":
        same(got, run("pallas", _mask_for=lambda *a, transposed=False, **k:
                      jnp.zeros((b, b), bool)), 1e-5, 2e-6)
    if takes == "none":
        same(got, run("pallas", _fill_dead=lambda x, tri, fill, *a, **k:
                      jnp.full_like(x, fill)), 1e-5, 2e-6)


# ---------------------------------------------------------------------------
# the ROWS layout: `fmha` reads the packed array as it lies (`flash_form`)
# ---------------------------------------------------------------------------

def _packed(rng, B, S, H, D, dtype=jnp.float32):
    return jnp.asarray(rng.normal(size=(B, S, 3, H, D)), dtype)


def _heads_path(qkv, **kw):
    """What `fmha` did before the rows layout, and still does where the
    layout does not apply: turn the array, run `flash_attention` (B, H,
    S, D), turn the result back."""
    q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
    return flash_attention(q, k, v, **kw).transpose(0, 2, 1, 3)


# head width -> heads to a 128-lane block; S = 80 in tiles of 16 x 32 is
# ragged on both sides (5 and 2.5 tiles), S = 96 in 32 x 32 is not
_ROW_CASES = {
    "two_to_a_block": dict(H=4, D=64),
    "four_to_a_block": dict(H=8, D=32),
    "one_to_a_block": dict(H=2, D=128),
    "whole_tiles": dict(H=2, D=64, S=96, block_q=32, block_k=32),
    "not_causal": dict(H=2, D=64, causal=False),
    "wide_q_tile": dict(H=2, D=64, block_q=32, block_k=16),
    "ring_offsets": dict(H=2, D=64, q_offset=48, k_offset=16),
    "keys_ahead": dict(H=2, D=64, q_offset=0, k_offset=32),
    "segments": dict(H=2, D=64, segs=True),
    "dropout": dict(H=4, D=64, dropout_p=0.25),
    "scale_other": dict(H=2, D=64, sm_scale=0.3),
    "lane_tiles": dict(H=2, D=64, S=256, block_q=128, block_k=128),
}


@pytest.mark.parametrize("case", sorted(_ROW_CASES))
def test_rows_layout_parity(rng, case):
    """`fmha` in the ROWS layout against the XLA composite AND against
    the (B, H, S, D) kernels, forward and the gradient of every third of
    the packed array (dq, dk, dv); the form read off `flash_form` and off
    the grids' ranks (forward and dq (b, j, qi), dk/dv (b, j, ki, c))."""
    from apex1_tpu.ops.attention import flash_form
    c = dict(_ROW_CASES[case])
    H, D, S = c.pop("H"), c.pop("D"), c.pop("S", 80)
    qkv = _packed(rng, 2, S, H, D)
    kw = dict(causal=c.pop("causal", True), block_q=c.pop("block_q", 16),
              block_k=c.pop("block_k", 32),
              q_offset=c.pop("q_offset", 0), k_offset=c.pop("k_offset", 0))
    if c.pop("segs", False):
        kw["segment_ids"] = jnp.asarray(np.arange(S) // 24)[None].repeat(2, 0)
    if "dropout_p" in c:
        # the mask is keyed on the TRUE head index: the same seed draws
        # the head layout's mask, or the gradients could not agree
        kw.update(dropout_p=c.pop("dropout_p"), dropout_seed=1234)
    if "sm_scale" in c:
        kw["sm_scale"] = c.pop("sm_scale")
    assert not c
    w = jnp.asarray(rng.normal(size=(2, S, H, D)), jnp.float32)

    def run(fn, impl):
        def f(qkv):
            with force_impl(impl):
                return jnp.sum(fn(qkv, **kw).astype(jnp.float32) * w)
        return jax.value_and_grad(f)(qkv)

    form = flash_form(H, H, S, S, D, packed=True, block_q=kw["block_q"],
                      block_k=kw["block_k"], dtype=jnp.float32)
    assert form == dict(layout="rows", heads_per_block=128 // D,
                        resident=(True, True),
                        blocks=(kw["block_q"], kw["block_k"]))
    (lr, gr), (lx, gx), (lh, gh) = (run(fmha, "pallas"), run(fmha, "xla"),
                                    run(_heads_path, "pallas"))
    np.testing.assert_allclose(lr, lx, rtol=1e-5)
    np.testing.assert_allclose(gr, gx, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(lr, lh, rtol=1e-5)
    np.testing.assert_allclose(gr, gh, rtol=1e-5, atol=2e-6)
    with force_impl("pallas"):
        ranks = _grid_ranks(jax.grad(lambda x: jnp.sum(fmha(x, **kw))), qkv)
    assert sorted(ranks) == [3, 3, 4]


def test_rows_layout_bf16_and_the_shapes_it_returns(rng):
    qkv = _packed(rng, 2, 48, 2, 64, jnp.bfloat16)
    with force_impl("pallas"):
        got, grad = jax.value_and_grad(
            lambda x: jnp.sum(fmha(x).astype(jnp.float32)))(qkv)
        out = fmha(qkv)
    with force_impl("xla"):
        want = fmha(qkv)
    assert out.shape == (2, 48, 2, 64) and out.dtype == jnp.bfloat16
    assert grad.shape == qkv.shape and grad.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=2e-2,
                               atol=2e-2)


@pytest.mark.parametrize("why,kw", [
    ("odd_heads", dict(H=3, D=64)),          # 3 heads do not fill 2-blocks
    ("wide_head", dict(H=2, D=80)),          # 128 % 80
    ("over_a_block", dict(H=2, D=256)),
    ("rows_too_long", dict(H=2, D=64, budget=200_000)),
])
def test_rows_layout_falls_back_to_the_heads_form(rng, monkeypatch, why, kw):
    """Where whole heads do not fill 128-lane blocks, or the rows do not
    fit VMEM, `fmha` turns its array and runs the (B, H, S, D) kernels:
    the parent's form, by `flash_form` and by the grids' ranks, and the
    composite's numbers."""
    from apex1_tpu.ops.attention import flash_form
    H, D = kw["H"], kw["D"]
    if "budget" in kw:
        import apex1_tpu.vmem_model as vm
        monkeypatch.setattr(vm, "budget_bytes", lambda *a: kw["budget"])
    form = flash_form(H, H, 80, 80, D, packed=True, block_q=16, block_k=32,
                      dtype=jnp.float32)
    assert form["layout"] == "heads" and form["heads_per_block"] == 1
    qkv = _packed(rng, 2, 80, H, D)
    call = lambda x: jnp.sum(fmha(x, block_q=16, block_k=32))
    with force_impl("pallas"):
        ranks = _grid_ranks(jax.grad(call), qkv)
        got = jax.grad(call)(qkv)
    assert sorted(ranks) == ([4, 4, 5] if "budget" in kw else [3, 3, 3])
    with force_impl("xla"):
        want = jax.grad(call)(qkv)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)


def test_flash_form_of_what_is_not_the_packed_array():
    """(B, H, S, D) operands have been turned already, a bias keeps the
    grid, and GQA in the rows layout is not taken: each is the heads
    form, whatever the head width."""
    from apex1_tpu.ops.attention import flash_form
    assert flash_form(16, 16, 1024, 1024, 64)["layout"] == "heads"
    assert flash_form(16, 16, 1024, 1024, 64, packed=True,
                      has_bias=True) == dict(
        layout="heads", heads_per_block=1, resident=(False, False),
        blocks=(512, 512))
    assert flash_form(32, 8, 1024, 1024, 64,
                      packed=True)["layout"] == "heads"


def test_flash_form_of_the_training_cell():
    """GPT-2 medium's call: 16 heads of 64 at S = 1024, bfloat16: two
    heads to a block, all three kernels resident, 512 x 512 tiles. GPT-2
    small's 12 heads fill six blocks the same way; four heads of 32 to a
    block at these tiles would not fit beside the row and fall back."""
    from apex1_tpu.ops.attention import flash_form
    want = dict(layout="rows", heads_per_block=2, resident=(True, True),
                blocks=(512, 512))
    assert flash_form(16, 16, 1024, 1024, 64, packed=True) == want
    assert flash_form(12, 12, 1024, 1024, 64, packed=True) == want
    assert flash_form(16, 16, 1024, 1024, 32,
                      packed=True)["layout"] == "heads"
    assert flash_form(16, 16, 1024, 1024, 32, packed=True, block_q=256,
                      block_k=256)["heads_per_block"] == 4


def test_flash_form_is_said_on_the_spine(rng, tmp_path):
    """A traced call says the form it took, once, as the counter
    `flash/form`: the rows layout for the packed array, the heads layout
    for (B, H, S, D); and the tiles a head's loop runs by body: "2 of 3
    on the diagonal at squares of 128" for a causal call in aligned
    tiles, none (every tile masked) for one with segment ids."""
    from apex1_tpu.obs import spine
    run = spine.ObsRun(str(tmp_path), component="test")
    old = spine.default_run()
    spine.set_default_run(run)
    try:
        with force_impl("pallas"):
            # shapes no other test uses: the calls are jitted, and a trace
            # another test made would say nothing here
            jax.grad(lambda x: jnp.sum(fmha(x, block_q=16, block_k=16)))(
                _packed(rng, 1, 40, 2, 64))
            q, k, v = _qkv(rng, B=1, Hq=3, Sq=40, D=16)
            flash_attention(q, k, v, block_q=16, block_k=16)
            q, k, v = _qkv(rng, B=1, Hq=1, Sq=512, D=16)
            kw = dict(causal=True, block_q=256, block_k=256)
            flash_attention(q, k, v, **kw)
            flash_attention(q, k, v, segment_ids=jnp.zeros((1, 512), int),
                            **kw)
    finally:
        spine.set_default_run(old)
        run.close()
    said = [e for e in spine.read_events(run.path, kinds=("counter",))
            if e["name"] == "flash/form"]
    assert [(e["layout"], e["heads_per_block"], e["resident_kv"],
             e["resident_q"], e["block_q"], e["block_k"]) for e in said] == [
        ("rows", 2, True, True, 16, 16), ("heads", 1, True, True, 16, 16),
        ("heads", 1, True, True, 256, 256),
        ("heads", 1, True, True, 256, 256)]
    assert [(e["diag_sub"], e["tiles_interior"], e["tiles_masked"],
             e["tiles_diagonal"]) for e in said] == [
        # 40 rows in tiles of 16, the last one padded: causal, then not
        (0, 1, 5, 0), (0, 4, 5, 0),
        (128, 1, 2, 2), (0, 0, 3, 0)]


def test_gpt2_training_path_takes_the_rows_layout(rng):
    """`models.gpt2.Block` hands the qkv product's output to `fmha`: with
    the kernels on, no transpose of a per-head array is left between the
    qkv product and `proj`, forward or backward."""
    from apex1_tpu.models.gpt2 import GPT2, GPT2Config
    model = GPT2(GPT2Config.tiny(num_heads=2, hidden_size=128))
    toks = jnp.asarray(rng.integers(0, 256, (2, 32)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)["params"]

    def loss(p):
        return jnp.sum(model.apply({"params": p}, toks).astype(jnp.float32))

    with force_impl("pallas"):
        text = str(jax.make_jaxpr(jax.grad(loss))(params))
        ranks = _grid_ranks(jax.grad(loss), params)
    assert "transpose[permutation=(0, 2, 1, 3)]" not in text
    # two layers: forward and dq (b, j, qi), dk/dv (b, j, ki, c)
    assert sorted(r for r in ranks if r >= 3) == [3, 3, 3, 3, 4, 4]
    with force_impl("xla"):
        assert "transpose[permutation=(0, 2, 1, 3)]" in str(
            jax.make_jaxpr(jax.grad(loss))(params))
