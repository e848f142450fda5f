"""`ops.decode_attend`: the serving step's attention as one kernel over
the dense pool, in interpret mode on the CPU.

- the attention equals the composite `cache_attend` (the off-TPU path
  and the parity gold) within the rounding of the pool's dtype, over
  ragged per-lane indices: 0, a block's last row, a block's first row,
  the pool's last position, chunks that straddle two blocks, GQA groups
  1 and 4, heads of 64 and 128, lanes marked idle;
- the append: after the call a leaf differs from its input in exactly
  rows ``idx[b] .. idx[b] + S - 1`` of LIVE lanes, bit for bit, and an
  idle lane is untouched;
- a WINDOW over a RING (a sliding-attention layer's leaf): kernel and
  composite against attention over the full history that the ring has
  partly forgotten, lanes shallower and deeper than the window, idle
  lanes, appends across the ring's end; windows and rings got wrong are
  seen; a ring that cannot hold its window is refused; without a window
  the kernel is traced as it was;
- `cached_attention` takes the kernel by what it sees in its input: a
  rank-1 index, `use_pallas()` and the row count; everything else is
  the composite;
- the engine built under `force_impl("pallas")` serves the tokens the
  composite engine serves, decode and verify.

What a CPU run cannot say (times, what the chip's compiler accepts) is
`tests/test_engine_aot.py`'s and the chip's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex1_tpu.models.generate import (cache_len, cache_write,
                                       cached_attention, init_cache)
from apex1_tpu.ops import _common
from apex1_tpu.ops.decode_attend import (DECODE_BLOCK, MAX_ROWS,
                                         check_decode_geometry,
                                         decode_attend)
from apex1_tpu.ops.paged_decode import cache_attend

BLK = DECODE_BLOCK


def _operands(B, Hq, Hkv, D, L, S, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(ks[0], (B, Hq, S, D), dtype),
            jax.random.normal(ks[1], (B, Hkv, S, D), dtype),
            jax.random.normal(ks[2], (B, Hkv, S, D), dtype),
            jax.random.normal(ks[3], (B, L, Hkv * D), dtype),
            jax.random.normal(ks[4], (B, L, Hkv * D), dtype))


def _ragged(L, S):
    """Per-lane indices that sit on every edge a block has: the first
    position, a block's last row and the next one's first, a chunk that
    straddles the two, the last position that fits, and two idle lanes."""
    return np.asarray([0, BLK - 1, BLK, BLK - S + 1 if S > 1 else 5,
                       -1, L - S, 2 * BLK - 2, -1], np.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("head", [64, 128])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify"])
def test_attention_equals_the_composite(s, group, head, dtype):
    Hkv, L = 2, 3 * BLK
    idx = _ragged(L, s)
    q, kn, vn, kp, vp = _operands(len(idx), Hkv * group, Hkv, head, L, s,
                                  dtype)
    want = cache_attend(q, cache_write(kp, kn, idx), cache_write(vp, vn, idx),
                        idx, sm_scale=0.2)
    with _common.force_impl("pallas"):
        got, _, _ = jax.jit(
            lambda *a: decode_attend(*a, sm_scale=0.2))(q, kn, vn, kp, vp,
                                                        jnp.asarray(idx))
    live = idx >= 0
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # float32: the flash fold against one softmax, at the ulp; bfloat16:
    # the probabilities are rounded before P.V on both sides, unnormalised
    # here and normalised there, so outputs may differ by an output step
    tol = (dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32
           else dict(rtol=0, atol=2 ** -6))
    np.testing.assert_allclose(got[live], want[live], **tol)
    assert not got[~live].any()          # an idle lane's rows are zero


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8],
                         ids=["f32", "bf16", "int8"])
@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify"])
def test_append_touches_the_new_rows_of_live_lanes_and_nothing_else(
        s, dtype):
    Hkv, D, L = 2, 64, 3 * BLK
    idx = _ragged(L, s)
    B = len(idx)
    pdt = jnp.float32 if dtype == jnp.int8 else dtype
    q, kn, vn, kp, vp = _operands(B, Hkv, Hkv, D, L, s, pdt)
    if dtype == jnp.int8:                 # the pool's capacity tier
        kn, vn, kp, vp = (jnp.clip(jnp.round(x * 30), -127, 127).astype(
            jnp.int8) for x in (kn, vn, kp, vp))
    with _common.force_impl("pallas"):
        _, k2, v2 = jax.jit(decode_attend)(q, kn, vn, kp, vp,
                                           jnp.asarray(idx))
    for new, before, after in ((kn, kp, k2), (vn, vp, v2)):
        want = np.asarray(before).copy()
        rows = np.asarray(new.astype(before.dtype)).transpose(
            0, 2, 1, 3).reshape(B, s, Hkv * D)
        for b in np.flatnonzero(idx >= 0):
            want[b, idx[b]:idx[b] + s] = rows[b]
        np.testing.assert_array_equal(np.asarray(after), want)
        assert after.dtype == before.dtype


def test_a_row_past_the_pools_end_is_dropped_like_the_composites():
    Hkv, D, L, s = 2, 64, 2 * BLK, 5
    idx = np.asarray([L - 2, 3], np.int32)      # rows L-2 .. L+2
    q, kn, vn, kp, vp = _operands(2, Hkv, Hkv, D, L, s, jnp.float32)
    with _common.force_impl("pallas"):
        _, k2, _ = jax.jit(decode_attend)(q, kn, vn, kp, vp,
                                          jnp.asarray(idx))
    np.testing.assert_array_equal(np.asarray(k2),
                                  np.asarray(cache_write(kp, kn, idx)))


@pytest.mark.parametrize("why", ["partial_block", "partial_tile",
                                 "too_many_rows", "over_budget"])
def test_geometry_is_refused_loudly(why, monkeypatch):
    ok = dict(length=3 * BLK, lanes=1024, rows=16, s=1, dtype=jnp.bfloat16)
    assert check_decode_geometry(**ok) == (BLK, 16, 16)
    bad = {"partial_block": dict(ok, length=3 * BLK - 1),
           "partial_tile": dict(ok, length=24),     # one block, 1.5 tiles
           "too_many_rows": dict(ok, s=BLK),
           "over_budget": ok}[why]
    if why == "over_budget":
        from apex1_tpu import vmem_model
        monkeypatch.setattr(vmem_model, "budget_bytes", lambda g=None: 1)
    with pytest.raises(ValueError, match="decode_attend"):
        check_decode_geometry(**bad)


# -- a window over a ring ---------------------------------------------------

def _history(B, Hkv, D, P, dtype, seed=3):
    """Every position's K and V row of B lanes, (B, P, Hkv * D) each."""
    kk, kv = jax.random.split(jax.random.key(seed))
    return (np.asarray(jax.random.normal(kk, (B, P, Hkv * D), dtype)),
            np.asarray(jax.random.normal(kv, (B, P, Hkv * D), dtype)))


def _ring_case(idx, S, L, Hq, Hkv, D, dtype):
    """Operands of a call at per-lane positions ``idx`` over rings of ``L``
    rows: the ring holds what a lane's earlier calls left there (position
    p in row p mod L, the newest wins; rows never written hold noise), the
    new rows are positions idx .. idx + S - 1 of the same history."""
    B, P = len(idx), max(max(idx), 0) + S
    hk, hv = _history(B, Hkv, D, P, dtype)
    noise = jax.random.normal(jax.random.key(9), (2, B, L, Hkv * D), dtype)
    kp, vp = np.asarray(noise[0]).copy(), np.asarray(noise[1]).copy()
    for b, i in enumerate(idx):
        for p_ in range(max(i - L, 0), max(i, 0)):
            kp[b, p_ % L], vp[b, p_ % L] = hk[b, p_], hv[b, p_]
    new = lambda h: jnp.asarray(np.stack(
        [h[b, max(i, 0):max(i, 0) + S] for b, i in enumerate(idx)])
        .reshape(B, S, Hkv, D).transpose(0, 2, 1, 3))
    q = jax.random.normal(jax.random.key(4), (B, Hq, S, D), dtype)
    return q, new(hk), new(hv), jnp.asarray(kp), jnp.asarray(vp), hk, hv


def _windowed_gold(q, hk, hv, idx, window, scale):
    """Softmax attention over the FULL history in float64, query j of lane
    b at position idx[b] + j seeing the ``window`` positions up to its
    own: no ring, no block."""
    q = np.asarray(q, np.float64)
    B, Hq, S, D = q.shape
    G = Hq // (hk.shape[2] // D)
    out = np.zeros(q.shape)
    for b in np.flatnonzero(np.asarray(idx) >= 0):
        for h in range(Hq):
            at = slice((h // G) * D, (h // G + 1) * D)
            for j in range(S):
                t = idx[b] + j
                lo = max(t - window + 1, 0)
                s = hk[b, lo:t + 1, at].astype(np.float64) @ q[b, h, j] \
                    * scale
                w = np.exp(s - s.max())
                out[b, h, j] = w / w.sum() @ hv[b, lo:t + 1, at].astype(
                    np.float64)
    return out


#: under a window of 2 blocks + 40 over a ring of 3 blocks: the first
#: position, inside the first window, the window's last and first full
#: positions, a block's two edges, the ring's last row and the row that
#: wraps onto its first, a chunk across the ring's end, idle lanes, and
#: lanes three to five times round the ring
_RING, _WINDOW = 3 * BLK, 2 * BLK + 40


def _ring_idx(S):
    return [0, 5, BLK - 1, BLK, _WINDOW - 1, _WINDOW, _WINDOW + 1,
            _RING - 1, _RING - S + 1 if S > 1 else _RING - 3, _RING, -1,
            _RING + 1, 2 * _RING + BLK - 2, 3 * _RING - 1,
            5 * _RING + 77, -1, 2 * _RING - S + 2]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify"])
def test_a_window_over_a_ring_equals_attention_over_the_full_history(
        s, dtype):
    """The kernel (interpreted) and the composite, both over the ring,
    against the history that the ring has partly forgotten: lanes
    shallower and deeper than the window, idle lanes, appends that cross
    the ring's end. And the append: the rings differ from their inputs in
    the new rows alone, where `cache_write` puts them."""
    idx = _ring_idx(s)
    q, kn, vn, kp, vp, hk, hv = _ring_case(idx, s, _RING, 8, 2, 64, dtype)
    ix = jnp.asarray(idx, jnp.int32)
    want = _windowed_gold(q, hk, hv, idx, _WINDOW, 0.2)
    k_gold = cache_write(kp, kn, ix, ring=True)
    v_gold = cache_write(vp, vn, ix, ring=True)
    comp = cache_attend(q, k_gold, v_gold, ix, sm_scale=0.2, window=_WINDOW)
    with _common.force_impl("pallas"):
        got, k2, v2 = jax.jit(lambda *a: decode_attend(
            *a, sm_scale=0.2, window=_WINDOW))(q, kn, vn, kp, vp, ix)
    live = np.asarray(idx) >= 0
    tol = 2e-5 if dtype == jnp.float32 else 2 ** -5
    for name, out in (("composite", comp), ("kernel", got)):
        err = np.abs(np.asarray(out, np.float64)[live] - want[live]).max()
        assert err < tol, (name, err)
    assert not np.asarray(got, np.float32)[~live].any()
    np.testing.assert_array_equal(np.asarray(k2), np.asarray(k_gold))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v_gold))
    changed = np.any(np.asarray(k2) != np.asarray(kp), axis=-1)
    assert changed.sum() == s * live.sum() and not changed[~live].any()
    # a scalar index (the prefill chunk's path) writes and reads the same
    for b in (9, 14, 16):                       # at, and past, the wrap
        one = cache_write(kp[b:b + 1], kn[b:b + 1], ix[b], ring=True)
        np.testing.assert_array_equal(np.asarray(one)[0],
                                      np.asarray(k_gold)[b])
        out = cache_attend(q[b:b + 1], one, v_gold[b:b + 1], ix[b],
                           sm_scale=0.2, window=_WINDOW)
        assert np.abs(np.asarray(out, np.float64)[0] - want[b]).max() < tol


@pytest.mark.parametrize("broken", ["no_window", "one_wider", "one_narrower",
                                    "ring_as_a_line"])
def test_a_window_or_a_ring_got_wrong_is_seen(broken):
    """What the comparison above would catch: the causal mask alone, a
    window one position off either way, and a ring read as if row r held
    position r."""
    idx = [_WINDOW + 9, 2 * _RING + 5, 3 * _RING - 1]
    q, kn, vn, kp, vp, hk, hv = _ring_case(idx, 1, _RING, 8, 2, 64,
                                           jnp.float32)
    ix = jnp.asarray(idx, jnp.int32)
    want = _windowed_gold(q, hk, hv, idx, _WINDOW, 0.2)
    k_all = cache_write(kp, kn, ix, ring=True)
    v_all = cache_write(vp, vn, ix, ring=True)
    if broken == "ring_as_a_line":
        got = cache_attend(q, k_all, v_all, ix % _RING, sm_scale=0.2)
    else:
        w = {"no_window": _RING, "one_wider": _WINDOW + 1,
             "one_narrower": _WINDOW - 1}[broken]
        with _common.force_impl("pallas"):
            got, _, _ = jax.jit(lambda *a: decode_attend(
                *a, sm_scale=0.2, window=w))(q, kn, vn, kp, vp, ix)
    assert np.abs(np.asarray(got, np.float64) - want).max() > 1e-3


def test_the_kernel_reads_no_block_wholly_below_the_window():
    """Every ring block that holds no position of a lane's window is
    filled with NaN: a block the kernel fetched would poison the lane's
    output (0 x NaN in P.V) even where the mask hides its scores. And the
    arithmetic of the walk at the published window: 2048 / 128 + 1 blocks
    at most, whatever the depth."""
    window, ring = BLK + 8, 4 * BLK
    idx = [5, 2 * BLK + 3, ring + 2 * BLK + 60, 3 * ring + 17]
    q, kn, vn, kp, vp, hk, hv = _ring_case(idx, 1, ring, 8, 2, 64,
                                           jnp.float32)
    kp, vp = np.asarray(kp).copy(), np.asarray(vp).copy()
    for b, i in enumerate(idx):
        held = {(p_ // BLK) % 4 for p_ in range(max(i - window + 1, 0),
                                                i + 1)}
        assert 1 <= len(held) <= 3
        for blk in set(range(4)) - held:
            kp[b, blk * BLK:(blk + 1) * BLK] = np.nan
            vp[b, blk * BLK:(blk + 1) * BLK] = np.nan
    with _common.force_impl("pallas"):
        got, _, _ = jax.jit(lambda *a: decode_attend(
            *a, sm_scale=0.2, window=window))(
                q, kn, vn, jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(idx, jnp.int32))
    want = _windowed_gold(q, hk, hv, idx, window, 0.2)
    assert np.abs(np.asarray(got, np.float64) - want).max() < 2e-5
    for depth in (0, 100, 2047, 2048, 2049, 2175, 4096, 4223, 8703):
        first = max(depth - 2048 + 1, 0) // BLK
        assert depth // BLK + 1 - first <= 2048 // BLK + 1


@pytest.mark.parametrize("why", ["ring_shorter_than_window_and_rows",
                                 "one_block_ring", "window_of_nothing"])
def test_a_ring_that_cannot_hold_its_window_is_refused(why):
    ok = dict(length=3 * BLK, lanes=512, rows=32, s=1, dtype=jnp.bfloat16)
    assert check_decode_geometry(**ok, window=2 * BLK + 40) == (BLK, 16, 32)
    # a leaf no longer than its window never wraps: any geometry a leaf
    # without a window may have
    assert check_decode_geometry(**dict(ok, length=BLK), window=BLK)
    bad = {"ring_shorter_than_window_and_rows":
           dict(ok, s=5, rows=160, window=3 * BLK - 3),
           "one_block_ring": dict(ok, length=BLK, window=BLK - 8),
           "window_of_nothing": dict(ok, window=0)}[why]
    with pytest.raises(ValueError, match="ring"):
        check_decode_geometry(**bad)
    q = jnp.ones((1, 2, 40, 64), jnp.float32)
    cache = init_cache(1, 1, 2, 2 * BLK, 64, jnp.float32)["layer0"]
    with pytest.raises(ValueError, match="ring"):
        cached_attention(q, q, q, cache, 0, window=2 * BLK - 20)


def test_without_a_window_the_kernel_is_traced_as_before():
    """No window: the call's jaxpr is the one the kernel had before it
    knew of windows, op for op (its sha256 at 9111a1f, the commit before,
    read there with this very call), so the three accepted serving cells'
    step programs lower to their parents' text. A later PR that changes
    the kernel on purpose reads it anew, and says so."""
    import hashlib
    idx = jnp.asarray([3, -1], jnp.int32)
    q, kn, _, kp, _ = _operands(2, 4, 2, 64, 2 * BLK, 1, jnp.float32)
    with _common.force_impl("pallas"):
        plain = str(jax.make_jaxpr(decode_attend)(q, kn, kn, kp, kp, idx))
        ring = str(jax.make_jaxpr(lambda *a: decode_attend(
            *a, window=BLK + 8))(q, kn, kn, kp, kp, idx))
    assert hashlib.sha256(plain.encode()).hexdigest()[:16] \
        == "f9facd897752e3e5"
    assert ring.count(" rem ") > plain.count(" rem ")


def _kernels_in(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("apex1_decode_attend")


@pytest.mark.parametrize("case", [
    "per_row_index", "verify_chunk", "scalar_index", "off_tpu", "bias",
    "valid_start", "too_many_rows", "prefill", "window", "window_scalar"])
def test_cached_attention_chooses_by_what_it_sees(case):
    """A rank-1 index where the kernels run, within the row bound: the
    kernel. A scalar index, a CPU, a bias, a left-pad mask, more rows
    than the kernel takes, a flash prefill: the composite."""
    B, H, D, L = 2, 2, 64, 2 * BLK
    S = {"verify_chunk": 3, "too_many_rows": MAX_ROWS // H + 1,
         "prefill": 4}.get(case, 1)
    q = jnp.ones((B, H, S, D), jnp.float32)
    cache = init_cache(1, B, H, L, D, jnp.float32)["layer0"]
    idx = jnp.asarray(0 if case in ("scalar_index", "prefill",
                                    "window_scalar")
                      else [3, -1], jnp.int32)
    kw = {"bias": dict(bias=jnp.zeros((1, H, 1, L))),
          "valid_start": dict(valid_start=jnp.zeros((B,), jnp.int32)),
          "window": dict(window=BLK + 8), "window_scalar": dict(window=BLK),
          "prefill": {}}.get(case, dict(chunk_decode=True))
    call = lambda q, cache, idx: cached_attention(q, q, q, cache, idx, **kw)
    with _common.force_impl("xla" if case == "off_tpu" else "pallas"):
        n = _kernels_in(call, q, cache, idx)
    assert n == (1 if case in ("per_row_index", "verify_chunk", "window")
                 else 0)


def test_the_stored_form_is_made_in_one_place():
    """(B, S_max, Hkv * D), a position's heads side by side in one row;
    the page form only where the paged pool asks for it."""
    cache = init_cache(2, 3, 4, 40, 64, jnp.bfloat16)
    assert sorted(cache) == ["layer0", "layer1"]
    assert cache["layer0"]["k"].shape == (3, 40, 256)
    assert cache_len(cache) == cache_len(cache["layer1"]["v"]) == 40
    pages = init_cache(1, 9, 4, 16, 64, jnp.int8, page_form=True)
    assert pages["layer0"]["v"].shape == (9, 4, 16, 64)


# -- the engine's step through the kernel ------------------------------------

@pytest.fixture(scope="module")
def tiny():
    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.generate import gpt2_decoder
    from apex1_tpu.models.gpt2 import GPT2, GPT2Config
    cfg = GPT2Config.tiny(policy=get_policy("O0"), max_seq_len=64)
    model = GPT2(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 7), jnp.int32))["params"]
    return (cfg, params) + gpt2_decoder(model)


@pytest.mark.parametrize("num_draft", [0, 3], ids=["decode", "verify"])
def test_engine_serves_the_composites_tokens_through_the_kernel(
        tiny, num_draft):
    """Requests that join and leave at different depths, one lane idle
    throughout: the engine whose step runs the kernel (interpreted) and
    the one whose step runs the composite emit the same tokens."""
    from apex1_tpu.serving.engine import Engine, EngineConfig
    cfg, params, apply_fn, make_cache = tiny
    rng = np.random.default_rng(5)
    plan = [(9, 6), (3, 8), (14, 4), (5, 5)]
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).tolist()
               for n, _ in plan]

    def serve(impl):
        with _common.force_impl(impl):
            eng = Engine(apply_fn, make_cache, params, EngineConfig(
                max_slots=3, max_len=40, prefill_chunk=4,
                num_draft=num_draft, vocab_size=cfg.vocab_size))
            ids = [eng.submit(prompts[0], max_new_tokens=plan[0][1])]
            eng.step()
            ids += [eng.submit(p, max_new_tokens=n)
                    for p, (_, n) in zip(prompts[1:], plan[1:])]
            eng.run(max_steps=200)
        return [eng.results[i].tokens.tolist() for i in ids]

    assert serve("pallas") == serve("xla")
