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
  the two-buffer schedule is traced as the kernel was before its queue;
- the QUEUE of fetches (PR 50): at every depth the serving cells derive,
  and at 2, 3 and 8, the attention and both leaves are the two-buffer
  schedule's bit for bit, over idle lanes inside, first and last, lanes
  of one block, a ring at its wrap and past three windows, a ring block
  read twice, chunks across an edge and the last lane; the order in which
  fetches start (a model of the kernel's cursor) visits every (lane,
  block) once, in the order they are consumed, never a block below a
  window, never into a buffer still owned, nothing past the end; the
  depth follows the row's bytes and the VMEM budget alone;
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
from apex1_tpu.ops import decode_attend as da
from apex1_tpu.ops.decode_attend import (DECODE_BLOCK, FETCH_BYTES, MAX_ROWS,
                                         check_decode_geometry,
                                         decode_attend, fetch_depth)
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


def _scheduled(window=None, **schedule):
    """`decode_attend` with the inner call's schedule forced: what only
    the parity checks do (the public call derives the depth from bytes)."""
    def call(q, kn, vn, kp, vp, idx):
        _, Hq, S, D = q.shape
        _, L, HD = kp.shape
        return da._decode_attend(
            q, kn, vn, kp, vp, jnp.asarray(idx, jnp.int32), scale=0.2,
            geometry=check_decode_geometry(L, HD, Hq * S, S, kp.dtype,
                                           window),
            interpret=True, **schedule,
            **({} if window is None else {"window": window}))
    return call


def test_without_a_window_the_two_buffer_schedule_is_traced_as_before():
    """The schedule before the queue (`run_on=False`, kept for the parity
    checks on the chip) is the kernel the three older serving cells ran,
    op for op: its jaxpr's sha256 is the one read at 9111a1f, before the
    kernel knew of windows or queues. The queue's own text is read anew
    by PR 50 (a later PR that changes the kernel on purpose reads it
    again, and says so); a ring still costs it a `rem` a block."""
    import hashlib
    idx = jnp.asarray([3, -1], jnp.int32)
    q, kn, _, kp, _ = _operands(2, 4, 2, 64, 2 * BLK, 1, jnp.float32)
    sha = lambda text: hashlib.sha256(text.encode()).hexdigest()[:16]

    def two_buffer(*a, **kw):
        with pytest.MonkeyPatch.context() as mp:
            inner = da._decode_attend
            mp.setattr(da, "_decode_attend", lambda *x, **k: inner(
                *x, **dict(k, depth=2, run_on=False)))
            return decode_attend(*a, **kw)

    with _common.force_impl("pallas"):
        before = str(jax.make_jaxpr(two_buffer)(q, kn, kn, kp, kp, idx))
        plain = str(jax.make_jaxpr(lambda *a: decode_attend(*a))(
            q, kn, kn, kp, kp, idx))
        ring = str(jax.make_jaxpr(lambda *a: decode_attend(
            *a, window=BLK + 8))(q, kn, kn, kp, kp, idx))
    assert sha(before) == "f9facd897752e3e5"
    assert sha(plain) == "c81b8ed74f54912f"
    assert ring.count(" rem ") > plain.count(" rem ")


# -- the queue of fetches ------------------------------------------------------

#: the depths the four serving cells' rows derive (GPT-2's 1024 lanes of
#: bfloat16: 4; granite's, lfm2's and Trinity-Mini's 512: 8), and 2, 3, 8
_DEPTHS = sorted({2, 3, 8, fetch_depth(1024, jnp.bfloat16),
                  fetch_depth(512, jnp.bfloat16)})

_QUEUE_CASES = {
    # idle lanes inside, every edge of a block, the last row that fits
    "ragged": lambda S: (None, 3 * BLK, _ragged(3 * BLK, S)),
    # idle lanes first and last, one live lane among idle ones
    "idle_ends": lambda S: (None, 3 * BLK, [-1, -1, 2 * BLK + 9, -1, -1,
                                            -1, 7, -1]),
    # lanes of one block, more of them than any queue is deep, then deep
    # lanes, and a one-block lane last
    "one_block_lanes": lambda S: (None, 3 * BLK, [3, 0, 100, BLK - S, 5, 64,
                                                  1, 2, 77, 9, 3 * BLK - S,
                                                  2 * BLK, 4]),
    "all_idle": lambda S: (None, 2 * BLK, [-1, -1, -1]),
    "one_lane": lambda S: (None, 3 * BLK, [2 * BLK + 5]),
    # shallower than the window, at the wrap, past three windows
    "ring": lambda S: (_WINDOW, _RING, _ring_idx(S)),
    # a ring of two blocks under a window that spans three: the block
    # that holds both ends of the window is read twice in one walk
    "ring_read_twice": lambda S: (BLK + 72, 2 * BLK, [
        2 * BLK + 70 - S, 5 * BLK + 100 - S, -1, 3, 7 * BLK + 71 - S,
        BLK + 71]),
}


@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify"])
@pytest.mark.parametrize("case", sorted(_QUEUE_CASES))
@pytest.mark.parametrize("depth", _DEPTHS)
def test_the_queue_gives_the_two_buffer_schedules_bits(depth, case, s):
    """WHEN a block is fetched moves, nothing else: the attention and
    both leaves equal the two-buffer schedule's bit for bit, whatever
    the other lanes hold."""
    window, L, idx = _QUEUE_CASES[case](s)
    if window is None:
        q, kn, vn, kp, vp = _operands(len(idx), 8, 2, 64, L, s, jnp.bfloat16)
    else:
        q, kn, vn, kp, vp, _, _ = _ring_case(idx, s, L, 8, 2, 64,
                                             jnp.bfloat16)
    ix = jnp.asarray(idx, jnp.int32)
    want = jax.jit(_scheduled(window, depth=2, run_on=False))(
        q, kn, vn, kp, vp, ix)
    got = jax.jit(_scheduled(window, depth=depth))(q, kn, vn, kp, vp, ix)
    for name, g, w in zip(("attention", "k", "v"), got, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32), name)
    if window is None and case != "all_idle":
        np.testing.assert_array_equal(
            np.asarray(got[1], np.float32),
            np.asarray(cache_write(kp, kn, ix), np.float32))


def test_the_two_buffer_schedule_is_two_deep():
    q, kn, vn, kp, vp = _operands(2, 2, 2, 64, 2 * BLK, 1, jnp.float32)
    with pytest.raises(ValueError, match="two deep"):
        _scheduled(depth=3, run_on=False)(q, kn, vn, kp, vp, [3, 4])


def _fetch_model(idx, S, L, window, depth):
    """The kernel's cursor, step for step, on the host: ``(started,
    consumed)``, two lists of ``(grid step, lane, block of the lane's
    walk, block of the leaf, buffer)`` in the order the kernel starts its
    fetches and waits for them; a started one also says how many blocks
    had been consumed when it was."""
    B, n_ring = len(idx), L // BLK

    def walk(ix):
        if window is None:
            return 0, min((ix + S - 1) // BLK + 1, n_ring)
        first = max(ix - window + 1, 0) // BLK
        return first, (ix + S - 1) // BLK + 1 - first

    def next_live(c):
        while c < B and idx[c] < 0:
            c += 1
        return c

    started, consumed = [], []
    cur = {}

    def issue(step, c, j, slot):
        if c >= B:
            return c, j
        first, n = walk(idx[c])
        started.append((step, c, j, (first + j) % n_ring, slot,
                        len(consumed)))
        return (next_live(c + 1), 0) if j + 1 >= n else (c, j + 1)

    for b in range(B):
        if b == 0:
            c, j = next_live(0), 0
            for k in range(depth - 1):
                c, j = issue(0, c, j, k)
            cur = dict(c=c, j=j, slot=0)
        if idx[b] < 0:
            continue
        first, n = walk(idx[b])
        for i in range(n):
            slot = cur["slot"]
            cur["c"], cur["j"] = issue(b, cur["c"], cur["j"],
                                       (slot or depth) - 1)
            consumed.append((b, b, i, (first + i) % n_ring, slot))
            cur["slot"] = 0 if slot == depth - 1 else slot + 1
    return started, consumed


@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify"])
@pytest.mark.parametrize("case", sorted(_QUEUE_CASES))
@pytest.mark.parametrize("depth", _DEPTHS)
def test_the_fetches_start_in_the_order_they_are_consumed(depth, case, s):
    """Every (lane, block) of the call is fetched exactly once and into
    the buffer it is awaited in, in the order the lanes consume them;
    never more than ``depth`` in flight, so never into a buffer whose
    block is not consumed yet; never a block below a window or past a
    pool's end; nothing once the last live lane's last block is started.
    And `serving.engine.fetch_ahead`, which the step span's
    ``kv_fetch_ahead`` is, counts the fetches an earlier lane's grid step
    started."""
    from apex1_tpu.serving.engine import fetch_ahead
    window, L, idx = _QUEUE_CASES[case](s)
    started, consumed = _fetch_model(idx, s, L, window, depth)
    assert [x[1:5] for x in started] == [x[1:] for x in consumed]
    want = []
    for b, ix in enumerate(idx):
        if ix < 0:
            continue
        lo = 0 if window is None else max(ix - window + 1, 0) // BLK
        hi = (ix + s - 1) // BLK if window is not None \
            else min((ix + s - 1) // BLK, L // BLK - 1)
        want += [(b, a - lo, a % (L // BLK)) for a in range(lo, hi + 1)]
    assert [x[1:4] for x in started] == want
    # the k-th fetch starts before the k-th block is awaited, and only
    # once the block that had its buffer (the one depth before it) has
    # been consumed: at most depth fetches are ever in flight
    for k, (step, lane, _, _, slot, done) in enumerate(started):
        assert step <= lane and k - depth < done <= k
        assert k < depth or consumed[k - depth][4] == slot
    live = [b for b, ix in enumerate(idx) if ix >= 0]
    blocks = [sum(1 for x in consumed if x[1] == b) for b in live]
    assert fetch_ahead(live, blocks, depth) == sum(
        1 for step, lane, *_ in started if step < lane)
    if depth > 2 and case == "one_block_lanes":
        assert fetch_ahead(live, blocks, depth) > len(live)


@pytest.mark.parametrize("lanes,dtype,depth", [
    (1024, jnp.bfloat16, 4), (512, jnp.bfloat16, 8), (512, jnp.int8, 16),
    (512, jnp.float32, 4), (4096, jnp.bfloat16, 2), (8192, jnp.float32, 2)])
def test_the_queues_depth_follows_the_rows_bytes_and_the_budget(
        lanes, dtype, depth, monkeypatch):
    """`FETCH_BYTES` of K and V blocks in flight, two fetches at least, a
    quarter of the VMEM budget at most: GPT-2's rows of 1024 lanes get
    half the depth of the 512-lane rows of granite, lfm2 and
    Trinity-Mini. Nothing else enters: not the lanes of the batch, the
    heads, the window or the leaf's length (in whole blocks)."""
    import inspect
    from apex1_tpu import vmem_model
    assert fetch_depth(lanes, dtype) == depth
    assert fetch_depth(lanes, dtype, 70 * BLK) == depth
    pair = 2 * BLK * lanes * jnp.dtype(dtype).itemsize
    assert depth == max(2, FETCH_BYTES // pair)
    assert list(inspect.signature(fetch_depth).parameters) == [
        "lanes", "dtype", "length"]
    monkeypatch.setattr(vmem_model, "budget_bytes",
                        lambda g=None: 4 * 3 * pair)
    assert fetch_depth(lanes, dtype) == min(depth, 3)
    src = inspect.getsource(da)
    assert "environ" not in src and "getenv" not in src


def test_the_public_call_keeps_the_derived_depth_of_buffers():
    """`decode_attend` hands the kernel `fetch_depth`'s buffers, and the
    frame `check_decode_geometry` prices holds that many."""
    from apex1_tpu import vmem_model
    q, kn, _, kp, _ = _operands(2, 4, 2, 64, 2 * BLK, 1, jnp.bfloat16)
    depth = fetch_depth(128, jnp.bfloat16)
    with _common.force_impl("pallas"):
        text = str(jax.make_jaxpr(lambda *a: decode_attend(*a))(
            q, kn, kn, kp, kp, jnp.asarray([3, -1], jnp.int32)))
    assert f"bf16[{depth},{BLK},128]" in text
    frame = lambda d: vmem_model.CHECKS["decode_attend"](
        {"block_l": BLK, "depth": d}, {"HD": 512, "Rq": 32, "W": 16}, 2,
        vmem_model.budget_bytes())[1]
    assert frame(8) - frame(2) == 6 * 2 * BLK * 512 * 2


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


@pytest.mark.parametrize("cache_dtype", [None, jnp.int8],
                         ids=["as_built", "int8"])
def test_the_engine_counts_with_the_kernels_own_depth(tiny, cache_dtype):
    """`kv_fetch_ahead` on the step span is reckoned with the depth the
    kernel derives for the pool's rows: read off the K leaf's width and
    dtype, as `decode_attend` reads them off its operand."""
    from apex1_tpu.serving.engine import Engine, EngineConfig, fetch_ahead
    cfg, params, apply_fn, make_cache = tiny
    eng = Engine(apply_fn, make_cache, params, EngineConfig(
        max_slots=3, max_len=40, prefill_chunk=4, vocab_size=cfg.vocab_size,
        cache_dtype=cache_dtype))
    leaf = jax.tree_util.tree_leaves(eng.kv.cache)[0]
    assert eng._fetch_depth == fetch_depth(leaf.shape[-1], leaf.dtype)
    assert fetch_depth(leaf.shape[-1], leaf.dtype) > 2
    # lanes 1 and 4 live, 12 and 3 blocks, a queue of 8: seven and three
    # are started before their lane's step; lane 0 starts its own
    assert fetch_ahead([1, 4], [12, 3], 8) == 7 + 3
    assert fetch_ahead([0, 4], [12, 3], 8) == 3
    assert fetch_ahead([], [], 8) == 0


def _hw_numerics():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hw_numerics.py")
    spec = importlib.util.spec_from_file_location("hw_numerics", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify"])
@pytest.mark.parametrize("cell", ["gpt2m", "granite4hm", "lfm2moe",
                                  "trinity_ring", "trinity_global"])
def test_the_chips_parity_check_draws_depths_onto_every_edge(cell, s):
    """`tools/hw_numerics.py --only decode_attend` is the proof the CPU
    cannot give (a copy completes where it starts here); what the CPU CAN
    hold is that its depths are the cells' own geometries and sit where
    the issue asks: idle lanes between live ones, lanes of one block, a
    row short of and past a block's edge, new rows astride one, and over
    a ring lanes shallower than the window, at the wrap and past three
    windows; every depth one the kernel takes."""
    hw = _hw_numerics()
    assert sorted(hw.DECODE_CELLS) == ["gpt2m", "granite4hm", "lfm2moe",
                                       "trinity_global", "trinity_ring"]
    B, Hq, Hkv, D, L, window = hw.DECODE_CELLS[cell]
    check_decode_geometry(L, Hkv * D, Hq * s, s, jnp.bfloat16, window)
    seen = set()
    for seed in range(6):
        idx = hw.decode_cell_depths(np.random.default_rng(seed), B, L, s,
                                    window)
        assert idx.shape == (B,) and idx.dtype == np.int32
        live = idx[idx >= 0]
        assert (idx < 0).sum() >= 1 and len(live) >= B // 2
        assert live.max() + s <= (4 * window if window else L)
        inside = np.flatnonzero(idx < 0)
        seen |= {"idle_inside"} if ((inside > 0) & (inside < B - 1)).any() \
            else set()
        seen |= {"one_block"} if (live + s <= BLK).any() else set()
        seen |= {"short_of_edge"} if ((live + s) % BLK == 0).any() else set()
        seen |= {"past_edge"} if ((live % BLK == 0) & (live > 0)).any() \
            else set()
        if s > 1:
            seen |= {"astride"} if (live // BLK != (live + s - 1) // BLK
                                    ).any() else set()
        if window:
            seen |= {"shallow"} if (live < window - 1).any() else set()
            seen |= {"wrap"} if ((live <= L - 1) & (live + s > L - 1)
                                 ).any() else set()
            seen |= {"three_windows"} if (live >= 3 * window).any() else set()
    want = {"idle_inside", "one_block", "short_of_edge", "past_edge"}
    want |= {"astride"} if s > 1 else set()
    want |= {"shallow", "wrap", "three_windows"} if window else set()
    assert seen == want
