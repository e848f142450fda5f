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


def _kernels_in(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("apex1_decode_attend")


@pytest.mark.parametrize("case", [
    "per_row_index", "verify_chunk", "scalar_index", "off_tpu", "bias",
    "valid_start", "too_many_rows", "prefill"])
def test_cached_attention_chooses_by_what_it_sees(case):
    """A rank-1 index where the kernels run, within the row bound: the
    kernel. A scalar index, a CPU, a bias, a left-pad mask, more rows
    than the kernel takes, a flash prefill: the composite."""
    B, H, D, L = 2, 2, 64, 2 * BLK
    S = {"verify_chunk": 3, "too_many_rows": MAX_ROWS // H + 1,
         "prefill": 4}.get(case, 1)
    q = jnp.ones((B, H, S, D), jnp.float32)
    cache = init_cache(1, B, H, L, D, jnp.float32)["layer0"]
    idx = jnp.asarray(0 if case in ("scalar_index", "prefill")
                      else [3, -1], jnp.int32)
    kw = {"bias": dict(bias=jnp.zeros((1, H, 1, L))),
          "valid_start": dict(valid_start=jnp.zeros((B,), jnp.int32)),
          "prefill": {}}.get(case, dict(chunk_decode=True))
    call = lambda q, cache, idx: cached_attention(q, q, q, cache, idx, **kw)
    with _common.force_impl("xla" if case == "off_tpu" else "pallas"):
        n = _kernels_in(call, q, cache, idx)
    assert n == (1 if case in ("per_row_index", "verify_chunk") else 0)


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
