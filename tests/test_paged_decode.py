"""`ops.paged_decode` + paged serving mode — the ISSUE 18 acceptance
spine. The paged engine must be INVISIBLE in the tokens: bit-identical
streams vs the dense engine at every tested temperature (greedy and
two sampling regimes), across speculative-decode verify, radix prefix
hits (with page sharing actually engaged), and the int8 cache tier —
all with the usual two traced executables. Below the engine: the
in-kernel threefry/gumbel stream is pinned BITWISE against
``jax.random`` (the counter-seed resubmission contract rides on it),
the fused sampling kernel against its composite, the paged-attention
kernel against the shared `cache_attend` composite, and the
`PagedKVPool` page-refcount lifecycle (a shared page is freed only at
zero references)."""

from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex1_tpu.core.policy import get_policy
from apex1_tpu.models.generate import gpt2_decoder
from apex1_tpu.models.gpt2 import GPT2, GPT2Config
from apex1_tpu.ops import _common
from apex1_tpu.ops.paged_decode import (PagedCache, _bits_to_gumbel,
                                        _uniform_bits, cache_attend,
                                        check_paged_geometry,
                                        fused_sample, gather_pages,
                                        paged_attend,
                                        paged_update_attend,
                                        sample_token, scatter_pages)
from apex1_tpu.serving import Engine, EngineConfig, PagedKVPool


# ---------------------------------------------------------------------------
# the in-kernel PRNG stream: bitwise against jax.random
# ---------------------------------------------------------------------------


@contextmanager
def _threefry_mode(partitionable):
    prev = bool(jax.config.jax_threefry_partitionable)
    jax.config.update("jax_threefry_partitionable", partitionable)
    try:
        yield
    finally:
        jax.config.update("jax_threefry_partitionable", prev)


class TestThreefryStream:
    @pytest.mark.parametrize("partitionable", [True, False])
    @pytest.mark.parametrize("n", [6, 7, 200, 257])
    def test_uniform_bits_bitwise_vs_jax_random(self, n, partitionable):
        """The pure-jnp threefry-2x32 reimplementation must reproduce
        jax's draw exactly under BOTH stream configs (partitionable is
        the installed default; odd counts exercise the original
        stream's zero-padded pair-partner path)."""
        key = jax.random.fold_in(jax.random.key(123), 7)
        k1, k2 = (jnp.uint32(x) for x in jax.random.key_data(key))
        col = jnp.arange(n, dtype=jnp.int32)
        mine = np.asarray(_uniform_bits(k1, k2, col, n,
                                        partitionable=partitionable))
        with _threefry_mode(partitionable):
            ref = np.asarray(jax.random.bits(key, (n,), jnp.uint32))
        np.testing.assert_array_equal(mine, ref)

    @pytest.mark.parametrize("partitionable", [True, False])
    def test_gumbel_bitwise_vs_jax_random(self, partitionable):
        key = jax.random.fold_in(jax.random.key(9), 3)
        k1, k2 = (jnp.uint32(x) for x in jax.random.key_data(key))
        with _threefry_mode(partitionable):
            g = np.asarray(_bits_to_gumbel(
                _uniform_bits(k1, k2, jnp.arange(129), 129)))
            ref = np.asarray(jax.random.gumbel(key, (129,), jnp.float32))
        np.testing.assert_array_equal(g, ref)

    def test_categorical_bitwise_vs_jax_random(self):
        """argmax(gumbel + logits) over the recomputed stream IS
        jax.random.categorical — the sampling identity the fused
        kernel's epilogue rests on."""
        key = jax.random.fold_in(jax.random.key(5), 11)
        lg = jax.random.normal(jax.random.key(1), (64,), jnp.float32)
        k1, k2 = (jnp.uint32(x) for x in jax.random.key_data(key))
        g = _bits_to_gumbel(_uniform_bits(k1, k2, jnp.arange(64), 64))
        assert int(jnp.argmax(g + lg)) == int(
            jax.random.categorical(key, lg))


# ---------------------------------------------------------------------------
# fused sampling epilogue
# ---------------------------------------------------------------------------


def _sample_rows_loop(logits, seeds, positions, **kw):
    """The dense engine's literal sampling ops, one row at a time."""
    out = []
    for r in range(logits.shape[0]):
        key = jax.random.fold_in(jax.random.key(int(seeds[r])),
                                 int(positions[r]))
        out.append(int(sample_token(logits[r][None], key, **kw)[0]))
    return np.asarray(out, np.int32)


class TestFusedSample:
    @pytest.mark.parametrize("temperature", [0.0, 0.7, 1.3])
    @pytest.mark.parametrize("top_k", [None, 5])
    def test_composite_matches_per_row_sampling(self, temperature,
                                                top_k):
        lg = jax.random.normal(jax.random.key(2), (5, 64), jnp.float32)
        seeds = np.asarray([3, 3, 7, 11, 7], np.int32)
        pos = np.asarray([0, 1, 9, 2, 9], np.int32)
        got = np.asarray(fused_sample(
            lg, seeds, pos, temperature=temperature, top_k=top_k,
            vocab_size=60))
        want = _sample_rows_loop(lg, seeds, pos,
                                 temperature=temperature, top_k=top_k,
                                 vocab_size=60)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("temperature", [0.0, 0.7, 1.3])
    @pytest.mark.parametrize("top_k", [None, 5])
    def test_kernel_bitwise_vs_composite(self, temperature, top_k):
        """The Pallas epilogue (interpret mode off-TPU) emits the SAME
        token ids as the composite — integer outputs make this an
        exact, not approximate, contract."""
        lg = jax.random.normal(jax.random.key(4), (4, 200), jnp.float32)
        seeds = np.asarray([1, 2, 3, 2], np.int32)
        pos = np.asarray([5, 0, 1, 7], np.int32)
        kw = dict(temperature=temperature, top_k=top_k, vocab_size=180)
        with _common.force_impl("xla"):
            want = np.asarray(fused_sample(lg, seeds, pos, **kw))
        with _common.force_impl("pallas"):
            got = np.asarray(fused_sample(lg, seeds, pos, **kw))
        np.testing.assert_array_equal(got, want)

    def test_vocab_mask_never_samples_padded_tail(self):
        lg = jnp.full((3, 64), 5.0)
        lg = lg.at[:, 50:].set(100.0)          # huge logits in the pad
        got = np.asarray(fused_sample(lg, [1, 2, 3], [0, 0, 0],
                                      temperature=1.3, vocab_size=50))
        assert (got < 50).all()


# ---------------------------------------------------------------------------
# page plumbing + the paged attention kernel
# ---------------------------------------------------------------------------


def _random_pages(key, num_pages, Hkv, P, D, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    if dtype == jnp.int8:
        mk = lambda k: jax.random.randint(  # noqa: E731
            k, (num_pages, Hkv, P, D), -127, 128, jnp.int8)
    else:
        mk = lambda k: jax.random.normal(  # noqa: E731
            k, (num_pages, Hkv, P, D), dtype)
    return mk(k1), mk(k2)


class TestPagePlumbing:
    def test_gather_scatter_roundtrip_page_spanning(self):
        """A write window that straddles a page boundary at an
        unaligned start must read back exactly."""
        kp, _ = _random_pages(jax.random.key(0), 9, 2, 4, 8)
        bt = jnp.asarray([[3, 1, 7], [2, 8, 5]], jnp.int32)
        vals = jax.random.normal(jax.random.key(1), (2, 2, 6, 8))
        start = jnp.asarray([3, 1], jnp.int32)   # spans pages 0->2 / 0->1
        kp2 = scatter_pages(kp, bt, vals, start)
        dense = gather_pages(kp2, bt, 12)
        for n in range(2):
            s = int(start[n])
            np.testing.assert_array_equal(
                np.asarray(dense[n, s:s + 6, :]),
                np.asarray(vals[n].transpose(1, 0, 2).reshape(6, -1)))

    def test_composite_matches_dense_cache_attend_bitwise(self):
        """Gather→cache_attend through a permuted block table must be
        BITWISE the dense math on the same logical lanes."""
        kp, vp = _random_pages(jax.random.key(2), 7, 2, 4, 8)
        bt = jnp.asarray([[5, 2, 6], [1, 4, 3]], jnp.int32)
        q = jax.random.normal(jax.random.key(3), (2, 4, 1, 8))
        lengths = jnp.asarray([9, 4], jnp.int32)
        k_all = gather_pages(kp, bt, 12)
        v_all = gather_pages(vp, bt, 12)
        want = cache_attend(q, k_all, v_all, lengths)
        got = paged_attend(q, kp, vp, bt, lengths, total_len=12)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("s", [1, 4])
    def test_kernel_matches_composite_f32(self, s):
        kp, vp = _random_pages(jax.random.key(4), 7, 2, 8, 16)
        bt = jnp.asarray([[5, 2, 6], [1, 4, 3]], jnp.int32)
        q = jax.random.normal(jax.random.key(5), (2, 4, s, 16))
        lengths = jnp.asarray([17, 6], jnp.int32)
        want = np.asarray(paged_attend(q, kp, vp, bt, lengths,
                                       total_len=24))
        with _common.force_impl("pallas"):
            got = np.asarray(paged_attend(q, kp, vp, bt, lengths,
                                          total_len=24))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_kernel_matches_composite_int8_fused_dequant(self):
        """int8 pages dequantize IN the kernel; tolerance is relative —
        ±127-scale values make online-softmax reassociation error scale
        with magnitude."""
        kp, vp = _random_pages(jax.random.key(6), 7, 2, 8, 16,
                               dtype=jnp.int8)
        bt = jnp.asarray([[5, 2, 6], [1, 4, 3]], jnp.int32)
        q = jax.random.normal(jax.random.key(7), (2, 4, 1, 16))
        lengths = jnp.asarray([20, 3], jnp.int32)
        want = np.asarray(paged_attend(q, kp, vp, bt, lengths,
                                       total_len=24))
        with _common.force_impl("pallas"):
            got = np.asarray(paged_attend(q, kp, vp, bt, lengths,
                                          total_len=24))
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())

    def test_paged_update_attend_matches_dense_update(self):
        """Scatter+attend == dynamic_update_slice+attend on the dense
        equivalent — the per-layer cache step the models thread."""
        kp, vp = _random_pages(jax.random.key(8), 7, 2, 4, 8)
        bt = jnp.asarray([[5, 2, 6], [1, 4, 3]], jnp.int32)
        q = jax.random.normal(jax.random.key(9), (2, 4, 1, 8))
        k_new = jax.random.normal(jax.random.key(10), (2, 2, 1, 8))
        v_new = jax.random.normal(jax.random.key(11), (2, 2, 1, 8))
        idx = jnp.asarray([7, 2], jnp.int32)
        pc = PagedCache(kp, vp, bt, 12)
        got, new_pc = paged_update_attend(q, k_new, v_new, pc, idx)
        k_all = gather_pages(kp, bt, 12)
        v_all = gather_pages(vp, bt, 12)
        from apex1_tpu.models.generate import cache_write
        k_up = jnp.concatenate([
            cache_write(k_all[n:n + 1], k_new[n:n + 1], int(idx[n]))
            for n in range(2)])
        v_up = jnp.concatenate([
            cache_write(v_all[n:n + 1], v_new[n:n + 1], int(idx[n]))
            for n in range(2)])
        want = cache_attend(q, k_up, v_up, idx)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(gather_pages(new_pc.k_pages, bt, 12)),
            np.asarray(k_up))

    def test_geometry_rejects_unaligned_page(self):
        with pytest.raises(ValueError, match="sublane-aligned"):
            check_paged_geometry(12, 64, 2, 1)

    def test_geometry_rejects_over_budget_page(self):
        with pytest.raises(ValueError, match="over budget"):
            check_paged_geometry(1 << 20, 128, 2, 1)


# ---------------------------------------------------------------------------
# the paged KV pool: page-granular sharing + refcounts
# ---------------------------------------------------------------------------


def _toy_cache(n, s, dtype=jnp.float32, page_form=False):
    shape = (n, 2, s, 4) if page_form else (n, s, 2 * 4)
    return {"layer0": {"k": jnp.zeros(shape, dtype),
                       "v": jnp.zeros(shape, dtype)}}


class TestPagedPool:
    def _pool(self, **kw):
        kw.setdefault("max_slots", 2)
        kw.setdefault("lane_len", 16)
        kw.setdefault("page_size", 4)
        return PagedKVPool(_toy_cache, **kw)

    def test_alloc_populates_row_free_resets_to_trash(self):
        pool = self._pool()
        assert pool.pages_per_lane == 4
        slot = pool.alloc()
        row = list(pool.block_tables[slot])
        assert 0 not in row and len(set(row)) == 4
        assert all(pool.page_refcount(p) == 1 for p in row)
        pool.free(slot)
        assert pool.block_tables[slot] == [0, 0, 0, 0]
        assert all(pool.page_refcount(p) == 0 for p in row)

    def test_sizing_invariant_alloc_never_fails(self):
        """Worst case — every slot full AND every registry entry
        pinning a retired donor's full lane — still leaves a free page
        for the next alloc (the no-page-faults decode-loop contract)."""
        pool = self._pool(max_pages=2)
        assert pool.num_pages == 1 + (2 + 2) * 4
        for i in range(2):
            s = pool.alloc()
            pool.register_prefix(s, (i,), 16)
            pool.free(s)
        a, b = pool.alloc(), pool.alloc()
        assert a is not None and b is not None
        assert pool.n_free_pages == 0     # exactly sized, never negative

    def test_shared_page_freed_only_at_zero_refs(self):
        """The central refcount property: a page shared by a registry
        entry and two block-table rows survives every partial release
        and is freed ONLY when the last reference drops."""
        pool = self._pool()
        free0 = pool.n_free_pages
        a = pool.alloc()
        key = (101, 102)
        page = pool.register_prefix(a, key, 9)   # floors to 2 pages
        assert page is not None and page.length == 8
        shared = list(page.page_ids)
        assert [pool.page_refcount(p) for p in shared] == [2, 2]
        pool.acquire_prefix(key, a)              # donor: bookkeeping no-op
        assert [pool.page_refcount(p) for p in shared] == [2, 2]

        b = pool.alloc()
        displaced = pool.block_tables[b][:2]
        pool.acquire_prefix(key, b)              # sharer: rewires by id
        assert pool.block_tables[b][:2] == shared
        assert [pool.page_refcount(p) for p in shared] == [3, 3]
        assert all(pool.page_refcount(p) == 0 for p in displaced)

        pool.free(a)                             # donor retires
        assert [pool.page_refcount(p) for p in shared] == [2, 2]
        pool.free(b)                             # last sharer retires
        assert [pool.page_refcount(p) for p in shared] == [1, 1]
        assert not set(shared) & set(pool._free_pages)

        assert pool.evict_prefix(key)            # registry entry drops
        assert all(pool.page_refcount(p) == 0 for p in shared)
        assert pool.n_free_pages == free0        # fully reclaimed

    def test_live_prefix_refuses_eviction(self):
        pool = self._pool()
        a = pool.alloc()
        key = (9,)
        pool.register_prefix(a, key, 8)
        pool.acquire_prefix(key, a)
        assert not pool.evict_prefix(key)        # refcount > 0
        with pytest.raises(RuntimeError, match="live"):
            pool.evict_prefix(key, force=True)
        pool.free(a)                             # releases via slot map
        assert pool.evict_prefix(key)

    def test_register_floors_to_page_multiple(self):
        pool = self._pool()
        a = pool.alloc()
        assert pool.register_prefix(a, (1,), 3) is None
        page = pool.register_prefix(a, (2,), 7)
        assert page.length == 4 and len(page.page_ids) == 1

    def test_lru_eviction_respects_refcounts(self):
        pool = self._pool(max_pages=1)
        a = pool.alloc()
        pool.register_prefix(a, (1,), 8)
        pool.acquire_prefix((1,), a)
        b = pool.alloc()
        pool.register_prefix(b, (2, 2), 8)     # over cap, but "one" live
        assert pool.has_prefix((1,)) and pool.has_prefix((2, 2))
        pool.free(a)                             # "one" refcount -> 0
        pool.register_prefix(b, (3, 3, 3), 16)  # triggers LRU sweep
        assert not pool.has_prefix((1,))


# ---------------------------------------------------------------------------
# the paged engine: token parity with the dense engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = GPT2Config.tiny(policy=get_policy("O0"), max_seq_len=64)
    model = GPT2(cfg)
    rng = np.random.default_rng(11)
    base = rng.integers(1, cfg.vocab_size, size=(12,)).astype(np.int32)
    prompt = jnp.asarray(base[None])
    params = model.init(jax.random.key(0), prompt)["params"]
    apply_fn, make_cache = gpt2_decoder(model)
    return cfg, params, apply_fn, make_cache, base


def _engine(tiny, **kw):
    cfg, params, apply_fn, make_cache, _ = tiny
    ekw = dict(max_slots=3, max_len=48, prefill_chunk=4,
               vocab_size=cfg.vocab_size)
    ekw.update(kw)
    return Engine(apply_fn, make_cache, params, EngineConfig(**ekw))


def _run_workload(eng, base, *, news=(6, 5, 7, 4), seeds=(5, 9, 2, 7)):
    """More requests than slots, mixed prompt lengths crossing chunk
    boundaries, staggered joins — the dense suite's acceptance shape."""
    lens = [3, 7, 5, 9]
    ids = [eng.submit(base[:lens[i]], max_new_tokens=news[i],
                      seed=seeds[i]) for i in range(3)]
    eng.step()
    ids.append(eng.submit(base[:lens[3]], max_new_tokens=news[3],
                          seed=seeds[3]))
    eng.run(max_steps=200)
    return [list(eng.results[r].tokens) for r in ids]


class TestPagedEngineParity:
    @pytest.mark.parametrize("temperature", [0.0, 0.7, 1.3])
    def test_tokens_bitwise_vs_dense_engine(self, tiny, temperature):
        """The tentpole acceptance: paged == dense token streams,
        exactly (counter-keyed sampling included), with the usual two
        executables and no retraces."""
        base = tiny[4]
        dense = _run_workload(_engine(tiny, temperature=temperature),
                              base)
        eng = _engine(tiny, temperature=temperature, paged=True)
        paged = _run_workload(eng, base)
        assert paged == dense
        assert eng.trace_counts == {"prefill": 1, "decode": 1}

    def test_spec_decode_verify_bitwise(self, tiny):
        """Speculative decode's verify executable (counter-keyed accept
        chain) through the paged path: same tokens, same executables."""
        base = tiny[4]
        dense = _run_workload(
            _engine(tiny, temperature=0.7, num_draft=2), base)
        eng = _engine(tiny, temperature=0.7, num_draft=2, paged=True)
        paged = _run_workload(eng, base)
        assert paged == dense
        assert eng.trace_counts == {"prefill": 1, "verify": 1}

    @pytest.mark.parametrize("num_draft", [0, 3],
                             ids=["decode", "verify"])
    def test_last_position_and_reused_lane_bitwise(self, tiny, num_draft):
        """Both pools run the same step body (`Engine._model_calls`'s
        `score`) over lanes at different depths: one request fills its
        lane to the last usable position (12 + 5 - 1 == max_len), lanes
        are reused after retirement, and the paged streams equal the
        dense ones bit for bit at temperature 0.7."""
        base = tiny[4]

        def run(**kw):
            eng = _engine(tiny, max_slots=2, max_len=16, temperature=0.7,
                          num_draft=num_draft, **kw)
            ids = [eng.submit(base[:12], max_new_tokens=5, seed=3),
                   eng.submit(base[:3], max_new_tokens=2, seed=4)]
            for _ in range(3):
                eng.step()
            ids += [eng.submit(base[:7], max_new_tokens=6, seed=5),
                    eng.submit(base[:5], max_new_tokens=4, seed=6)]
            eng.run(max_steps=100)
            return [list(eng.results[r].tokens) for r in ids]

        dense = run()
        assert [len(t) for t in dense] == [5, 2, 6, 4]
        assert run(paged=True) == dense

    def test_int8_cache_tier_bitwise(self, tiny):
        """The int8 KV tier quantizes at scatter exactly like the dense
        tier's update — the paged path must not perturb a single
        token."""
        base = tiny[4]
        dense = _run_workload(
            _engine(tiny, temperature=0.7, cache_dtype=jnp.int8), base)
        eng = _engine(tiny, temperature=0.7, cache_dtype=jnp.int8,
                      paged=True)
        paged = _run_workload(eng, base)
        assert paged == dense

    def test_radix_prefix_hits_bitwise_with_page_sharing(self, tiny):
        """Three requests sharing a 10-token prefix: the paged pool
        must register page-aligned shared pages, serve hits by page id
        (no copy-on-admit), and still match the dense engine token for
        token."""
        base = tiny[4]

        def run(paged):
            eng = _engine(tiny, max_slots=2, temperature=0.7,
                          paged=paged)
            rids = [eng.submit(
                np.concatenate([base[:10],
                                np.asarray([3 + i], np.int32)]),
                max_new_tokens=6, seed=50 + i) for i in range(3)]
            eng.run(max_steps=300)
            return [list(eng.results[r].tokens) for r in rids], eng

        dense, _ = run(False)
        paged, eng = run(True)
        assert paged == dense
        stats = eng.kv.prefix_stats()
        assert any(v["hits"] >= 2 and v["pages"]
                   for v in stats.values()), stats

    def test_explicit_prefix_submission_bitwise(self, tiny):
        base = tiny[4]
        pre = tuple(int(t) for t in base[:9])

        def run(paged):
            eng = _engine(tiny, max_slots=2, temperature=1.3,
                          paged=paged)
            rids = [eng.submit(np.asarray([5 + i, 9], np.int32),
                               max_new_tokens=5, prefix=pre,
                               seed=7 + i) for i in range(3)]
            eng.run(max_steps=300)
            return [list(eng.results[r].tokens) for r in rids]

        assert run(True) == run(False)

    def test_pallas_interpret_engine_bitwise(self, tiny):
        """The kernel path end-to-end: an engine BUILT under
        force_impl('pallas') routes decode through the paged-attention
        kernel + fused sampling epilogue (interpret mode on CPU) and
        still emits the dense engine's exact tokens."""
        base = tiny[4]
        dense_eng = _engine(tiny, max_slots=2, temperature=0.7)
        rd = [dense_eng.submit(base[:7 + i], max_new_tokens=4,
                               seed=3 + i) for i in range(2)]
        dense_eng.run(max_steps=100)
        dense = [list(dense_eng.results[r].tokens) for r in rd]
        with _common.force_impl("pallas"):
            eng = _engine(tiny, max_slots=2, temperature=0.7,
                          paged=True)
            rp = [eng.submit(base[:7 + i], max_new_tokens=4,
                             seed=3 + i) for i in range(2)]
            eng.run(max_steps=100)
        paged = [list(eng.results[r].tokens) for r in rp]
        assert paged == dense

    def test_page_size_validation(self, tiny):
        with pytest.raises(ValueError, match="page_size"):
            EngineConfig(max_slots=2, max_len=32, vocab_size=256,
                         paged=True, page_size=0)
