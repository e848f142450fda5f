"""Multi-tenant LoRA serving (``serving.lora`` + the engine's fused
logits epilogue) — the ISSUE 19 acceptance spine: one engine batch
mixing LoRA-on slots across two adapters with an adapterless control
must emit token streams BIT-IDENTICAL to per-tenant solo runs, across
the dense, paged-gold, paged-kernel, and speculative paths, with the
usual two executables and no retraces.  Plus the store's page-lifetime
control plane (the APX202 publish discipline's host half) and the
fleetsim noisy-tenant isolation drill that maps tenants onto QoS
classes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex1_tpu.models.generate import llama_decoder
from apex1_tpu.models.llama import Llama, LlamaConfig
from apex1_tpu.ops import _common
from apex1_tpu.serving.engine import Engine, EngineConfig
from apex1_tpu.serving.lora import LoraAdapterStore

RANK = 2

# two tenants share a prompt with the adapterless control: if the
# adapters were inert the parity assertions would prove nothing
PROMPTS = {101: ([3, 1, 4, 1, 5], "tenant-a"),
           102: ([2, 7, 1, 8], "tenant-b"),
           103: ([3, 1, 4, 1, 5], None)}


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, num_layers=2,
                      num_heads=4, num_kv_heads=2, ffn_size=64,
                      max_seq_len=64)
    model = Llama(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    apply_fn, make_cache = llama_decoder(model)
    k = jax.random.key(1)
    adapters = {}
    for name in ("tenant-a", "tenant-b"):
        k, ka, kb = jax.random.split(k, 3)
        adapters[name] = (
            jax.random.normal(ka, (cfg.hidden_size, RANK)) * 0.2,
            jax.random.normal(kb, (RANK, cfg.vocab_size)) * 0.2)
    return cfg, params, apply_fn, make_cache, adapters


def _engine(tiny, **kw):
    cfg, params, apply_fn, make_cache, adapters = tiny
    ekw = dict(max_slots=4, max_len=32, prefill_chunk=4,
               temperature=0.7, seed=7, lora_rank=RANK,
               lora_max_adapters=4)
    ekw.update(kw)
    eng = Engine(apply_fn, make_cache, params, EngineConfig(**ekw),
                 lora_head=params["output"])
    for name, (A, B) in adapters.items():
        eng.register_adapter(name, A, B, scale=2.0)
    return eng


def _run(eng, active):
    for rid in sorted(active):
        toks, tenant = PROMPTS[rid]
        eng.submit(np.asarray(toks, np.int32), 8, req_id=rid,
                   tenant=tenant, seed=1000 + rid)
    eng.run(max_steps=100)
    return {rid: list(eng.results[rid].tokens) for rid in active}


# ---------------------------------------------------------------------------
# the adapter-page store: lifetime control plane
# ---------------------------------------------------------------------------


class TestLoraAdapterStore:
    def _store(self, **kw):
        kws = dict(hidden=8, vocab=16, rank=2, max_adapters=2)
        kws.update(kw)
        return LoraAdapterStore(**kws)

    def _ab(self, st, seed=0):
        rng = np.random.default_rng(seed)
        return (rng.normal(size=(st.hidden, st.rank)),
                rng.normal(size=(st.rank, st.vocab)))

    def test_register_acquire_release_refcounts(self):
        st = self._store()
        pages = st.register("acme", *self._ab(st))
        assert len(pages) == st.rank and 0 not in pages
        assert all(st.page_refcount(p) == 1 for p in pages)

        row, on = st.acquire("acme", slot=0)
        assert on and list(row) == list(pages)
        row2, on2 = st.acquire("acme", slot=1)
        assert on2
        assert all(st.page_refcount(p) == 3 for p in pages)

        # unregister drops only the registry's ref — in-flight slots
        # keep the pages readable (teardown half of the publish race)
        st.unregister("acme")
        assert all(st.page_refcount(p) == 2 for p in pages)
        assert st.n_free == 0 + (st.num_pages - 1 - st.rank)

        st.release(0)
        st.release(1)
        assert all(st.page_refcount(p) == 0 for p in pages)
        assert st.n_free == st.num_pages - 1  # zero page never frees

    def test_duplicate_register_raises(self):
        st = self._store()
        st.register("acme", *self._ab(st))
        with pytest.raises(ValueError, match="already registered"):
            st.register("acme", *self._ab(st))

    def test_shape_validation(self):
        st = self._store()
        A, B = self._ab(st)
        with pytest.raises(ValueError, match="A shape"):
            st.register("x", A.T, B)
        with pytest.raises(ValueError, match="B shape"):
            st.register("x", A, B.T)

    def test_unknown_or_none_adapter_is_zero_row(self):
        st = self._store()
        for who in (None, "ghost"):
            row, on = st.acquire(who, slot=3)
            assert not on and not row.any()
        st.release(3)  # no-op: adapterless slots own nothing

    def test_slot_double_acquire_raises(self):
        st = self._store()
        st.register("acme", *self._ab(st))
        st.acquire("acme", slot=0)
        with pytest.raises(ValueError, match="already holds"):
            st.acquire("acme", slot=0)

    def test_pool_exhaustion_is_loud(self):
        st = self._store(max_adapters=1)
        st.register("acme", *self._ab(st))
        with pytest.raises(RuntimeError, match="out of pages"):
            st.register("zeta", *self._ab(st))
        # sizing invariant: max_adapters registrations can't exhaust
        st.unregister("acme")
        st.register("zeta", *self._ab(st))

    def test_unregister_unknown_raises(self):
        with pytest.raises(KeyError, match="ghost"):
            self._store().unregister("ghost")

    def test_scale_folded_into_b_pages_and_zero_page_stays_zero(self):
        st = self._store()
        A, B = self._ab(st)
        pages = st.register("acme", A, B, scale=4.0)
        for j, pid in enumerate(pages):
            np.testing.assert_allclose(
                np.asarray(st.a_pages[pid]), A.T[j], rtol=1e-6)
            np.testing.assert_allclose(
                np.asarray(st.b_pages[pid]),
                B[j] * (4.0 / st.rank), rtol=1e-6)
        assert not np.asarray(st.a_pages[0]).any()
        assert not np.asarray(st.b_pages[0]).any()


# ---------------------------------------------------------------------------
# engine wiring: validation + parity
# ---------------------------------------------------------------------------


class TestEngineLoraValidation:
    def test_lora_rank_requires_head(self, tiny):
        cfg, params, apply_fn, make_cache, _ = tiny
        with pytest.raises(ValueError, match="lora_head"):
            Engine(apply_fn, make_cache, params,
                   EngineConfig(max_slots=2, max_len=32, lora_rank=2))

    def test_config_negatives(self):
        with pytest.raises(ValueError, match="lora_rank"):
            EngineConfig(max_slots=2, max_len=32, lora_rank=-1)
        with pytest.raises(ValueError, match="lora_max_adapters"):
            EngineConfig(max_slots=2, max_len=32, lora_rank=2,
                         lora_max_adapters=0)

    def test_register_without_lora_raises(self, tiny):
        cfg, params, apply_fn, make_cache, _ = tiny
        eng = Engine(apply_fn, make_cache, params,
                     EngineConfig(max_slots=2, max_len=32))
        with pytest.raises(RuntimeError, match="lora"):
            eng.register_adapter("acme", np.zeros((32, 2)),
                                 np.zeros((2, 97)))


class TestLoraEngineParity:
    def test_mixed_batch_bitwise_vs_solo_dense(self, tiny):
        """The acceptance criterion: one batch mixing two adapters and
        an adapterless control == per-tenant solo runs, bit for bit —
        and the adapters really steer the stream (101 and 103 share a
        prompt but must diverge)."""
        mixed = _run(_engine(tiny), set(PROMPTS))
        for rid in PROMPTS:
            assert mixed[rid] == _run(_engine(tiny), {rid})[rid], rid
        assert mixed[101] != mixed[103], \
            "adapter had no effect on the stream"

    def test_two_executables_no_retrace(self, tiny):
        eng = _engine(tiny)
        _run(eng, set(PROMPTS))
        assert eng.trace_counts == {"prefill": 1, "decode": 1}

    def test_paged_gold_matches_dense(self, tiny):
        dense = _run(_engine(tiny), set(PROMPTS))
        eng = _engine(tiny, paged=True)
        assert _run(eng, set(PROMPTS)) == dense
        assert eng.trace_counts == {"prefill": 1, "decode": 1}

    def test_paged_kernel_matches_dense(self, tiny):
        """The fused epilogue for real: an engine BUILT under
        force_impl('pallas') routes the adapter delta through the
        `ops.lora_epilogue.lora_delta` kernel (interpret mode on CPU)
        inside the paged decode/prefill epilogues."""
        dense = _run(_engine(tiny), set(PROMPTS))
        with _common.force_impl("pallas"):
            eng = _engine(tiny, paged=True)
            paged = _run(eng, set(PROMPTS))
        assert paged == dense

    def test_speculative_verify_matches_dense(self, tiny):
        """Draft/verify path: the adapter delta lands on every verify
        row (K+1 logits per slot), so accept chains — and therefore
        tokens — match the plain decode engine's exactly when both run
        the same sampling contract."""
        dense = _run(_engine(tiny, num_draft=2), set(PROMPTS))
        for rid in PROMPTS:
            assert dense[rid] == _run(
                _engine(tiny, num_draft=2), {rid})[rid], rid
        eng = _engine(tiny, num_draft=2, paged=True)
        assert _run(eng, set(PROMPTS)) == dense
        assert eng.trace_counts == {"prefill": 1, "verify": 1}

    @pytest.mark.parametrize("kind", ["dense", "paged", "spec"])
    def test_full_lane_in_mixed_batch_bitwise_vs_solo(self, tiny, kind):
        """The epilogue row is vmapped over a batch forward whose lanes
        sit at different depths: a tenant's request that fills its lane
        to the last usable position (5 + 28 - 1 == max_len), beside
        another tenant's and an adapterless one, equals its solo run."""
        kw = {"dense": {}, "paged": dict(paged=True),
              "spec": dict(num_draft=2)}[kind]

        def run(active):
            eng = _engine(tiny, **kw)
            for rid in sorted(active):
                toks, tenant = PROMPTS[rid]
                eng.submit(np.asarray(toks, np.int32),
                           28 if rid == 101 else 8, req_id=rid,
                           tenant=tenant, seed=1000 + rid)
            eng.run(max_steps=200)
            return {rid: list(eng.results[rid].tokens) for rid in active}

        mixed = run(set(PROMPTS))
        assert len(mixed[101]) == 28
        for rid in PROMPTS:
            assert mixed[rid] == run({rid})[rid], rid

    def test_slots_reusable_after_retire(self, tiny):
        """Adapter pages release at retirement: more requests than
        slots forces reuse; refcounts must return to quiescent."""
        eng = _engine(tiny, max_slots=2)
        out = _run(eng, set(PROMPTS))
        assert len(out) == 3
        st = eng._lora
        assert not st._slot_pages
        assert st.n_free == st.num_pages - 1 - 2 * RANK  # registry refs


# ---------------------------------------------------------------------------
# tenant isolation under noisy-neighbor overload (fleetsim)
# ---------------------------------------------------------------------------


class TestTenantIsolationDrill:
    def test_guaranteed_tenant_holds_slo_under_noisy_overload(self):
        """Tenant=adapter maps onto the QoS ladder: a noisy tenant
        ('zeta') hammering the sheddable class must not drag the
        guaranteed tenant ('acme') below its SLO — the frontend sheds
        the noise instead.  This is the serving-control-plane half of
        multi-tenancy; token-level isolation is the parity suite."""
        from apex1_tpu.autopilot import drill
        from apex1_tpu.testing.fleetsim import (Trace, run_fleet,
                                                synthetic_trace)

        quiet = synthetic_trace(
            "steady", seed=21, horizon_s=3.0, base_rate=6.0,
            class_mix={"guaranteed": 1.0}, tenants=("acme",))
        noisy = synthetic_trace(
            "adversarial_overload", seed=22, horizon_s=3.0,
            base_rate=40.0, overload_mult=3.0,
            class_mix={"sheddable": 1.0}, tenants=("zeta",))
        merged = Trace(
            kind="adversarial_overload", seed=21, horizon_s=3.0,
            requests=sorted(quiet.requests + noisy.requests,
                            key=lambda r: r.t))

        rep = run_fleet(merged, drill.frontend_config(),
                        sim=drill.sim_config())

        att = rep.slo_attainment("guaranteed", drill.SLO_LATENCY_S)
        assert att >= drill.SLO_ATTAINMENT, (
            f"guaranteed attainment {att:.3f} under noisy tenant "
            f"(SLO {drill.SLO_ATTAINMENT})")
        # the isolation was load-bearing: the noisy class really was
        # shed/degraded while the guaranteed class sailed through
        assert rep.rejected.get("sheddable", 0) > 0, rep.summary
        assert rep.rejected.get("guaranteed", 0) == 0, rep.summary
