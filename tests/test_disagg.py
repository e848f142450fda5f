"""Disaggregated prefill/decode serving (ISSUE 16): phase-aware pools
behind the `ServingFrontend` surface with manifest-verified KV handoff.

What must hold, in order of importance:

1. **Handoff parity** — a stream that prefills in one pool and decodes
   in another is bit-identical to an uninterrupted single-engine run at
   temperature > 0 (the counter-keyed per-request seed, PR 7 — not
   greedy luck), including across corruption/kill re-routes.
2. **Integrity is typed** — a corrupt or torn page surfaces as a
   `HandoffError` at the arrival re-digest and the request re-routes;
   silent garbage tokens are structurally impossible.
3. **Never stranded** — a prefill replica dying inside the handoff
   window re-routes the request (decode-pool re-prefill), it does not
   strand it.
4. **Stability** — the fleetsim's new two-tier knobs at defaults leave
   every pre-existing trace kind and episode fingerprint byte-identical
   to what the perf_results corpus banked before disagg landed.
5. **The point of it all** — under an adversarial long-prompt trace the
   disaggregated fleet holds guaranteed-class TTFT where the unified
   fleet (same total replicas) fails, and the autopilot's pool-ratio
   law actuates `shift_pool` from windowed TTFT/TPOT evidence.
"""

import numpy as np
import pytest

from apex1_tpu.autopilot.policy import (AutopilotConfig, ControllerState,
                                        FleetView, SLOTarget, decide)
from apex1_tpu.serving import Engine, EngineConfig, FrontendConfig
from apex1_tpu.serving.disagg import (DisaggConfig, DisaggFrontend,
                                      HandoffError, extract_page,
                                      install_page, verify_page)
from apex1_tpu.testing.chaos import (HandoffCorruption, HandoffWindowKill,
                                     toy_decoder)
from apex1_tpu.testing.fleetsim import (FleetSimConfig, run_fleet,
                                        synthetic_trace)

ECFG = dict(max_slots=3, max_len=48, prefill_chunk=4, vocab_size=61,
            temperature=0.8, seed=7)


@pytest.fixture(scope="module")
def toy():
    return toy_decoder()


def _engine(toy, **kw):
    apply_fn, make_cache, params = toy
    return Engine(apply_fn, make_cache, params,
                  EngineConfig(**{**ECFG, **kw}))


def _front(toy, fault=None, **dkw):
    apply_fn, make_cache, params = toy

    def make_engine():
        return Engine(apply_fn, make_cache, params, EngineConfig(**ECFG))

    pool = dict(n_replicas=1, capacity_per_replica=8, hedge_after_s=None)
    return DisaggFrontend(
        make_engine,
        DisaggConfig(prefill=FrontendConfig(**pool),
                     decode=FrontendConfig(**pool),
                     prefill_chunk=ECFG["prefill_chunk"], **dkw),
        fault=fault)


def _assert_solo_parity(toy, front, prompts, rids):
    """Every stream must equal an uninterrupted single-engine run with
    the same derived seed — the acceptance bar for every handoff path,
    including the re-routed ones."""
    ref = _engine(toy)
    for p, rid in zip(prompts, rids):
        res = front.poll(rid)
        assert res is not None and res.status == "done", (rid, res)
        sub = front._subs[rid]
        rr = ref.submit(p, max_new_tokens=sub.max_new_tokens,
                        seed=sub.seed)
        ref.run(max_steps=300)
        np.testing.assert_array_equal(res.tokens, ref.results[rr].tokens)


# ---------------------------------------------------------------------------
# kv_transfer: the manifest-verified page contract
# ---------------------------------------------------------------------------


class TestKVTransfer:
    @pytest.fixture()
    def src(self, toy):
        """An engine that served one 9-token prompt — its chunk-aligned
        8-token prefix page sits in the radix store (engine
        auto-registration)."""
        eng = _engine(toy)
        prompt = np.random.default_rng(3).integers(
            0, 61, (9,)).astype(np.int32)
        eng.submit(prompt, max_new_tokens=4, seed=11)
        eng.run(max_steps=100)
        return eng, tuple(int(t) for t in prompt[:8])

    def test_extract_verify_install_roundtrip(self, toy, src):
        eng, key = src
        page = eng.kv.get_prefix(key)
        assert page is not None, "precondition: page registered"
        moved = extract_page(eng, key)
        assert moved.length == 8 and moved.key == key
        assert moved.nbytes() > 0
        verify_page(moved)                       # arrival gate passes
        dst = _engine(toy)
        assert install_page(dst, moved) is True
        assert dst.kv.has_prefix(key)
        # duplicate delivery: dropped (False), not a pool-contract crash
        assert install_page(dst, moved) is False

    def test_missing_page_is_typed(self, toy, src):
        eng, key = src
        with pytest.raises(HandoffError, match="not in the source"):
            extract_page(eng, key[:4])           # never registered

    def test_corrupt_page_is_typed_and_names_digest(self, src):
        import jax

        eng, key = src
        page = extract_page(eng, key)
        # one bit flipped on the "wire" after departure digests
        leaves, treedef = jax.tree_util.tree_flatten(page.lane)
        i = next(j for j, x in enumerate(leaves) if np.asarray(x).size)
        arr = np.array(leaves[i])
        arr.reshape(-1).view(np.uint8)[0] ^= 0xFF
        leaves[i] = arr
        page.lane = jax.tree_util.tree_unflatten(treedef, leaves)
        with pytest.raises(HandoffError, match="sha256"):
            verify_page(page)

    def test_install_verifies_before_touching_pool(self, toy, src):
        eng, key = src
        page = extract_page(eng, key)
        page.entries[0]["sha256"] = "0" * 64
        dst = _engine(toy)
        with pytest.raises(HandoffError):
            install_page(dst, page)
        assert not dst.kv.has_prefix(key)        # nothing installed


# ---------------------------------------------------------------------------
# the disaggregated frontend: routing, parity, fault paths
# ---------------------------------------------------------------------------


class TestDisaggServing:
    def test_handoff_parity_and_hit_skips_prefill(self, toy):
        rng = np.random.default_rng(0)
        # len 3: share point < chunk -> routed straight to decode; the
        # rest prefill in the prefill pool and hand their page off
        lens = (3, 5, 9, 7, 6)
        prompts = [rng.integers(0, 61, (n,)).astype(np.int32)
                   for n in lens]
        front = _front(toy)
        rids = [front.submit(p, max_new_tokens=6 + i % 4)
                for i, p in enumerate(prompts)]
        front.run_until_drained(timeout_s=60.0)
        _assert_solo_parity(toy, front, prompts, rids)
        s = front.summary()
        handoffs = [t for t in front.metrics.transitions
                    if t["event"] == "handoff"]
        assert len(handoffs) == len(lens) - 1
        # the 0-counters: failure counters REPORT AT ZERO on a clean
        # run (absence of evidence must be visible, not missing keys)
        assert s["counters"]["handoff_failures"] == 0
        assert s["counters"]["handoff_reroutes"] == 0
        assert "handoff_parity_mismatches" not in s["counters"]
        assert rids[0] not in front.prefill.metrics.records
        # per-phase split in the window: TTFT (prefill pressure) and
        # TPOT (decode pressure) per QoS class
        w = s["window"]["per_class"]["best_effort"]
        assert "ttft_p99_ms" in w and "tpot_p99_ms" in w
        assert s["pools"]["prefill"]["n_alive"] == 1

        # resubmission: the decode pool's radix index already holds the
        # full-prompt page — the prefill pool is NOT touched
        rid2 = front.submit(prompts[1], max_new_tokens=8)
        front.run_until_drained(timeout_s=60.0)
        assert rid2 not in front.prefill.metrics.records
        _assert_solo_parity(toy, front, [prompts[1]], [rid2])
        eng = front.decode.replicas[0].engine
        assert eng.metrics.get_counter("prefix_hits") >= 1

    def test_corrupt_handoff_rerouted_with_parity(self, toy):
        """A bit flipped on the wire AFTER departure digests: the
        arrival re-digest must catch it (typed `integrity` failure),
        the request must re-route and still finish solo-identical —
        never silent garbage."""
        fault = HandoffCorruption(at_handoff=0)
        front = _front(toy, fault=fault)
        p = np.random.default_rng(1).integers(0, 61, (9,)).astype(np.int32)
        rid = front.submit(p, max_new_tokens=7)
        front.run_until_drained(timeout_s=60.0)
        assert fault.fired == 1
        _assert_solo_parity(toy, front, [p], [rid])
        c = front.summary()["counters"]
        assert c["handoff_failures"] == 1 and c["handoff_reroutes"] == 1
        fails = [t for t in front.metrics.transitions
                 if t["event"] == "handoff_failure"]
        assert fails and fails[0]["failure"] == "integrity"
        assert "sha256" in fails[0]["reason"]

    def test_handoff_window_kill_rerouted_never_stranded(self, toy):
        """ISSUE 16 fix: the only prefill replica dies between prefill
        completion and handoff acknowledgment. The request must
        re-route (decode-pool re-prefill) and complete with parity; the
        supervisor restarts the replica."""
        kill = HandoffWindowKill(at_handoff=0)
        front = _front(toy, fault=kill)
        p = np.random.default_rng(2).integers(0, 61, (7,)).astype(np.int32)
        rid = front.submit(p, max_new_tokens=6)
        front.run_until_drained(timeout_s=60.0)
        assert kill.fired == 1
        _assert_solo_parity(toy, front, [p], [rid])
        c = front.summary()["counters"]
        assert c["handoff_failures"] == 1 and c["handoff_reroutes"] == 1
        fails = [t for t in front.metrics.transitions
                 if t["event"] == "handoff_failure"]
        assert fails and fails[0]["failure"] == "window_kill"
        front.prefill.pump(1)
        assert front.prefill.replica_states() == ["alive"]

    def test_handoff_latency_window_still_parity(self, toy):
        """A nonzero transfer latency holds pages in flight (the
        window the kill fault targets) — delivery after the delay must
        still verify + install + finish with parity."""
        front = _front(toy, handoff_latency_s=0.05)
        p = np.random.default_rng(4).integers(0, 61, (9,)).astype(np.int32)
        rid = front.submit(p, max_new_tokens=5)
        front.run_until_drained(timeout_s=60.0)
        _assert_solo_parity(toy, front, [p], [rid])
        assert front.summary()["counters"]["handoffs"] >= 1


# ---------------------------------------------------------------------------
# fleetsim: the two-tier model, and fingerprint stability of everything
# that predates it
# ---------------------------------------------------------------------------


class TestFleetsimDisagg:
    def test_new_trace_kind_shape_and_determinism(self):
        t1 = synthetic_trace("adversarial_long_prompt", seed=11,
                             horizon_s=2.0, base_rate=12.0)
        t2 = synthetic_trace("adversarial_long_prompt", seed=11,
                             horizon_s=2.0, base_rate=12.0)
        assert t1.fingerprint() == t2.fingerprint()
        # guaranteed keeps short prompts; the long-prompt pressure is
        # carried by the other classes (the head-of-line adversary)
        by_qos = {}
        for r in t1.requests:
            by_qos.setdefault(r.qos, []).append(r.prompt_len)
        assert max(by_qos["guaranteed"]) <= 8
        assert max(by_qos["best_effort"] + by_qos["sheddable"]) >= 18

    def test_preexisting_trace_fingerprints_unchanged(self):
        """The exact trace fingerprints from BEFORE the two-tier model
        landed — the new trace kind and knobs must not perturb the
        shared rng call order."""
        assert synthetic_trace("bursty", seed=20260804, horizon_s=6.0,
                               base_rate=25.0).fingerprint() \
            == "2981efa90ab97ccf"
        assert synthetic_trace("diurnal", seed=20260804, horizon_s=6.0,
                               base_rate=25.0).fingerprint() \
            == "d62120db0aafb066"
        from apex1_tpu.autopilot.drill import overload_trace
        assert overload_trace(seed=20260804, horizon_s=6.0).fingerprint() \
            == "d8cc6aa26cd8f672"

    def test_new_knobs_inert_on_preexisting_kinds(self):
        """`long_prompt_lens` only binds on the new kind; the sim's
        disagg knobs default off. Neither may move an old trace."""
        a = synthetic_trace("bursty", seed=9, horizon_s=2.0,
                            base_rate=12.0)
        b = synthetic_trace("bursty", seed=9, horizon_s=2.0,
                            base_rate=12.0, long_prompt_lens=(50, 60))
        assert a.fingerprint() == b.fingerprint()
        cfg = FleetSimConfig()
        assert (cfg.disagg, cfg.handoff_latency_s,
                cfg.prefill_round_cost) == (False, 0.0, False)

    def test_disagg_episode_deterministic_with_handoffs(self):
        trace = synthetic_trace("adversarial_long_prompt", seed=13,
                                horizon_s=1.5, base_rate=10.0,
                                prompt_lens=(2, 4))
        fcfg = FrontendConfig(n_replicas=2, capacity_per_replica=8,
                              hedge_after_s=None)
        sim = FleetSimConfig(disagg=True, prefill_replicas=1,
                             prefill_round_cost=True, max_len=64)
        r1 = run_fleet(trace, fcfg, sim=sim)
        r2 = run_fleet(trace, fcfg, sim=sim)
        assert r1.fingerprint() == r2.fingerprint()
        assert r1.summary["counters"]["handoffs"] > 0
        assert r1.summary["counters"]["handoff_failures"] == 0
        assert all(o["status"] != "lost" for o in r1.outcomes)

    def test_headline_disagg_holds_ttft_where_unified_fails(self):
        """The A/B the subsystem exists for: same total replicas, same
        adversarial long-prompt trace, honest prefill round cost.
        Unified interleaves long prefills with decode steps and blows
        the guaranteed-class TTFT bound; the split fleet keeps decode
        slots clear of long prefills and holds it — with every common
        finished stream token-identical across the two fleets (same
        request id ⇒ same derived seed ⇒ transitively pinned to solo
        generate)."""
        trace = synthetic_trace(
            "adversarial_long_prompt", seed=20260807, horizon_s=4.0,
            base_rate=25.0, prompt_lens=(2, 4),
            long_prompt_lens=(18, 30),
            class_mix={"guaranteed": 0.4, "best_effort": 0.35,
                       "sheddable": 0.25})
        fcfg = FrontendConfig(n_replicas=3, capacity_per_replica=8,
                              hedge_after_s=None)
        uni = run_fleet(trace, fcfg, sim=FleetSimConfig(
            prefill_round_cost=True, max_len=64))
        dis = run_fleet(trace, fcfg, sim=FleetSimConfig(
            disagg=True, prefill_replicas=1,
            prefill_round_cost=True, max_len=64))
        bound = 0.12
        att_uni = uni.ttft_attainment("guaranteed", bound)
        att_dis = dis.ttft_attainment("guaranteed", bound)
        assert att_uni <= 0.97, att_uni          # unified FAILS the bound
        assert att_dis >= 0.99, att_dis          # disagg HOLDS it
        assert dis.summary["counters"]["handoffs"] > 0
        assert dis.summary["counters"]["handoff_failures"] == 0
        # cross-fleet token parity on every request both fleets finished
        sha = {o["idx"]: o["tokens_sha1"] for o in uni.outcomes
               if o["status"] == "done"}
        common = [o for o in dis.outcomes
                  if o["status"] == "done" and o["idx"] in sha]
        assert len(common) >= 20
        for o in common:
            assert o["tokens_sha1"] == sha[o["idx"]], o


# ---------------------------------------------------------------------------
# pool-ratio law: pure policy, then the closed loop
# ---------------------------------------------------------------------------


def _pool_cfg(**over):
    kw = dict(slo={"best_effort": SLOTarget(ttft_p99_ms=100.0,
                                            tpot_p99_ms=50.0)},
              fit_hedge=False, pool_sustain=3, pool_cooldown=4)
    kw.update(over)
    return AutopilotConfig(**kw)


def _pool_view(ttft_ms, tpot_ms, *, pools="both", n=32):
    if pools == "both":
        pools = {"prefill": {"n_replicas": 1, "n_alive": 1,
                             "inflight": 0, "load_fraction": 0.0},
                 "decode": {"n_replicas": 3, "n_alive": 3,
                            "inflight": 0, "load_fraction": 0.0}}
    window = {"best_effort": {"n": n, "latency_p99_ms": 10.0}}
    if ttft_ms is not None:
        window["best_effort"]["ttft_p99_ms"] = ttft_ms
    if tpot_ms is not None:
        window["best_effort"]["tpot_p99_ms"] = tpot_ms
    return FleetView(mode="normal", load_fraction=0.4, inflight=4,
                     capacity=32, n_replicas=4, n_alive=4,
                     admission_limit=None, window=window,
                     per_tenant={}, pools=pools)


def _shifts(view, state, cfg, ticks):
    out = []
    for t in range(ticks):
        out += [(t, a) for a in decide(view, state, cfg)
                if a.kind == "shift_pool"]
    return out


class TestPoolRatioPolicy:
    def test_inert_on_unified_fleet(self):
        # massive imbalance, but no pools snapshot -> the law never fires
        v = _pool_view(400.0, 10.0, pools=None)
        assert _shifts(v, ControllerState(), _pool_cfg(), 20) == []

    def test_inert_on_half_a_comparison(self):
        # TTFT pressure measurable, TPOT not: which phase is slowER is
        # unknowable -> no action, and the sustain counter resets
        st = ControllerState()
        assert _shifts(_pool_view(400.0, None), st, _pool_cfg(), 20) == []
        assert st.pool_imbalance_ticks == 0

    def test_deadband_absorbs_mild_imbalance(self):
        # 1.2x vs 1.0x normalized: inside the 1.3 deadband forever
        v = _pool_view(120.0, 50.0)
        assert _shifts(v, ControllerState(), _pool_cfg(), 20) == []

    def test_thin_window_actuates_nothing(self):
        v = _pool_view(400.0, 10.0, n=3)       # < min_window samples
        assert _shifts(v, ControllerState(), _pool_cfg(), 20) == []

    def test_sustain_then_shift_then_cooldown(self):
        # prefill pressure 3.0 vs decode 0.5, sustained
        v = _pool_view(300.0, 25.0)
        cfg = _pool_cfg()
        got = _shifts(v, ControllerState(), cfg, 14)
        assert len(got) >= 2
        first_t, first = got[0]
        assert first_t == cfg.pool_sustain - 1  # not before sustain
        assert first.params == {"to": "prefill"}
        ev = first.evidence
        assert ev["pressure_prefill"] == pytest.approx(3.0)
        assert ev["pressure_decode"] == pytest.approx(0.5)
        assert ev["ttft"]["class"] == "best_effort"
        # refractory: consecutive shifts at least pool_cooldown apart
        assert got[1][0] - first_t >= cfg.pool_cooldown

    def test_decode_side_and_side_flip_resets_sustain(self):
        cfg = _pool_cfg()
        pools = {"prefill": {"n_alive": 2}, "decode": {"n_alive": 2}}
        v_dec = _pool_view(50.0, 200.0, pools=pools)
        got = _shifts(v_dec, ControllerState(), cfg, 6)
        assert got and got[0][1].params == {"to": "decode"}
        # alternating pressured side never accumulates sustain
        st = ControllerState()
        v_pre = _pool_view(300.0, 25.0, pools=pools)
        for i in range(12):
            acts = decide(v_pre if i % 2 else v_dec, st, cfg)
            assert [a for a in acts if a.kind == "shift_pool"] == []

    def test_donor_pool_never_drained(self):
        # decode is the donor but holds ONE replica: each phase always
        # keeps a pool, so the law must decline forever
        pools = {"prefill": {"n_alive": 3}, "decode": {"n_alive": 1}}
        v = _pool_view(300.0, 25.0, pools=pools)
        assert _shifts(v, ControllerState(), _pool_cfg(), 20) == []

    def test_closed_loop_shift_banked_on_live_fleet(self):
        """End to end: a long-prompt-heavy episode starves the 1-replica
        prefill tier, windowed TTFT/TPOT pressures diverge, and the
        autopilot actuates `shift_pool` toward prefill — banked as a
        `pool_shift` transition AND an autopilot episode entry with the
        per-phase evidence attached. Replayable bit-identically."""
        trace = synthetic_trace(
            "adversarial_long_prompt", seed=20260807, horizon_s=5.0,
            base_rate=25.0, prompt_lens=(2, 4),
            long_prompt_lens=(18, 30),
            class_mix={"guaranteed": 0.3, "best_effort": 0.45,
                       "sheddable": 0.25})
        fcfg = FrontendConfig(n_replicas=4, capacity_per_replica=8,
                              hedge_after_s=None)
        sim = FleetSimConfig(disagg=True, prefill_replicas=1,
                             prefill_round_cost=True, max_len=64)
        ap = AutopilotConfig(
            slo={"best_effort": SLOTarget(ttft_p99_ms=120.0,
                                          tpot_p99_ms=60.0)},
            max_replicas=4, fit_hedge=False)
        rep = run_fleet(trace, fcfg, sim=sim, autopilot=ap)
        shifts = [a for a in rep.actions if a["action"] == "shift_pool"]
        assert shifts, "pool-ratio law never actuated"
        assert all(a["params"] == {"to": "prefill"} for a in shifts)
        assert all("pressure_prefill" in a["evidence"] for a in shifts)
        banked = [t for t in rep.transitions
                  if t["event"] == "pool_shift"]
        assert len(banked) >= len(shifts)
        rep2 = run_fleet(trace, fcfg, sim=sim, autopilot=ap)
        assert rep.fingerprint() == rep2.fingerprint()
