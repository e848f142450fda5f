"""PR 13 — telemetry-driven fleet autopilot + replayable fleet
simulator + perf-drift tripwire.

The headline drill (module fixture, shared by every assertion): on a
replayed adversarial-overload trace, EVERY static threshold-ladder
config in the stated sweep misses guaranteed-class SLO attainment
while the autopilot — same baseline provisioning, same (trace, seed) —
holds it; the full actuation history is reconstructable from banked
events; and the episode replays bit-identically. Around it: the
rolling-window metrics satellite, the frontend knob surface, the pure
policy hysteresis/ladder, simulator determinism under chaos, and the
jax-free drift gate's three exit codes.
"""

import importlib.util
import json
import pathlib

import numpy as np
import pytest

from apex1_tpu.autopilot import (Autopilot, AutopilotConfig,
                                 ControllerState, FleetView, SLOTarget,
                                 decide, drill)
from apex1_tpu.serving import (Backpressure, FrontendConfig,
                               ReplicaConfig, ServingFrontend,
                               ServingMetrics)
from apex1_tpu.testing.fleetsim import (FleetSimConfig, Trace,
                                        VirtualClock, run_fleet,
                                        synthetic_trace)

_REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def headline():
    """ONE run of the full drill (3 static arms + the autopilot arm);
    every headline assertion reads from it."""
    return drill.run_headline()


# ---------------------------------------------------------------------------
# satellite: rolling-window per-class percentiles
# ---------------------------------------------------------------------------


class TestWindowMetrics:
    @staticmethod
    def _terminal(m, rid, t0, dt, *, qos, status="done", tenant=None):
        m.event(rid, "queued", now=t0, qos=qos, tenant=tenant)
        m.event(rid, "first_token", now=t0 + dt / 2)
        m.event(rid, status, now=t0 + dt)

    def test_window_diverges_from_whole_run_after_load_shift(self):
        """The satellite's point: whole-run percentiles freeze late
        signal under early history; the ring does not. 20 slow
        guaranteed requests then 8 fast ones — whole-run p99 stays
        ~2 s, the 8-deep window reads the NEW regime (~0.1 s)."""
        m = ServingMetrics(window=8)
        for i in range(20):
            self._terminal(m, i, float(i), 2.0, qos="guaranteed")
        for i in range(20, 28):
            self._terminal(m, i, float(i), 0.1, qos="guaranteed")
        s = m.summary()
        assert s["latency_p99_ms"] > 1500.0          # frozen history
        w = s["window"]
        assert w["size"] == 8
        g = w["per_class"]["guaranteed"]
        assert g["n"] == 8 and g["done"] == 8
        assert g["latency_p99_ms"] < 200.0           # live signal
        assert g["ttft_p99_ms"] < 100.0

    def test_window_separates_classes_and_tenants(self):
        m = ServingMetrics(window=32)
        for i in range(6):
            self._terminal(m, i, float(i), 0.5, qos="guaranteed",
                           tenant="acme")
        for i in range(6, 10):
            self._terminal(m, i, float(i), 3.0, qos="sheddable",
                           tenant="zeta",
                           status="evicted" if i % 2 else "done")
        w = m.summary()["window"]
        assert w["per_class"]["guaranteed"]["done"] == 6
        assert w["per_class"]["sheddable"]["n"] == 4
        assert w["per_class"]["sheddable"]["done"] == 2
        assert w["per_class"]["guaranteed"]["latency_p99_ms"] \
            < w["per_class"]["sheddable"]["latency_p99_ms"]
        assert set(w["per_tenant"]) == {"acme", "zeta"}
        # tenant stats are TTFT-only (they feed the hedge-budget fit)
        assert "latency_p99_ms" not in w["per_tenant"]["acme"]

    def test_whole_run_fields_unchanged_by_ring(self):
        """Whole-run keys keep their meaning and presence."""
        m = ServingMetrics(window=2)
        for i in range(5):
            self._terminal(m, i, float(i), 1.0, qos="best_effort")
        s = m.summary()
        assert s["requests"] == 5 and s["done"] == 5
        assert s["window"]["size"] == 2  # ring clamped, run fields not

    def test_rejections_hit_done_rate_not_latency_percentiles(self):
        """A refusal is terminal at its queued instant. It must count
        against the windowed done-rate (the signal that sees
        admission-induced misses) WITHOUT contributing a fake 0.0 s
        latency that would deflate the percentiles — under a rejection
        flood, a latency-only SLO must not read 'excellent' (review
        finding)."""
        m = ServingMetrics(window=16)
        for i in range(4):
            self._terminal(m, i, float(i), 2.0, qos="guaranteed")
        for i in range(4, 12):                  # flood of refusals
            m.event(i, "queued", now=float(i), qos="guaranteed")
            m.event(i, "rejected", now=float(i), reason="capacity")
        s = m.summary()
        g = s["window"]["per_class"]["guaranteed"]
        assert g["n"] == 12 and g["done"] == 4  # done-rate sees them
        assert g["latency_p99_ms"] > 1500.0     # percentiles do not
        assert s["latency_p99_ms"] > 1500.0     # whole-run likewise


# ---------------------------------------------------------------------------
# the frontend knob surface
# ---------------------------------------------------------------------------


def _never_build():
    raise AssertionError("engine must not be built in this test")


class TestFrontendKnobs:
    def test_admission_limit_caps_capacity_and_is_banked(self):
        clock = VirtualClock()
        front = ServingFrontend(
            _never_build,
            FrontendConfig(n_replicas=2, capacity_per_replica=8,
                           hedge_after_s=None),
            clock=clock)
        assert front.capacity == 16
        front.set_admission_limit(2, by="test", why="fit")
        assert front.capacity == 2
        front.submit([1, 2], max_new_tokens=4, req_id=0)
        front.submit([1, 2], max_new_tokens=4, req_id=1)
        with pytest.raises(Backpressure):
            front.submit([1, 2], max_new_tokens=4, req_id=2)
        front.set_admission_limit(None, by="test")
        assert front.capacity == 16
        front.submit([1, 2], max_new_tokens=4, req_id=3)
        lims = [t for t in front.metrics.transitions
                if t["event"] == "admission_limit"]
        assert [t["limit"] for t in lims] == [2, None]
        assert lims[0]["by"] == "test" and lims[0]["why"] == "fit"
        # the refusal joined the lifecycle stream: the window sees
        # admission-induced misses the latency percentiles cannot
        w = front.metrics.summary()["window"]
        assert w["per_class"]["best_effort"]["n"] == 1
        assert w["per_class"]["best_effort"]["done"] == 0

    def test_external_mode_control_disables_load_ladder(self):
        front = ServingFrontend(
            _never_build,
            FrontendConfig(n_replicas=1, capacity_per_replica=4,
                           mode_control="external", sustain_rounds=1,
                           hedge_after_s=None),
            clock=VirtualClock())
        for i in range(4):     # 100% load fraction, sustained
            front.submit([1], max_new_tokens=2, req_id=i)
        for _ in range(5):
            front._update_mode()
        assert front.mode == "normal"    # ladder is off
        front.set_mode("shedding", by="autopilot",
                       evidence={"breaches": ["x"]})
        assert front.mode == "shedding"
        flip = [t for t in front.metrics.transitions
                if t["event"] == "mode"][-1]
        assert flip["by"] == "autopilot" and flip["to"] == "shedding"
        assert flip["evidence"] == {"breaches": ["x"]}
        with pytest.raises(ValueError):
            front.set_mode("panic")
        with pytest.raises(ValueError):
            ServingFrontend(_never_build,
                            FrontendConfig(mode_control="bogus"))

    def test_attach_flips_only_this_frontend_not_shared_config(self):
        """Attaching an Autopilot must not mutate the (possibly
        shared) FrontendConfig: a sibling frontend built from the same
        config keeps its load ladder (review finding)."""
        cfg = FrontendConfig(n_replicas=1, capacity_per_replica=4,
                             hedge_after_s=None)
        fa = ServingFrontend(_never_build, cfg, clock=VirtualClock())
        fb = ServingFrontend(_never_build, cfg, clock=VirtualClock())
        Autopilot(fa, AutopilotConfig())
        assert fa.mode_control == "external"
        assert fb.mode_control == "load"      # sibling unaffected
        assert cfg.mode_control == "load"     # config untouched

    def test_retire_replica_unknown_id_is_none_not_a_crash(self):
        """A stale or negative explicit id (replayed from a banked
        transition of another episode) is 'nothing retirable', never
        an IndexError or an alias-from-the-end drain."""
        front = ServingFrontend(
            _never_build,
            FrontendConfig(n_replicas=2, capacity_per_replica=4,
                           hedge_after_s=None),
            clock=VirtualClock())
        assert front.retire_replica(99) is None
        assert front.retire_replica(-1) is None
        assert front.n_alive == 2             # nothing drained

    def test_hedge_budget_per_tenant_resolution(self):
        front = ServingFrontend(
            _never_build,
            FrontendConfig(n_replicas=1, hedge_after_s=0.25),
            clock=VirtualClock())
        assert front._hedge_budget_for("acme") == 0.25   # static cfg
        front.set_hedge_budget(0.5, by="autopilot")      # fitted default
        front.set_hedge_budget(0.1, tenant="acme", by="autopilot")
        front.set_hedge_budget(None, tenant="zeta")      # disabled
        assert front._hedge_budget_for("acme") == 0.1
        assert front._hedge_budget_for("zeta") is None
        assert front._hedge_budget_for("other") == 0.5
        banked = [t for t in front.metrics.transitions
                  if t["event"] == "hedge_budget"]
        assert [(t["tenant"], t["budget_s"]) for t in banked] == \
            [(None, 0.5), ("acme", 0.1), ("zeta", None)]

    def test_add_and_retire_replica_drains_then_stops(self):
        from apex1_tpu.serving import Engine, EngineConfig
        from apex1_tpu.testing.chaos import toy_decoder

        apply_fn, make_cache, params = toy_decoder()
        ecfg = EngineConfig(max_slots=2, max_len=32, prefill_chunk=4,
                            vocab_size=61, seed=3)
        clock = VirtualClock()
        front = ServingFrontend(
            lambda: Engine(apply_fn, make_cache, params, ecfg),
            FrontendConfig(n_replicas=1, capacity_per_replica=8,
                           hedge_after_s=None,
                           replica=ReplicaConfig(watchdog_s=1e9)),
            clock=clock)
        assert front.retire_replica() is None    # never below one
        rid2 = front.add_replica(by="autopilot")
        assert rid2 == 1 and front.n_alive == 2
        assert front.capacity == 16
        r0 = front.submit([1, 2, 3], max_new_tokens=4, req_id=100)
        front.pump(1)                            # route + admit work
        clock.advance(0.01)
        got = front.retire_replica(by="autopilot")
        assert got is not None
        assert front.n_alive == 1                # no new routes to it
        front.run_until_drained(timeout_s=60.0)
        for _ in range(3):
            front.pump(1)                        # let retirement land
        assert front.poll(r0).status == "done"
        summ = front.summary()
        assert summ["replicas"][got]["state"] == "stopped"
        assert not summ["replicas"][got]["retiring"]
        events = [t["event"] for t in front.metrics.transitions]
        assert "replica_added" in events
        assert "replica_retiring" in events
        assert "replica_retired" in events
        assert summ["n_replicas"] == 2 and summ["n_alive"] == 1
        # the retired supervisor stays (ids are route indices) but its
        # engine must not: a scale_up/scale_down cycle that pinned a
        # KV cache per retirement would leak the fleet's memory
        assert front.replicas[got].engine is None

    def test_summary_schema_has_control_surface(self, headline):
        """The satellite: summary() is ONE structured dict carrying
        mode history + per-replica restart/hedge/shed counters
        (docs/serving.md § Frontend summary)."""
        s = headline.auto.summary
        for key in ("mode", "mode_history", "n_replicas", "n_alive",
                    "capacity", "inflight", "load_fraction",
                    "admission_limit", "hedge_budgets", "window",
                    "counters", "replicas"):
            assert key in s, key
        for rep in s["replicas"].values():
            for key in ("state", "restarts", "generation",
                        "engines_built", "steps", "load", "retiring",
                        "hedges", "sheds"):
                assert key in rep, key
        assert all(t["event"] == "mode" for t in s["mode_history"])


# ---------------------------------------------------------------------------
# pure policy: hysteresis, ladder order, fits
# ---------------------------------------------------------------------------


def _view(**over) -> FleetView:
    base = dict(mode="normal", load_fraction=0.5, inflight=8,
                capacity=16, n_replicas=2, n_alive=2,
                admission_limit=None,
                window={"guaranteed": {
                    "n": 20, "done": 20, "latency_p99_ms": 100.0}},
                per_tenant={})
    base.update(over)
    return FleetView(**base)


def _breach_view(**over):
    return _view(window={"guaranteed": {
        "n": 20, "done": 20, "latency_p99_ms": 5000.0}}, **over)


def _cfg(**over) -> AutopilotConfig:
    kw = dict(slo={"guaranteed": SLOTarget(latency_p99_ms=1000.0,
                                           success_rate=0.9)},
              min_replicas=2, max_replicas=4, breach_sustain=3,
              clear_sustain=4, cooldown_ticks=2, min_window=8,
              fit_hedge=False)
    kw.update(over)
    return AutopilotConfig(**kw)


class TestPolicy:
    def test_no_evidence_freezes_instead_of_clearing(self):
        """An evidence-free tick is NOT a "clear" tick: with every
        SLO'd class below min_window (e.g. guaranteed entries crowded
        out of the shared ring by sheddable churn mid-overload), the
        controller must freeze — relaxing the admission limit or
        de-escalating on zero evidence walks straight back into the
        overload (review finding)."""
        cfg, st = _cfg(), ControllerState()
        blind = _view(mode="degraded", admission_limit=4,
                      window={"guaranteed": {"n": 2, "done": 2}})
        for _ in range(cfg.clear_sustain * 3):
            assert decide(blind, st, cfg) == []
        assert st.clear_ticks == 0 and st.breach_ticks == 0
        # evidence returns clean -> relaxation resumes normally
        clear = _view(mode="degraded", admission_limit=4)
        acts = []
        for _ in range(cfg.clear_sustain):
            acts += decide(clear, st, cfg)
        assert [a.kind for a in acts] == ["set_admission"]

    def test_sub_sustain_breach_never_actuates(self):
        """Anti-flap, rung zero: a breach shorter than breach_sustain
        produces NOTHING, however severe."""
        cfg, st = _cfg(), ControllerState()
        for _ in range(cfg.breach_sustain - 1):
            assert decide(_breach_view(), st, cfg) == []
        assert decide(_view(), st, cfg) == []        # burst over
        assert st.breach_ticks == 0
        for _ in range(cfg.breach_sustain - 1):      # second burst:
            assert decide(_breach_view(), st, cfg) == []   # no carry

    def test_thin_evidence_never_actuates(self):
        cfg, st = _cfg(), ControllerState()
        thin = _view(window={"guaranteed": {
            "n": 3, "done": 0, "latency_p99_ms": 9000.0}})
        for _ in range(10):
            assert decide(thin, st, cfg) == []

    def test_escalation_ladder_order_and_cooldown(self):
        """Sustained breach walks shed → scale → scale → degrade →
        admission, one rung per cooldown window, evidence attached."""
        cfg, st = _cfg(), ControllerState()
        view = _breach_view()
        kinds = []
        for _ in range(60):
            acts = decide(view, st, cfg)
            for a in acts:
                kinds.append(a.kind)
                assert a.evidence["breaches"], "evidence required"
                if a.kind == "escalate":
                    view = _breach_view(mode=a.params["mode"],
                                        n_alive=view.n_alive)
                elif a.kind == "scale_up":
                    view = _breach_view(mode=view.mode,
                                        n_alive=view.n_alive + 1)
                elif a.kind == "set_admission":
                    view = _breach_view(
                        mode=view.mode, n_alive=view.n_alive,
                        admission_limit=a.params["limit"])
            if kinds and kinds[-1] == "set_admission":
                break
        assert kinds == ["escalate", "scale_up", "scale_up",
                         "escalate", "set_admission"]

    def test_relaxation_unwinds_in_reverse_and_needs_headroom(self):
        cfg = _cfg()
        st = ControllerState()
        view = _view(mode="degraded", n_alive=4, admission_limit=10,
                     load_fraction=0.2)
        kinds = []
        for _ in range(80):
            for a in decide(view, st, cfg):
                kinds.append((a.kind, a.params.get("mode")))
                if a.kind == "set_admission":
                    view = _view(mode=view.mode, n_alive=view.n_alive,
                                 admission_limit=None,
                                 load_fraction=0.2)
                elif a.kind == "deescalate":
                    view = _view(mode=a.params["mode"],
                                 n_alive=view.n_alive,
                                 load_fraction=0.2)
                elif a.kind == "scale_down":
                    view = _view(mode=view.mode,
                                 n_alive=view.n_alive - 1,
                                 load_fraction=0.2)
            if view.mode == "normal" and view.n_alive == 2:
                break
        assert kinds == [("set_admission", None),
                         ("deescalate", "shedding"),
                         ("scale_down", None), ("scale_down", None),
                         ("deescalate", "normal")]
        # and NO scale-down without percentile headroom, however low
        # the load: clear ticks accumulate but capacity stays
        st2 = ControllerState()
        tight = _view(n_alive=4, load_fraction=0.1,
                      window={"guaranteed": {
                          "n": 20, "done": 20,
                          "latency_p99_ms": 800.0}})  # > 0.5 * target
        for _ in range(20):
            assert decide(tight, st2, cfg) == []

    def test_success_rate_breach_detected(self):
        """The admission-miss dimension: healthy latency, rotten
        done-rate — the exact signature a hard overload shows through
        a rejecting front door."""
        cfg, st = _cfg(), ControllerState()
        v = _view(window={"guaranteed": {
            "n": 40, "done": 20, "latency_p99_ms": 100.0}})
        acts = []
        for _ in range(cfg.breach_sustain):
            acts = decide(v, st, cfg)
        assert [a.kind for a in acts] == ["escalate"]
        b = acts[0].evidence["breaches"]
        assert b[0]["metric"] == "success_rate"
        assert b[0]["value"] == 0.5

    def test_hedge_fit_from_tenant_ttft(self):
        cfg = _cfg(fit_hedge=True, fit_every=1, hedge_multiplier=3.0,
                   hedge_floor_s=0.05)
        st = ControllerState()
        v = _view(per_tenant={"acme": {"n": 20, "ttft_p99_ms": 100.0},
                              "thin": {"n": 2, "ttft_p99_ms": 9.0}})
        acts = decide(v, st, cfg)
        assert [(a.kind, a.params["tenant"]) for a in acts] == \
            [("fit_hedge", "acme")]
        assert acts[0].params["budget_s"] == pytest.approx(0.3)
        assert decide(v, st, cfg) == []   # unchanged ⇒ no re-emit
        v2 = _view(per_tenant={"acme": {"n": 20,
                                        "ttft_p99_ms": 500.0}})
        assert [a.params["budget_s"] for a in decide(v2, st, cfg)] \
            == [pytest.approx(1.5)]


# ---------------------------------------------------------------------------
# simulator determinism (+ chaos composition) and the traces
# ---------------------------------------------------------------------------


def _small_sim(seed=11, autopilot=True, chaos=True):
    from apex1_tpu.testing.chaos import kill_schedule

    trace = synthetic_trace("bursty", seed=seed, horizon_s=2.5,
                            base_rate=20.0)
    return run_fleet(
        trace, drill.frontend_config(),
        sim=drill.sim_config(),
        autopilot=drill.autopilot_config(fit_hedge=True)
        if autopilot else None,
        chaos=kill_schedule(seed=seed, n_replicas=2, lo=5, hi=40)
        if chaos else None)


class TestSimulatorDeterminism:
    def test_same_trace_seed_bit_identical_with_chaos(self):
        """THE determinism pin: same (trace, seed) — autopilot on,
        replica kill mid-episode — twice, bit-identical transition
        history AND token streams (the fingerprint hashes both)."""
        a, b = _small_sim(), _small_sim()
        assert a.transitions == b.transitions
        assert a.outcomes == b.outcomes
        assert a.actions == b.actions
        assert a.fingerprint() == b.fingerprint()
        # the kill really happened and was recovered
        events = [t["event"] for t in a.transitions]
        assert "replica_dead" in events and "replica_restart" in events

    def test_different_seed_differs(self):
        assert _small_sim(seed=12, chaos=False).fingerprint() \
            != _small_sim(seed=13, chaos=False).fingerprint()

    def test_single_token_requests_get_ttft(self):
        """A request whose first token and terminal result land in the
        same supervision round still gets its first_token stamp —
        TTFT percentiles (and the hedge-budget fit they feed) must not
        systematically exclude the FASTEST requests (review finding:
        collection used to pop them from the live set before the TTFT
        probe ran)."""
        trace = synthetic_trace("steady", seed=3, horizon_s=2.0,
                                base_rate=10.0, new_tokens=(1, 1))
        rep = run_fleet(trace, drill.frontend_config(),
                        sim=drill.sim_config())
        done = [o for o in rep.outcomes if o["status"] == "done"]
        assert done and all(o["ttft"] is not None for o in done)

    def test_trace_save_load_replay(self, tmp_path):
        """A recorded trace replays identically to the in-memory one
        that was banked."""
        t1 = synthetic_trace("diurnal", seed=5, horizon_s=2.0,
                             base_rate=15.0)
        path = t1.save(str(tmp_path / "trace.jsonl"))
        t2 = Trace.load(path)
        assert t2 == t1
        assert t2.fingerprint() == t1.fingerprint()
        with pytest.raises(ValueError, match="not a"):
            (tmp_path / "bad.jsonl").write_text('{"schema": "nope"}\n')
            Trace.load(str(tmp_path / "bad.jsonl"))

    def test_trace_kinds_and_generator_determinism(self):
        with pytest.raises(ValueError, match="unknown trace kind"):
            synthetic_trace("weekly", seed=1)
        t1 = synthetic_trace("adversarial_overload", seed=9,
                             horizon_s=3.0)
        t2 = synthetic_trace("adversarial_overload", seed=9,
                             horizon_s=3.0)
        assert t1.fingerprint() == t2.fingerprint()
        # the overload phase is really hotter than the shoulders
        mid = [r for r in t1.requests if 0.75 <= r.t < 2.4]
        edge = [r for r in t1.requests if r.t < 0.75 or r.t >= 2.4]
        assert len(mid) / 1.65 > 2.0 * len(edge) / 1.35


# ---------------------------------------------------------------------------
# anti-flap on a live fleet
# ---------------------------------------------------------------------------


class TestAntiFlap:
    def test_single_burst_never_scales_or_degrades(self):
        """A one-burst trace whose spike would trip any load-fraction
        trigger (arrivals in one control window exceed the shed
        threshold) actuates NOTHING: the percentile+hysteresis
        controller holds still through a burst the queue can absorb."""
        trace = synthetic_trace("bursty", seed=77, horizon_s=2.5,
                                base_rate=25.0, n_bursts=1,
                                burst_len_s=0.2, burst_mult=6.0)
        # the burst is real: some 0.25s window carries more arrivals
        # than the static ladder's shed threshold of frontend capacity
        times = np.asarray([r.t for r in trace.requests])
        peak = max(np.sum((times >= t) & (times < t + 0.25))
                   for t in np.arange(0.0, 2.3, 0.05))
        assert peak >= 0.75 * 32
        rep = run_fleet(trace, drill.frontend_config(),
                        sim=drill.sim_config(),
                        autopilot=drill.autopilot_config())
        assert rep.actions == []
        assert rep.summary["mode"] == "normal"
        assert rep.summary["n_replicas"] == drill.N_BASELINE

    def test_no_oscillation_on_sustained_overload(self, headline):
        """The overload episode escalates monotonically and relaxes at
        most once — never the up/down/up ping-pong hysteresis exists
        to forbid."""
        kinds = [a["action"] for a in headline.auto.actions]
        assert kinds.count("scale_up") <= drill.N_MAX - drill.N_BASELINE
        if "scale_down" in kinds:
            assert "scale_up" not in kinds[kinds.index("scale_down"):]
        ups = [i for i, k in enumerate(kinds) if k == "escalate"]
        downs = [i for i, k in enumerate(kinds) if k == "deescalate"]
        assert not ups or not downs or max(ups) < min(downs)


# ---------------------------------------------------------------------------
# THE headline drill
# ---------------------------------------------------------------------------


class TestHeadlineDrill:
    def test_every_static_misses_autopilot_holds(self, headline):
        v = headline.verdict()
        assert v["every_static_misses"], v
        assert v["autopilot_holds"], v
        # with margin on both sides of the SLO line, so ambient noise
        # in future refactors shows up as a drift, not a flake
        assert all(a <= 0.85 for a in v["static"].values()), v
        assert v["autopilot"] >= 0.93, v

    def test_autopilot_scaled_and_scoped(self, headline):
        """It held the SLO the way the tentpole claims: elastic
        capacity + percentile-driven modes, from baseline
        provisioning."""
        rep = headline.auto
        kinds = [a["action"] for a in rep.actions]
        assert "scale_up" in kinds
        assert "escalate" in kinds
        assert rep.summary["n_replicas"] > drill.N_BASELINE
        assert rep.summary["n_replicas"] <= drill.N_MAX
        added = [t for t in rep.transitions
                 if t["event"] == "replica_added"]
        assert len(added) == kinds.count("scale_up")
        # every static arm stayed at baseline (the sweep premise)
        for r in headline.static.values():
            assert r.summary["n_replicas"] == drill.N_BASELINE
            assert r.actions == []

    def test_actuations_banked_with_evidence(self, headline):
        """Every actuation appears in the transition history as an
        ``autopilot`` event whose evidence names the triggering
        breach."""
        rep = headline.auto
        banked = [t for t in rep.transitions
                  if t["event"] == "autopilot"]
        assert [t["action"] for t in banked] == \
            [a["action"] for a in rep.actions]
        for t, a in zip(banked, rep.actions):
            assert t["evidence"] == a["evidence"]
            if t["action"] in ("escalate", "scale_up",
                               "set_admission"):
                br = t["evidence"]["breaches"]
                assert br and br[0]["class"] == "guaranteed"
                assert br[0]["metric"] in ("latency_p99_ms",
                                           "success_rate")

    def test_headline_replay_bit_identical(self, headline):
        """Acceptance: the drill itself is bit-deterministic under
        (trace, seed)."""
        rerun = run_fleet(headline.trace, drill.frontend_config(),
                          sim=drill.sim_config(),
                          autopilot=drill.autopilot_config())
        assert rerun.fingerprint() == headline.auto.fingerprint()

    def test_episode_reconstructable_from_spine(self, tmp_path,
                                                monkeypatch):
        """With the obs spine armed, a (smaller) episode's full
        actuation history is reconstructable from the banked run file
        alone — action kinds, params, evidence, and order."""
        from apex1_tpu.obs import spine

        monkeypatch.setenv("APEX1_OBS_DIR", str(tmp_path))
        try:
            rep = run_fleet(
                drill.overload_trace(horizon_s=3.5),
                drill.frontend_config(),
                sim=drill.sim_config(),
                autopilot=drill.autopilot_config())
        finally:
            run = spine.default_run()
            path = run.path
            spine.set_default_run(None)
        assert rep.actions, "episode must have actuated"
        events = spine.read_events(path)
        acts = [e for e in events if e.get("name") == "autopilot.action"]
        got = [{"t": a["t_ctrl"], "tick": a["tick"],
                "action": a["action"], "params": a["params"],
                "result": a["result"], "evidence": a["evidence"]}
               for a in acts]
        assert got == rep.actions
        # the detections rode along too: serving transitions (mode
        # flips, sheds) are in the same stream
        names = {e.get("name") for e in events}
        assert "serving.transition" in names
        assert "serving.request" in names


# ---------------------------------------------------------------------------
# the drift gate (jax-free tripwire)
# ---------------------------------------------------------------------------


def _load_check_drift():
    spec = importlib.util.spec_from_file_location(
        "_check_drift_for_test", _REPO / "tools" / "check_drift.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def drift_mod():
    return _load_check_drift()


def _mini_corpus(tmp_path, *, measured_scale=1.0):
    """A minimal joinable corpus: one prediction row, one [tpu]
    record, a calibration table whose factor matches the fit
    exactly."""
    from apex1_tpu.obs import calibrate

    d = tmp_path / "pr"
    d.mkdir(exist_ok=True)
    row = {"name": "gpt2", "flops": 1e12, "bytes": 1e9,
           "units_per_step": 1e6}
    (d / "predicted_r1.json").write_text(json.dumps({"steps": [row]}))
    rate = calibrate.predicted_step_rate(row, "v5e")
    measured = rate / 2.0 * measured_scale
    (d / "bench_gpt2.log").write_text(json.dumps(
        {"metric": "tok/s [tpu]", "value": measured}) + "\n")
    cal = {"schema": calibrate.SCHEMA, "generation": "v5e",
           "factors": {"step:gpt2": {"slowdown": 2.0, "n": 1,
                                     "backend": "tpu"}},
           "proxy_factors": {}, "excluded": [], "pairs": []}
    (d / "calibration.json").write_text(json.dumps(cal))
    (d / "tuning").mkdir(exist_ok=True)
    return d


class TestDriftGate:
    def test_repo_without_a_table_fails_closed(self, drift_mod):
        """The repo ships no calibration table (no chip corpus exists
        for the current code): the gate cannot read its evidence, so
        it must not pass — exit 2, not 0."""
        assert drift_mod.run_gate(str(_REPO / "perf_results")) == 2

    def test_in_band_synthetic(self, tmp_path, drift_mod):
        assert drift_mod.run_gate(str(_mini_corpus(tmp_path))) == 0

    def test_drifted_record_fails(self, tmp_path, drift_mod):
        d = _mini_corpus(tmp_path, measured_scale=0.5)  # 2x slower
        assert drift_mod.run_gate(str(d)) == 1

    def test_failure_output_names_offending_record_path(
            self, tmp_path, drift_mod, capsys):
        """ISSUE 14 satellite: a drift failure must name the record
        PATH that carries the out-of-band measurement, not just the
        key — the fix is one open() away."""
        d = _mini_corpus(tmp_path, measured_scale=0.5)
        assert drift_mod.run_gate(str(d)) == 1
        out = capsys.readouterr().out
        assert ("offending record: "
                + str(d / "bench_gpt2.log")) in out

    def test_uncalibrated_new_key_fails(self, tmp_path, drift_mod):
        d = _mini_corpus(tmp_path)
        cal = json.loads((d / "calibration.json").read_text())
        cal["factors"] = {}                  # stale table, new record
        (d / "calibration.json").write_text(json.dumps(cal))
        assert drift_mod.run_gate(str(d)) == 1

    def test_tolerates_spec_serving_record_fields(self, tmp_path,
                                                  drift_mod):
        """ISSUE 15 satellite: a serving record's shape
        (multiplier_sweep rows with prefix_hit_rate / accept_rate /
        goodput + the int8 capacity block) banked into the corpus dir
        must not move the gate — serving benches join no
        predicted-step row, so they are NOT calibration evidence and
        the gate must neither fit from them nor fail-closed on them."""
        d = _mini_corpus(tmp_path)
        rec = {"metric": "serving tokens/sec gpt2-serving [cpu]",
               "value": 1234.5, "unit": "tokens/sec",
               "multiplier_sweep": {
                   "rows": [{"config": "radix_spec",
                             "prefix_hit_rate": 0.92,
                             "accept_rate": 0.41,
                             "goodput_tokens_per_sec": 999.0}],
                   "goodput_multiple": 1.31,
                   "int8_capacity": {"slots_bf16": 8,
                                     "slots_int8_same_budget": 16}}}
        (d / "bench_spec_serving.json").write_text(json.dumps(rec))
        (d / "bench_spec_serving_cpu.log").write_text(
            json.dumps(rec) + "\n")
        assert drift_mod.run_gate(str(d)) == 0

    def test_fail_closed_on_unreadable_evidence(self, tmp_path,
                                                drift_mod):
        d = _mini_corpus(tmp_path)
        (d / "calibration.json").write_text("{broken")
        assert drift_mod.run_gate(str(d)) == 2
        _mini_corpus(tmp_path)               # restore the table
        assert drift_mod.run_gate(str(d)) == 0
        (d / "tuning" / "flash_attention.json").write_text("{nope")
        assert drift_mod.run_gate(str(d)) == 2
        missing = tmp_path / "nowhere"
        missing.mkdir()
        assert drift_mod.run_gate(str(missing)) == 2   # no table at all

    def test_band_is_configurable(self, tmp_path, drift_mod):
        d = _mini_corpus(tmp_path, measured_scale=0.8)  # ratio 0.8
        assert drift_mod.run_gate(str(d), band=(0.75, 1.3),
                                  refit_tol=0.5) == 0
        assert drift_mod.run_gate(str(d), band=(0.9, 1.1),
                                  refit_tol=0.5) == 1
