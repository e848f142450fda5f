"""`serving.disagg.kv_transfer` error taxonomy + the re-route ladder.

Every `HandoffError` reason the module can raise is pinned here as
REACHABLE by a concrete fault — eviction, torn transfer, renamed /
reshaped / retyped / bit-flipped leaves, and the frontend's own
no-alive-source window — and every rung of the frontend's bounded
re-route ladder is exercised end-to-end:

  rung 1  radix-hit skip      (an earlier attempt's page already landed)
  rung 2  re-prefill survivor (prefill pool still routable)
  rung 3  decode re-prefill   (no prefill survivor this round)
  rung 4  LOUD eviction       (attempts > max_handoff_attempts)

Rungs 1-3 must end token-identical to an uninterrupted single-engine
run (the counter-keyed seed contract); rung 4 must end in a typed
`evicted` result that names the attempt budget — never a hang, never
silent garbage. The APX3xx protocol models
(`apex1_tpu.lint.protocols`, DisaggHandoffModel) prove this ladder
over every interleaving of the bounded configs; these tests pin the
SAME ladder on the shipped code with real pages.
"""

import numpy as np
import pytest

from apex1_tpu.serving import Engine, EngineConfig, FrontendConfig
from apex1_tpu.serving.disagg import (DisaggConfig, DisaggFrontend,
                                      HandoffError, extract_page,
                                      install_page, verify_page)
from apex1_tpu.testing.chaos import (HandoffCorruption, HandoffWindowKill,
                                     ServingFault, toy_decoder)

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
    ref = _engine(toy)
    for p, rid in zip(prompts, rids):
        res = front.poll(rid)
        assert res is not None and res.status == "done", (rid, res)
        sub = front._subs[rid]
        rr = ref.submit(p, max_new_tokens=sub.max_new_tokens,
                        seed=sub.seed)
        ref.run(max_steps=300)
        np.testing.assert_array_equal(res.tokens, ref.results[rr].tokens)


def _events(front, name):
    return [t for t in front.metrics.transitions if t["event"] == name]


# ---------------------------------------------------------------------------
# unit tier: every HandoffError reason, by mutation class
# ---------------------------------------------------------------------------


class TestHandoffErrorTaxonomy:
    @pytest.fixture()
    def src(self, toy):
        """An engine holding one chunk-aligned 8-token prefix page."""
        eng = _engine(toy)
        prompt = np.random.default_rng(3).integers(
            0, 61, (9,)).astype(np.int32)
        eng.submit(prompt, max_new_tokens=4, seed=11)
        eng.run(max_steps=100)
        return eng, tuple(int(t) for t in prompt[:8])

    def _leaf(self, page):
        return np.array(page.lane["toy"]["h"])

    def test_lru_evicted_page_is_typed_at_extract(self, src):
        """The availability reason: the page existed at prefill
        completion but was evicted before the transfer started — the
        exact race `extract_page`'s message names."""
        eng, key = src
        assert eng.kv.evict_prefix(key, force=True)
        with pytest.raises(HandoffError,
                           match="evicted before transfer"):
            extract_page(eng, key)

    def test_torn_transfer_leaf_count_both_directions(self, src):
        eng, key = src
        page = extract_page(eng, key)
        arr = self._leaf(page)
        page.lane = {"toy": {}}                  # a leaf lost in flight
        with pytest.raises(HandoffError,
                           match="0 leaves on arrival, 1 at departure"):
            verify_page(page)
        page.lane = {"toy": {"h": arr, "h2": arr}}   # a leaf invented
        with pytest.raises(HandoffError,
                           match="2 leaves on arrival, 1 at departure"):
            verify_page(page)

    def test_renamed_leaf_is_a_path_mismatch(self, src):
        eng, key = src
        page = extract_page(eng, key)
        page.lane = {"toy": {"z": self._leaf(page)}}
        with pytest.raises(HandoffError, match="path mismatch"):
            verify_page(page)

    def test_transposed_leaf_is_a_shape_mismatch(self, src):
        eng, key = src
        page = extract_page(eng, key)
        arr = self._leaf(page)
        page.lane = {"toy": {"h": arr.reshape(
            (arr.shape[1], arr.shape[0]) + arr.shape[2:])}}
        with pytest.raises(HandoffError, match="shape mismatch"):
            verify_page(page)

    def test_reinterpreted_leaf_is_a_dtype_mismatch(self, src):
        """Same bytes, same shape, different dtype (the classic
        serialization-metadata bug): the dtype field must catch it —
        the sha256 alone would pass."""
        eng, key = src
        page = extract_page(eng, key)
        arr = self._leaf(page)
        page.lane = {"toy": {"h": arr.view(np.int32)}}
        with pytest.raises(HandoffError, match="dtype mismatch"):
            verify_page(page)

    def test_bit_flip_is_a_sha256_mismatch_naming_the_leaf(self, src):
        eng, key = src
        page = extract_page(eng, key)
        arr = self._leaf(page)
        arr.reshape(-1).view(np.uint8)[-1] ^= 0x01
        page.lane = {"toy": {"h": arr}}
        with pytest.raises(HandoffError,
                           match=r"leaf \['toy'\]\['h'\] sha256"):
            verify_page(page)

    def test_install_never_touches_pool_on_any_mismatch(self, toy, src):
        eng, key = src
        dst = _engine(toy)
        for mutate in (lambda p: p.entries.pop(),
                       lambda p: p.entries[0].update(sha256="0" * 64)):
            page = extract_page(eng, key)
            mutate(page)
            with pytest.raises(HandoffError):
                install_page(dst, page)
            assert not dst.kv.has_prefix(key)


# ---------------------------------------------------------------------------
# integration tier: each rung of the re-route ladder, with parity
# ---------------------------------------------------------------------------


class _InstallThenKill(ServingFault):
    """The lost-ack race: the page REACHES the decode pool, then the
    source dies before the acknowledgment — the re-route must take the
    radix-hit-skip rung, not redo the prefill."""

    def __init__(self):
        self.front = None                # bound after construction
        self.fired = 0

    def on_handoff(self, replica_id, req_id, page):
        if self.fired:
            return
        self.fired += 1
        eng = self.front.decode.replicas[0].engine
        assert eng is not None, "decode pool not started at handoff"
        install_page(eng, page)
        from apex1_tpu.serving.replica import ReplicaKilled
        raise ReplicaKilled(
            f"chaos: source {replica_id} died after transfer of "
            f"request {req_id}, before the ack")


class _AlwaysCorrupt(ServingFault):
    """Sticky corruption: every handoff attempt's page is flipped on
    the wire — the crash-loop form the attempt budget exists for."""

    def __init__(self):
        self.fired = 0

    def on_handoff(self, replica_id, req_id, page):
        arr = np.array(page.lane["toy"]["h"])
        arr.reshape(-1).view(np.uint8)[0] ^= 0xFF
        page.lane = {"toy": {"h": arr}}
        self.fired += 1


class TestRerouteLadder:
    def _prompt(self, seed, n=9):
        return np.random.default_rng(seed).integers(
            0, 61, (n,)).astype(np.int32)

    def test_rung1_radix_hit_skip_after_lost_ack(self, toy):
        fault = _InstallThenKill()
        front = _front(toy, fault=fault)
        fault.front = front
        p = self._prompt(21)
        rid = front.submit(p, max_new_tokens=6)
        front.run_until_drained(timeout_s=60.0)
        assert fault.fired == 1
        _assert_solo_parity(toy, front, [p], [rid])
        # the rung's identity: one window_kill failure, one reroute,
        # and ZERO delivered handoffs — the page was already there, so
        # the decode pool radix-hit the installed prefix instead
        c = front.summary()["counters"]
        assert c["handoff_failures"] == 1 and c["handoff_reroutes"] == 1
        assert c.get("handoffs", 0) == 0
        assert _events(front, "handoff_failure")[0]["failure"] \
            == "window_kill"
        eng = front.decode.replicas[0].engine
        assert eng.metrics.get_counter("prefix_hits") >= 1

    def test_rung2_reprefill_on_survivor_after_integrity(self, toy):
        """One corrupt wire transfer: the arrival digest rejects it,
        the prefill pool is still alive, so attempt 1 re-prefills
        there and the SECOND handoff delivers."""
        fault = HandoffCorruption(at_handoff=0)
        front = _front(toy, fault=fault)
        p = self._prompt(22)
        rid = front.submit(p, max_new_tokens=6)
        front.run_until_drained(timeout_s=60.0)
        _assert_solo_parity(toy, front, [p], [rid])
        fails = _events(front, "handoff_failure")
        assert [f["failure"] for f in fails] == ["integrity"]
        assert "sha256" in fails[0]["reason"]
        delivered = _events(front, "handoff")
        assert delivered and delivered[-1]["attempt"] == 1
        assert _events(front, "handoff_reroute")[0]["attempt"] == 1

    def test_rung2_source_store_eviction_reroutes(self, toy):
        """The frontend's own availability reason ("no alive prefill
        replica"): the page vanishes from the source store between
        prefill completion and collection — typed, rerouted, parity."""
        front = _front(toy)
        p = self._prompt(23)
        rid = front.submit(p, max_new_tokens=6)
        # drive the PREFILL pool alone to completion (poll does not
        # pop — the frontend has not collected the leg yet)...
        for _ in range(200):
            front.prefill.pump(1)
            if front.prefill.poll(rid) is not None:
                break
        assert front.prefill.poll(rid).status == "done"
        # ...then evict its page from the source store before the
        # frontend's next pump opens the handoff window
        key = tuple(int(t) for t in p[:8])
        assert front.prefill.replicas[0].engine.kv.evict_prefix(
            key, force=True)
        front.run_until_drained(timeout_s=60.0)
        _assert_solo_parity(toy, front, [p], [rid])
        fails = _events(front, "handoff_failure")
        assert fails and fails[0]["failure"] == "integrity"
        assert "no alive prefill replica" in fails[0]["reason"]
        # the re-prefill re-registered the page: attempt 1 delivered
        assert _events(front, "handoff")[-1]["attempt"] == 1

    def test_rung3_decode_reprefills_when_no_survivor(self, toy):
        """Window kill of the ONLY prefill replica: at re-route time
        there is no prefill survivor, so the decode pool re-prefills
        the whole prompt — slower, never stranded, still parity."""
        kill = HandoffWindowKill(at_handoff=0)
        front = _front(toy, fault=kill)
        p = self._prompt(24)
        rid = front.submit(p, max_new_tokens=6)
        front.run_until_drained(timeout_s=60.0)
        assert kill.fired == 1
        _assert_solo_parity(toy, front, [p], [rid])
        # the rung's identity: rerouted once, and NO handoff ever
        # delivered — the whole stream came out of the decode pool
        c = front.summary()["counters"]
        assert c["handoff_reroutes"] == 1 and c.get("handoffs", 0) == 0
        assert _events(front, "handoff") == []

    def test_rung4_loud_eviction_at_attempt_budget(self, toy):
        """Sticky corruption on EVERY attempt: the ladder must stop at
        ``max_handoff_attempts`` with a typed `evicted` result naming
        the budget and the cause — a loud eviction, not a hang — and
        an unrelated healthy request must be untouched by it."""
        fault = _AlwaysCorrupt()
        front = _front(toy, fault=fault, max_handoff_attempts=2)
        p_bad = self._prompt(25)
        p_ok = self._prompt(26, n=3)       # < chunk: routed direct
        rid_bad = front.submit(p_bad, max_new_tokens=6)
        rid_ok = front.submit(p_ok, max_new_tokens=5)
        front.run_until_drained(timeout_s=60.0)
        res = front.poll(rid_bad)
        assert res is not None and res.status == "evicted"
        assert "handoff failed after 2 attempts" in res.reason
        assert "sha256" in res.reason
        # attempts 1..2 rerouted; the 3rd failure breaches the budget
        assert fault.fired == 3
        c = front.summary()["counters"]
        assert c["handoff_failures"] == 3 and c["handoff_reroutes"] == 2
        assert [t["attempt"] for t in _events(front, "handoff_reroute")] \
            == [1, 2]
        _assert_solo_parity(toy, front, [p_ok], [rid_ok])
