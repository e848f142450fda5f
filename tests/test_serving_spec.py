"""Engine-integrated speculative decode (ISSUE 15): the draft/verify
state machine must be INVISIBLE in the tokens — exact-match acceptance
against the target's counter-keyed stream means the emitted sequence is
bit-identical to the non-speculative engine AND to solo
`models.generate`, at temperature 0 and > 0, under staggered
join/leave, resubmission, int8 KV, and radix prefix hits. Drafts are
pure latency hints; what speculation changes is dispatch count, and the
accept-rate observables are what the banked benches read."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex1_tpu.core.policy import get_policy
from apex1_tpu.models.generate import generate, gpt2_decoder
from apex1_tpu.models.gpt2 import GPT2, GPT2Config
from apex1_tpu.serving import Engine, EngineConfig, ngram_propose
from apex1_tpu.testing.chaos import toy_decoder


@pytest.fixture(scope="module")
def tiny():
    """Tiny fp32 GPT-2 + its decoder pair + a solo-generate oracle."""
    cfg = GPT2Config.tiny(policy=get_policy("O0"), max_seq_len=64)
    model = GPT2(cfg)
    rng = np.random.default_rng(7)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 6)),
                         jnp.int32)
    params = model.init(jax.random.key(0), prompt)["params"]
    apply_fn, make_cache = gpt2_decoder(model)

    def solo(tokens, n_new):
        cache = make_cache(1, len(tokens) + n_new)
        return np.asarray(generate(
            apply_fn, params, jnp.asarray([tokens], jnp.int32),
            max_new_tokens=n_new, cache=cache,
            vocab_size=cfg.vocab_size))[0]

    return cfg, params, apply_fn, make_cache, solo


def _toy_engine(**kw):
    apply_fn, make_cache, params = toy_decoder()
    ekw = dict(max_slots=3, max_len=48, prefill_chunk=4, vocab_size=61,
               temperature=0.9, seed=5)
    ekw.update(kw)
    dp = ekw.pop("draft_propose", None)
    return Engine(apply_fn, make_cache, params, EngineConfig(**ekw),
                  draft_propose=dp)


class TestNgramPropose:
    def test_prompt_lookup_copies_continuation(self):
        # suffix (7, 8) occurred earlier, followed by 9, 1
        h = [3, 7, 8, 9, 1, 2, 7, 8]
        np.testing.assert_array_equal(ngram_propose(h, 2), [9, 1])

    def test_most_recent_occurrence_wins(self):
        # suffix (5,) occurs at idx 0 (-> 1) and idx 2 (-> 9): recency
        h = [5, 1, 5, 9, 5]
        np.testing.assert_array_equal(ngram_propose(h, 1), [9])

    def test_fallback_repeats_last_token(self):
        np.testing.assert_array_equal(ngram_propose([4], 3), [4, 4, 4])
        np.testing.assert_array_equal(ngram_propose([1, 2, 3], 2),
                                      [3, 3])

    def test_short_continuation_padded(self):
        # match lands at the very end: continuation shorter than k
        h = [7, 8, 2, 7, 8]
        out = ngram_propose(h, 3)
        assert out[0] == 2 and out.shape == (3,)

    def test_validation(self):
        with pytest.raises(ValueError, match="k must be"):
            ngram_propose([1], 0)
        with pytest.raises(ValueError, match="non-empty"):
            ngram_propose([], 2)


class TestSpecTokenParity:
    def test_greedy_staggered_join_leave_token_identical(self, tiny,
                                                         rng):
        """THE tentpole pin at temperature 0: the speculative engine
        under the mixed staggered workload emits exactly what solo
        greedy `generate` does, with exactly its two executables
        (prefill + verify — decode is never traced)."""
        cfg, params, apply_fn, make_cache, solo = tiny
        eng = Engine(apply_fn, make_cache, params,
                     EngineConfig(max_slots=3, max_len=48,
                                  prefill_chunk=4, num_draft=3,
                                  vocab_size=cfg.vocab_size))
        lens = [3, 7, 5, 9, 4, 6]
        news = [6, 5, 7, 4, 6, 5]
        prompts = [rng.integers(0, cfg.vocab_size, (L,)).tolist()
                   for L in lens]
        ids = [eng.submit(p, max_new_tokens=n)
               for p, n in zip(prompts[:3], news[:3])]
        eng.step()
        ids.append(eng.submit(prompts[3], max_new_tokens=news[3]))
        eng.step()
        ids += [eng.submit(p, max_new_tokens=n)
                for p, n in zip(prompts[4:], news[4:])]
        eng.run(max_steps=200)
        for p, n, rid in zip(prompts, news, ids):
            res = eng.results[rid]
            assert res.status == "done"
            np.testing.assert_array_equal(res.tokens, solo(p, n))
        assert eng.trace_counts == {"prefill": 1, "verify": 1}
        s = eng.metrics.summary()
        assert s["done"] == 6
        assert "accept_rate" in s        # banked, whatever its value

    @pytest.mark.parametrize("accepted", ["none", "some", "all"])
    def test_verify_rows_at_per_lane_offsets_match_solo(self, tiny, rng,
                                                        accepted):
        """The verify step writes K+1 rows per lane at that lane's own
        offset (PR 26: selected in by position, one batch forward). With
        drafts that are all wrong, right up to the second, or all right
        — 0, some, all of them accepted — lanes at different depths emit
        solo greedy `generate`'s tokens, one of them up to the last
        usable position (its last rounds write into the pool's slack,
        K + 1 > prefill_chunk, to position max_len + K - 1)."""
        cfg, params, apply_fn, make_cache, solo = tiny
        K, V = 3, cfg.vocab_size
        plan = [(9, 8), (3, 6), (6, 5), (4, 7)]   # 9 + 8 - 1 == max_len
        prompts = [rng.integers(0, V, (L,)).tolist() for L, _ in plan]
        want = {tuple(p): [int(t) for t in solo(p, n)]
                for p, (_, n) in zip(prompts, plan)}

        def propose(history, k):
            p = next(q for q in want if tuple(history[:len(q)]) == q)
            i = len(history) - len(p)
            out = (want[p] + [0] * k)[i:i + k]
            if accepted == "none":
                out[0] = (out[0] + 1) % V
            elif accepted == "some":
                out[1] = (out[1] + 1) % V
            return np.asarray(out, np.int32)

        eng = Engine(apply_fn, make_cache, params,
                     EngineConfig(max_slots=3, max_len=16, prefill_chunk=2,
                                  num_draft=K, vocab_size=V),
                     draft_propose=propose)
        from apex1_tpu.models.generate import cache_len
        from apex1_tpu.ops.decode_attend import DECODE_BLOCK
        s_max = cache_len(eng.kv.cache)
        assert s_max >= 16 + K and s_max % DECODE_BLOCK == 0
        ids = [eng.submit(prompts[0], max_new_tokens=plan[0][1])]
        eng.step()
        ids += [eng.submit(p, max_new_tokens=n)
                for p, (_, n) in zip(prompts[1:], plan[1:])]
        eng.run(max_steps=200)
        for p, rid in zip(prompts, ids):
            assert eng.results[rid].status == "done"
            assert list(eng.results[rid].tokens) == want[tuple(p)]
        recs = [eng.metrics.records[r] for r in ids]
        if accepted == "none":
            assert all(r.n_accepted == 0 for r in recs)
        elif accepted == "all":
            assert all(r.n_accepted == r.n_drafted for r in recs)
        else:
            assert all(0 < r.n_accepted < r.n_drafted for r in recs)
        assert eng.trace_counts == {"prefill": 1, "verify": 1}

    def test_sampled_identical_to_nonspec_engine(self):
        """Temperature 0.9: exact-match verify emits the target's
        counter stream verbatim — bit-identical to the plain engine,
        whatever the drafts guessed."""
        a = _toy_engine()
        b = _toy_engine(num_draft=4)
        prompts = [[7, 3, 9, 1, 4], [2, 2, 5], [8, 1, 1, 6, 6, 6]]
        ra = [a.submit(p, max_new_tokens=9, seed=100 + i)
              for i, p in enumerate(prompts)]
        rb = [b.submit(p, max_new_tokens=9, seed=100 + i)
              for i, p in enumerate(prompts)]
        a.run(max_steps=80)
        b.run(max_steps=80)
        for x, y in zip(ra, rb):
            np.testing.assert_array_equal(a.results[x].tokens,
                                          b.results[y].tokens)
        assert b.trace_counts == {"prefill": 1, "verify": 1}

    def test_oracle_draft_accepts_everything(self):
        """A draft source that knows the answer (the non-spec engine's
        own output) is fully accepted: accept_rate 1.0 where the
        request's tail still has K tokens to verify, and the whole
        stream lands in ceil((new-1)/(K+1)) verify rounds."""
        a = _toy_engine()
        ra = a.submit([7, 3, 9], max_new_tokens=9, seed=42)
        a.run(max_steps=60)
        want = [int(t) for t in a.results[ra].tokens]
        full = [7, 3, 9] + want

        def oracle(history, k):
            i = len(history) - 3            # tokens emitted so far
            out = (want + [0] * k)[i:i + k]
            return np.asarray(out, np.int32)

        b = _toy_engine(num_draft=2, draft_propose=oracle)
        rb = b.submit([7, 3, 9], max_new_tokens=9, seed=42)
        b.run(max_steps=60)
        np.testing.assert_array_equal(b.results[rb].tokens, want)
        rec = b.metrics.records[rb]
        # 8 post-prefill tokens over K+1=3 per round = 3 rounds; the
        # last round caps emission at the remaining 2, and every draft
        # the verify could reach matched
        assert rec.n_drafted == 6 and rec.n_accepted == 6
        assert rec.accept_rate == 1.0
        assert b.metrics.summary()["accept_rate"] == 1.0

    def test_truncated_final_round_never_inflates_accept_rate(self):
        """Review-finding regression: drafts past the emission window
        (max_new_tokens reached mid-round) are not credited — a
        2-token request under K=4 oracle drafting banks exactly the
        one draft position that could land, not 4."""
        a = _toy_engine()
        ra = a.submit([7, 3, 9], max_new_tokens=2, seed=42)
        a.run(max_steps=20)
        want = [int(t) for t in a.results[ra].tokens]

        def oracle(history, k):
            i = len(history) - 3
            return np.asarray((want + [0] * (k + 2))[i:i + k], np.int32)

        b = _toy_engine(num_draft=4, draft_propose=oracle)
        rb = b.submit([7, 3, 9], max_new_tokens=2, seed=42)
        b.run(max_steps=20)
        np.testing.assert_array_equal(b.results[rb].tokens, want)
        rec = b.metrics.records[rb]
        # one verify round, remaining=1: one usable draft position
        assert rec.n_drafted == 1 and rec.n_accepted == 1
        assert rec.accept_rate == 1.0

    def test_eos_early_stop_matches_nonspec_truncation(self):
        """EOS inside an accepted speculative run retires at exactly
        the non-spec stream's truncation point — tokens past the EOS
        in the same verify round are discarded. (Toy decoder: the
        truncation logic is model-agnostic, and the GPT-2 composition
        is already covered by the staggered greedy pin — no second
        real-model engine compile on the fast gate.)"""
        a = _toy_engine()
        ra = a.submit([4, 2, 7, 7], max_new_tokens=10, seed=77)
        a.run(max_steps=60)
        full = [int(t) for t in a.results[ra].tokens]
        eos = full[3]
        b = _toy_engine(eos_id=eos, num_draft=3)
        rb = b.submit([4, 2, 7, 7], max_new_tokens=10, seed=77)
        b.run(max_steps=60)
        res = b.results[rb]
        assert res.status == "done" and res.reason == "eos"
        want = full[:full.index(eos) + 1]
        np.testing.assert_array_equal(res.tokens, want)


class TestSpecSeedContract:
    def test_resubmission_idempotent_mid_flight(self):
        """The counter-seed contract survives speculation: a spec
        request killed mid-flight and resubmitted (same id, fresh spec
        engine) regenerates the identical stream — and a NON-spec
        engine given the same id produces it too (speculation is not
        part of the stream's identity)."""
        from apex1_tpu.serving import new_request_id
        rid = new_request_id()
        a = _toy_engine(num_draft=3)
        a.submit([5, 1, 2, 8], max_new_tokens=9, req_id=rid)
        a.step(); a.step()                    # mid-flight...
        partial = a.cancel(rid)               # ...the stream dies
        assert partial
        b = _toy_engine(num_draft=3)
        b.submit([5, 1, 2, 8], max_new_tokens=9, req_id=rid)
        b.run(max_steps=60)
        c = _toy_engine()
        c.submit([5, 1, 2, 8], max_new_tokens=9, req_id=rid)
        c.run(max_steps=60)
        np.testing.assert_array_equal(b.results[rid].tokens,
                                      c.results[rid].tokens)
        # the cancelled partial is a strict prefix of the regenerated
        # stream — same contract as non-spec eviction partials
        part = a.results[rid].tokens
        np.testing.assert_array_equal(
            part, b.results[rid].tokens[:part.size])


class TestSpecComposition:
    def test_int8_tier_with_radix_and_spec_token_identical(self):
        """The dtype-flip parity drill extended to the new paths
        (ISSUE 15 satellite): int8 KV pool + radix prefix hits + the
        speculative verify loop, tokens bit-identical to the fp32
        non-spec engine (toy cache values < 128 make int8 exact)."""
        shared = [9, 9, 4, 4, 1, 2, 3, 4, 5]   # >= 2 chunks shared
        tails = [[6, 7], [6, 7], [8]]
        gold = _toy_engine()
        g_ids = [gold.submit(shared + t, max_new_tokens=7,
                             seed=50 + i)
                 for i, t in enumerate(tails)]
        gold.run(max_steps=80)
        q = _toy_engine(num_draft=3, cache_dtype=jnp.int8)
        q_ids = [q.submit(shared + t, max_new_tokens=7, seed=50 + i)
                 for i, t in enumerate(tails)]
        q.run(max_steps=80)
        for gr, qr in zip(g_ids, q_ids):
            np.testing.assert_array_equal(gold.results[gr].tokens,
                                          q.results[qr].tokens)
        s = q.metrics.summary()
        assert s["prefix_hit_rate"] > 0      # the radix path really ran
        # int8 pool really is the half-size tier
        assert q.kv.pool_bytes() * 4 == gold.kv.pool_bytes()

    @pytest.mark.slow  # 870s-cap headroom (~3s): fleet-LEVEL spec
    # composition; the tier-1 pins already cover spec determinism at
    # engine level (TestSpecSeedContract) and fleetsim determinism
    # without spec (test_autopilot) — full run via check_all --all
    def test_fleetsim_episode_with_spec_is_deterministic(self):
        """Fleet-level: the same (trace, seed, spec config) replays to
        a bit-identical fingerprint, and the per-request token digests
        match the non-spec episode's exactly (speculation shifts
        latency, never tokens) — with accept_rate flowing into the
        report."""
        from apex1_tpu.serving import FrontendConfig
        from apex1_tpu.testing.fleetsim import (FleetSimConfig,
                                                run_fleet,
                                                synthetic_trace)
        trace = synthetic_trace("steady", seed=3, horizon_s=2.0,
                                base_rate=12.0)
        fc = dict(n_replicas=2, capacity_per_replica=8,
                  hedge_after_s=None)
        spec = FleetSimConfig(num_draft=2)
        r1 = run_fleet(trace, FrontendConfig(**fc), sim=spec)
        r2 = run_fleet(trace, FrontendConfig(**fc), sim=spec)
        assert r1.fingerprint() == r2.fingerprint()
        base = run_fleet(trace, FrontendConfig(**fc),
                         sim=FleetSimConfig())
        d_spec = {o["idx"]: o["tokens_sha1"] for o in r1.outcomes
                  if o["status"] == "done"}
        d_base = {o["idx"]: o["tokens_sha1"] for o in base.outcomes
                  if o["status"] == "done"}
        shared = set(d_spec) & set(d_base)
        assert shared
        assert all(d_spec[i] == d_base[i] for i in shared)
        assert "accept_rate" in r1.to_json()
