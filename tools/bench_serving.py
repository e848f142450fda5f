"""Offered-load benchmark for `apex1_tpu.serving.Engine` — the
continuous-batching headline: tokens/sec, p50/p99 time-to-first-token,
and slot occupancy across an offered-load sweep, against the SERIAL
baseline (each request through its own jitted `models.generate` call,
one after another — the repo's status quo before the engine).

Emits ONE JSON line (bench.py's `_emit` convention) with the peak
sweep point as the headline ``value`` plus the per-load rows, e.g.::

  {"metric": "serving tokens/sec gpt2-serving [cpu]", "value": ...,
   "unit": "tokens/sec", "vs_serial": 2.7, "sweep": [...]}

``vs_serial`` >= 2.0 at 8 concurrent staggered requests is the
acceptance line (CPU proxy): decode is weight-streaming-bound, so the
pooled step serves 8 rows for nearly the price of 1 — continuous
batching converts that into throughput the serial loop leaves idle.

``--replicas N [M ...]`` adds the multi-replica axis: the same offered
load through a `serving.ServingFrontend` (threaded supervised
replicas), reporting tokens/sec per replica count — the ROADMAP 2(d)
near-linear-scaling observable. ``--chaos`` arms a seed-keyed
replica-kill mid-sweep and reports GOODPUT (tokens of COMPLETED
requests per second) across the kill + restart + resubmission cycle —
the number that shows fault tolerance costing throughput, not
correctness (every request still completes; parity is tier-1's job).

``--prefix-len N`` (default 24) arms the GOODPUT-MULTIPLIER sweep
(ISSUE 15): a shared-system-prompt trace (every request = one shared
N-token system prompt + its own tail, submitted WITHOUT ``prefix=`` —
the radix matcher must find the sharing itself) measured at the peak
load three ways — the PR-14 baseline (prefix cache off, no
speculation), radix cache on, and radix + speculative decode
(``--num-draft`` drafts, n-gram self-drafting). Rows carry
``prefix_hit_rate``, ``accept_rate``, and goodput; the headline
``goodput_multiple`` is radix+spec over baseline at EQUAL offered
load, with token parity vs the solo-generate oracle asserted on every
rep of every row. An analytic int8-KV capacity row
(`perf_model.serving_capacity`) prices the third multiplier: slots the
same pool HBM buys at int8 vs bf16 (correctness of the dtype flip is
tier-1's dtype-flip parity drills, not this bench).

The PAGED A/B sweep (ISSUE 18, on unless ``--skip-paged``) re-measures
the peak load through the paged KV pool (`EngineConfig(paged=True)`:
block-table page addressing, no copy-on-admit) adjacent to a fresh
dense run, with token parity vs the solo-generate oracle asserted on
every rep of BOTH engines — the A/B prices pool bookkeeping, never
correctness. It also banks a per-phase attribution of the paged decode
step — attention (gather + attend at the live block table), dequant
(the int8 lane cast the TPU kernel fuses away), sample (the fused
epilogue at the step's logits shape), host (engine step wall minus the
decode executable) — measured as standalone jitted phases at the
engine's EXACT mid-decode shapes, emitted onto the obs spine and
parsed back off the banked events (the trace-parser path, like the
disagg breakdown). CPU-proxy caveat: these rows price the COMPOSITE
ops; what the proxy cannot measure (kernel fusion wins, HBM page
streaming) is spelled out in docs/paged_decode.md.

The LoRA A/B (ISSUE 19, ``--lora-tenants N``) prices multi-tenancy at
the peak load: the same offered load through a LoRA-armed engine with
every request on ONE adapter (single-tenant) vs round-robin across N
adapters (multi-tenant), measured adjacent so the ratio isolates the
cross-tenant page gather; ``lora_vs_dense`` prices the fused adapter
epilogue itself against the plain head. Token parity is asserted on
every rep of both rows against per-tenant SOLO runs (each
(prompt, tenant) pair alone through the same engine config) — the
tier-1 mixed-batch bitwise criterion re-asserted at bench scale, so
the A/B prices the epilogue, never correctness.

``--out FILE`` banks the accumulating record via
``manifest.atomic_write_json`` after EVERY sweep point (kill-safe,
like bench.py --out): an interrupted sweep keeps each completed point.

Usage::

  python tools/bench_serving.py                  # full sweep (1,2,4,8)
  python tools/bench_serving.py --smoke          # CPU-gate smoke (~1 min)
  python tools/bench_serving.py --replicas 1 2 --chaos \
      --out perf_results/bench_serving_replicas.json
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _bank(path, record):
    """Kill-safe banking: temp-file + atomic rename on every call, so
    an interrupted sweep keeps every completed point (the bench.py
    --out contract)."""
    if not path:
        return
    from apex1_tpu.resilience.manifest import atomic_write_json
    atomic_write_json(path, record)


def main():
    ap = argparse.ArgumentParser()
    # decode is weight-streaming-bound; the model must be big enough
    # that streaming its weights (not per-step dispatch) dominates, or
    # the CPU proxy under-reports the batching win (hidden 256 measured
    # 1.4x where hidden 512 measures ~2.9x steady-state)
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--new", type=int, default=32,
                    help="tokens generated per request")
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--loads", type=int, nargs="*", default=[1, 2, 4, 8],
                    help="concurrency sweep points (engine slots)")
    ap.add_argument("--requests-per-slot", type=int, default=3,
                    help="offered load: requests = this x slots, so the "
                         "pool stays saturated past the arrival ramp "
                         "(concurrency is still bounded by the slots)")
    ap.add_argument("--stagger", type=int, default=2,
                    help="engine steps between arrivals")
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--prefix-len", type=int, default=24,
                    help="shared system-prompt length for the "
                         "goodput-multiplier sweep (0 disables it)")
    ap.add_argument("--num-draft", type=int, default=4,
                    help="drafts per verify for the speculative axis "
                         "of the multiplier sweep")
    ap.add_argument("--skip-paged", action="store_true",
                    help="skip the paged-pool A/B + per-phase "
                         "attribution at the peak load")
    ap.add_argument("--phase-reps", type=int, default=5,
                    help="timing reps per attribution phase")
    ap.add_argument("--lora-tenants", type=int, default=0,
                    help="multi-tenant LoRA A/B at the peak load: "
                         "single-tenant vs N adapters round-robin, "
                         "token parity vs per-tenant solo runs on "
                         "every rep (0 disables the axis)")
    ap.add_argument("--lora-rank", type=int, default=4)
    ap.add_argument("--replicas", type=int, nargs="*", default=[],
                    help="multi-replica sweep points (ServingFrontend; "
                         "empty = skip the replica axis)")
    ap.add_argument("--slots-per-replica", type=int, default=4)
    ap.add_argument("--chaos", action="store_true",
                    help="kill one replica mid-sweep (seed-keyed, "
                         "testing.chaos.kill_schedule) and measure "
                         "goodput across restart + resubmission")
    ap.add_argument("--chaos-seed", type=int, default=20260804)
    ap.add_argument("--disagg", action="store_true",
                    help="unified vs disaggregated fleetsim A/B at "
                         "EQUAL offered load on an adversarial "
                         "long-prompt trace (virtual clock; control "
                         "logic, not silicon numbers), with per-phase "
                         "TTFT/TPOT breakdown parsed back off the obs "
                         "spine")
    ap.add_argument("--disagg-seed", type=int, default=20260807)
    ap.add_argument("--out", type=str, default=None,
                    help="bank the record here (atomic write after "
                         "every sweep point — kill-safe)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model + [1, 4] sweep for the CPU gate "
                         "(correctness/plumbing only: a dispatch-"
                         "dominated tiny model can't show the batching "
                         "win — the ratio is the full sweep's job)")
    args = ap.parse_args()
    if args.smoke:
        args.hidden, args.layers, args.vocab = 128, 2, 256
        args.new, args.loads = 16, [1, 4]
        args.prefix_len = min(args.prefix_len, 12)
        args.num_draft = min(args.num_draft, 3)
        args.lora_tenants = min(args.lora_tenants, 2)
        if args.replicas:
            args.replicas = args.replicas[:2]

    # default to CPU for a proxy-able bench (JAX_PLATFORMS overrides)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from apex1_tpu.testing import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()

    import jax.numpy as jnp
    import numpy as np

    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.generate import generate, gpt2_decoder
    from apex1_tpu.models.gpt2 import GPT2, GPT2Config
    from apex1_tpu.serving import (Backpressure, Engine, EngineConfig,
                                   ServingMetrics)

    max_slots = max(args.loads)
    n_req_max = args.requests_per_slot * max_slots
    max_len = args.prompt_len + args.new + 8
    # the position table must also cover the multiplier sweep's
    # prefix-extended prompts (prefix + own + new) — sizing from
    # max_len alone would run sequences past max_seq_len and fail on
    # a confusing token-parity assert instead (review finding)
    mult_total = args.prefix_len + args.prompt_len + args.new + 8
    cfg = GPT2Config.tiny(policy=get_policy("O0"), vocab_size=args.vocab,
                          hidden_size=args.hidden, num_layers=args.layers,
                          num_heads=args.heads,
                          max_seq_len=max(128, max_len, mult_total))
    model = GPT2(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size,
                            (args.prompt_len,)).astype(np.int32)
               for _ in range(n_req_max)]
    params = model.init(jax.random.key(0),
                        jnp.asarray(prompts[0][None]))["params"]
    apply_fn, make_cache = gpt2_decoder(model)

    # ---- serial baseline: one jitted generate per request, back to
    # back (compile excluded — one warmup call at the fixed shape)
    gen = jax.jit(functools.partial(
        generate, apply_fn, max_new_tokens=args.new,
        vocab_size=cfg.vocab_size))

    def serial_run(n_req):
        outs = []
        for i in range(n_req):
            cache = make_cache(1, max_len)
            outs.append(gen(params, jnp.asarray(prompts[i][None]),
                            cache=cache))
        return [np.asarray(o)[0] for o in outs]

    serial_out = serial_run(n_req_max)      # compile + the oracle run

    def serial_best(n_req, reps=3):
        """Best-of-``reps`` serial tokens/sec over ``n_req`` requests —
        measured ADJACENT to each engine point so machine drift over
        the sweep cancels in the ratio instead of polluting it (the
        baseline still gets every benefit of the doubt: its best rep).
        """
        best_s = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            serial_run(n_req)
            best_s = min(best_s, time.perf_counter() - t0)
        return n_req * args.new / best_s

    # ---- engine sweep: n staggered arrivals into an n-slot pool
    sweep = []
    serial_tps = 0.0
    for load in args.loads:
        n_req = args.requests_per_slot * load
        serial_tps = serial_best(n_req)
        eng = Engine(apply_fn, make_cache, params,
                     EngineConfig(max_slots=load, max_len=max_len,
                                  prefill_chunk=args.chunk,
                                  vocab_size=cfg.vocab_size,
                                  max_queue=n_req))
        # warm both executables off the clock (jit compile), then bench
        # a fresh engine-shaped workload on the SAME engine (the two
        # executables are already traced; trace_counts pins that)
        wid = eng.submit(prompts[0], max_new_tokens=2)
        eng.run(max_steps=8)
        assert eng.results[wid].status == "done"
        # best-of-3, mirroring the serial baseline's best-of-3: both
        # sides shed co-tenant noise; parity is asserted on every rep
        dt = float("inf")
        for _ in range(3):
            eng.metrics = ServingMetrics()  # drop prior reps' records
            eng.results.clear()
            t0 = time.perf_counter()
            ids = []
            k = 0
            while k < n_req or eng.scheduler.depth or eng.n_active:
                if k < n_req:
                    ids.append(eng.submit(prompts[k],
                                          max_new_tokens=args.new))
                    k += 1
                    for _ in range(args.stagger - 1):
                        eng.step()
                eng.step()
            rep = time.perf_counter() - t0
            for i, rid in enumerate(ids):  # parity stays the oracle
                np.testing.assert_array_equal(eng.results[rid].tokens,
                                              serial_out[i])
            if rep < dt:
                dt, s = rep, eng.metrics.summary()
        assert eng.trace_counts == {"prefill": 1, "decode": 1}, \
            eng.trace_counts
        tps = n_req * args.new / dt
        sweep.append({
            "load": load, "tokens_per_sec": round(tps, 1),
            "serial_tokens_per_sec": round(serial_tps, 1),
            "vs_serial": round(tps / serial_tps, 3),
            "ttft_p50_ms": round(s.get("ttft_p50_ms", 0.0), 2),
            "ttft_p99_ms": round(s.get("ttft_p99_ms", 0.0), 2),
            "mean_occupancy": round(s.get("mean_occupancy", 0.0), 3),
        })

    best = max(sweep, key=lambda r: r["tokens_per_sec"])
    backend = jax.default_backend()
    record = {
        "metric": f"serving tokens/sec gpt2-serving [{backend}]",
        "value": best["tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_serial": best["vs_serial"],
        "serial_tokens_per_sec": best["serial_tokens_per_sec"],
        "model": {"hidden": args.hidden, "layers": args.layers,
                  "vocab": args.vocab, "new": args.new,
                  "prompt_len": args.prompt_len},
        "sweep": sweep,
    }
    _bank(args.out, record)

    # ---- goodput-multiplier sweep (ISSUE 15): a shared-system-prompt
    # trace at the peak load, measured at EQUAL offered load under the
    # PR-14 baseline (no sharing exploited, no speculation), the radix
    # prefix cache, and radix + speculative decode. Parity vs the
    # solo-generate oracle holds on every rep of every row — the
    # multipliers must be invisible in the tokens.
    if args.prefix_len > 0:
        from apex1_tpu.perf_model import (kv_cache_bytes,
                                          serving_capacity)

        load = max(args.loads)
        n_req = args.requests_per_slot * load
        sysp = rng.integers(0, cfg.vocab_size,
                            (args.prefix_len,)).astype(np.int32)
        mult_prompts = [np.concatenate([sysp, p]) for p in
                        prompts[:n_req]]
        mult_len = args.prefix_len + args.prompt_len + args.new + 8
        # the oracle: solo generate of each FULL prompt (compile once
        # at the new shape, off the clock)
        m_oracle = []
        for p in mult_prompts:
            cache = make_cache(1, mult_len)
            m_oracle.append(np.asarray(
                gen(params, jnp.asarray(p[None]), cache=cache))[0])

        def mult_row(tag, prefix_cache, num_draft):
            eng = Engine(apply_fn, make_cache, params,
                         EngineConfig(max_slots=load, max_len=mult_len,
                                      prefill_chunk=args.chunk,
                                      vocab_size=cfg.vocab_size,
                                      max_queue=n_req,
                                      prefix_cache=prefix_cache,
                                      num_draft=num_draft))
            # warm the executables off the clock with a NON-sharing
            # prompt: the warmup must not seed the radix store with
            # the trace's system prompt (the first REAL request pays
            # the cold miss, like production)
            wid = eng.submit(prompts[0][:4], max_new_tokens=2)
            eng.run(max_steps=16)
            assert eng.results[wid].status == "done"
            best_s, s = float("inf"), None
            for _ in range(3):
                eng.metrics = ServingMetrics()
                eng.results.clear()
                t0 = time.perf_counter()
                ids = []
                k = 0
                while k < n_req or eng.scheduler.depth or eng.n_active:
                    if k < n_req:
                        ids.append(eng.submit(mult_prompts[k],
                                              max_new_tokens=args.new))
                        k += 1
                        for _ in range(args.stagger - 1):
                            eng.step()
                    eng.step()
                rep = time.perf_counter() - t0
                for i, rid in enumerate(ids):   # parity stays the oracle
                    np.testing.assert_array_equal(
                        eng.results[rid].tokens, m_oracle[i])
                if rep < best_s:
                    best_s, s = rep, eng.metrics.summary()
            expect = {"prefill": 1,
                      ("verify" if num_draft else "decode"): 1}
            assert eng.trace_counts == expect, eng.trace_counts
            return {
                "config": tag,
                "prefix_cache": prefix_cache,
                "num_draft": num_draft,
                "goodput_tokens_per_sec": round(
                    n_req * args.new / best_s, 1),
                "prefix_hit_rate": (round(s["prefix_hit_rate"], 4)
                                    if "prefix_hit_rate" in s else None),
                "prefix_saved_tokens": s.get("prefix_saved_tokens"),
                "accept_rate": (round(s["accept_rate"], 4)
                                if "accept_rate" in s else None),
            }

        base_row = mult_row("baseline_pr14", False, 0)
        radix_row = mult_row("radix", True, 0)
        spec_row = mult_row("radix_spec", True, args.num_draft)
        # structural gates (the check_all --smoke coverage of the radix
        # and speculative paths): the multipliers actually fired. The
        # goodput RATIO is read off the banked record, not asserted —
        # same policy as the main sweep's >= 2x line.
        assert radix_row["prefix_hit_rate"] > 0, radix_row
        assert spec_row["prefix_hit_rate"] > 0, spec_row
        assert spec_row["accept_rate"] > 0, spec_row
        head_dim = args.hidden // args.heads
        pool_len = mult_len + max(args.chunk, args.num_draft + 1) - 1
        bf16_budget = kv_cache_bytes(args.layers, args.heads, head_dim,
                                     pool_len, load, 2)
        record["multiplier_sweep"] = {
            "offered_load": {"slots": load, "requests": n_req,
                             "prefix_len": args.prefix_len,
                             "own_len": args.prompt_len,
                             "new": args.new},
            "rows": [base_row, radix_row, spec_row],
            # the headline: the best multiplier configuration over the
            # PR-14 baseline at EQUAL offered load (the operator picks
            # ONE config per deployment; speculation's win is
            # TPU-shaped — weight-streaming-bound decode — and may
            # invert on the CPU proxy, where the bankable observable
            # is its accept_rate, not its wall-clock: docs/serving.md)
            "goodput_multiple": round(
                max(radix_row["goodput_tokens_per_sec"],
                    spec_row["goodput_tokens_per_sec"])
                / base_row["goodput_tokens_per_sec"], 3),
            "best_config": max(
                (radix_row, spec_row),
                key=lambda r: r["goodput_tokens_per_sec"])["config"],
            # the third multiplier, priced analytically: the same pool
            # HBM at the int8 tier (capacity only — the dtype-flip
            # parity drills in tier-1 license the flip, this bench's
            # fp32 test model would not survive a raw int8 cast)
            "int8_capacity": {
                "pool_len": pool_len,
                "kv_pool_bytes_bf16": bf16_budget,
                "slots_bf16": load,
                "slots_int8_same_budget": serving_capacity(
                    bf16_budget, args.layers, args.heads, head_dim,
                    pool_len, 1),
            },
        }
        _bank(args.out, record)

    # ---- paged A/B + per-phase attribution (ISSUE 18): the peak load
    # through the paged KV pool, measured ADJACENT to a fresh dense run
    # (drift cancels in the ratio), token parity vs the solo-generate
    # oracle on every rep of both engines. The attribution measures the
    # paged decode step's phases as standalone jitted callables at the
    # engine's EXACT mid-decode shapes, emits each rep onto the obs
    # spine, and reconstructs the breakdown from the banked events —
    # proving the trace carries the attribution, not just this process.
    if not args.skip_paged:
        import tempfile

        from apex1_tpu.obs import spine as obs_spine
        from apex1_tpu.ops.paged_decode import (cache_attend,
                                                fused_sample,
                                                gather_pages)

        load = max(args.loads)
        n_req = args.requests_per_slot * load

        def ab_engine(paged):
            eng = Engine(apply_fn, make_cache, params,
                         EngineConfig(max_slots=load, max_len=max_len,
                                      prefill_chunk=args.chunk,
                                      vocab_size=cfg.vocab_size,
                                      max_queue=n_req, paged=paged))
            wid = eng.submit(prompts[0], max_new_tokens=2)
            eng.run(max_steps=8)
            assert eng.results[wid].status == "done"
            best = float("inf")
            for _ in range(3):
                eng.metrics = ServingMetrics()
                eng.results.clear()
                t0 = time.perf_counter()
                ids = []
                k = 0
                while k < n_req or eng.scheduler.depth or eng.n_active:
                    if k < n_req:
                        ids.append(eng.submit(prompts[k],
                                              max_new_tokens=args.new))
                        k += 1
                        for _ in range(args.stagger - 1):
                            eng.step()
                    eng.step()
                rep = time.perf_counter() - t0
                for i, rid in enumerate(ids):  # paged must be invisible
                    np.testing.assert_array_equal(
                        eng.results[rid].tokens, serial_out[i])
                best = min(best, rep)
            assert eng.trace_counts == {"prefill": 1, "decode": 1}, \
                eng.trace_counts
            return eng, n_req * args.new / best

        _, dense_tps = ab_engine(False)
        eng, paged_tps = ab_engine(True)

        # park the paged engine mid-decode so the live block table,
        # page store, and control vectors give the attribution its
        # real shapes (all rows admitted, none near retirement)
        for p in prompts[:load]:
            eng.submit(p, max_new_tokens=args.new)
        while eng.scheduler.depth:
            eng.step()
        for _ in range(2):
            eng.step()

        L = eng.kv.lane_len
        bt = eng._d_bt
        entry = next(iter(eng.kv.pages.values()))
        kp, vp = entry["k"], entry["v"]
        D = kp.shape[-1]
        prng = np.random.default_rng(7)
        q = jnp.asarray(prng.standard_normal(
            (load, args.heads, 1, D)), jnp.float32)
        lg = jnp.asarray(prng.standard_normal(
            (load, cfg.vocab_size)), jnp.float32)

        def attn_fn(kp, vp, bt, idxs, q):
            # one layer of the step's attention math: block-table
            # gather + masked attend at each row's live depth
            k_all = gather_pages(kp, bt, L).astype(jnp.float32)
            v_all = gather_pages(vp, bt, L).astype(jnp.float32)
            return cache_attend(q, k_all, v_all, idxs)

        # the dequant pass the TPU kernel fuses away: int8 lanes (one
        # layer's K, as gathered for one step) cast up to f32
        lanes8 = jax.jit(lambda p, b: gather_pages(p, b, L).astype(
            jnp.int8))(kp, bt)
        sample_kw = dict(temperature=0.7, vocab_size=cfg.vocab_size)
        phases = {
            "attention": (jax.jit(attn_fn),
                          (kp, vp, bt, eng._d_idxs, q)),
            "dequant": (jax.jit(lambda x: x.astype(jnp.float32)),
                        (lanes8,)),
            "sample": (jax.jit(functools.partial(fused_sample,
                                                 **sample_kw)),
                       (lg, eng._d_seeds, eng._d_pos)),
        }

        def dev_step():
            out = eng._decode(eng.params, eng.kv.pages, eng._d_bt,
                              eng._d_toks, eng._d_idxs, eng._d_active,
                              eng._d_seeds, eng._d_pos)
            jax.block_until_ready(out)   # state untouched: outputs
            #                              dropped, no donation on cpu

        obs_tmp = tempfile.mkdtemp(prefix="bench_paged_obs_")
        run = obs_spine.ObsRun(dir=obs_tmp, component="bench_paged")
        obs_spine.set_default_run(run)
        try:
            for name, (fn, fargs) in phases.items():
                jax.block_until_ready(fn(*fargs))    # compile off-clock
                for r in range(args.phase_reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(*fargs))
                    obs_spine.emit(
                        "event", "bench.paged_phase", phase=name,
                        rep=r, ms=(time.perf_counter() - t0) * 1e3)
            # host = full engine step minus the decode executable —
            # slot bookkeeping, token fetch, metrics, retire scan
            dev_step()                               # executable warm
            for r in range(args.phase_reps):
                t0 = time.perf_counter()
                dev_step()
                dev_ms = (time.perf_counter() - t0) * 1e3
                t0 = time.perf_counter()
                assert eng.step() == load            # rows stay active
                step_ms = (time.perf_counter() - t0) * 1e3
                obs_spine.emit("event", "bench.paged_phase",
                               phase="host", rep=r,
                               ms=max(0.0, step_ms - dev_ms))
        finally:
            run.close()
            obs_spine.set_default_run(None)

        # the trace-parser path: the breakdown is rebuilt from the
        # banked events, not from in-process floats
        samples = {}
        for e in obs_spine.read_events(run.path):
            if e.get("name") == "bench.paged_phase":
                samples.setdefault(e["phase"], []).append(
                    float(e["ms"]))
        assert set(samples) == {"attention", "dequant", "sample",
                                "host"}, sorted(samples)
        per_phase = {
            name: {"n": len(v),
                   "p50_ms": round(float(np.percentile(v, 50)), 4),
                   "min_ms": round(float(min(v)), 4)}
            for name, v in sorted(samples.items())}
        record["paged_sweep"] = {
            "load": load,
            "page_size": eng.kv.page_size,
            "pages_per_lane": eng.kv.pages_per_lane,
            "tokens_per_sec_dense": round(dense_tps, 1),
            "tokens_per_sec_paged": round(paged_tps, 1),
            # pool bookkeeping priced at equal load; parity asserted
            # above, so any gap here is block-table plumbing, never
            # tokens. CPU-proxy caveat: composite-op timings — the
            # fusion/page-streaming wins are TPU-only
            # (docs/paged_decode.md)
            "paged_vs_dense": round(paged_tps / dense_tps, 3),
            "per_phase": per_phase,
            "phase_shapes": {
                "slots": load, "lane_len": L,
                "page_size": eng.kv.page_size,
                "head_dim": D, "heads": args.heads,
                "vocab": cfg.vocab_size, "layers_note":
                    "attention/dequant rows are PER LAYER "
                    f"(x{args.layers} per step); dequant is one "
                    "layer's K lanes (x2 for K+V)"},
        }
        _bank(args.out, record)

    # ---- multi-tenant LoRA A/B (ISSUE 19): the peak load through a
    # LoRA-armed engine, single-tenant vs N tenants round-robin,
    # measured adjacent so the ratio isolates the cross-tenant page
    # gather in the fused logits epilogue. Parity on every rep of both
    # rows is against per-tenant SOLO runs — the tier-1 mixed-batch
    # bitwise criterion at bench scale, so the A/B prices the
    # epilogue's wall-clock, never its tokens.
    if args.lora_tenants > 0:
        load = max(args.loads)
        n_req = args.requests_per_slot * load
        R = args.lora_rank
        names = [f"tenant-{i}" for i in range(args.lora_tenants)]
        arng = np.random.default_rng(11)
        adapters = {nm: (arng.standard_normal((args.hidden, R)) * 0.05,
                         arng.standard_normal((R, args.vocab)) * 0.05)
                    for nm in names}

        def lora_engine():
            eng = Engine(
                apply_fn, make_cache, params,
                EngineConfig(max_slots=load, max_len=max_len,
                             prefill_chunk=args.chunk,
                             vocab_size=cfg.vocab_size, max_queue=n_req,
                             lora_rank=R,
                             lora_max_adapters=args.lora_tenants),
                lora_head=params["wte"])   # gpt2: weight-tied (V, H)
            for nm, (A, B) in adapters.items():
                eng.register_adapter(nm, A, B, scale=2.0)
            # warmup rides the SAME two executables (LoRA-off slots
            # share them via the zero page — no retrace)
            wid = eng.submit(prompts[0], max_new_tokens=2, seed=1)
            eng.run(max_steps=8)
            assert eng.results[wid].status == "done"
            return eng

        # the oracle: every (prompt, tenant) pair either row will
        # batch, run ALONE through one reusable engine (slot reuse +
        # page refcounts are tier-1's job; seeds pinned per request so
        # solo and mixed draw identical sampling streams)
        oracle = {}
        solo = lora_engine()
        for nt in (1, args.lora_tenants):
            for k in range(n_req):
                key = (k, names[k % nt])
                if key in oracle:
                    continue
                solo.results.clear()
                rid = solo.submit(prompts[k], max_new_tokens=args.new,
                                  tenant=key[1], seed=7000 + k)
                solo.run(max_steps=args.new + 32)
                assert solo.results[rid].status == "done"
                oracle[key] = np.asarray(solo.results[rid].tokens)

        def lora_row(tag, nt):
            eng = lora_engine()
            best = float("inf")
            for _ in range(3):
                eng.metrics = ServingMetrics()
                eng.results.clear()
                t0 = time.perf_counter()
                ids = []
                k = 0
                while k < n_req or eng.scheduler.depth or eng.n_active:
                    if k < n_req:
                        ids.append(eng.submit(
                            prompts[k], max_new_tokens=args.new,
                            tenant=names[k % nt], seed=7000 + k))
                        k += 1
                        for _ in range(args.stagger - 1):
                            eng.step()
                    eng.step()
                rep = time.perf_counter() - t0
                for i, rid in enumerate(ids):   # mixed == solo, bitwise
                    np.testing.assert_array_equal(
                        eng.results[rid].tokens,
                        oracle[(i, names[i % nt])])
                best = min(best, rep)
            assert eng.trace_counts == {"prefill": 1, "decode": 1}, \
                eng.trace_counts
            assert not eng._lora._slot_pages   # pages all released
            return {"config": tag, "tenants": nt,
                    "tokens_per_sec": round(n_req * args.new / best, 1)}

        single_row = lora_row("lora_single_tenant", 1)
        multi_row = lora_row("lora_multi_tenant", args.lora_tenants)
        # dense reference from the main sweep's peak-load point: the
        # epilogue's cost over the plain head (same offered load; the
        # sweep ran moments ago on this machine)
        dense_tps = next(r["tokens_per_sec"] for r in sweep
                         if r["load"] == load)
        record["lora_sweep"] = {
            "rank": R, "adapters": args.lora_tenants, "load": load,
            "requests": n_req,
            "rows": [single_row, multi_row],
            "multi_vs_single": round(
                multi_row["tokens_per_sec"]
                / single_row["tokens_per_sec"], 3),
            "dense_tokens_per_sec": dense_tps,
            "lora_vs_dense": round(
                multi_row["tokens_per_sec"] / dense_tps, 3),
        }
        _bank(args.out, record)

    # ---- replica axis: the same offered load through the supervised
    # multi-replica frontend (threaded serve loops; the main thread is
    # the supervision tick) — near-linear scaling is ROADMAP 2(d)'s
    # acceptance observable, goodput-under-kill is PR 7's
    if args.replicas:
        from apex1_tpu.serving import (EngineConfig, FrontendConfig,
                                       ReplicaConfig, ServingFrontend)
        from apex1_tpu.testing.chaos import kill_schedule

        slots = args.slots_per_replica
        record["replica_sweep"] = []
        for n_rep in args.replicas:
            n_req = args.requests_per_slot * slots * n_rep
            e_cfg = EngineConfig(max_slots=slots,
                                 max_len=max_len,
                                 prefill_chunk=args.chunk,
                                 vocab_size=cfg.vocab_size,
                                 max_queue=max(n_req, 8))

            def make_engine():
                return Engine(apply_fn, make_cache, params, e_cfg)

            front = ServingFrontend(
                make_engine,
                FrontendConfig(
                    n_replicas=n_rep,
                    capacity_per_replica=slots + e_cfg.max_queue,
                    hedge_after_s=None,
                    # worst-case first step INCLUDES the fresh
                    # engine's XLA compile — the watchdog must not
                    # read a compile as a hang
                    replica=ReplicaConfig(watchdog_s=600.0))).start()
            # warm every replica's two executables off the clock
            # (mirrors the engine sweep's warmup; a CHAOS restart's
            # recompile stays IN the window — that is the honest cost
            # of the kill)
            warm = [front.submit(prompts[0], max_new_tokens=2)
                    for _ in range(n_rep)]
            front.run_until_drained(timeout_s=1800.0)
            t0 = time.perf_counter()
            k = 0
            while k < n_req:
                try:
                    front.submit(prompts[k % len(prompts)],
                                 max_new_tokens=args.new)
                    k += 1
                except Backpressure:
                    front.pump()
            fault = None
            if args.chaos and n_rep > 1:
                # armed only NOW, offset from the victim's CURRENT
                # step count: supervisor steps tick on idle iterations
                # too, so a pre-armed absolute step would fire inside
                # the off-the-clock warmup and the "chaos" row would
                # measure an uninterrupted sweep (review finding).
                # With every request just accepted, the offset lands
                # mid-decode — streams are genuinely in flight.
                fault = kill_schedule(args.chaos_seed,
                                      n_replicas=n_rep, lo=2,
                                      hi=2 + args.new)
                fault.at_step += front.replicas[fault.replica].steps
                front.replicas[fault.replica].fault = fault
            results = front.run_until_drained(timeout_s=1800.0)
            dt = time.perf_counter() - t0
            front.stop()
            done = [r for rid, r in results.items()
                    if r.status == "done" and rid not in warm]
            good_tokens = sum(int(r.tokens.size) for r in done)
            counters = front.metrics.summary()["counters"]
            row = {
                "replicas": n_rep,
                "requests": n_req,
                "completed": len(done),
                "goodput_tokens_per_sec": round(good_tokens / dt, 1),
                "chaos": bool(fault),
                "replica_restarts": counters["replica_restarts"],
            }
            if fault is not None:
                row["kill"] = {"replica": fault.replica,
                               "step": fault.at_step,
                               "fired": fault.fired}
            record["replica_sweep"].append(row)
            _bank(args.out, record)

    # ---- disaggregation axis (ISSUE 16): unified vs two-pool fleet
    # at EQUAL offered load and EQUAL total replicas on an adversarial
    # long-prompt trace, under the metered prefill-cost model — the
    # CPU-proxy record of the head-of-line claim. Virtual-clock
    # numbers: this banks control-loop/routing behavior (what the
    # proxy CAN prove), never silicon latency (docs/serving.md).
    if args.disagg:
        import tempfile

        from apex1_tpu.obs import spine as obs_spine
        from apex1_tpu.serving import FrontendConfig
        from apex1_tpu.testing.fleetsim import (FleetSimConfig,
                                                run_fleet,
                                                synthetic_trace)

        horizon = 2.0 if args.smoke else 4.0
        ttft_slo_s = 0.12
        tr = synthetic_trace(
            "adversarial_long_prompt", seed=args.disagg_seed,
            horizon_s=horizon, base_rate=25.0,
            # guaranteed stays short (direct-decode under disagg);
            # best_effort/sheddable drag 18-30-token prefills through
            prompt_lens=(2, 4), long_prompt_lens=(18, 30),
            class_mix={"guaranteed": 0.4, "best_effort": 0.35,
                       "sheddable": 0.25})
        fcfg = FrontendConfig(n_replicas=3, capacity_per_replica=8,
                              hedge_after_s=None)
        sims = (
            ("unified", FleetSimConfig(max_len=64,
                                       prefill_round_cost=True)),
            ("disagg", FleetSimConfig(max_len=64,
                                      prefill_round_cost=True,
                                      disagg=True,
                                      prefill_replicas=1)),
        )

        def phase_breakdown(events):
            """Per-phase percentiles per QoS class, reconstructed from
            the spine's ``serving.request`` lifecycle events alone (the
            obs trace parser path — proves the banked events carry the
            episode, not just the in-memory records). Disagg pools
            mirror their own lifecycle beside the end-to-end one under
            the same request id; min(first_token)/max(done) collapses
            the duplicates back to the end-to-end view."""
            per = {}
            for e in events:
                if e.get("name") != "serving.request":
                    continue
                r = per.setdefault(int(e["req"]), {})
                ev, t = e.get("event"), e.get("t_serving")
                if ev == "queued":
                    r.setdefault("qos", e.get("qos"))
                    r["t_q"] = min(t, r.get("t_q", t))
                elif ev == "first_token":
                    r["t_f"] = min(t, r.get("t_f", t))
                elif ev == "done":
                    r["t_d"] = max(t, r.get("t_d", t))
                    r["n"] = max(int(e.get("n_generated", 0)),
                                 r.get("n", 0))
            out = {}
            for r in per.values():
                if not ("qos" in r and "t_q" in r and "t_f" in r
                        and "t_d" in r):
                    continue
                d = out.setdefault(r["qos"], {"ttfts": [], "tpots": []})
                d["ttfts"].append(r["t_f"] - r["t_q"])
                if r.get("n", 0) >= 2:
                    d["tpots"].append(
                        (r["t_d"] - r["t_f"]) / (r["n"] - 1))
            return {
                cls: {
                    "n": len(d["ttfts"]),
                    "ttft_p50_ms": round(float(np.percentile(
                        d["ttfts"], 50)) * 1e3, 2),
                    "ttft_p99_ms": round(float(np.percentile(
                        d["ttfts"], 99)) * 1e3, 2),
                    "tpot_p99_ms": (round(float(np.percentile(
                        d["tpots"], 99)) * 1e3, 2)
                        if d["tpots"] else None),
                } for cls, d in sorted(out.items())}

        obs_tmp = tempfile.mkdtemp(prefix="bench_disagg_obs_")
        rows, reports = [], {}
        for tag, sim in sims:
            run = obs_spine.ObsRun(dir=obs_tmp,
                                   component=f"bench_disagg_{tag}")
            obs_spine.set_default_run(run)
            try:
                rep = run_fleet(tr, fcfg, sim=sim)
            finally:
                run.close()
                obs_spine.set_default_run(None)
            reports[tag] = rep
            j = rep.to_json()
            row = {
                "config": tag,
                "guaranteed_ttft_attainment": round(
                    rep.ttft_attainment("guaranteed", ttft_slo_s), 4),
                "goodput_tok_per_virtual_s":
                    j["goodput_tok_per_virtual_s"],
                "per_class": j["per_class"],
                "per_phase": phase_breakdown(
                    obs_spine.read_events(run.path)),
                "fingerprint": j["fingerprint"],
            }
            for k in ("handoffs", "handoff_failures",
                      "handoff_reroutes"):
                if k in j:
                    row[k] = j[k]
            rows.append(row)
        # cross-fleet token parity: a request done under BOTH fleets
        # carries the same id, hence the same derived seed, hence must
        # carry the SAME tokens — the handoff (and every re-route) is
        # invisible in the stream, which transitively pins the disagg
        # streams to solo generate (the unified engine's tier-1
        # contract)
        uni = {o["idx"]: o["tokens_sha1"]
               for o in reports["unified"].outcomes
               if o["status"] == "done"}
        dis = {o["idx"]: o["tokens_sha1"]
               for o in reports["disagg"].outcomes
               if o["status"] == "done"}
        common = sorted(set(uni) & set(dis))
        assert common, "no request completed under both fleets"
        for idx in common:
            assert uni[idx] == dis[idx], \
                f"request {idx}: disagg stream diverged from unified"
        d_row, u_row = rows[1], rows[0]
        assert d_row["handoffs"] > 0 and \
            d_row["handoff_failures"] == 0, d_row
        # structural gate only (like the >= 2x line): the banked
        # record carries the margin, the gate just proves the split
        # didn't LOSE the guaranteed class
        assert (d_row["guaranteed_ttft_attainment"]
                >= u_row["guaranteed_ttft_attainment"]), rows
        record["disagg_sweep"] = {
            "trace": {"kind": tr.kind, "seed": tr.seed,
                      "arrivals": len(tr.requests),
                      "horizon_s": horizon,
                      "fingerprint": tr.fingerprint()},
            "replicas_total": fcfg.n_replicas,
            "ttft_slo_s": ttft_slo_s,
            "parity_checked_requests": len(common),
            "rows": rows,
        }
        _bank(args.out, record)

    print(json.dumps(record), flush=True)
    # every sweep point already asserted (a) token parity against the
    # solo-generate oracle for every request and (b) exactly two traced
    # executables — reaching here IS the smoke gate; the >= 2x
    # acceptance ratio is read off the banked full-size sweep
    # (perf_results/bench_serving_cpu.log), where the model is big
    # enough for weight streaming, not dispatch, to dominate
    return 0


if __name__ == "__main__":
    sys.exit(main())
