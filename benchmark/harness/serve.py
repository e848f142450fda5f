"""Runner for cells of kind `serve_open` and `serve_closed`: one process,
one thread driving `Engine.step()`, a replayed trace, exact intervals.

- The trace's shape comes from the traffic file's `shape_seed`
  (`loadgen`); `--seed` decides the weights and the token ids only.
- A BOUNDARY is the moment the `Engine.step()` that READ a token on the
  host returned: since PR 33 one call after the call that launched it, since
  PR 45 two calls after where the launch is not hidden (`gpt2m_serve_chat`).
  A token's time is its boundary. The window
  opens at the first boundary at or after warm-up + ramp and closes at the
  first boundary at or after `--seconds` later.
- `serve_tok_s` = tokens processed between the two boundaries (a prompt's
  real tokens when its prefill calls have completed, an output token when
  the host has it) over the MEASURED interval.
- `tpot_p50_ms` / `itl_p95_ms` are over every gap between consecutive
  token boundaries of every request, for gaps that ended inside the
  window. The gap from a request's due time to its first token is not a
  token gap: it is the time to first token, reported apart.
- What is read from the program: `Engine.step()`'s return (active slots),
  `engine.results`, and the COUNTS in `engine.metrics.records` (tokens
  generated, prompt tokens, prefix tokens saved) plus its `queued` /
  `prefill` stamps for the queue wait. No token time comes from there.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import builders, check, device, loadgen, stats


class _Live:
    __slots__ = ("idx", "t_due", "n_seen", "last_t", "first_seen")

    def __init__(self, idx, t_due):
        self.idx, self.t_due = idx, t_due
        self.n_seen, self.last_t, self.first_seen = 0, None, False


def import_program(cfg: dict) -> None:
    """The program's modules a serving cell needs, imported inside the
    `import` phase of set-up so that the later phases time work alone."""
    import apex1_tpu.serving.engine  # noqa: F401
    b = builders.get(cfg)
    b.decoder(b.model("O2"))


def build_engine(cfg: dict, traffic: dict, dev, seed: int):
    from apex1_tpu.serving.engine import Engine, EngineConfig
    b = builders.get(cfg)
    model = b.model(traffic.get("opt_level", "O2"))
    shapes = b.param_shapes(model)
    sharding = jax.sharding.SingleDeviceSharding(dev)

    def fresh_params():
        return builders.make_params(shapes, seed, jnp.bfloat16, sharding)

    engine = Engine(*b.decoder(model), fresh_params(), EngineConfig(
        vocab_size=b.vocab_size, **traffic["engine"]))
    return b, engine, fresh_params


def _sync(engine) -> None:
    kv = engine.kv
    pages = getattr(kv, "pages", None)      # the paged pool, where it is
    jax.block_until_ready(kv.cache if pages is None else pages)


def warm_up(engine, traffic: dict, vocab: int, seed: int) -> int:
    """Every slot once, through both executables: a two-chunk prompt and
    three tokens each. Slot-indexed helper programs (lane snapshots,
    control-vector patches) compile here, not in the window."""
    eng = traffic["engine"]
    n = int(eng["max_slots"])
    rng = np.random.default_rng([int(seed), 3])
    ids = [engine.submit(rng.integers(0, vocab, eng["prefill_chunk"] + 1)
                         .astype(np.int32), 3, seed=i) for i in range(n)]
    steps = 0
    while any(r not in engine.results for r in ids):
        engine.step()
        steps += 1
        if steps > 50 * n:
            raise RuntimeError("warm-up made no progress")
    for r in ids:
        res = engine.pop_result(r)
        if res.status != "done":
            raise RuntimeError(f"warm-up request ended {res.status}")
    engine.metrics.drain()
    _sync(engine)
    return steps


def run(cell: dict, cfg: dict, traffic: dict, args, phases, meter,
        devices: list, profiler=None) -> dict:
    from apex1_tpu.serving.scheduler import Backpressure
    seed = args.seed
    traced = profiler is not None
    seconds = float(args.seconds)
    if traced:
        seconds = min(seconds, float(traffic.get("trace", {})
                                     .get("seconds", 5)))
    closed = traffic["kind"] == "serve_closed"
    b, engine, fresh_params = build_engine(cfg, traffic, devices[0], seed)
    phases.lap("init")
    trace = loadgen.make_trace(
        traffic, loadgen.n_requests_for(traffic, float(args.seconds)))
    prompts = loadgen.token_ids(trace, seed, b.vocab_size)
    n_total = len(prompts)
    warm_up(engine, traffic, b.vocab_size, seed)
    phases.lap("warmup")
    if traffic.get("gc_freeze", False):
        gc.collect()
        gc.freeze()

    ramp_s = float(traffic.get("ramp", {}).get("seconds", 0.0))
    clock = time.perf_counter
    live, finished = {}, []
    attempted = failed = 0
    next_i = 0
    bounds, work, active, step_ms = [], [], [], []
    gaps, ttft, qwait, late = [], [], [], []
    records = engine.metrics.records
    results = engine.results

    def submit(i, t_due):
        nonlocal attempted, failed
        attempted += 1
        try:
            rid = engine.submit(prompts[i], int(trace.output_len[i]),
                                seed=i)
        except Backpressure:
            failed += 1
            return
        live[rid] = _Live(i, t_due)

    t0 = clock()
    if closed:
        for _ in range(int(traffic["callers"])):
            submit(next_i, 0.0)
            next_i += 1
    window = None
    t_open = None
    started_trace = False
    span = None
    while window is None:
        now = clock() - t0
        if traced and not started_trace and now >= ramp_s - 1.0:
            profiler.start()       # its stall lands in the ramp
            started_trace = True
        with jax.profiler.TraceAnnotation("loadgen"):
            if not closed:
                while (next_i < n_total
                       and trace.arrival_s[next_i] <= now):
                    due = float(trace.arrival_s[next_i])
                    late.append((now, now - due))
                    submit(next_i, due)
                    next_i += 1
                    now = clock() - t0
        if not live:
            if next_i >= n_total:
                raise RuntimeError("the trace ran out before the window "
                                   "closed")
            time.sleep(2e-4)
            continue
        if span is None and t_open is None and now >= ramp_s:
            # the next boundary opens the window
            span = jax.profiler.TraceAnnotation("bench/window")
            span.__enter__()
        with jax.profiler.TraceAnnotation("engine/step"):
            t_a = clock()
            n_act = engine.step()
            t_b = clock()
        w = 0
        done = []
        for rid, st in live.items():
            rec = records.get(rid)
            if rec is None:
                continue
            if not st.first_seen and rec.t_first_token is not None:
                st.first_seen = True
                w += rec.n_prompt - rec.prefix_saved
                ttft.append((t_b - t0, t_b - t0 - st.t_due))
                if rec.t_prefill is not None and rec.t_queued is not None:
                    qwait.append((t_b - t0, rec.t_prefill - rec.t_queued))
            d = rec.n_generated - st.n_seen
            if d > 0:
                w += d
                st.n_seen = rec.n_generated
                if st.last_t is not None:
                    gaps.append((t_b - t0, t_b - st.last_t))
                st.last_t = t_b
            if rid in results:
                done.append(rid)
        for rid in done:
            st = live.pop(rid)
            res = engine.pop_result(rid)
            if res.status == "done":
                finished.append({"t": t_b - t0, "prompt": prompts[st.idx],
                                 "tokens": np.asarray(res.tokens)})
            else:
                failed += 1
            if closed and next_i < n_total:
                submit(next_i, t_b - t0)
                next_i += 1
        bounds.append(t_b - t0)
        work.append(w)
        active.append(n_act)
        step_ms.append(1e3 * (t_b - t_a))
        if t_open is None:
            if span is not None:
                _sync(engine)
                t_open = bounds[-1]
                in_window = meter.mark()
                phases.lap("ramp")
                setup_s = phases.total()
        else:
            window = stats.exact_window(bounds, t_open, seconds)
    _sync(engine)
    span.__exit__(None, None, None)
    if traced:
        profiler.stop()
    compiled_in_window = meter.since(in_window)["compiles"]
    peak = device.memory_peak_bytes(devices)

    def inside(pairs):
        return [v for t, v in pairs
                if window.t_open < t <= window.t_close]

    tokens = stats.count_in_window(work, window)
    w_gaps = [1e3 * g for g in inside(gaps)]
    w_ttft = [1e3 * v for v in inside(ttft)]
    w_wait = [1e3 * v for v in inside(qwait)]
    w_late = [1e3 * v for v in inside(late)]
    w_steps = step_ms[window.i_open + 1:window.i_close + 1]
    w_active = active[window.i_open + 1:window.i_close + 1]
    w_done = [f for f in finished
              if window.t_open < f["t"] <= window.t_close]
    p50, p95 = stats.percentile(w_gaps, 50), stats.percentile(w_gaps, 95)
    print(f"window: {window.seconds:.4f} s between engine-step boundaries "
          f"{window.i_open} and {window.i_close} ({len(w_steps)} steps); "
          f"{tokens:.0f} tokens; {len(w_done)} requests finished; "
          f"compilations inside the window: {compiled_in_window}",
          flush=True)
    print(f"window: token gaps n={p50.n}: p50 {p50.value:.3f} ms, p95 "
          f"{p95.value:.3f} ms; first tokens n={len(w_ttft)}; engine-step "
          f"p50 {stats.percentile(w_steps, 50).value:.3f} ms "
          f"(n={len(w_steps)})", flush=True)
    print(f"window: time to first token p50 "
          f"{stats.percentile(w_ttft, 50).value:.1f} ms, p95 "
          f"{stats.percentile(w_ttft, 95).value:.1f} ms; mean active slots "
          f"{float(np.mean(w_active)):.1f}; in flight at close {len(live)}; "
          f"arrivals submitted late p99 "
          f"{stats.percentile(w_late, 99).value:.2f} ms", flush=True)

    # the check: a seeded sample of what the window finished, longest in
    k = int(traffic.get("check_requests", 8))
    sample = check.pick_sample(w_done, k, seed)
    t_check = time.perf_counter()
    rows = [("requests_failed", failed, 0, failed == 0),
            ("compilations_in_window", compiled_in_window, 0,
             compiled_in_window == 0),
            ("requests_to_compare_missing", int(not sample), 0,
             bool(sample))]
    summary = {}
    # the program's state is freed before the reference's weights exist
    del engine, records, results
    gc.collect()
    if sample:
        quant = check.control_quant(args.control)
        with jax.default_device(devices[0]):
            summary = check.serve_gaps(
                args.reference, b.ref_cfg, fresh_params(), sample,
                int(traffic["engine"]["max_len"]),
                int(traffic["output_len"].get(
                    "max", traffic["output_len"].get("value", 1))),
                quant)
        limits = check.load_limits(cell["name"], args.root)
        print(f"check: {summary['n_requests']} requests, "
              f"{summary['n_tokens']} served tokens, reference logit std "
              f"{summary['logit_std']:.4f}", flush=True)
        rows += check.compare_serving(summary, limits)
        if "control_widest_gap" in summary:
            print(f"control: the reference in {quant} puts "
                  f"first a token {summary['control_widest_gap']:.6g} "
                  f"below the reference's best (limit "
                  f"{limits['logit_gap']:.6g}): "
                  f"{'fails, as it must' if summary['control_widest_gap'] > limits['logit_gap'] else 'PASSES - limit too loose'}",
                  flush=True)
    ok = check.print_rows(rows)
    print(f"check: took {time.perf_counter() - t_check:.1f} s (not in "
          f"setup_s); failed requests {failed}", flush=True)

    scalars = {"setup_s": setup_s,
               "serve_tok_s": tokens / window.seconds,
               "window.interval_s": window.seconds,
               "window.steps": len(w_steps),
               "window.compiles": compiled_in_window,
               "occupancy_pct": 100.0 * float(np.mean(w_active))
               / int(traffic["engine"]["max_slots"])}
    if w_gaps:
        scalars["tpot_p50_ms"] = p50.value
        scalars["itl_p95_ms"] = p95.value
    series = {"engine_step_ms": w_steps, "token_gap_ms": w_gaps,
              "ttft_ms": w_ttft, "queue_wait_ms": w_wait,
              "loadgen_late_ms": w_late}
    return {"correct": bool(ok), "attempted": attempted, "failed": failed,
            "scalars": scalars, "series": series,
            "memory_peak_bytes": peak, "check": summary, "compared": rows}
