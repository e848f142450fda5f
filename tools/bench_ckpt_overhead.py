"""Async-checkpointing overhead A/B — the acceptance number for the
resilient runtime (docs/robustness.md): steady-state step time with
background saves must sit within 5% of the no-checkpoint baseline.

Three loop variants over the SAME jitted (donating) train step:

- ``baseline``: N steps, no checkpointing.
- ``async``: N steps with ``ResilientCheckpointer.save`` at the
  configured cadence — the device-side snapshot + enqueue is the only
  on-loop cost; the host fetch, sha256 manifest, and orbax write run on
  the background worker while later steps train. The queue drain runs
  OUTSIDE the timed region (steady state is the claim; drain is bounded
  by one in-flight save).
- ``sync``: the same cadence through ``save_sync`` — the save-step
  samples (the steps that paid a full synchronous write) report the
  cost the async path is hiding, per save.

Measurement protocol: the three variants run interleaved across
``--rounds`` adjacent rounds (async first, fully drained before the
round's baseline starts, so no background work leaks across segments);
every individual step is blocked on and timed, and the headline is
the median over rounds of the per-round ratio of median step times
(async/baseline) — on a shared 2-core CI box background load both
spikes (single slow steps) and sustained shifts (slow seconds) swing
wall clocks 3x, so single A/Bs are noise; the within-round median
rejects spikes, the within-round ratio cancels shifts, and the
across-round median rejects rounds a shift split in half.

The save CADENCE is part of the claim: the interval must exceed one
save's duration (~0.5 s here; the checkpointer bounds in-flight saves
at one, so a faster cadence degrades toward sync BY DESIGN), as it
does by orders of magnitude at any production cadence. On the CPU
proxy the background fetch/sha256/write contends for the step's own
cores — the TPU number can only be better (the step runs on the
device, the worker on an otherwise idle host).

Emits one JSON line (the queue's tee-to-``perf_results/`` contract):
``value`` = median async overhead in %, plus per-variant median
ms/step and the sync comparison.

Usage: python tools/bench_ckpt_overhead.py [--iters N] [--every K]
       [--rounds R] (CPU proxy: JAX_PLATFORMS=cpu, banked at
       perf_results/ckpt_overhead_cpu.log)
"""

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _emit(record):
    print(json.dumps(record), flush=True)


def _build(accel):
    """One jitted (donating) train step + a fresh-state factory, sized
    so a CPU step is ~25 ms. B sets the compute:state ratio — a
    realistic step does far more flops per byte of checkpoint state
    than a toy one, and on the CPU proxy the background worker contends
    for the step's cores, so a too-small step reads as phantom
    checkpoint overhead."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex1_tpu.amp import Amp
    from apex1_tpu.optim.fused_sgd import fused_sgd

    E, depth, B = (1024, 8, 256) if accel else (256, 6, 512)
    rng = np.random.default_rng(0)
    # host-side master copies: each make_state() call uploads FRESH
    # device buffers (the donating step deletes the previous loop's)
    host_params = {f"w{i}": (rng.normal(size=(E, E)) * 0.02
                             ).astype(np.float32)
                   for i in range(depth)}
    x = jnp.asarray(rng.normal(size=(B, E)), jnp.float32)

    def loss_fn(p, x):
        h = x
        for i in range(depth):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean(jnp.square(h))

    amp = Amp(tx=fused_sgd(1e-3), opt_level="O0")
    step = jax.jit(amp.make_train_step(loss_fn), donate_argnums=0)

    def make_state():
        return amp.init({k: jnp.asarray(v)
                         for k, v in host_params.items()})

    return step, make_state, x


def _segment(step, make_state, x, iters, *, save_every=None, ck=None,
             sync=False):
    """Per-step wall-clock samples (ms) for one segment — each step is
    blocked on, so a sample covers exactly one step plus whatever save
    cost (enqueue or full sync write) that step incurred. The donation
    + async-save combination is exactly the production hazard the
    checkpointer's device-side snapshot exists for."""
    import jax

    state = make_state()
    state, _ = step(state, x)                 # warmup (compile once)
    jax.block_until_ready(state.params)
    samples = []
    for i in range(iters):
        t0 = time.perf_counter()
        state, _m = step(state, x)
        if save_every and (i + 1) % save_every == 0:
            if sync:
                ck.save_sync(int(i + 1), state,
                             meta={"data_step": i + 1})
            else:
                ck.save(int(i + 1), state, meta={"data_step": i + 1})
        jax.block_until_ready(state.params)
        samples.append((time.perf_counter() - t0) * 1e3)
    return samples


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=80,
                    help="steps per segment")
    ap.add_argument("--every", type=int, default=40,
                    help="save cadence inside the saving segments "
                    "(interval must exceed one save's duration — see "
                    "module docstring)")
    ap.add_argument("--rounds", type=int, default=9,
                    help="adjacent async/baseline/sync rounds; the "
                    "headline is the median of per-round ratios")
    args = ap.parse_args()

    import jax

    from apex1_tpu.resilience import ResilientCheckpointer

    backend = jax.default_backend()
    accel = backend not in ("cpu",)
    step, make_state, x = _build(accel)

    with tempfile.TemporaryDirectory() as d:
        # one untimed shakeout of each variant (compile, allocator,
        # orbax first-save setup) before any timed round
        _segment(step, make_state, x, 4)
        with ResilientCheckpointer(os.path.join(d, "w"), keep=2) as ck:
            _segment(step, make_state, x, 4, save_every=4, ck=ck)
            ck.wait()
        rounds = []
        drains = []
        for r in range(args.rounds):
            row = {}
            with ResilientCheckpointer(os.path.join(d, f"a{r}"),
                                       keep=2) as ck:
                row["async"] = _segment(
                    step, make_state, x, args.iters,
                    save_every=args.every, ck=ck)
                t0 = time.perf_counter()
                ck.wait()               # drain BEFORE baseline starts
                drains.append(time.perf_counter() - t0)
            row["baseline"] = _segment(step, make_state, x, args.iters)
            with ResilientCheckpointer(os.path.join(d, f"s{r}"),
                                       keep=2) as ck:
                row["sync"] = _segment(
                    step, make_state, x, args.iters,
                    save_every=args.every, ck=ck, sync=True)
            rounds.append(row)

    # per-round medians, then the MEDIAN-OF-RATIOS across rounds: the
    # within-round median rejects load spikes, the within-round ratio
    # cancels sustained load shifts (the variants of one round ran
    # adjacent in time), and the across-round median rejects any round
    # where a shift landed mid-round anyway
    rmed = lambda row, k: statistics.median(row[k])
    med = lambda k: statistics.median(rmed(row, k) for row in rounds)
    overhead = statistics.median(
        rmed(row, "async") / rmed(row, "baseline") - 1.0
        for row in rounds)
    # the saving steps themselves: sync pays the full write on-loop
    # (the hidden cost), async pays only the snapshot+enqueue
    save_step = lambda k: statistics.median(
        v for row in rounds
        for p, v in enumerate(row[k])
        if (p + 1) % args.every == 0)
    record = {
        "metric": f"ckpt_overhead [{backend}]",
        "value": round(overhead * 100, 2),
        "unit": "% steady-state step-time overhead (async vs none, "
                "per-step medians over interleaved rounds)",
        "baseline_ms": round(med("baseline"), 3),
        "async_ms": round(med("async"), 3),
        "async_save_step_ms": round(save_step("async"), 3),
        "sync_save_step_ms": round(save_step("sync"), 3),
        "hidden_ms_per_save": round(save_step("sync")
                                    - med("baseline"), 3),
        "drain_s": round(max(drains), 3),
        "saves_per_segment": args.iters // args.every,
        "iters": args.iters, "rounds": args.rounds,
        "pass_5pct": bool(overhead <= 0.05),
    }
    _emit(record)
    if not record["pass_5pct"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
