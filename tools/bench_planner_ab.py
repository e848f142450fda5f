"""A/B: the auto-parallel planner's pick vs the hand-tuned layout —
the wall-clock form of ROADMAP item 1's acceptance contract (the
pricing form is pinned in tier-1 by tests/test_planner.py).

Two legs:

1. PRICING (runs anywhere, no devices needed): for each banked bench
   shape (gpt2, llama_longctx, the llama-8B projection) price the
   hand-tuned layout and the planner's pick through the calibrated
   cost engine against the committed calibration.json, and emit the
   ratio — planner within ~10% of (i.e. at or below 1.10x) the hand
   config is the pass line.

2. MEASURED (needs >= 2 devices): build the SAME model under (a) the
   hand-tuned example layout and (b) the planner's pick for the live
   device count, time both `models.llama_3d` train steps, and emit
   both rates + the measured ratio. On a single-chip window this leg
   emits a skip record (rc 0 — the queue must keep moving); on CPU it
   rehearses on the 8-device virtual mesh with a tiny model,
   validating the command line end-to-end.

Usage: python tools/bench_planner_ab.py [--iters K] [--skip-measured]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _emit(record):
    print(json.dumps(record), flush=True)


#: the hand-tuned comparators the pricing leg scores against — the
#: exact layouts the repo's bench/aot history picked by hand:
#: gpt2/llama_longctx are the single-chip bench configs;
#: llama8b is aot_check --flagship's dp2 x pp2 x tp4 on 16 chips.
def _hand_cases():
    from apex1_tpu import planner

    S = planner.BANKED_SHAPES
    return [
        ("gpt2", S["gpt2"], 1, "v5e",
         planner.Layout(num_microbatches=16)),
        ("llama_longctx", S["llama_longctx"], 1, "v5e",
         planner.Layout(num_microbatches=1)),
        ("llama8b", S["llama8b"], 16, "v5p",
         planner.Layout(dp=2, pp=2, tp=4, num_microbatches=4)),
    ]


def pricing_leg():
    from apex1_tpu import planner

    worst = 0.0
    for name, shape, n, gen, hand in _hand_cases():
        hand_price = planner.price_layout(shape, hand, generation=gen)
        plan = planner.make_plan(shape, n, generation=gen)
        pick = plan["predicted"]
        ratio = (pick["calibrated_step_ms"]
                 / hand_price["calibrated_step_ms"])
        worst = max(worst, ratio)
        _emit({
            "metric": f"planner_ab pricing {name} [{gen} x{n}]",
            "hand_mesh": hand.mesh_str(),
            "hand_calibrated_ms": round(
                hand_price["calibrated_step_ms"], 3),
            "planner_mesh": plan["mesh"],
            "planner_calibrated_ms": round(
                pick["calibrated_step_ms"], 3),
            "planner_over_hand": round(ratio, 4),
            "calibration": pick["calibration"]["source"],
            "pass": ratio <= 1.10,
        })
    return worst


def measured_leg(iters):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex1_tpu import planner
    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.llama import LlamaConfig
    from apex1_tpu.models.llama_3d import (Llama3DConfig,
                                           make_train_step)

    backend = jax.default_backend()
    devices = jax.devices()
    n = len(devices)
    if n < 2:
        _emit({"metric": f"planner_ab measured [{backend}]",
               "value": 0.0,
               "error": f"devices available: {n} — skipped (multichip "
                        f"window required for a layout A/B)"})
        return
    on_accel = backend not in ("cpu",)
    if on_accel:
        mcfg = LlamaConfig(vocab_size=32000, max_seq_len=2048,
                           num_layers=8, num_heads=32, num_kv_heads=4,
                           hidden_size=2048, ffn_size=5632, remat=True,
                           policy=get_policy("O2"))
    else:
        import dataclasses
        mcfg = dataclasses.replace(
            LlamaConfig.tiny(policy=get_policy("O2")),
            max_seq_len=128, remat=True)
    global_batch = 4 * n
    shape = planner.ModelShape.from_llama(mcfg, name="llama_3d",
                                          global_batch=global_batch)
    gen = None
    if on_accel:
        from apex1_tpu.core.capability import get_capability
        gen = get_capability().generation

    # the hand comparator: the flagship recipe's shape — dp=2 fixed,
    # tp as deep as the kv heads allow, pp the remainder (the same
    # rule tools/aot_check.py --flagship applies by hand). An odd or
    # otherwise unfactorable device count has no hand layout of this
    # family — skip record, not a traceback (the queue must keep
    # moving).
    cands = [t for t in (1, 2, 4, 8)
             if n % (2 * t) == 0 and n // (2 * t) >= 1
             and shape.num_kv_heads % t == 0
             and shape.seq_len % t == 0]
    if not cands:
        _emit({"metric": f"planner_ab measured [{backend}]",
               "value": 0.0,
               "error": f"no dp=2-family hand comparator for n={n} "
                        f"devices — skipped"})
        return
    tp = max(cands)
    dp = 2
    pp = n // (dp * tp)
    hand_cfg = Llama3DConfig(model=mcfg, dp=dp, pp=pp, tp=tp,
                             num_microbatches=global_batch // dp,
                             microbatch_size=1)
    plan = planner.make_plan(shape, n, generation=gen,
                             allow_zero=False)
    plan_cfg = planner.llama3d_config_from_plan(plan, mcfg)

    def timed(tag, cfg):
        step, state, _ = make_train_step(cfg)
        rng = np.random.default_rng(0)
        dshape = (cfg.num_microbatches, mcfg.max_seq_len,
                  cfg.microbatch_size * cfg.dp * cfg.ep)
        tokens = jnp.asarray(
            rng.integers(0, mcfg.vocab_size, dshape), jnp.int32)
        labels = jnp.roll(tokens, -1, axis=1)
        state, loss = step(state, tokens, labels)   # compile + warm
        jax.block_until_ready((state, loss))
        t0 = time.perf_counter()
        for _ in range(iters):
            state, loss = step(state, tokens, labels)
        jax.block_until_ready((state, loss))
        dt = (time.perf_counter() - t0) / iters
        del state
        return dt

    t_hand = timed("hand", hand_cfg)
    t_plan = timed("plan", plan_cfg)
    tok = shape.tokens_per_step
    _emit({
        "metric": f"planner_ab measured [{backend}]",
        "value": round(tok / t_plan / n, 1),
        "unit": "tokens/sec/chip",
        "hand_mesh": f"dp={dp} pp={pp} tp={tp}",
        "hand_step_ms": round(t_hand * 1e3, 2),
        "hand_rate": round(tok / t_hand / n, 1),
        "planner_mesh": plan["mesh"],
        "planner_step_ms": round(t_plan * 1e3, 2),
        "planner_over_hand_time": round(t_plan / t_hand, 4),
        "predicted_calibrated_ms": round(
            plan["predicted"]["calibrated_step_ms"], 3),
        "iters": iters,
    })


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--skip-measured", action="store_true",
                    help="pricing leg only (no backend init)")
    args = ap.parse_args()

    print("== planner_ab pricing (calibrated cost engine, banked "
          "shapes) ==", flush=True)
    worst = pricing_leg()
    print(f"pricing leg worst planner/hand ratio: {worst:.3f} "
          f"({'PASS' if worst <= 1.10 else 'FAIL'} at the 1.10 line)",
          flush=True)
    if args.skip_measured:
        return 0 if worst <= 1.10 else 1

    print("== planner_ab measured (live mesh) ==", flush=True)
    # JAX_PLATFORMS=cpu rehearses on the 8-device virtual mesh (the
    # device-count flag only acts before first backend init); otherwise
    # THIS process initialises the default backend — one process per
    # chip, so no probing child
    on_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if on_cpu:
        from apex1_tpu.testing import force_virtual_cpu_devices
        force_virtual_cpu_devices(8)
    from apex1_tpu.testing import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    measured_leg(args.iters or (2 if on_cpu else 6))
    return 0 if worst <= 1.10 else 1


if __name__ == "__main__":
    raise SystemExit(main())
