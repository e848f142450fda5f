"""Does ``lax.cond`` ELIDE the untaken branch's compute on TPU?

VERDICT r2 weak #6: the pipeline bubble-skip (`schedules.pipeline_apply
skip_bubbles`) and ring-attention causal-skip (`parallel/ring_attention`)
both claim `lax.cond` saves the work of invalid ticks. XLA is allowed to
flatten a conditional into `select` (both branches execute) when the
branches are cheap or the predicate is vectorized — in which case the
"skip" saves nothing. This probe times, on the real chip:

  heavy(x)                      # unconditional heavy branch
  cond(False, heavy, light, x)  # traced predicate, always light
  light(x)                      # unconditional light branch

inside a fori_loop (one dispatch), where heavy = N chained matmuls and
light = x + 1. If cond-false tracks light (not heavy), the branch is
genuinely skipped and the per-tick skip claims hold on this backend.

Run: python tools/cond_elision_probe.py
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp

    from apex1_tpu.testing import enable_persistent_compilation_cache

    enable_persistent_compilation_cache()
    backend = jax.default_backend()
    if backend == "cpu":        # smoke-test the harness only
        N, D, LOOP = 4, 256, 5
    else:
        N, D, LOOP = 24, 2048, 50
    x = jnp.asarray(np.random.default_rng(0).normal(size=(D, D)),
                    jnp.bfloat16)

    def heavy(x):
        def body(_, a):
            return jnp.tanh(a @ x)
        return jax.lax.fori_loop(0, N, body, x)

    def light(x):
        return x + 1.0

    def timed(fn, *args):
        def looped(*args):
            def body(_, a):
                return fn(a, *args[1:])
            return jax.lax.fori_loop(0, LOOP, body, args[0])
        c = jax.jit(looped)
        c(*args).block_until_ready()          # compile + warm
        t0 = time.perf_counter()
        c(*args).block_until_ready()
        return (time.perf_counter() - t0) / LOOP * 1e3   # ms/iter

    t_heavy = timed(heavy, x)
    # the predicate must be TRACED (a constant would fold at compile time
    # and prove nothing) — same situation as the pipeline's per-tick
    # validity scalar
    pred_false = jnp.asarray(False)
    pred_true = jnp.asarray(True)
    t_cond_false = timed(
        lambda a, p: jax.lax.cond(p, heavy, light, a), x, pred_false)
    t_cond_true = timed(
        lambda a, p: jax.lax.cond(p, heavy, light, a), x, pred_true)
    t_light = timed(light, x)

    # elided if the false-branch cond costs << the heavy branch
    elides = t_cond_false < 0.25 * t_heavy
    print(json.dumps({
        "backend": backend,
        "ms_heavy": round(t_heavy, 4),
        "ms_cond_true": round(t_cond_true, 4),
        "ms_cond_false": round(t_cond_false, 4),
        "ms_light": round(t_light, 4),
        "cond_elides_untaken_branch": bool(elides),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
