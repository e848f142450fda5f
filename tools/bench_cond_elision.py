"""A/B wall-clock timing of the two production ``lax.cond`` skips —
the pipeline bubble-skip (``schedules.pipeline_apply skip_bubbles``) and
the ring-attention causal-skip (``parallel.ring_attention``).

VERDICT item: both skips are EXECUTABLE-verified (cond survives to the
optimized TPU executable — tools/cond_elision_aot.py r4) and
synthetically timed (tools/cond_elision_probe.py: cond-false tracks the
light branch), but the production sites themselves were never A/B
timed. This tool runs each site twice — skip enabled vs disabled — in
one process and emits a single JSON line with both speedups:

- pipeline: ``pipeline_apply(..., skip_bubbles=True/False)`` over a pp
  ring with a transformer-stage-sized ``stage_fn``. Expected win scales
  with the bubble share (p−1)/(M+p−1).
- ring: causal ``ring_attention(..., skip_masked=True/False)`` fwd+bwd.
  Expected win approaches the strictly-future shard share ~(n−1)/2n of
  attend FLOPs.

Device requirements: >= 2 devices for both sites. On a single-chip
window it emits a skip record (rc 0 — the queue must keep moving); on
CPU (rehearsal) it builds the 8-device virtual mesh with tiny shapes,
validating the command line end-to-end. NOTE: CPU cond elision differs
from TPU (that is the point of measuring on silicon) — rehearsal
numbers validate plumbing, not the claim.

Usage: python tools/bench_cond_elision.py [--pp N] [--cp N] [--iters K]
"""

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _emit(record):
    print(json.dumps(record), flush=True)


def _timed(compiled, args, iters):
    import jax
    out = compiled(*args)
    jax.block_until_ready(out)               # warmup, same executable
    t0 = time.perf_counter()
    out = compiled(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    chk = float(jax.tree_util.tree_leaves(out)[-1])
    if not math.isfinite(chk):
        raise RuntimeError(f"non-finite check value {chk}")
    return dt


def _bench_pipeline(mesh, n, accel, iters):
    """pipeline_apply fwd with a stage-sized matmul chain, skip on/off."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from apex1_tpu.transformer.pipeline_parallel import schedules

    E, M, depth = (1024, 2 * n, 4) if accel else (128, 2 * n, 2)
    dtype = jnp.bfloat16 if accel else jnp.float32
    rng = np.random.default_rng(0)
    # (stages, V=1, depth, E, E) weights, stage-major so P("pp") shards
    w = jnp.asarray(rng.normal(size=(n, 1, depth, E, E)) * 0.02, dtype)
    mbs = jnp.asarray(rng.normal(size=(M, 8, E)), dtype)

    def stage_fn(params, x):
        for i in range(depth):
            x = jnp.tanh(x @ params[i])
        return x

    def run(skip):
        def inner(w, mbs):
            last = (jax.lax.axis_index("pp") == n - 1).astype(jnp.float32)
            outs = schedules.pipeline_apply(
                stage_fn, w[0], mbs, broadcast_outputs=False,
                skip_bubbles=skip)
            return jax.lax.psum(
                last * jnp.mean(jnp.square(outs.astype(jnp.float32))),
                "pp")

        sm = jax.shard_map(inner, mesh=mesh,
                           in_specs=(P("pp"), P()), out_specs=P(),
                           check_vma=False)

        def many(w, mbs):
            def body(_, acc):
                return acc + sm(w, mbs)
            return jax.lax.fori_loop(0, iters, body, jnp.float32(0.0))

        return jax.jit(many).lower(w, mbs).compile()

    t_on = _timed(run(True), (w, mbs), iters)
    t_off = _timed(run(False), (w, mbs), iters)
    return {"skip_ms": round(t_on * 1e3, 3),
            "noskip_ms": round(t_off * 1e3, 3),
            "speedup": round(t_off / t_on, 4),
            "shape": {"pp": n, "E": E, "M": M, "depth": depth}}


def _bench_ring(mesh, n, accel, iters):
    """Causal ring attention fwd+bwd, future-shard skip on/off."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from apex1_tpu.parallel.ring_attention import ring_attention

    if accel:
        B, Hq, Hkv, D, S = 1, 32, 4, 64, 16384
        dtype = jnp.bfloat16
    else:
        B, Hq, Hkv, D, S = 1, 4, 2, 16, 512
        dtype = jnp.float32
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, Hq, S, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), dtype)
    spec = P(None, None, "cp", None)

    def run(skip):
        sm = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "cp", causal=True,
                                           skip_masked=skip),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False)
        grad = jax.grad(
            lambda q, k, v: jnp.sum(sm(q, k, v).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))

        def many(q, k, v):
            def one(q):
                dq, dk, dv = grad(q, k, v)
                return (q + (1e-6 * dq).astype(q.dtype),
                        jnp.sum(dq) + jnp.sum(dk) + jnp.sum(dv))

            def body(_, carry):
                return one(carry[0])

            return jax.lax.fori_loop(0, iters - 1, body, one(q))

        return jax.jit(many).lower(q, k, v).compile()

    t_on = _timed(run(True), (q, k, v), iters)
    t_off = _timed(run(False), (q, k, v), iters)
    return {"skip_ms": round(t_on * 1e3, 3),
            "noskip_ms": round(t_off * 1e3, 3),
            "speedup": round(t_off / t_on, 4),
            "shape": {"cp": n, "B": B, "Hq": Hq, "Hkv": Hkv, "S": S,
                      "D": D}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pp", type=int, default=None)
    ap.add_argument("--cp", type=int, default=None)
    ap.add_argument("--iters", type=int, default=None)
    args = ap.parse_args()

    import jax

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

    from apex1_tpu.core.mesh import make_mesh

    backend = jax.default_backend()
    devices = jax.devices()
    accel = backend not in ("cpu",)
    n_pp = args.pp or min(len(devices), 4)
    n_cp = args.cp or min(len(devices), 4)
    iters = args.iters or (8 if accel else 2)
    if min(n_pp, n_cp) < 2:
        _emit({"metric": f"cond_elision_ab [{backend}]", "value": 0.0,
               "error": f"pipeline/ring need >= 2 devices, have "
                        f"{len(devices)} — skipped (multichip window "
                        f"required)"})
        return

    record = {"metric": f"cond_elision_ab [{backend}]", "unit":
              "x (noskip/skip step time)"}
    failed = False
    for name, fn, n in (("pipeline_bubble_skip", _bench_pipeline, n_pp),
                        ("ring_causal_skip", _bench_ring, n_cp)):
        try:
            axis = "pp" if name.startswith("pipeline") else "cp"
            mesh = make_mesh(**{axis: n}, dp=1, devices=devices[:n])
            record[name] = fn(mesh, n, accel, iters)
        except Exception as e:
            failed = True
            record[name] = {"error":
                            f"{type(e).__name__}: {str(e)[:300]}"}
    # headline value: the ring skip speedup (the larger claimed win)
    record["value"] = (record.get("ring_causal_skip", {})
                       .get("speedup", 0.0))
    _emit(record)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
