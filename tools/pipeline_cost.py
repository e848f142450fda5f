"""Measure the scan-pipeline's cost model (VERDICT r1 next#6):

1. bubble-FLOP overhead — `cost_analysis` FLOPs of the pipelined fwd+bwd
   vs the unpartitioned model on the same global batch (predicted ratio:
   (VM + P − 1) / (VM) since bubble ticks execute `stage_fn` on zeros);
2. activation memory — `memory_analysis` temp bytes of the pipeline
   step with and without the `remat_stage` lever.

Runs on the virtual CPU mesh (analysis only; no TPU needed).
Usage: python tools/pipeline_cost.py [--layers 8] [--hidden 1024]
       [--mb 2] [--seq 256] [--microbatches 8] [--pp 4]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from apex1_tpu.testing import (enable_persistent_compilation_cache,
                               force_virtual_cpu_devices)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--mb", type=int, default=2)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--pp", type=int, default=4)
    args = ap.parse_args()

    force_virtual_cpu_devices(max(args.pp, 4))
    enable_persistent_compilation_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as Ps

    from apex1_tpu.core.mesh import make_mesh
    from apex1_tpu.transformer.pipeline_parallel.schedules import (
        pipeline_apply)

    P_, M, L = args.pp, args.microbatches, args.layers
    lps = L // P_
    E, mb, S = args.hidden, args.mb, args.seq
    mesh = make_mesh(pp=P_, dp=1)
    rng = np.random.default_rng(0)
    params = jnp.asarray(rng.normal(size=(1, P_, lps, E, E)) * 0.02,
                         jnp.float32)
    mbs = jnp.asarray(rng.normal(size=(M, S, mb, E)), jnp.float32)

    def stage(p_stage, x):
        # unrolled so cost_analysis counts every layer (scan bodies are
        # priced once regardless of trip count)
        def layer(x, w):
            return x + jnp.tanh(x @ w)
        x, _ = jax.lax.scan(lambda x, w: (layer(x, w), None), x, p_stage,
                            unroll=True)
        return x

    def pipe_loss(params, mbs, remat, unroll, skip=True):
        def inner(params, mbs):
            s = jax.lax.axis_index("pp")
            last = (s == P_ - 1).astype(jnp.float32)
            outs = pipeline_apply(stage, params[:, 0], mbs,
                                  broadcast_outputs=False,
                                  remat_stage=remat, scan_unroll=unroll,
                                  skip_bubbles=skip)
            return last * jnp.mean(jnp.square(outs))

        return jax.shard_map(inner, mesh=mesh,
                             in_specs=(Ps(None, "pp"), Ps()),
                             out_specs=Ps(), check_vma=False)(params, mbs)

    def flat_loss(params, mbs):
        def apply_all(x):
            for s in range(P_):
                x = stage(params[0, s], x)
            return x
        return jnp.mean(jnp.square(jax.vmap(apply_all)(mbs)))

    def analyze(name, fn, *a):
        c = jax.jit(jax.value_and_grad(fn)).lower(*a).compile()
        cost = c.cost_analysis()
        mem = c.memory_analysis()
        fl = float(cost.get("flops", float("nan")))
        print(f"{name:34s} flops {fl/1e9:8.2f} G   "
              f"temp {mem.temp_size_in_bytes/2**20:8.1f} MiB")
        return fl, mem.temp_size_in_bytes

    print(f"pp={P_} M={M} V=1 layers={L} hidden={E} mb={mb} seq={S}")
    fl_flat, _ = analyze("unpartitioned fwd+bwd",
                         lambda p: flat_loss(p, mbs), params)
    # FLOPs need the tick scan UNROLLED (cost_analysis prices a scan body
    # once); memory uses the production rolled form
    fl_pipe, _ = analyze("pipeline fwd+bwd (unrolled ticks)",
                         lambda p: pipe_loss(p, mbs, False, True), params)
    _, tmp_pipe = analyze("pipeline fwd+bwd",
                          lambda p: pipe_loss(p, mbs, False, 1), params)
    _, tmp_remat = analyze("pipeline fwd+bwd (remat_stage)",
                           lambda p: pipe_loss(p, mbs, True, 1), params)
    pred = (M + P_ - 1) / M
    # fl_pipe is PER-DEVICE; the flat program runs the whole model on one
    # device, so total pipeline work = P x per-device. NOTE: static
    # cost_analysis prices a lax.cond's branches whether or not they run,
    # so with skip_bubbles this ratio is an UPPER bound — the executed
    # ratio is measured by the wall-clock A/B below.
    print(f"\nbubble-FLOP ratio pipeline/flat (static): "
          f"{P_ * fl_pipe / fl_flat:.3f}  "
          f"(mask-only predicted (M+P-1)/M = {pred:.3f})")
    print(f"activation temp: naive {tmp_pipe/2**20:.1f} MiB -> remat "
          f"{tmp_remat/2**20:.1f} MiB "
          f"({tmp_pipe / max(tmp_remat, 1):.2f}x reduction)")

    # --- true 1F1B: memory/FLOPs vs the grad-through-scan forms ---
    from apex1_tpu.transformer.pipeline_parallel.schedules import (
        one_f_one_b)

    def fb_1f1b(params, mbs):
        def loss_mb(y, m):
            return jnp.mean(jnp.square(y)) / M

        def inner(params, mbs):
            loss, grads, dmb = one_f_one_b(stage, params[0, 0], mbs,
                                           loss_mb)
            return jax.lax.psum(loss, "pp"), grads[None, None], dmb

        return jax.shard_map(inner, mesh=mesh,
                             in_specs=(Ps(None, "pp"), Ps()),
                             out_specs=(Ps(), Ps(None, "pp"), Ps()),
                             check_vma=False)(params, mbs)

    c = jax.jit(fb_1f1b).lower(params, mbs).compile()
    mem = c.memory_analysis()
    print(f"{'true 1F1B (one_f_one_b)':34s} flops      n/a   "
          f"temp {mem.temp_size_in_bytes/2**20:8.1f} MiB   "
          f"(ring: P x activations, no recompute)")

    # --- bubble-skip A/B: does the lax.cond actually elide the compute? ---
    import time

    def timed(fn, *a, iters=5):
        c = jax.jit(jax.value_and_grad(fn)).lower(*a).compile()
        hlo = c.as_text()
        has_cond = " conditional(" in hlo or "conditional." in hlo
        jax.block_until_ready(c(*a))  # warmup
        t0 = time.perf_counter()
        for _ in range(iters):
            out = c(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters, has_cond

    t_skip, cond_in_hlo = timed(
        lambda p: pipe_loss(p, mbs, False, 1, skip=True), params)
    t_mask, _ = timed(
        lambda p: pipe_loss(p, mbs, False, 1, skip=False), params)
    # ideal executed-tick ratio: mask runs T=M+P-1 stage ticks, skip runs M
    print(f"\nbubble-skip wall-clock A/B (fwd+bwd, rolled scan): "
          f"mask {t_mask*1e3:.1f} ms -> cond-skip {t_skip*1e3:.1f} ms "
          f"({t_mask/t_skip:.3f}x; ideal {(M+P_-1)/M:.3f}x), "
          f"HLO conditional present: {cond_in_hlo}")


if __name__ == "__main__":
    main()
