"""A/B: fused vs decomposed vs serialized communication at the two
fused-comm-kernel sites (`ops.fused_collective`) — the wall-clock form
of what hlo_probe pins structurally and predict_perf's fused comms term
prices analytically.

Legs, timed fwd+bwd over a tp/cp ring:

1. **SP boundary MLP** (column+row parallel linear at the Megatron-SP
   boundary): ``monolithic`` (gather-region + dot / dot +
   reduce-scatter-region — the legacy path), ``decomposed`` (PR 4's
   chunk-pipelined `mappings` rings, ``overlap=True``), ``fused``
   (`fused_all_gather_matmul` + `fused_matmul_reduce_scatter`: same
   ring, per-chunk dot in the Pallas chunk kernel), and ``serialized``
   (`fused_all_gather_matmul_serial`, the rotate-then-dot floor).
2. **ring attention** at the llama_longctx shape: `ring_attention`
   (decomposed merge) vs `all_gather_flash_attention` (merge fused into
   the kernel epilogue) — fwd+bwd.
3. with ``--rdma`` (accelerator, >= 2 devices): the single-kernel
   `matmul_reduce_scatter_rdma` fwd — the first wall-clock datum for
   the paper-shape kernel (numerics UNVERIFIED until this runs; the
   tool also checks its output against the ppermute form and reports
   the max abs diff in the record — the hardware-window parity drill).

Device requirements: a ring needs >= 2 devices; a single chip emits
a skip record. With JAX_PLATFORMS=cpu the 8-device virtual mesh builds
and shapes shrink (command-line rehearsal; timings meaningless,
plumbing validated).

Usage: python tools/bench_fused_comm.py [--n N] [--iters K] [--rdma]
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=None,
                    help="ring size (default: all available devices)")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--rdma", action="store_true",
                    help="also time + parity-check the single-kernel "
                         "RDMA reduce-scatter (accelerator only)")
    args = ap.parse_args()

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

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from apex1_tpu.core.mesh import make_mesh
    from apex1_tpu.ops import fused_collective as fc
    from apex1_tpu.parallel.ring_attention import ring_attention
    from apex1_tpu.transformer import tensor_parallel as tp

    backend = jax.default_backend()
    devices = jax.devices()
    n = args.n or min(len(devices), 8)
    if n < 2:
        _emit({"metric": f"fused_comm_ab [{backend}]", "value": 0.0,
               "error": f"ring needs >= 2 devices, have {len(devices)} "
                        f"— skipped (multichip window required)"})
        return
    accel = backend not in ("cpu",)
    if accel:
        S, hid, ffn = 8192, 2048, 8192
        B, Hq, Hkv, Sa, D = 1, 32, 4, 16384, 64
        iters = args.iters or 8
        dtype = jnp.bfloat16
    else:
        S, hid, ffn = 64, 16, 32
        B, Hq, Hkv, Sa, D = 1, 4, 2, 128, 16
        iters = args.iters or 2
        dtype = jnp.float32
    mesh = make_mesh(tp=n, dp=1, devices=devices[:n])
    rng = np.random.default_rng(0)
    rc = 0

    def timed(make_loss, arrs, in_specs, name):
        """fwd+bwd iters in one dispatch (bench.py methodology); each
        iteration feeds the previous gradient back so the body is not
        loop-invariant."""
        sm = jax.shard_map(make_loss, mesh=mesh, in_specs=in_specs,
                           out_specs=P(), check_vma=False)

        def loss(*a):
            return sm(*a).sum()

        grad = jax.grad(loss, argnums=0)

        def many(*a):
            def one(x):
                g = grad(x, *a[1:])
                return (x + (1e-6 * g).astype(x.dtype),
                        jnp.sum(g.astype(jnp.float32)))

            def body(_, carry):
                return one(carry[0])

            return jax.lax.fori_loop(0, iters - 1, body, one(a[0]))

        compiled = jax.jit(many).lower(*arrs).compile()
        out = compiled(*arrs)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = compiled(*arrs)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters
        if not math.isfinite(float(out[1])):
            raise RuntimeError(f"{name}: non-finite check value")
        return dt

    # ---- leg 1: SP boundary MLP (GLOBAL arrays; shard_map shards) ----
    x = jnp.asarray(rng.normal(size=(S, hid)), dtype)
    w1 = jnp.asarray(rng.normal(size=(hid, ffn)) * 0.02, dtype)
    w2 = jnp.asarray(rng.normal(size=(ffn, hid)) * 0.02, dtype)
    mlp_specs = (P("tp", None), P(None, "tp"), P("tp", None))

    def mlp(col_kw, row_kw):
        def run(x, w1, w2):
            h = tp.column_parallel_linear(
                x, w1, sequence_parallel_enabled=True, axis_name="tp",
                **col_kw)
            h = jax.nn.gelu(h)
            y = tp.row_parallel_linear(
                h, w2, sequence_parallel_enabled=True, axis_name="tp",
                **row_kw)
            return jnp.sum(y.astype(jnp.float32) ** 2)
        return run

    def serial_mlp(x, w1, w2):
        h = fc.fused_all_gather_matmul_serial(x, w1, "tp", 0)
        h = jax.nn.gelu(h.astype(x.dtype))
        y = tp.row_parallel_linear(
            h, w2, sequence_parallel_enabled=True, axis_name="tp")
        return jnp.sum(y.astype(jnp.float32) ** 2)

    try:
        legs = {
            "monolithic": mlp({}, {}),
            "decomposed": mlp(dict(overlap=True), dict(overlap=True)),
            "fused": mlp(dict(fused=True), dict(fused=True)),
            "serialized": serial_mlp,
        }
        times = {k: timed(f, (x, w1, w2), mlp_specs, k)
                 for k, f in legs.items()}
        _emit({
            "metric": f"fused_comm_ab sp_mlp fwd+bwd tp={n} S={S} "
                      f"[{backend}]",
            "value": round(times["monolithic"] / times["fused"], 4),
            "unit": "x (monolithic/fused step time)",
            **{f"{k}_ms": round(v * 1e3, 3) for k, v in times.items()},
            "shape": {"S": S, "hid": hid, "ffn": ffn, "tp": n,
                      "iters": iters},
        })
    except Exception as e:
        _emit({"metric": f"fused_comm_ab sp_mlp [{backend}]",
               "value": 0.0,
               "error": f"{type(e).__name__}: {str(e)[:300]}"})
        rc = 1

    # ---- leg 2: ring attention, merge in the kernel epilogue ---------
    q = jnp.asarray(rng.normal(size=(B, Hq, Sa, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, Sa, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, Sa, D)), dtype)
    aspec = (P(None, None, "tp", None),) * 3

    try:
        def ring_loss(q, k, v):
            return jnp.sum(ring_attention(
                q, k, v, "tp", causal=True).astype(jnp.float32) ** 2)

        def agf_loss(q, k, v):
            return jnp.sum(fc.all_gather_flash_attention(
                q, k, v, "tp", causal=True).astype(jnp.float32) ** 2)

        t_ring = timed(ring_loss, (q, k, v), aspec, "ring")
        t_agf = timed(agf_loss, (q, k, v), aspec, "agf")
        _emit({
            "metric": f"fused_comm_ab attn fwd+bwd cp={n} S={Sa} "
                      f"[{backend}]",
            "value": round(t_ring / t_agf, 4),
            "unit": "x (decomposed-merge/fused-merge step time)",
            "ring_ms": round(t_ring * 1e3, 3),
            "fused_ms": round(t_agf * 1e3, 3),
            "shape": {"B": B, "Hq": Hq, "Hkv": Hkv, "S": Sa, "D": D,
                      "cp": n, "iters": iters},
        })
    except Exception as e:
        _emit({"metric": f"fused_comm_ab attn [{backend}]", "value": 0.0,
               "error": f"{type(e).__name__}: {str(e)[:300]}"})
        rc = 1

    # ---- leg 3 (opt-in, accelerator): the RDMA kernel ----------------
    if args.rdma:
        if not accel:
            _emit({"metric": "fused_comm_ab rdma [cpu]", "value": 0.0,
                   "error": "rdma kernel is compiled-TPU only — "
                            "skipped on cpu rehearsal"})
        else:
            try:
                # gate-verified VMEM frame (see matmul_reduce_scatter_
                # rdma docstring): chunk=256, per-shard K=1024, N=512
                Sr, Kr, Nr = 256 * n, 1024 * n, 512
                xr = jnp.asarray(rng.normal(size=(Sr, Kr)), dtype)
                wr = jnp.asarray(rng.normal(size=(Kr, Nr)) * 0.02,
                                 dtype)
                rspec = (P(None, "tp"), P("tp", None))

                def run_rdma(x, w):
                    return fc.matmul_reduce_scatter_rdma(x, w, "tp")

                def run_ring(x, w):
                    return fc.fused_matmul_reduce_scatter(x, w, "tp", 0)

                outs = {}
                ts = {}
                for nm, f in (("rdma", run_rdma), ("ring", run_ring)):
                    sm = jax.shard_map(f, mesh=mesh, in_specs=rspec,
                                       out_specs=P("tp", None),
                                       check_vma=False)
                    compiled = jax.jit(sm).lower(xr, wr).compile()
                    o = compiled(xr, wr)
                    jax.block_until_ready(o)
                    t0 = time.perf_counter()
                    for _ in range(iters):
                        o = compiled(xr, wr)
                    jax.block_until_ready(o)
                    ts[nm] = (time.perf_counter() - t0) / iters
                    outs[nm] = np.asarray(o, np.float32)
                # THE hardware parity drill: first execution evidence
                # for the RDMA kernel's numerics
                maxdiff = float(np.abs(outs["rdma"] - outs["ring"]).max())
                _emit({
                    "metric": f"fused_comm_ab rdma_mrs fwd tp={n} "
                              f"[{backend}]",
                    "value": round(ts["ring"] / ts["rdma"], 4),
                    "unit": "x (ppermute-ring/rdma-kernel time)",
                    "rdma_ms": round(ts["rdma"] * 1e3, 3),
                    "ring_ms": round(ts["ring"] * 1e3, 3),
                    "max_abs_diff_vs_ring": maxdiff,
                    "shape": {"S": Sr, "K": Kr // n, "N": Nr,
                              "tp": n},
                })
            except Exception as e:
                _emit({"metric": f"fused_comm_ab rdma [{backend}]",
                       "value": 0.0,
                       "error": f"{type(e).__name__}: {str(e)[:300]}"})
                rc = 1

    sys.exit(rc)


if __name__ == "__main__":
    main()
