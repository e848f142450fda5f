"""AOT compile-check the Pallas kernels AND the full bench train steps
for a real TPU target WITHOUT hardware: libtpu's compile-only PJRT
topology client lowers through Mosaic exactly as a real chip would, so
kernel lowering errors, VMEM exhaustion, and whole-step HBM overflow
surface here instead of in the driver's benchmark run.

Usage: python tools/aot_check.py [--topology v5e:2x2]
        [--kernels] [--steps] [--collectives]   (default: all three)

- Kernel checks shard the batch over a dp mesh (Mosaic kernels are not
  auto-partitionable), sized so PER-DEVICE shapes equal the single-chip
  bench shapes.
- Step checks compile `tools/aot_steps.py`'s train and decode steps
  single-device with donated state and report the HBM breakdown.
- Collectives checks compile the distributed shard_map programs (ring
  attention, Ulysses, MoE double-all_to_all, scan+ppermute pipeline)
  against the multi-chip topology — ICI collective lowering + Mosaic
  in one program.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# persistent cache: repeated AOT gates on this single-core box are
# compile-dominated; cached Mosaic/XLA artifacts make re-runs cheap
from apex1_tpu.testing import (  # noqa: E402
    enable_persistent_compilation_cache)

enable_persistent_compilation_cache()


def _gen_from_topology(topology: str) -> str:
    return topology.split(":")[0]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--kernels", action="store_true")
    ap.add_argument("--steps", action="store_true")
    ap.add_argument("--collectives", action="store_true")
    ap.add_argument("--flagship", action="store_true",
                    help="Llama-3-8B dp x pp x tp train step at v5p-32 "
                         "scale (BASELINE config 4)")
    ap.add_argument("--flagship-topology", default="v5p:2x2x4")
    args = ap.parse_args()
    if not (args.kernels or args.steps or args.collectives
            or args.flagship):
        args.kernels = args.steps = args.collectives = True
        args.flagship = True

    # Make dispatch pick the REAL (non-interpret) Pallas path, and block
    # planning match the target chip (named explicitly: none is attached).
    import apex1_tpu.ops._common as _common
    from apex1_tpu.core import capability as _cap
    _common.on_tpu = lambda: True          # use_pallas() -> True
    _common.interpret_mode = lambda: False  # real Mosaic lowering
    with _cap.target_generation(_gen_from_topology(args.topology)):
        ok = _run_checks(args)
    print("ALL OK" if ok else "FAILURES PRESENT", flush=True)
    sys.exit(0 if ok else 1)


def _run_checks(args) -> bool:
    from apex1_tpu.core import capability as _cap

    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from apex1_tpu.ops import force_impl

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.topology)
    n = len(topo.devices)
    mesh = Mesh(np.array(topo.devices).reshape(n), ("dp",))
    ok = True

    def abstract(tree, sharding):
        """ShapeDtypeStructs placed on the target (arrays or structs)."""
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    # chip_smoke.py's own programs are gated below (Engine executables
    # under --steps, the DDP step under --collectives)
    import chip_smoke
    smoke_model = chip_smoke.make_model()

    # Verify the patches reach the DISPATCH THE KERNELS USE (they import
    # interpret_mode/on_tpu by reference; a refactor that snapshots the
    # mode at import would silently AOT-check the interpreter instead of
    # Mosaic): a Mosaic lowering must contain a tpu_custom_call.
    from apex1_tpu.ops import layer_norm as _ln
    _s1 = SingleDeviceSharding(topo.devices[0])
    _txt = jax.jit(
        lambda x: _ln(x, jnp.ones((128,), jnp.float32),
                      jnp.zeros((128,), jnp.float32))).lower(
        jax.ShapeDtypeStruct((8, 128), jnp.float32,
                             sharding=_s1)).as_text()
    assert "tpu_custom_call" in _txt or "mosaic" in _txt.lower(), (
        "Pallas dispatch is NOT taking the Mosaic path — aot_check "
        "results would be meaningless")

    def report(name, lower_fn):
        nonlocal ok
        try:
            mem = lower_fn().compile().memory_analysis()
            tmp = mem.temp_size_in_bytes / 2**30
            arg = mem.argument_size_in_bytes / 2**30
            print(f"  OK   {name:48s} temp {tmp:6.2f} GiB  "
                  f"args {arg:6.2f} GiB", flush=True)
        except Exception as e:
            ok = False
            print(f"  FAIL {name}: {type(e).__name__}: {str(e)[:300]}",
                  flush=True)

    def check(name, fn, shapes, *, dtypes=jnp.bfloat16, in_specs=None,
              grad=False):
        """Kernel check: shapes are PER-DEVICE; sharded dims scale by n."""
        if not isinstance(dtypes, (tuple, list)):
            dtypes = [dtypes] * len(shapes)
        in_specs = in_specs or (P("dp"),) * len(shapes)
        # global shape = per-device shape scaled along the sharded dim
        def gshape(shp, spec):
            if spec == P():
                return shp
            return (shp[0] * n,) + tuple(shp[1:])
        arrs = [jax.ShapeDtypeStruct(
                    gshape(shp, spec), dt,
                    sharding=NamedSharding(mesh, spec))
                for shp, dt, spec in zip(shapes, dtypes, in_specs)]

        def run():
            def local(*xs):
                with force_impl("pallas"):
                    out = fn(*xs)
                return out

            if grad:
                base = local

                def local(*xs):  # noqa: F811
                    fi = tuple(i for i, x in enumerate(xs)
                               if jnp.issubdtype(x.dtype, jnp.floating))
                    return jax.grad(
                        lambda *a: jnp.sum(base(*a).astype(jnp.float32)),
                        argnums=fi)(*xs)

            out_specs = jax.tree_util.tree_map(
                lambda _: P("dp"), jax.eval_shape(local, *arrs))
            smapped = jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                                    out_specs=out_specs, check_vma=False)
            return jax.jit(smapped).lower(*arrs)

        report(name, run)

    if args.kernels:
        print(f"== Pallas kernels (per-device = bench shapes), "
              f"{args.topology} ==", flush=True)
        from apex1_tpu.ops import (layer_norm, rms_norm,
                                   scaled_upper_triang_masked_softmax,
                                   softmax_cross_entropy_loss)
        from apex1_tpu.ops.attention import flash_attention, fmha
        from apex1_tpu.ops.linear_xent import linear_cross_entropy
        from apex1_tpu.ops.rope import apply_rotary_pos_emb, rope_tables

        fa = lambda q, k, v: flash_attention(q, k, v, causal=True)
        for nm, shp in (("flash gpt2 B16 (16,12,1024,64)",
                         (16, 12, 1024, 64)),
                        ("flash longctx (1,32,16384,64)",
                         (1, 32, 16384, 64))):
            check(f"{nm} fwd", fa, [shp] * 3)
            check(f"{nm} fwd+bwd", fa, [shp] * 3, grad=True)
        # the ROWS layout (PR 41): the packed array as the qkv product
        # leaves it, two heads of 64 to a 128-lane block (GPT-2 medium's
        # call), with and without the in-kernel dropout
        for nm, fn in (("", lambda x: fmha(x, causal=True)),
                       (" dropout p=0.1", lambda x: fmha(
                           x, causal=True, dropout_p=0.1,
                           dropout_seed=1234))):
            check(f"fmha rows gpt2m (8,1024,3,16,64){nm} fwd+bwd", fn,
                  [(8, 1024, 3, 16, 64)], grad=True)
        # GQA (Hq/Hkv = 8): the dkv kernel accumulates the group in VMEM
        # and writes Hkv-sized fp32 outputs — temp must stay near the
        # group=1 case, not 8x it
        gq, gkv = (1, 32, 16384, 64), (1, 4, 16384, 64)
        check("flash longctx GQA (Hq32/Hkv4,16k,64) fwd", fa,
              [gq, gkv, gkv])
        check("flash longctx GQA (Hq32/Hkv4,16k,64) fwd+bwd", fa,
              [gq, gkv, gkv], grad=True)
        # additive-bias flash (T5 rel-pos path): dbias rides the extra
        # broadcast-accumulating backward pass — bias replicated (head
        # bias shared across the dp shards)
        fab = lambda q, k, v, b: flash_attention(q, k, v, bias=b)
        bshp = (2, 8, 1024, 64)
        check("flash bias T5-ish (2,8,1024,64) fwd+bwd", fab,
              [bshp, bshp, bshp, (1, 8, 1024, 1024)],
              in_specs=(P("dp"), P("dp"), P("dp"), P()), grad=True)
        # in-kernel probability dropout (bert-pretrain config: attention
        # dropout 0.1): the Mosaic gate for pltpu.prng_seed/random_bits
        # in all three kernels — tier-1 only exercises the interpret-
        # mode hash path, so THIS is the real TPU guard (same standing-
        # risk shape as the ring collectives gate)
        fad = lambda q, k, v: flash_attention(
            q, k, v, causal=True, dropout_p=0.1, dropout_seed=1234)
        dshp = (8, 12, 512, 64)
        check("flash dropout p=0.1 (8,12,512,64) fwd", fad, [dshp] * 3)
        check("flash dropout p=0.1 (8,12,512,64) fwd+bwd", fad,
              [dshp] * 3, grad=True)
        check("flash dropout longctx (1,32,16384,64) fwd+bwd", fad,
              [(1, 32, 16384, 64)] * 3, grad=True)
        from apex1_tpu.ops import fused_bias_dropout_add
        check("bias_dropout_add (16384,1024) fwd+bwd",
              lambda x, r, b: fused_bias_dropout_add(
                  x, r, bias=b, p=0.1, seed=42),
              [(16384, 1024), (16384, 1024), (1024,)],
              dtypes=[jnp.bfloat16, jnp.bfloat16, jnp.float32],
              in_specs=(P("dp"), P("dp"), P()), grad=True)

        T, Hid, V = 16 * 1023, 768, 50432
        check(f"linear_xent gpt2 ({T},{Hid},{V}) fwd+bwd",
              lambda x, w: linear_cross_entropy(
                  x, w, jnp.zeros((x.shape[0],), jnp.int32),
                  num_classes=V - 200),
              [(T, Hid), (V, Hid)], in_specs=(P("dp"), P()), grad=True)

        g = jnp.ones((768,), jnp.float32)
        check("layer_norm (16384,768) fwd+bwd",
              lambda x: layer_norm(x, g, jnp.zeros_like(g)),
              [(16384, 768)], grad=True)
        check("rms_norm (16384,2048) fwd+bwd",
              lambda x: rms_norm(x, jnp.ones((2048,), jnp.float32)),
              [(16384, 2048)], grad=True)
        check("causal softmax (16,12,1024,1024) fwd+bwd",
              lambda x: scaled_upper_triang_masked_softmax(x, scale=0.125),
              [(16, 12, 1024, 1024)], dtypes=jnp.float32, grad=True)
        check("xentropy (16368,50432) fwd+bwd",
              lambda x: softmax_cross_entropy_loss(
                  x, jnp.zeros((x.shape[0],), jnp.int32),
                  num_classes=50257),
              [(16368, 50432)], dtypes=jnp.float32, grad=True)
        cos, sin = rope_tables(jnp.arange(16384), 64)
        check("rope llama (1,16384,32,64) fwd+bwd",
              lambda x: apply_rotary_pos_emb(x, cos, sin),
              [(1, 16384, 32, 64)], grad=True)
        # int8 weight-only decode GEMM (dequant fused in VMEM): decode-row
        # x against a llama-head-sized weight; weight+scale replicated
        from apex1_tpu.ops import int8_matmul
        check("int8 matmul decode (8,4096)x(32000,4096) fwd",
              lambda x, wq, s: int8_matmul(x, wq, s),
              [(8, 4096), (32000, 4096), (32000,)],
              dtypes=[jnp.bfloat16, jnp.int8, jnp.float32],
              in_specs=(P("dp"), P(), P()))

        # paged ragged decode attention + fused sampling epilogue
        # (ISSUE 18): the serving engine's paged decode step at real
        # engine shapes, BOTH cache tiers (int8 dequant fused in-kernel
        # and bf16). `check_paged_geometry` runs at trace time against
        # the registry-shared vmem model, so an unregistered/unfittable
        # page geometry fails THIS gate loudly — the kernel path never
        # silently falls back to the composite.
        from apex1_tpu.ops.paged_decode import (check_paged_geometry,
                                                fused_sample,
                                                paged_attend)

        # llama-head decode rows (Hq32/Hkv8 GQA, D=128) over a
        # 2048-token lane at page 16 -> T=128 pages per block-table row;
        # the page pool is pool-wide state (replicated), rows shard dp
        N_s, Hq_s, Hkv_s, D_s, P_s = 8, 32, 8, 128, 16
        T_s = 2048 // P_s
        n_pg = 1 + N_s * T_s
        pa = lambda q, kp, vp, bt, ln: paged_attend(q, kp, vp, bt, ln)
        pv = lambda q, kp, vp, bt, ln: paged_attend(
            q, kp, vp, bt, ln, total_len=T_s * P_s)
        for tier, cdt in (("int8", jnp.int8), ("bf16", jnp.bfloat16)):
            check(f"paged_attend decode {tier} "
                  f"(8,Hq32/Hkv8,D128,page16,T128)", pa,
                  [(N_s, Hq_s, 1, D_s), (n_pg, Hkv_s, P_s, D_s),
                   (n_pg, Hkv_s, P_s, D_s), (N_s, T_s), (N_s,)],
                  dtypes=[jnp.bfloat16, cdt, cdt, jnp.int32, jnp.int32],
                  in_specs=(P("dp"), P(), P(), P("dp"), P("dp")))
            # the speculative verify row class: S = K+1 = 5 queries per
            # slot through the same pages
            check(f"paged_attend verify {tier} (8,Hq32/Hkv8,S5)", pv,
                  [(N_s, Hq_s, 5, D_s), (n_pg, Hkv_s, P_s, D_s),
                   (n_pg, Hkv_s, P_s, D_s), (N_s, T_s), (N_s,)],
                  dtypes=[jnp.bfloat16, cdt, cdt, jnp.int32, jnp.int32],
                  in_specs=(P("dp"), P(), P(), P("dp"), P("dp")))
        for tag, kw in (("greedy", dict(temperature=0.0)),
                        ("T0.7", dict(temperature=0.7))):
            check(f"fused_sample epilogue {tag} (8,50432)",
                  lambda lg, s, p, kw=kw: fused_sample(
                      lg, s, p, vocab_size=50257, **kw),
                  [(N_s, 50432), (N_s,), (N_s,)],
                  dtypes=[jnp.float32, jnp.int32, jnp.int32],
                  in_specs=(P("dp"), P("dp"), P("dp")))
        # the loud-failure half of the contract: a sublane-misaligned
        # page and an over-budget page must RAISE at trace time, never
        # fall back
        for bad in (12, 1 << 20):
            try:
                check_paged_geometry(bad, D_s, Hq_s // Hkv_s, 1)
            except ValueError as e:
                print(f"  OK   paged geometry page={bad:>7} raises: "
                      f"{str(e)[:60]}", flush=True)
            else:
                ok = False
                print(f"  FAIL paged geometry gate: page={bad} must "
                      f"raise ValueError", flush=True)

        # the dense pool's step attention (PR 29): append + ragged read
        # in one kernel over leaves stored (slots, positions, Hkv * D),
        # at the `gpt2m_serve_chat` cell's shapes (48 slots x 1152 x 16
        # heads of 64, decode and a K=4 verify) and at llama-head rows
        # (GQA 32/8, D=128) in both cache tiers, and at the rows of 512
        # lanes of `granite4hm_serve_chat` (48 x 1280) and
        # `lfm2moe_serve_rollout` (96 x 2816), whose queue of fetches is
        # twice as deep as GPT-2's (PR 50: `fetch_depth`, 8 against 4).
        # `check_decode_geometry` runs at trace time against the
        # registry-shared vmem model, the queue's buffers in its frame.
        from apex1_tpu.ops.decode_attend import (check_decode_geometry,
                                                 decode_attend)
        for tag, (B_d, Hq_d, Hkv_d, D_d, L_d), tiers in (
                ("gpt2m chat cell", (48, 16, 16, 64, 1152),
                 (("bf16", jnp.bfloat16),)),
                ("llama heads", (8, 32, 8, 128, 2048),
                 (("bf16", jnp.bfloat16), ("int8", jnp.int8))),
                ("granite4hm chat cell", (48, 32, 8, 64, 1280),
                 (("bf16", jnp.bfloat16),)),
                ("lfm2moe rollout cell", (96, 32, 8, 64, 2816),
                 (("bf16", jnp.bfloat16),))):
            for tier, cdt in tiers:
                for S_d in (1, 5):
                    check(f"decode_attend {tag} {tier} S={S_d} "
                          f"({B_d},Hq{Hq_d}/Hkv{Hkv_d},D{D_d},L{L_d})",
                          lambda q, kn, vn, kp, vp, ix: decode_attend(
                              q, kn, vn, kp, vp, ix),
                          [(B_d, Hq_d, S_d, D_d), (B_d, Hkv_d, S_d, D_d),
                           (B_d, Hkv_d, S_d, D_d), (B_d, L_d, Hkv_d * D_d),
                           (B_d, L_d, Hkv_d * D_d), (B_d,)],
                          dtypes=[jnp.bfloat16, jnp.bfloat16, jnp.bfloat16,
                                  cdt, cdt, jnp.int32])
        # a WINDOW over a RING (PR 48), at the `trinitymini_serve_longctx`
        # cell's shapes: 16 lanes, rows of 512 lanes (GQA 32/4, D=128),
        # the sliding layers' rings of 2304 rows under a window of 2048
        # and, without a window, the global layers' leaves of 8960
        for tag, L_w, kw in (("ring 2304 window 2048", 2304,
                              {"window": 2048}),
                             ("global leaf 8960", 8960, {})):
            check(f"decode_attend trinity-mini cell {tag} "
                  f"(16,Hq32/Hkv4,D128,L{L_w})",
                  lambda q, kn, vn, kp, vp, ix, kw=kw: decode_attend(
                      q, kn, vn, kp, vp, ix, **kw),
                  [(16, 32, 1, 128), (16, 4, 1, 128), (16, 4, 1, 128),
                   (16, L_w, 512), (16, L_w, 512), (16,)],
                  dtypes=[jnp.bfloat16] * 5 + [jnp.int32])
        for bad_len, bad_s in ((1151, 1), (1152, 128)):
            try:
                check_decode_geometry(bad_len, 1024, 16 * bad_s, bad_s,
                                      jnp.bfloat16)
            except ValueError as e:
                print(f"  OK   decode geometry L={bad_len} S={bad_s} "
                      f"raises: {str(e)[:60]}", flush=True)
            else:
                ok = False
                print(f"  FAIL decode geometry gate: L={bad_len} S={bad_s} "
                      f"must raise ValueError", flush=True)
        try:        # a ring shorter than its window and one call's rows
            check_decode_geometry(2048, 512, 160, 5, jnp.bfloat16, 2046)
        except ValueError as e:
            print(f"  OK   decode geometry ring=2048 window=2046 S=5 "
                  f"raises: {str(e)[:60]}", flush=True)
        else:
            ok = False
            print("  FAIL decode geometry gate: a ring of 2048 under a "
                  "window of 2046 and 5 rows must raise", flush=True)

        # the decode step's state update of a state-space layer (PR 34),
        # at the `granite4hm_serve_chat` cell's shapes: 48 slots of 64
        # heads of 64 by a state of 128, stored two heads a row
        from apex1_tpu.ops.ssm import ssm_step
        check("ssm_step granite chat cell (48,H64,P64,N128)", ssm_step,
              [(48, 64, 64), (48, 64), (64,), (48, 128), (48, 128), (64,),
               (48, 32, 128, 128), (48,)],
              dtypes=[jnp.float32] * 7 + [jnp.int32],
              in_specs=(P("dp"), P("dp"), P(), P("dp"), P("dp"), P(),
                        P("dp"), P("dp")))

        # the grouped expert product of a sparse layer (PR 38), at the
        # `lfm2moe_serve_rollout` cell's shapes: 8 experts of 2048 x 1792
        # held, the decode step's 96 rows x top-4 in their padded frame
        from apex1_tpu.ops.moe_experts import moe_experts, padded_rows
        rows = padded_rows(96 * 4, 8)
        check("moe_experts lfm2 rollout cell (96x4 rows, E8, 2048x1792)",
              moe_experts,
              [(rows, 2048), (rows,), (8, 2048, 1792), (8, 2048, 1792),
               (8, 1792, 2048), (8,), (8,)],
              dtypes=[jnp.bfloat16, jnp.float32] + [jnp.bfloat16] * 3
              + [jnp.int32] * 2,
              in_specs=(P(),) * 7)
        # and at the `trinitymini_serve_longctx` cell's (PR 48): 16 small
        # experts of 2048 x 1024 held, the step's 16 rows x top-8 and a
        # prefill chunk's 256 x top-8
        for tag, n_rows in (("step 16x8 rows", 16 * 8),
                            ("prefill chunk 256x8 rows", 256 * 8)):
            rows = padded_rows(n_rows, 16)
            check(f"moe_experts trinity-mini cell ({tag}, E16, 2048x1024)",
                  moe_experts,
                  [(rows, 2048), (rows,), (16, 2048, 1024),
                   (16, 2048, 1024), (16, 1024, 2048), (16,), (16,)],
                  dtypes=[jnp.bfloat16, jnp.float32] + [jnp.bfloat16] * 3
                  + [jnp.int32] * 2,
                  in_specs=(P(),) * 7)

        # chunked preference/distill losses, fused GLU, LoRA epilogue
        # (ISSUE 19): the chunked-loss VJP recomputes per vocab chunk
        # through the linear_xent stats kernels; fused_glu is the llama
        # fused_mlp tile; lora_delta is the multi-tenant serving
        # epilogue's scalar-prefetched page gather. Both dtypes — the
        # registry tables price each (kernel, dtype) separately.
        from apex1_tpu.ops.chunked_loss import (check_chunk_geometry,
                                                chunked_logprob)
        from apex1_tpu.ops.fused_dense import (check_glu_geometry,
                                               fused_glu)
        from apex1_tpu.ops.lora_epilogue import (check_lora_geometry,
                                                 lora_delta)

        T_c, H_c, V_c = 8 * 1024, 768, 50432
        R_l, Hd_l, V_l = 8, 4096, 50432
        n_lp = 1 + 4 * R_l
        for dt in (jnp.bfloat16, jnp.float32):
            tag = jnp.dtype(dt).name
            check(f"chunked_logprob gpt2 ({T_c},{H_c},{V_c}) cv8192 "
                  f"{tag} fwd+bwd",
                  lambda x, w: chunked_logprob(
                      x, w, jnp.zeros((x.shape[0],), jnp.int32),
                      chunk_v=8192, num_classes=V_c - 200),
                  [(T_c, H_c), (V_c, H_c)], dtypes=dt,
                  in_specs=(P("dp"), P()), grad=True)
            check(f"fused_glu llama mlp (8192,4096,14336) {tag} "
                  f"fwd+bwd", fused_glu,
                  [(8192, 4096), (4096, 14336), (4096, 14336)],
                  dtypes=dt, in_specs=(P("dp"), P(), P()), grad=True)
            check(f"lora_delta epilogue (8,H4096,V50432,r8) {tag}",
                  lora_delta,
                  [(8, Hd_l), (n_lp, Hd_l), (n_lp, V_l), (8, R_l)],
                  dtypes=[dt, jnp.float32, jnp.float32, jnp.int32],
                  in_specs=(P("dp"), P(), P(), P("dp")))
        # loud-failure half: misaligned and over-budget geometries for
        # all three new kernels must RAISE at trace time
        for nm, bad_fn in (
                ("chunk_v=100 misaligned",
                 lambda: check_chunk_geometry(100, 768)),
                ("chunk_v=1<<24 over-budget",
                 lambda: check_chunk_geometry(1 << 24, 8192)),
                ("glu block_t=7 misaligned",
                 lambda: check_glu_geometry(7, 128, 4096)),
                ("glu block_f=1<<16 over-budget",
                 lambda: check_glu_geometry(512, 1 << 16, 8192)),
                ("lora block_v=100 misaligned",
                 lambda: check_lora_geometry(8, 4096, 50432, 100)),
                ("lora block_v=1<<20 over-budget",
                 lambda: check_lora_geometry(8, 8192, 50432, 1 << 20))):
            try:
                bad_fn()
            except ValueError as e:
                print(f"  OK   geometry {nm} raises: {str(e)[:60]}",
                      flush=True)
            else:
                ok = False
                print(f"  FAIL geometry gate: {nm} must raise "
                      f"ValueError", flush=True)

    if args.steps:
        print(f"== full bench train steps (single device, "
              f"tools/aot_steps.py), {args.topology} ==", flush=True)
        import aot_steps

        s1 = SingleDeviceSharding(topo.devices[0])

        def to_shape(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                               jnp.asarray(x).dtype,
                                               sharding=s1), tree)

        for cfg_name in sorted(aot_steps.BENCHES):
            def run(cfg_name=cfg_name):
                state, step, batch, *_ = aot_steps.BENCHES[cfg_name](True)
                return jax.jit(step, donate_argnums=0).lower(
                    to_shape(state), *to_shape(batch))

            report(f"bench step [{cfg_name}]", run)

        # speculative decoding: the vmap-of-while + chunk-verify program
        # is the one control-flow construct no bench config exercises —
        # prove it lowers for the real target (small model, real K)
        def run_spec():
            import functools

            from apex1_tpu.core.policy import get_policy
            from apex1_tpu.models.generate import (llama_decoder,
                                                   speculative_generate)
            from apex1_tpu.models.llama import Llama, LlamaConfig

            cfg_t = LlamaConfig.tiny(policy=get_policy("O2"),
                                     max_seq_len=128, num_layers=4,
                                     hidden_size=256, ffn_size=512,
                                     vocab_size=1024)
            cfg_d = LlamaConfig.tiny(policy=get_policy("O2"),
                                     max_seq_len=128, num_layers=1,
                                     hidden_size=128, ffn_size=256,
                                     vocab_size=1024)
            tgt, drf = Llama(cfg_t), Llama(cfg_d)
            prompt = jnp.zeros((4, 16), jnp.int32)
            # init must be jitted: EAGER pallas on the CPU host under
            # the Mosaic patches fails ("only interpret mode on CPU") —
            # same rule the bench builders follow
            pt = jax.jit(tgt.init)(jax.random.key(0), prompt)["params"]
            pd = jax.jit(drf.init)(jax.random.key(1), prompt)["params"]
            t_fn, mk_t = llama_decoder(tgt)
            d_fn, mk_d = llama_decoder(drf)
            N, K = 32, 4
            spec = functools.partial(
                speculative_generate, t_fn, pt, d_fn, pd,
                max_new_tokens=N, num_draft=K, vocab_size=1024)
            return jax.jit(spec).lower(
                to_shape(prompt),
                target_cache=to_shape(mk_t(4, 16 + N + K + 1)),
                draft_cache=to_shape(mk_d(4, 16 + N + K + 1)))

        report("speculative decode [vmap-of-while, chunk-verify]",
               run_spec)

        # chip_smoke.py's serve phase: the two serving.Engine
        # executables for GPT-2 125M at the smoke's own sizes, dense
        # (one batch forward over the donated pool) and paged
        # (paged_attend + fused_sample kernels) — in no other gate, and
        # on the chip the paged form builds a different program than
        # any CPU test runs
        from apex1_tpu.models.generate import gpt2_decoder
        from apex1_tpu.serving.engine import Engine

        smoke_cfg = smoke_model.cfg
        smoke_params = abstract(jax.eval_shape(
            lambda: smoke_cfg.policy.cast_to_compute(
                chip_smoke.init_params(smoke_model))), s1)
        sv = chip_smoke.SERVE
        i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=s1)
        chunk = jax.ShapeDtypeStruct((1, sv["prefill_chunk"]), jnp.int32,
                                     sharding=s1)
        for paged in (False, True):
            eng = Engine(*gpt2_decoder(smoke_model), smoke_params,
                         chip_smoke.engine_config(
                             smoke_cfg, paged=paged,
                             max_slots=sv["max_slots"],
                             max_len=sv["max_len"],
                             prefill_chunk=sv["prefill_chunk"]))
            ctl = abstract((eng._d_toks, eng._d_idxs, eng._d_active,
                            eng._d_seeds, eng._d_pos), s1)
            if paged:
                pool = abstract((eng.kv.pages, eng._d_bt), s1)
                pre = (*pool, i32, chunk, i32, i32, i32)
            else:
                pool = (abstract(eng.kv.cache, s1),)
                pre = (*pool, i32, abstract(eng.kv.zeros_lane, s1),
                       jax.ShapeDtypeStruct((), jnp.bool_, sharding=s1),
                       chunk, i32, i32, i32)
            tag = "paged" if paged else "dense"
            report(f"engine prefill gpt2-125M [{tag}]",
                   lambda e=eng, a=pre: e._prefill.lower(smoke_params, *a))
            report(f"engine decode gpt2-125M [{tag}]",
                   lambda e=eng, a=(*pool, *ctl): e._decode.lower(
                       smoke_params, *a))

    if args.collectives:
        print(f"== distributed shard_map programs (ICI collectives + "
              f"Mosaic), {args.topology} ==", flush=True)
        from apex1_tpu.core.mesh import make_mesh
        from apex1_tpu.parallel.ring_attention import ring_attention
        from apex1_tpu.parallel.ulysses import ulysses_attention
        from apex1_tpu.transformer.moe import (MoEConfig,
                                               moe_shard_map_apply)
        from apex1_tpu.transformer.pipeline_parallel.schedules import (
            pipeline_apply)

        def coll(name, builder):
            def run():
                f, arrs = builder()
                return jax.jit(f).lower(*arrs)
            report(name, run)

        B, H, S, D = 2, 16, 4096, 128   # S is GLOBAL (sharded over cp=n)
        cp_mesh = make_mesh(cp=n, dp=1, devices=list(topo.devices))

        def mk_attn(kind):
            def builder():
                qs = NamedSharding(cp_mesh, P(None, None, "cp"))
                arrs = [jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16,
                                             sharding=qs)] * 3

                def local(q, k, v):
                    with force_impl("pallas"):
                        if kind == "ring":
                            return ring_attention(q, k, v, "cp",
                                                  causal=True)
                        return ulysses_attention(q, k, v, "cp",
                                                 causal=True)

                # DEFAULT check_vma (True): guards the vma
                # declaration on pallas_call out_shapes (review r5 —
                # with check_vma=False here, the shipped-default
                # config was untraceable and no gate caught it)
                f = jax.shard_map(local, mesh=cp_mesh,
                                  in_specs=(P(None, None, "cp"),) * 3,
                                  out_specs=P(None, None, "cp"))
                return f, arrs
            return builder

        coll(f"ring attention cp={n} (S={S} global)", mk_attn("ring"))
        coll(f"ulysses attention cp={n} (S={S} global)", mk_attn("uly"))

        # --- communication-overlap probes (apex1_tpu.testing.hlo_probe):
        # the double-buffered ring's pinned property — every scan body
        # issues collective-permute-start BEFORE the attention compute
        # and consumes -done AFTER it — asserted on the OPTIMIZED v5e
        # executable text, forward AND backward, with the retained
        # serialized ring as the negative control (the probe must be
        # falsifiable). This AOT gate is the REAL guard for the TPU
        # ring path: on the CPU suite the Pallas ring only executes in
        # interpret mode under check_vma=False (VERDICT r5 Weak #7) —
        # see testing/hlo_probe.py STANDING-RISK NOTE.
        from apex1_tpu.parallel.ring_attention import (ring_attention,
                                                       ring_attention_serial)
        from apex1_tpu.testing.hlo_probe import (assert_collective_overlap,
                                                 check_collective_overlap)

        def probe(name, build_fn, *, expect_fail=False):
            nonlocal ok
            try:
                f, arrs = build_fn()
                txt = jax.jit(f).lower(*arrs).compile().as_text()
                if expect_fail:
                    rep = check_collective_overlap(txt)
                    if rep.ok or not rep.bodies:
                        raise AssertionError(
                            f"negative control must FAIL the probe, got "
                            f"ok={rep.ok} bodies={len(rep.bodies)}")
                    print(f"  OK   {name:48s} FAILS probe as required",
                          flush=True)
                else:
                    rep = assert_collective_overlap(txt,
                                                    expect_mode="async")
                    det = "; ".join(b.detail for b in rep.bodies)
                    print(f"  OK   {name:48s} {det[:70]}", flush=True)
            except Exception as e:
                ok = False
                print(f"  FAIL {name}: {type(e).__name__}: "
                      f"{str(e)[:300]}", flush=True)

        Bp, Hp, Sp, Dp = 1, 4, 4096, 128
        cp_spec = P(None, None, "cp")
        psh = NamedSharding(cp_mesh, cp_spec)
        parrs = [jax.ShapeDtypeStruct((Bp, Hp, Sp, Dp), jnp.bfloat16,
                                      sharding=psh)] * 3

        def ring_fwd_builder():
            def local(q, k, v):
                with force_impl("pallas"):
                    return ring_attention(q, k, v, "cp", causal=True)
            return jax.shard_map(local, mesh=cp_mesh,
                                 in_specs=(cp_spec,) * 3,
                                 out_specs=cp_spec), parrs

        def ring_bwd_builder():
            def local(q, k, v):
                with force_impl("pallas"):
                    return ring_attention(q, k, v, "cp", causal=True)
            sm = jax.shard_map(local, mesh=cp_mesh,
                               in_specs=(cp_spec,) * 3,
                               out_specs=cp_spec)

            def loss(q, k, v):
                return jnp.sum(sm(q, k, v).astype(jnp.float32) ** 2)

            return jax.grad(loss, argnums=(0, 1, 2)), parrs

        def ring_serial_builder():
            def local(q, k, v):
                with force_impl("pallas"):
                    return ring_attention_serial(q, k, v, "cp",
                                                 causal=True)
            return jax.shard_map(local, mesh=cp_mesh,
                                 in_specs=(cp_spec,) * 3,
                                 out_specs=cp_spec), parrs

        probe(f"overlap probe: ring fwd cp={n}", ring_fwd_builder)
        probe(f"overlap probe: ring fwd+bwd cp={n}", ring_bwd_builder)
        probe(f"overlap probe: serialized ring (negative)",
              ring_serial_builder, expect_fail=True)

        # --- fused comm-kernels (PR 9, ops.fused_collective): Mosaic
        # lowering + async overlap probes for the forms tier-1 can only
        # execute in interpret mode. Positive/negative pairs per the
        # probe-falsifiability rule. The RDMA kernel below has NO
        # XLA collective at all — its gate is the compile itself
        # (numerics UNVERIFIED on hardware).
        from apex1_tpu.ops.fused_collective import (
            all_gather_flash_attention, fused_all_gather_matmul,
            fused_all_gather_matmul_serial, fused_matmul_reduce_scatter,
            matmul_reduce_scatter_rdma)

        tp_mesh3 = make_mesh(tp=n, dp=1, devices=list(topo.devices))
        S_f, hid_f, ffn_f = 8192, 2048, 8192
        ns3 = lambda spec: NamedSharding(tp_mesh3, spec)
        fused_arrs = [
            jax.ShapeDtypeStruct((S_f, hid_f), jnp.bfloat16,
                                 sharding=ns3(P("tp"))),
            jax.ShapeDtypeStruct((hid_f, ffn_f), jnp.bfloat16,
                                 sharding=ns3(P(None, "tp"))),
            jax.ShapeDtypeStruct((ffn_f, hid_f), jnp.bfloat16,
                                 sharding=ns3(P("tp", None))),
        ]

        def fused_mlp_builder():
            def local(x, w1, w2):
                with force_impl("pallas"):
                    h = fused_all_gather_matmul(x, w1, "tp", 0)
                    return fused_matmul_reduce_scatter(
                        h.astype(jnp.bfloat16), w2, "tp", 0)

            f = jax.shard_map(
                local, mesh=tp_mesh3,
                in_specs=(P("tp"), P(None, "tp"), P("tp", None)),
                out_specs=P("tp"), check_vma=False)
            return f, fused_arrs

        def fused_serial_builder():
            def local(x, w1):
                with force_impl("pallas"):
                    return fused_all_gather_matmul_serial(x, w1, "tp", 0)

            f = jax.shard_map(
                local, mesh=tp_mesh3,
                in_specs=(P("tp"), P(None, "tp")),
                out_specs=P(None, "tp"), check_vma=False)
            return f, fused_arrs[:2]

        probe(f"overlap probe: fused SP matmuls tp={n}",
              fused_mlp_builder)
        probe("overlap probe: serialized fused AG-matmul (negative)",
              fused_serial_builder, expect_fail=True)

        def agf_builder():
            # the 16k GQA llama_longctx target shape, merge fused into
            # the kernel epilogue
            def local(q, k, v):
                with force_impl("pallas"):
                    return all_gather_flash_attention(q, k, v, "cp",
                                                      causal=True)
            return jax.shard_map(local, mesh=cp_mesh,
                                 in_specs=(cp_spec,) * 3,
                                 out_specs=cp_spec,
                                 check_vma=False), [
                jax.ShapeDtypeStruct((1, 32, 16384, 64), jnp.bfloat16,
                                     sharding=NamedSharding(cp_mesh,
                                                            cp_spec)),
                jax.ShapeDtypeStruct((1, 4, 16384, 64), jnp.bfloat16,
                                     sharding=NamedSharding(cp_mesh,
                                                            cp_spec)),
                jax.ShapeDtypeStruct((1, 4, 16384, 64), jnp.bfloat16,
                                     sharding=NamedSharding(cp_mesh,
                                                            cp_spec))]

        probe(f"overlap probe: fused AG-flash 16k GQA cp={n}",
              agf_builder)

        def agf_bwd_builder():
            f, arrs = agf_builder()

            def loss(q, k, v):
                return jnp.sum(f(q, k, v).astype(jnp.float32) ** 2)

            return jax.grad(loss, argnums=(0, 1, 2)), arrs

        probe(f"overlap probe: fused AG-flash fwd+bwd cp={n}",
              agf_bwd_builder)

        def fused_vp_ce_builder():
            # packed-stat kernel + 2-collective merge, Mosaic-lowered
            from apex1_tpu.transformer.tensor_parallel.cross_entropy \
                import vocab_parallel_linear_cross_entropy
            T, Hd, V = 8192, 2048, 50432

            def local(x, w, t):
                with force_impl("pallas"):
                    return vocab_parallel_linear_cross_entropy(
                        x, w, t, axis_name="tp", fused=True,
                        num_classes=V - 200)

            f = jax.shard_map(local, mesh=tp_mesh3,
                              in_specs=(P(), P("tp", None), P()),
                              out_specs=P(), check_vma=False)
            arrs = [jax.ShapeDtypeStruct((T, Hd), jnp.bfloat16,
                                         sharding=ns3(P())),
                    jax.ShapeDtypeStruct((V, Hd), jnp.bfloat16,
                                         sharding=ns3(P("tp", None))),
                    jax.ShapeDtypeStruct((T,), jnp.int32,
                                         sharding=ns3(P()))]
            return f, arrs

        coll(f"fused vocab-parallel linear CE tp={n} (packed merge)",
             fused_vp_ce_builder)

        def rdma_builder():
            def local(x, w):
                with force_impl("pallas"):
                    return matmul_reduce_scatter_rdma(x, w, "tp")

            f = jax.shard_map(local, mesh=tp_mesh3,
                              in_specs=(P(None, "tp"), P("tp", None)),
                              out_specs=P("tp", None), check_vma=False)
            # per-shard (S=1024, K=1024, N=512): chunk 256 -> frame =
            # 2 send + 2 recv fp32 slots (2 MiB) + double-buffered
            # x/w/out blocks ~ 6 MiB, inside the v5e budget. The
            # kernel's VMEM rule (established BY this gate, now CODE in
            # apex1_tpu.vmem_model.rdma_check — shared with
            # tuning.registry's gating and graftlint's APX208 pass):
            # chunk=512, K=1024, N=1024 measured RESOURCE_EXHAUSTED.
            from apex1_tpu.vmem_model import budget_bytes, rdma_check
            fits, est = rdma_check(
                256, 1024, 512, 2,
                budget_bytes(_gen_from_topology(args.topology)))
            over, _ = rdma_check(512, 1024, 1024, 2,
                                 budget_bytes("v5e"))
            assert fits and not over, (
                "vmem_model.rdma_check disagrees with the gate's "
                "established data points — the shared sizing model "
                f"drifted (fits={fits} est={est} over={over})")
            arrs = [jax.ShapeDtypeStruct((1024, 1024 * n), jnp.bfloat16,
                                         sharding=ns3(P(None, "tp"))),
                    jax.ShapeDtypeStruct((1024 * n, 512), jnp.bfloat16,
                                         sharding=ns3(P("tp", None)))]
            return f, arrs

        coll(f"RDMA matmul->reduce-scatter kernel tp={n} (compile "
             f"gate; numerics await hardware)", rdma_builder)

        def tp_overlap_builder():
            # chunk-pipelined decomposed collective matmuls (the
            # overlap= path of Column/RowParallelLinear under SP)
            from apex1_tpu.transformer.tensor_parallel import mappings
            tp_mesh2 = make_mesh(tp=n, dp=1, devices=list(topo.devices))
            S_l, hid, ffn = 2048, 1024, 4096

            def local(x, w1, w2):
                h = mappings.all_gather_matmul(x, w1, "tp", 0)
                return mappings.matmul_reduce_scatter(
                    h.astype(jnp.bfloat16), w2, "tp", 0)

            f = jax.shard_map(
                local, mesh=tp_mesh2,
                in_specs=(P("tp"), P(None, "tp"), P("tp", None)),
                out_specs=P("tp"), check_vma=False)
            ns = lambda spec: NamedSharding(tp_mesh2, spec)
            arrs = [
                jax.ShapeDtypeStruct((S_l * n, hid), jnp.bfloat16,
                                     sharding=ns(P("tp"))),
                jax.ShapeDtypeStruct((hid, ffn), jnp.bfloat16,
                                     sharding=ns(P(None, "tp"))),
                jax.ShapeDtypeStruct((ffn, hid), jnp.bfloat16,
                                     sharding=ns(P("tp", None))),
            ]
            return f, arrs

        probe(f"overlap probe: decomposed TP matmuls tp={n}",
              tp_overlap_builder)

        def moe_builder():
            ep_mesh = make_mesh(ep=n, dp=1, devices=list(topo.devices))
            cfg = MoEConfig(num_experts=2 * n, top_k=2,
                            capacity_factor=1.25, hidden_size=2048,
                            ffn_size=5632)
            xs = NamedSharding(ep_mesh, P("ep"))
            ws = NamedSharding(ep_mesh, P("ep"))
            arrs = [
                jax.ShapeDtypeStruct((8192 * n, 2048), jnp.bfloat16,
                                     sharding=xs),
                jax.ShapeDtypeStruct((2048, 2 * n), jnp.float32,
                                     sharding=NamedSharding(ep_mesh, P())),
                jax.ShapeDtypeStruct((2 * n, 2048, 5632), jnp.bfloat16,
                                     sharding=ws),
                jax.ShapeDtypeStruct((2 * n, 5632, 2048), jnp.bfloat16,
                                     sharding=ws),
            ]

            def local(x, wg, w1, w2):
                y, aux = moe_shard_map_apply(x, wg, w1, w2, cfg)
                return y, jax.lax.pmean(aux, "ep")

            f = jax.shard_map(local, mesh=ep_mesh,
                              in_specs=(P("ep"), P(), P("ep"), P("ep")),
                              out_specs=(P("ep"), P()), check_vma=False)
            return f, arrs

        coll(f"MoE all_to_all ep={n} (8k tok/dev, H=2048)", moe_builder)

        def pp_builder():
            pp_mesh = make_mesh(pp=n, dp=1, devices=list(topo.devices))
            M, mb, hid = 2 * n, 2, 1024
            ps = NamedSharding(pp_mesh, P(None, "pp"))

            def stage_fn(p, x):
                return jnp.tanh(x @ p)

            def local(chunk_params, mbs):
                local_p = chunk_params[:, 0]   # (V=1, hid, hid)
                outs = pipeline_apply(stage_fn, local_p, mbs,
                                      num_chunks=1)
                return jnp.sum(outs.astype(jnp.float32))

            f = jax.shard_map(
                local, mesh=pp_mesh,
                in_specs=(P(None, "pp"), P()), out_specs=P(),
                check_vma=False)
            arrs = [jax.ShapeDtypeStruct((1, n, hid, hid), jnp.float32,
                                         sharding=ps),
                    jax.ShapeDtypeStruct((M, mb, hid), jnp.float32,
                                         sharding=NamedSharding(pp_mesh,
                                                                P()))]
            return f, arrs

        coll(f"pipeline scan+ppermute pp={n}", pp_builder)

        def tp_sp_builder():
            # phase-1 core of the driver dryrun: Megatron TP + sequence
            # parallelism fwd+bwd (all-gather fwd / reduce-scatter bwd
            # pairs + psum) in one integrated program
            from apex1_tpu.transformer.tensor_parallel import layers as tpl
            tp_mesh = make_mesh(tp=n, dp=1, devices=list(topo.devices))
            S_l, mb, hid, ffn = 512, 4, 2048, 8192  # per-dev seq shard

            def local(x, w1, b1, w2, b2):
                def loss_fn(w1, b1, w2, b2):
                    h = tpl.column_parallel_linear(
                        x, w1, b1, sequence_parallel_enabled=True)
                    h = jax.nn.gelu(h)
                    h = tpl.row_parallel_linear(
                        h, w2, bias=b2, sequence_parallel_enabled=True)
                    return jnp.sum(h.astype(jnp.float32))

                g_w1, g_b1, g_w2, g_b2 = jax.grad(
                    loss_fn, argnums=(0, 1, 2, 3))(w1, b1, w2, b2)
                # replicated b2 under SP: local db2 sums only the local
                # seq shard — psum completes it (and puts the psum
                # collective on the lowered path, per the section name)
                return g_w1, g_b1, g_w2, jax.lax.psum(g_b2, "tp")

            f = jax.shard_map(
                local, mesh=tp_mesh,
                in_specs=(P("tp"), P(None, "tp"), P("tp"),
                          P("tp", None), P()),
                out_specs=(P(None, "tp"), P("tp"), P("tp", None), P()),
                check_vma=False)
            ns = lambda spec: NamedSharding(tp_mesh, spec)
            arrs = [
                jax.ShapeDtypeStruct((S_l * n, mb, hid), jnp.bfloat16,
                                     sharding=ns(P("tp"))),
                jax.ShapeDtypeStruct((hid, ffn), jnp.bfloat16,
                                     sharding=ns(P(None, "tp"))),
                jax.ShapeDtypeStruct((ffn,), jnp.bfloat16,
                                     sharding=ns(P("tp"))),
                jax.ShapeDtypeStruct((ffn, hid), jnp.bfloat16,
                                     sharding=ns(P("tp", None))),
                jax.ShapeDtypeStruct((hid,), jnp.bfloat16,
                                     sharding=ns(P())),
            ]
            return f, arrs

        coll(f"TP+SP column/row linear fwd+bwd tp={n}", tp_sp_builder)

        # chip_smoke.py's DDP phase: the README train step inside
        # shard_map over dp=n, 16 x 1024 per chip, GPT-2 125M
        def ddp_lower():
            dp_mesh = make_mesh(dp=n, devices=list(topo.devices))
            amp, step = chip_smoke.ddp_step(smoke_model, dp_mesh)
            state = abstract(
                jax.eval_shape(lambda: amp.init(
                    chip_smoke.init_params(smoke_model))),
                NamedSharding(dp_mesh, P()))
            d = chip_smoke.DDP
            toks = jax.ShapeDtypeStruct(
                (n * d["per_chip_batch"], d["seq"]), jnp.int32,
                sharding=NamedSharding(dp_mesh, P("dp")))
            return step.lower(state, toks)

        report(f"chip_smoke DDP step gpt2-125M dp={n}", ddp_lower)

        # the README's "a mesh + specs + one jit" form with kernels on
        # cannot compile for more than one chip: GSPMD refuses to
        # partition Mosaic kernels. It must fail with THAT error — a
        # silent switch to the composites would hide the device.
        def gspmd_lower():
            from apex1_tpu.core.policy import get_policy
            from apex1_tpu.models.gpt2 import GPT2Config, param_specs
            tiny = chip_smoke.make_model(
                GPT2Config.tiny(policy=get_policy("O2")))
            tp_mesh = make_mesh(tp=n, dp=1, devices=list(topo.devices))
            amp, step = chip_smoke.train_step(tiny)
            state = jax.eval_shape(
                lambda: amp.init(chip_smoke.init_params(tiny)))
            sharded = jax.tree_util.tree_map(
                lambda x, spec: abstract(x, NamedSharding(tp_mesh, spec)),
                state.params, param_specs(state.params))
            import dataclasses
            state = dataclasses.replace(
                abstract(state, NamedSharding(tp_mesh, P())),
                params=sharded)
            toks = jax.ShapeDtypeStruct(
                (4, 128), jnp.int32, sharding=NamedSharding(tp_mesh, P()))
            return step.lower(state, toks).compile()

        try:
            gspmd_lower()
        except NotImplementedError as e:
            if "cannot be automatically partitioned" not in str(e):
                raise
            print(f"  OK   GSPMD multi-chip step with kernels raises: "
                  f"{str(e)[:70]}", flush=True)
        else:
            ok = False
            print("  FAIL GSPMD multi-chip step with kernels must raise "
                  "'Mosaic kernels cannot be automatically partitioned'",
                  flush=True)

    if args.flagship:
        # BASELINE config 4 at target scale: Llama-3-8B full 3D train
        # step (dp x pp x tp + SP + remat + fused Adam) against a
        # v5p-32-class topology — OOMs surface HERE, not on hardware
        ftopo_name = args.flagship_topology
        print(f"== flagship: Llama-3-8B dp2 x pp2 x tp4 (+SP, remat) "
              f"train step, {ftopo_name} ==", flush=True)
        from apex1_tpu.core.mesh import make_mesh as mk
        from apex1_tpu.core.policy import get_policy
        from apex1_tpu.models.llama import LlamaConfig
        from apex1_tpu.models.llama_3d import (Llama3DConfig,
                                               abstract_state, build_step)

        # the Pallas block planners must see the flagship chip's VMEM
        # budget, not the --topology generation's
        with _cap.target_generation(_gen_from_topology(ftopo_name)):
            ftopo = topologies.get_topology_desc(platform="tpu",
                                                 topology_name=ftopo_name)
            fn_dev = len(ftopo.devices)
            # dp=2 fixed; tp bounded by the 8 kv heads; pp >= 2 so the
            # pipeline axis is actually exercised
            cands = [t for t in (1, 2, 4, 8)
                     if fn_dev % (2 * t) == 0 and fn_dev // (2 * t) >= 2]
            if not cands:
                raise SystemExit(f"--flagship-topology needs >= 8 chips with "
                                 f"even count, got {fn_dev}")
            tp = max(cands)
            dp = 2
            pp = fn_dev // (dp * tp)
            gen = _gen_from_topology(ftopo_name)
            print(f"   mesh dp={dp} pp={pp} tp={tp} over {fn_dev} chips",
                  flush=True)
            # 8B defaults, bf16 compute, per-layer remat
            mcfg = LlamaConfig(policy=get_policy("O2"), remat=True)
            fcfg = Llama3DConfig(model=mcfg, dp=dp, pp=pp, tp=tp,
                                 num_microbatches=max(4, 2 * pp),
                                 microbatch_size=1)
            fmesh = mk(dp=dp, pp=pp, tp=tp, devices=list(ftopo.devices),
                       allow_split_physical_axes=True)

            def flagship_run():
                step, _, _, _ = build_step(fcfg, fmesh)
                state, data = abstract_state(fcfg, fmesh)
                return step.lower(state, data, data)

            report(f"flagship 8B train step ({gen} x{fn_dev})", flagship_run)

            # PLANNER GATE (ROADMAP item 1): the auto-parallel planner's
            # OWN 8B pick for this topology, AOT-lowered so XLA's memory
            # analysis verifies what the analytic pre-filter promised —
            # the planner must never queue an unverified layout into a
            # hardware window. dp/pp/tp family only: the gate guards the
            # search's HBM arithmetic, not every axis composition (cp/ep
            # lowering is covered by the dedicated sections above/below).
            from apex1_tpu import planner as _planner

            pshape = _planner.ModelShape.from_llama(
                mcfg, global_batch=2 * fn_dev // max(2, tp),
                name="llama8b")
            pplan = _planner.make_plan(pshape, fn_dev, generation=gen,
                                       allow_cp=False, allow_ep=False,
                                       allow_zero=False)
            pm = pplan["mesh"]
            print(f"   planner pick dp={pm['dp']} pp={pm['pp']} "
                  f"tp={pm['tp']} "
                  f"M={pplan['schedule']['num_microbatches']}: analytic "
                  f"{pplan['memory']['total']:.1f} of "
                  f"{pplan['memory']['budget']:.1f} GiB/chip, "
                  f"{pplan['predicted']['calibrated_step_ms']:.1f} ms/step "
                  f"calibrated", flush=True)
            pcfg = _planner.llama3d_config_from_plan(pplan, mcfg)
            pmesh = mk(dp=pm["dp"], pp=pm["pp"], tp=pm["tp"],
                       devices=list(ftopo.devices),
                       allow_split_physical_axes=True)

            def planner_run():
                step, _, _, _ = build_step(pcfg, pmesh)
                state, data = abstract_state(pcfg, pmesh)
                return step.lower(state, data, data)

            report(f"planner 8B pick dp{pm['dp']} pp{pm['pp']} "
                   f"tp{pm['tp']} ({gen} x{fn_dev})", planner_run)

            # BASELINE config 5 at scale: 8B LONG-CONTEXT — sequence 32k
            # sharded over cp (ring attention inside the same step)
            lc_cfg = Llama3DConfig(
                model=LlamaConfig(policy=get_policy("O2"), remat=True,
                                  max_seq_len=32768),
                dp=1, pp=2, cp=2, tp=fn_dev // 4, num_microbatches=4,
                microbatch_size=1)
            lc_mesh = mk(dp=1, pp=2, cp=2, tp=fn_dev // 4,
                         devices=list(ftopo.devices),
                         allow_split_physical_axes=True)

            def longctx_run():
                step, _, _, _ = build_step(lc_cfg, lc_mesh)
                state, data = abstract_state(lc_cfg, lc_mesh)
                return step.lower(state, data, data)

            report(f"flagship 8B long-ctx S=32k cp2 ({gen} x{fn_dev})",
                   longctx_run)

            # the same 8B step on the INTERLEAVED true 1F1B schedule (V=2
            # group-cycled chunks, recirculation FIFOs, residual ring) —
            # proves the staggered-scan schedule lowers through Mosaic at
            # production scale, not just on the CPU test mesh
            il_cfg = Llama3DConfig(model=mcfg, dp=dp, pp=pp, tp=tp,
                                   num_microbatches=2 * pp,
                                   microbatch_size=1, num_chunks=2,
                                   schedule="1f1b")

            def interleaved_run():
                step, _, _, _ = build_step(il_cfg, fmesh)
                state, data = abstract_state(il_cfg, fmesh)
                return step.lower(state, data, data)

            report(f"flagship 8B interleaved-1F1B V=2 ({gen} x{fn_dev})",
                   interleaved_run)

            # SELECTIVE recompute (Megatron --recompute-activations) on both
            # schedules — the source rows of docs/parallel.md's schedule x
            # remat memory table; keep them reproducible by this command
            import dataclasses as _dc
            sel_m = _dc.replace(
                mcfg, remat_policy="dots_with_no_batch_dims_saveable")
            for sname, base in (("scan", fcfg), ("interleaved-1F1B V=2",
                                                 il_cfg)):
                sel_cfg = _dc.replace(base, model=sel_m)

                def sel_run(cfg_=sel_cfg):
                    step, _, _, _ = build_step(cfg_, fmesh)
                    state, data = abstract_state(cfg_, fmesh)
                    return step.lower(state, data, data)

                report(f"flagship 8B {sname} + selective remat "
                       f"({gen} x{fn_dev})", sel_run)
            # analytic per-stage parameter budget (SPMD allocates the
            # pp-replicated embedding/head on every stage)
            m = fcfg.model
            lay = sum(int(np.prod(s)) for s in (
                (m.hidden_size, m.num_heads * m.head_dim),
                (m.hidden_size, m.num_kv_heads * m.head_dim),
                (m.hidden_size, m.num_kv_heads * m.head_dim),
                (m.num_heads * m.head_dim, m.hidden_size),
                (m.hidden_size, m.ffn_size),
                (m.hidden_size, m.ffn_size),
                (m.ffn_size, m.hidden_size)))
            # layers_per_stage is per (chunk, stage) slot — a stage holds
            # num_chunks of them
            per_stage = lay * fcfg.layers_per_stage * fcfg.num_chunks / tp
            embhead = 2 * m.vocab_size * m.hidden_size / tp
            f32x3 = 12 / 2**30  # master + 2 moments, fp32 bytes
            from apex1_tpu.core.capability import get_capability
            hbm = get_capability(gen).hbm_bytes / 2**30
            print(f"       per-stage params/chip: blocks "
                  f"{per_stage * f32x3:5.2f} GiB, emb+head "
                  f"{embhead * f32x3:5.2f} GiB (fp32 x3 opt); chip HBM "
                  f"{hbm:.0f} GiB ({gen})", flush=True)

    return ok


if __name__ == "__main__":
    main()
