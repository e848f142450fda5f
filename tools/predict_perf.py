"""Offline roofline PREDICTION for every bench config + Pallas kernel —
the falsifiable perf table VERDICT r4 Missing #2 asked for.

Four rounds of kernel/step tuning are AOT- and numerics-verified but have
never been timed. This tool makes that work scoreable offline:
it AOT-compiles the EXACT bench.py train steps and the individual Pallas
kernels through libtpu's compile-only topology client (same machinery as
tools/aot_check.py), reads post-optimization FLOPs and bytes-accessed
from XLA's cost model, and tables the roofline prediction

    t_pred = max(flops / peak_bf16_flops, hbm_bytes / peak_hbm_bw)

per config against the v5e (bench chip) and v5p capability rows
(core/capability.py spec-sheet numbers). The first real hardware window
then CONFIRMS or EMBARRASSES this table (bench.py prints measured
step_ms + MFU in the same units).

How to read the numbers honestly:
- The prediction is an UPPER BOUND on throughput: XLA's "bytes accessed"
  is the post-fusion HLO cost model's count of operand+output bytes per
  op, which approximates HBM traffic but ignores achieved-bandwidth
  derating, DMA/compute overlap gaps, scalar-core stalls, and ICI time.
  Measured tokens/sec at or above ~60% of predicted = the program is
  roofline-shaped; below ~50% = a schedule or kernel is leaving real
  performance on the floor and the per-kernel table localizes where.
- THE PALLAS BLIND SPOT (the reason each step compiles TWICE): the HLO
  cost model cannot see inside `tpu_custom_call`, so a Pallas-lowered
  program under-reports flops by exactly the kernels' share (flash
  attention + fused LM-head CE are ~40% of a GPT-2 step). Logical
  FLOPs therefore come from a second compile with `force_impl("xla")`
  (same math through composite ops); HBM bytes come from the Pallas
  compile (the composite would overcount bytes by the S^2 score
  materializations flash exists to avoid — while the Pallas compile's
  custom-call operand bytes are the right first-order traffic).
  `flops_xla / flops_pallas_visible` is tabled per config as the MFU
  CORRECTION FACTOR: bench.py's on-hardware `mfu` divides measured
  time into cost_analysis flops of the Pallas program, so multiply
  bench.py's mfu by this factor for true model-flops utilization.
- XLA counts a fused multiply-add as 2 flops, matching bench.py.
- SCANNED-LOOP BLIND SPOT (decode/decode_int8): the cost model counts
  a `lax.scan`/`fori_loop` body's loop-INVARIANT operands (the model
  weights a decode loop streams every step) ONCE, not once per
  iteration, so the decode rows' bytes — and therefore their
  HBM-bound time — are ~Nx optimistic for an N-step decode. The
  decode rows are retained for flop bookkeeping only; the honest
  decode floor is BASELINE.md's weight-streaming arithmetic.
- The per-kernel table is ANALYTIC (formulas in `_KERNEL_CASES`):
  cost-model numbers are meaningless for custom calls, so kernel
  rooflines use counted matmul flops and operand/result bytes.
- v5p columns reuse the v5e-lowered program's flops/bytes with v5p
  peaks (identical HLO math; Pallas block shapes differ on v5p but
  block shape changes traffic only at the margin).

Usage:
    python tools/predict_perf.py [--out perf_results/predicted_r5.md]
        [--json perf_results/predicted_r5.json] [--configs gpt2,bert,...]
        [--skip-kernels]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from apex1_tpu.testing import (  # noqa: E402
    enable_persistent_compilation_cache)

enable_persistent_compilation_cache()

TOPOLOGY = "v5e:2x2"   # lowering target; single-device programs


def _cost(compiled):
    """(flops, bytes_accessed) from the optimized executable's cost model."""
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    # total operand+output traffic: XLA reports the aggregate under
    # "bytes accessed"; per-operand keys ("bytes accessed0{}", ...)
    # are subsets of it, so the aggregate alone is the roofline input
    nbytes = float(cost.get("bytes accessed", 0.0))
    return flops, nbytes


def _roofline(flops, nbytes, cap, ici_exposed_bytes=0.0):
    """Predicted (seconds, bound, mfu) — now the LIBRARY roofline
    (`apex1_tpu.perf_model.roofline`, docstring there): the planner and
    this CLI must price through the same arithmetic or their numbers
    drift (the reason perf_model exists)."""
    from apex1_tpu.perf_model import roofline

    return roofline(flops, nbytes, cap,
                    ici_exposed_bytes=ici_exposed_bytes)


def predict_steps(topo, configs):
    """AOT-compile each bench step single-device; return prediction rows."""
    import bench as bench_mod
    from jax.sharding import SingleDeviceSharding

    s1 = SingleDeviceSharding(topo.devices[0])

    def to_shape(tree):
        import jax.numpy as jnp
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                           jnp.asarray(x).dtype,
                                           sharding=s1), tree)

    from apex1_tpu.ops import force_impl

    def to_shape_cpu(tree):
        import jax.numpy as jnp
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                           jnp.asarray(x).dtype), tree)

    rows = []
    for name in configs:
        try:
            (state, step, batch, units_per_step, _iters, metric, unit,
             proxy) = bench_mod.BENCHES[name](True)
            sh_state, sh_batch = to_shape(state), to_shape(batch)
            cpu_state, cpu_batch = to_shape_cpu(state), to_shape_cpu(batch)
            del state, batch

            # impl pinned INSIDE a fresh closure per mode: jax's trace
            # cache is keyed on the function object, so two lowerings of
            # the SAME `step` would alias one jaxpr and force_impl at
            # lower()-time would silently no-op (the r3 hw_numerics
            # vacuous-comparison bug class, re-observed here in r5)
            def mode_step(mode):
                def run(st, *b):
                    with force_impl(mode):
                        return step(st, *b)
                return run

            # Pallas compile: bytes are first-order honest, flops are
            # blind to custom-call interiors
            compiled_p = jax.jit(mode_step("auto"), donate_argnums=0).lower(
                sh_state, *sh_batch).compile()
            flops_vis, nbytes = _cost(compiled_p)
            mem = compiled_p.memory_analysis()
            # forced-composite compile: the LOGICAL flop count (same
            # math, every matmul visible to the cost model). Compiled
            # for CPU, unsharded: the composite materializes the S^2
            # score tensors flash exists to avoid, so it cannot FIT the
            # v5e HBM budget — it only needs to COUNT (flop counting on
            # optimized HLO is backend-invariant for these programs)
            compiled_x = jax.jit(mode_step("xla"), donate_argnums=0).lower(
                cpu_state, *cpu_batch).compile()
            flops, _bytes_x = _cost(compiled_x)
            rows.append(dict(
                name=name, metric=metric, unit=unit, proxy=proxy,
                units_per_step=units_per_step, flops=flops, bytes=nbytes,
                flops_pallas_visible=flops_vis,
                mfu_correction=(flops / flops_vis if flops_vis else None),
                # single-chip bench programs move no ICI bytes; the keys
                # exist so multichip rows can carry the comms term
                # bench.py::_predicted_rate prices (exposed = NOT hidden
                # behind compute; see predict_comms)
                ici_bytes=0.0, ici_exposed_bytes=0.0,
                temp_gib=mem.temp_size_in_bytes / 2**30,
                args_gib=mem.argument_size_in_bytes / 2**30))
            print(f"  OK   {name:14s} flops {flops:.3e} "
                  f"(visible {flops_vis:.3e})  bytes {nbytes:.3e}",
                  flush=True)
        except Exception as e:
            print(f"  FAIL {name}: {type(e).__name__}: {str(e)[:200]}",
                  flush=True)
            rows.append(dict(name=name, error=f"{type(e).__name__}: {e}"))
    return rows


def _kernel_cases():
    """The per-kernel analytic table — moved verbatim to
    `apex1_tpu.perf_model.kernel_cases` (formula docstring there) so
    the planner's attention/CE pricing and this CLI share one set of
    formulas."""
    from apex1_tpu.perf_model import kernel_cases

    return kernel_cases()


def predict_kernels(_topo):
    """Analytic roofline rows for the Pallas kernels (the HLO cost model
    is blind inside tpu_custom_call — see module docstring)."""
    rows = []
    for name, flops, nbytes in _kernel_cases():
        rows.append(dict(name=name, flops=float(flops),
                         bytes=float(nbytes), source="analytic"))
        print(f"  OK   {name:40s} flops {flops:.3e}  "
              f"bytes {nbytes:.3e}  [analytic]", flush=True)
    return rows


def predict_comms():
    """Analytic ICI comms term for the ring-attention CP path at the
    llama_longctx attention shape (the 16k config that measured 0.36x
    its single-chip roofline): per ring step the K/V shard transfer
    either serializes against the attend (the pre-overlap schedule) or
    hides behind it (the double-buffered schedule, hlo_probe-pinned).
    ``exposed_bytes`` is what `_roofline`'s comms term prices — the
    overlapped rows carry only the residual the attend cannot cover,
    so bench.py's `predicted`/`roofline_ratio` sees the win instead of
    silently crediting serialized transfers as free.

    Forward per visiting shard: K+V bf16 hops vs 4·B·Hq·S_l²·D·0.5
    causal attend flops. Backward: K/V hops + fp32 dK/dV accumulator
    hops (the travelling-accumulator schedule pays one extra seed hop,
    n instead of n−1 — see parallel/ring_attention.py) vs the ~2.5x
    fwd per-shard backward compute.
    """
    from apex1_tpu.perf_model import ring_attention_comms

    B, Hq, Hkv, S, D = 1, 32, 4, 16384, 64
    rows = []
    for gen in ("v5e", "v5p"):
        for n in (4, 8):
            m = ring_attention_comms(gen, n, B=B, Hq=Hq, Hkv=Hkv, S=S,
                                     D=D)
            if m is None:
                # capability row carries no ICI figure — nothing to
                # price
                print(f"  SKIP ring comms {gen}: no ici_gbps in "
                      f"capability row", flush=True)
                break
            link = m["link_gbps"]
            for phase, total, serial_t, overlap_exp in (
                    ("fwd", m["fwd_bytes"], (n - 1) * m["t_hop_f"],
                     m["exp_f_overlap"]),
                    ("bwd", m["bwd_bytes"], n * m["t_hop_b"],
                     m["exp_b_overlap"])):
                rows.append(dict(
                    name=f"ring llama_longctx {phase} cp={n}",
                    generation=gen, cp=n, phase=phase,
                    ici_bytes=float(total),
                    exposed_bytes_serial=float(total),
                    exposed_bytes_overlap=float(overlap_exp),
                    t_serial_ms=serial_t * 1e3,
                    t_exposed_overlap_ms=(overlap_exp / (link * 1e9))
                    * 1e3,
                    source="analytic"))
            print(f"  OK   ring comms {gen} cp={n}: fwd hop "
                  f"{m['kv_hop'] / 2**20:.1f} MiB vs attend "
                  f"{m['t_att'] * 1e3:.2f} "
                  f"ms -> exposed {m['exp_f_overlap'] / 2**20:.1f} MiB "
                  f"(serial {m['fwd_bytes'] / 2**20:.1f})", flush=True)
    return rows


def predict_comms_fused():
    """Analytic ICI term for the Megatron-SP boundary matmul at a
    llama-8B-ish MLP shape, priced across the THREE schedules the repo
    now ships (docs/parallel.md "Fused comm-kernels"):

    - ``serial``: the monolithic collective (or the rotate-then-dot
      negative control) — every byte exposed.
    - ``overlap``: PR 4's chunk-pipelined ppermute ring AND the fused
      ppermute form (`ops.fused_collective.fused_matmul_reduce_scatter`,
      same schedule with the dot in a Pallas kernel) — exposed = the
      per-hop residual the chunk dot cannot cover. This is the
      BEST-CASE number: it assumes the XLA scheduler actually hoists
      every permute (hlo_probe pins the dependence shape, not the
      achieved schedule).
    - ``fused_rdma``: the single-kernel RDMA form
      (`matmul_reduce_scatter_rdma`) — grid-sequenced overlap, so the
      bound is STRUCTURAL, not scheduler-dependent: exposed ≈ the
      prologue hop (pipeline fill) plus the same bandwidth residual;
      on compute-rich shapes that is the prologue hop only.

    bench.py's `roofline_ratio` prices a record's `ici_exposed_bytes`
    at the per-link rate, so the three forms are scored honestly
    against each other, not assumed free.
    """
    from apex1_tpu.perf_model import sp_boundary_comms

    S, hid, ffn = 8192, 4096, 14336   # global seq, llama-8B MLP dims
    rows = []
    for gen in ("v5e", "v5p"):
        for n in (4, 8):
            # matmul->reduce-scatter at the row-parallel boundary:
            # x (S, ffn/n) @ w (ffn/n, hid), travelling fp32 chunk acc
            m = sp_boundary_comms(gen, n, rows=S, out_width=hid,
                                  ffn=ffn)
            if m is None:
                print(f"  SKIP fused comms {gen}: no ici_gbps in "
                      f"capability row", flush=True)
                break
            link = m["link_gbps"]
            rows.append(dict(
                name=f"SP matmul_reduce_scatter tp={n}",
                generation=gen, tp=n,
                ici_bytes=m["total"],
                exposed_bytes_serial=m["exposed_serial"],
                exposed_bytes_overlap=m["exposed_overlap"],
                exposed_bytes_fused=m["exposed_fused"],
                t_serial_ms=n * m["t_hop"] * 1e3,
                t_exposed_overlap_ms=(m["exposed_overlap"]
                                      / (link * 1e9)) * 1e3,
                t_exposed_fused_ms=(m["exposed_fused"]
                                    / (link * 1e9)) * 1e3,
                source="analytic"))
            print(f"  OK   fused comms {gen} tp={n}: hop "
                  f"{m['hop'] / 2**20:.1f} MiB vs dot "
                  f"{m['t_dot'] * 1e3:.2f} ms "
                  f"-> exposed serial {m['total'] / 2**20:.0f} / overlap "
                  f"{m['exposed_overlap'] / 2**20:.1f} / fused "
                  f"{m['exposed_fused'] / 2**20:.1f}"
                  f" MiB", flush=True)
    return rows


def annotate_calibration(step_rows):
    """Stamp each step row with the banked TPU-fitted slowdown factor
    (`apex1_tpu.obs.calibrate` — perf_results/calibration.json) and the
    calibrated v5e prediction: ``calibrated = analytic x slowdown`` in
    time terms. Fail-safe: no table, or no factor for a config, leaves
    the row untouched — the analytic prediction stands alone, as it did
    before any silicon was measured."""
    from apex1_tpu.obs.calibrate import load_calibration

    doc = load_calibration()
    if doc is None:
        return None
    for r in step_rows:
        if "error" in r:
            continue
        f = doc.get("factors", {}).get(f"step:{r['name']}")
        if isinstance(f, dict) and isinstance(f.get("slowdown"),
                                              (int, float)):
            r["calibration_slowdown"] = f["slowdown"]
            r["calibration_n"] = f.get("n")
    return doc


def render(step_rows, kernel_rows, comms_rows=(), fused_rows=(),
           calibration=None):
    from apex1_tpu.core.capability import get_capability
    v5e, v5p = get_capability("v5e"), get_capability("v5p")
    lines = []
    w = lines.append
    w("# Predicted performance — round 5 (offline roofline, NOT measured)")
    w("")
    w("Source: `python tools/predict_perf.py` — XLA cost model (flops, "
      "bytes accessed) of the post-optimization v5e executables for the "
      "exact `bench.py` steps and Pallas kernels, against the "
      "`core/capability.py` spec rows "
      f"(v5e {v5e.bf16_tflops:.0f} TF bf16 / {v5e.hbm_gbps:.0f} GB/s; "
      f"v5p {v5p.bf16_tflops:.0f} TF / {v5p.hbm_gbps:.0f} GB/s).")
    w("")
    w("`t_pred = max(flops/peak_flops, bytes/peak_bw)` — an UPPER bound "
      "on throughput (no overlap gaps, no bandwidth derating, no ICI). "
      "Measured ≥ ~60% of predicted tok/s = roofline-shaped program; "
      "< ~50% = localize the loss with the per-kernel table + "
      "`tools/profile_step.py`. See module docstring for the full "
      "honesty contract.")
    w("")
    w("## Bench configs (per train step, single chip)")
    w("")
    w("| config | units/step | GFLOPs | HBM GiB | AI (fl/B) | bound "
      "| v5e pred ms | v5e pred rate | v5e pred MFU | v5p pred ms "
      "| proxy | pred/proxy | mfu corr |")
    w("|---|---|---|---|---|---|---|---|---|---|---|---|---|")
    for r in step_rows:
        if "error" in r:
            w(f"| {r['name']} | — | — | — | — | — | — | — | — | — | — "
              f"| — | ERROR: {r['error'][:80]} |")
            continue
        te, be, me = _roofline(r["flops"], r["bytes"], v5e)
        tp, _, _ = _roofline(r["flops"], r["bytes"], v5p)
        rate = r["units_per_step"] / te
        ai = r["flops"] / r["bytes"] if r["bytes"] else float("inf")
        corr = r.get("mfu_correction")
        corr_s = f"{corr:.2f}x" if corr else "n/a"
        w(f"| {r['name']} | {r['units_per_step']} "
          f"| {r['flops'] / 1e9:,.1f} | {r['bytes'] / 2**30:.2f} "
          f"| {ai:.0f} | {be} | {te * 1e3:.1f} | {rate:,.0f} {r['unit']} "
          f"| {me:.2f} | {tp * 1e3:.1f} | {r['proxy']:,.0f} "
          f"| {rate / r['proxy']:.2f} | {corr_s} |")
    w("")
    w("`mfu corr` = logical flops / Pallas-visible flops: multiply "
      "bench.py's measured on-chip `mfu` by this factor for true model-"
      "flops utilization (bench.py's cost_analysis cannot see inside "
      "tpu_custom_call). decode_int8's huge factor is expected: "
      "essentially every matmul of that program runs inside the int8 "
      "Pallas GEMM, so the visible count is near zero.")
    w("")
    w("The `pred/proxy` column is the prediction of `bench.py`'s "
      "`vs_baseline` against the PINNED A100 comparator rows "
      "(BASELINE.md \"Pinned A100 comparator\"); the headline claim on "
      "the table is GPT-2, whose only measurement (round 1, pre-tuning) "
      "was 42,027 tok/s.")
    w("")
    cal_rows = [r for r in step_rows if r.get("calibration_slowdown")]
    if cal_rows:
        w("## Calibrated predictions (banked silicon history applied)")
        w("")
        w("Factors from `perf_results/calibration.json` "
          "(`apex1_tpu.obs.calibrate` — TPU-fitted slowdown = analytic "
          "rate / measured rate over the banked bench logs"
          + (f", {calibration.get('n_pairs')} pairs"
             if calibration else "") + "). `calibrated ms` = analytic "
          "x slowdown: what the NEXT run of this config should "
          "actually take if nothing regressed — the planner-facing "
          "number. cpu-proxy factors are never applied here.")
        w("")
        w("| config | slowdown (n) | v5e analytic ms | v5e calibrated "
          "ms | calibrated rate |")
        w("|---|---|---|---|---|")
        # priced through the SAME function the factors were fitted
        # against (calibrate.predicted_step_rate, comms term included)
        # — _roofline alone would drop a multichip row's exposed-ICI
        # term and overstate the calibrated rate by exactly that share
        from apex1_tpu.obs.calibrate import predicted_step_rate
        for r in cal_rows:
            rate = predicted_step_rate(r, "v5e")
            if not rate:
                continue
            te = r["units_per_step"] / rate
            s = r["calibration_slowdown"]
            w(f"| {r['name']} | {s:.2f}x ({r.get('calibration_n')}) "
              f"| {te * 1e3:.1f} | {te * s * 1e3:.1f} "
              f"| {r['units_per_step'] / (te * s):,.0f} {r['unit']} |")
        w("")
    w("DECODE-ROW CAVEAT: the cost model counts the scanned decode "
      "loop's loop-invariant weight buffers ONCE, not once per decode "
      "step, so the decode/decode_int8 bytes — and their HBM-bound "
      "predictions — are ~Nx optimistic for an N-step decode. Those "
      "rows are flop bookkeeping only; the honest decode floor is "
      "BASELINE.md's weight-streaming arithmetic (module docstring, "
      "\"SCANNED-LOOP BLIND SPOT\").")
    w("")
    w("## Pallas kernels (per invocation at bench shapes)")
    w("")
    w("Flops/bytes here are ANALYTIC (formulas in "
      "`tools/predict_perf.py::_kernel_cases` — the HLO cost model "
      "cannot see inside `tpu_custom_call`, so compiled numbers would "
      "be zeros). `tools/bench_kernels.py` measures the same shapes on "
      "silicon.")
    w("")
    w("| kernel | GFLOPs | HBM MiB | AI | bound | v5e pred ms "
      "| v5e pred TF/s |")
    w("|---|---|---|---|---|---|---|")
    for r in kernel_rows:
        if "error" in r:
            w(f"| {r['name']} | — | — | — | — | — | ERROR: "
              f"{r['error'][:80]} |")
            continue
        te, be, _ = _roofline(r["flops"], r["bytes"], v5e)
        ai = r["flops"] / r["bytes"] if r["bytes"] else float("inf")
        tf = r["flops"] / te / 1e12 if te else 0.0
        w(f"| {r['name']} | {r['flops'] / 1e9:,.2f} "
          f"| {r['bytes'] / 2**20:,.1f} | {ai:.0f} | {be} "
          f"| {te * 1e3:.3f} | {tf:.1f} |")
    w("")
    if comms_rows:
        w("## ICI comms term — ring attention at the llama_longctx "
          "shape (analytic)")
        w("")
        w("`exposed` = transfer time NOT hidden behind compute — the "
          "serialized (pre-overlap) schedule exposes every hop; the "
          "double-buffered schedule exposes only the residual per-hop "
          "time the attend cannot cover. bench.py's "
          "`predicted`/`roofline_ratio` prices a row's "
          "`ici_exposed_bytes` at the per-link rate "
          "(`core.capability.ici_link_gbps`), so the overlap win is "
          "scoreable, not just asserted (the schedule property itself "
          "is pinned by `testing.hlo_probe` in tools/aot_check.py).")
        w("")
        w("| ring phase | gen | cp | ICI MiB | exposed serial ms "
          "| exposed overlapped ms |")
        w("|---|---|---|---|---|---|")
        for r in comms_rows:
            w(f"| {r['phase']} | {r['generation']} | {r['cp']} "
              f"| {r['ici_bytes'] / 2**20:,.1f} "
              f"| {r['t_serial_ms']:.2f} "
              f"| {r['t_exposed_overlap_ms']:.2f} |")
        w("")
    if fused_rows:
        w("## ICI comms term — fused comm-kernels at the SP boundary "
          "(analytic)")
        w("")
        w("Three schedules for the same matmul+reduce-scatter "
          "(`tools/predict_perf.py::predict_comms_fused`): `serial` "
          "exposes every byte; `overlap` (PR 4's ppermute ring and the "
          "fused ppermute form — same schedule, dot in a Pallas "
          "kernel) exposes only the per-hop residual the chunk dot "
          "cannot cover; `fused rdma` "
          "(`ops.fused_collective.matmul_reduce_scatter_rdma`) "
          "exposes ≈ the prologue hop only — tile-granular overlap "
          "inside one kernel. `tools/bench_fused_comm.py` measures "
          "the same three forms (queued as fused_comm_ab).")
        w("")
        w("| boundary | gen | tp | ICI MiB | exposed serial ms "
          "| exposed overlap ms | exposed fused ms |")
        w("|---|---|---|---|---|---|---|")
        for r in fused_rows:
            w(f"| {r['name']} | {r['generation']} | {r['tp']} "
              f"| {r['ici_bytes'] / 2**20:,.1f} "
              f"| {r['t_serial_ms']:.2f} "
              f"| {r['t_exposed_overlap_ms']:.2f} "
              f"| {r['t_exposed_fused_ms']:.2f} |")
        w("")
    w("Validation protocol: measure step_ms for every config above on "
      "the chip; divide measured by predicted and record the ratio per "
      "row. Ratios cluster tight (±15%) for roofline-shaped programs; "
      "an outlier row is the tuning target.")
    return "\n".join(lines) + "\n"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="perf_results/predicted_r5.md")
    ap.add_argument("--json", default="perf_results/predicted_r5.json")
    ap.add_argument("--configs", default=None,
                    help="comma-separated subset of bench configs")
    ap.add_argument("--skip-kernels", action="store_true")
    args = ap.parse_args()

    # identical dispatch patching to aot_check.py: real Mosaic lowering,
    # block planning for the lowering target — the numbers must price
    # the REAL kernels
    import apex1_tpu.ops._common as _common
    from apex1_tpu.core.capability import target_generation
    _common.on_tpu = lambda: True
    _common.interpret_mode = lambda: False
    with target_generation(TOPOLOGY.split(":")[0]):
        _predict(args)


def _predict(args):
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=TOPOLOGY)

    import bench as bench_mod
    # planner-driven multichip configs (bench.PLANNED_BENCHES) build
    # their mesh from the live device count — they cannot be priced by
    # this single-chip AOT path and are priced by the planner's own
    # cost engine instead; excluding them keeps the banked
    # predicted_*.json rows byte-stable across the planner's arrival
    configs = (args.configs.split(",") if args.configs
               else sorted(set(bench_mod.BENCHES)
                           - bench_mod.PLANNED_BENCHES))

    print(f"== step cost models ({TOPOLOGY}) ==", flush=True)
    step_rows = predict_steps(topo, configs)
    kernel_rows = []
    if not args.skip_kernels:
        print(f"== kernel cost models ({TOPOLOGY}) ==", flush=True)
        kernel_rows = predict_kernels(topo)
    print("== ICI comms term (ring attention, analytic) ==", flush=True)
    comms_rows = predict_comms()
    print("== ICI comms term (fused SP boundary, analytic) ==",
          flush=True)
    fused_rows = predict_comms_fused()

    print("== calibration annotation (banked factors) ==", flush=True)
    cal_doc = annotate_calibration(step_rows)
    print("  applied" if cal_doc else
          "  no banked calibration.json — analytic only", flush=True)

    md = render(step_rows, kernel_rows, comms_rows, fused_rows,
                calibration=cal_doc)
    for path in (args.out, args.json):
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
    with open(args.out, "w") as f:
        f.write(md)
    with open(args.json, "w") as f:
        json.dump({"topology": TOPOLOGY, "steps": step_rows,
                   "kernels": kernel_rows, "comms": comms_rows,
                   "comms_fused": fused_rows,
                   "calibration": ({"source": "perf_results/"
                                    "calibration.json",
                                    "generated_unix":
                                    cal_doc.get("generated_unix")}
                                   if cal_doc else None)},
                  f, indent=1)
    print(f"wrote {args.out} + {args.json}", flush=True)
    failures = sum("error" in r
                   for r in step_rows + kernel_rows + comms_rows
                   + fused_rows)
    print(f"{failures} failures" if failures else "ALL OK", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
