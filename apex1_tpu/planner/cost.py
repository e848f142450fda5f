"""The layout cost engine — analytic step pricing, silicon-corrected.

Prices one (ModelShape, Layout) pair in milliseconds per optimizer
step, through exactly the machinery the repo already trusts:

- compute + HBM terms ride `apex1_tpu.perf_model.roofline` (the
  AMP-style planner of arXiv 2210.07297 is only as good as its cost
  model; this one is fitted to nothing on the chip, ROADMAP D7);
- attention flops come from `perf_model.flash_flops_bytes` with the
  shipped two-pass-backward factor, the LM-head CE from
  `perf_model.linear_xent_flops`;
- ICI terms come from `perf_model.sp_boundary_comms` (the Megatron-SP
  boundary at the layout's OWN shard shape, exposed per the layout's
  ``sp_mode`` — serial / overlap / fused, PR 9's kernel-selection
  dimension) and `perf_model.ring_attention_comms` (cp ring), plus
  ring all-reduce gradient sync over the data replicas
  (`perf_model.allreduce_bytes` — the same bytes whether plain dp or
  the ZeRO reduce-scatter/all-gather split);
- the pipeline bubble multiplies the whole step by (M + pp - 1) / M;
- CALIBRATION: the analytic time is multiplied by the banked
  TPU-fitted slowdown (`obs.calibrate.step_slowdown` for the shape's
  own bench config; else the geometric mean of every banked tpu step
  factor, labelled ``fleet-geomean``; else 1.0 labelled
  ``uncalibrated``). cpu-proxy factors are NEVER applied — the
  calibrate module's own contract. `kernel_slowdown` is consulted for
  the SP-boundary kernels (tpu-backed entries only, i.e. PR 9's A/B
  once a window banks it); today's cpu-swept tables return None and
  the term stays analytic.

What a calibrated price licenses (docs/planner.md spells this out):
RANKING layouts against each other and against the banked history —
not predicting wall-clock on unmeasured silicon to better than the
fitted residual spread (x1.35 on the banked corpus).
"""

from __future__ import annotations

import math
from typing import Optional

from apex1_tpu.perf_model import (allreduce_bytes, flash_flops_bytes,
                                  linear_xent_flops,
                                  ring_attention_comms, roofline,
                                  sp_boundary_comms)
from apex1_tpu.planner import memory
from apex1_tpu.planner.layouts import Layout, ModelShape

DTYPE_BYTES = 2   # bf16 compute


def step_flops(shape: ModelShape) -> dict:
    """Global fwd+bwd flops per optimizer step, by component.

    Dense matmuls count 2*M*N*K fwd and x3 for fwd+bwd (dX + dW);
    flash attention carries its own x4.5 two-pass-backward factor
    (`perf_model.flash_flops_bytes` docstring); the fused LM-head CE
    is the 6*T*E*V fwd+bwd total (`perf_model.linear_xent_flops`)."""
    E, F, V = shape.hidden_size, shape.ffn_size, shape.vocab_size
    HD = shape.num_heads * shape.head_dim
    KD = shape.num_kv_heads * shape.head_dim
    T = shape.tokens_per_step
    qkvo = 2.0 * T * (E * HD + 2 * E * KD + HD * E)
    if shape.moe:
        mlp = (2.0 * T * E * shape.num_experts          # router
               + shape.moe_top_k * 4.0 * T * E * F)     # w1 + w2
    else:
        mlp = 6.0 * T * E * F                           # gate, up, down
    linear = shape.num_layers * (qkvo + mlp) * 3.0      # fwd+bwd
    attn_f, _ = flash_flops_bytes(shape.global_batch, shape.num_heads,
                                  shape.num_kv_heads, shape.seq_len,
                                  shape.head_dim, causal=True,
                                  grad=True)
    attn = shape.num_layers * attn_f
    ce = float(linear_xent_flops(T, E, V))
    return dict(linear=linear, attn=attn, ce=ce,
                total=linear + attn + ce)


def _sp_exposed_bytes(shape: ModelShape, layout: Layout,
                      generation: str) -> float:
    """Per-device exposed ICI bytes from the Megatron-SP boundaries of
    ONE step: per layer 2 all-gathers + 2 reduce-scatters forward, the
    mirrored duals backward — each priced at the layout's shard shape
    and exposed per its sp_mode."""
    if layout.tp < 2:
        return 0.0
    rows = (shape.seq_len // layout.cp) * layout.microbatch_size
    E, F = shape.hidden_size, shape.ffn_size
    HD = shape.num_heads * shape.head_dim
    KD = shape.num_kv_heads * shape.head_dim
    key = f"exposed_{layout.sp_mode}"
    boundaries = (
        # (local K of the overlapped chunk dot, out width, acc bytes,
        #  hop width). AG boundaries hop the bf16 INPUT activation
        # (width E — constant in tp, the dot's output shard is not
        # what travels); RS boundaries hop the fp32 partial-result
        # accumulator (width = the output, hop_width None).
        # attn AG -> qkv col-parallel dot
        (E, (HD + 2 * KD) // layout.tp, DTYPE_BYTES, E),
        # attn RS after wo row-parallel dot
        (HD // layout.tp, E, 4, None),
        # mlp AG -> gate+up col-parallel dot
        (E, 2 * F // layout.tp, DTYPE_BYTES, E),
        # mlp RS after down row-parallel dot
        (F // layout.tp, E, 4, None),
    )
    per_layer = 0.0
    for local_k, out_w, acc, hop_w in boundaries:
        m = sp_boundary_comms(generation, layout.tp, rows=rows,
                              local_k=max(1, local_k),
                              out_width=max(1, out_w), acc_bytes=acc,
                              hop_width=hop_w)
        if m is None:
            return 0.0
        per_layer += m[key]
    layers_dev = shape.num_layers / layout.pp
    # backward mirrors every boundary through the dual collective
    return per_layer * 2.0 * layers_dev * layout.num_microbatches


def _cp_exposed_bytes(shape: ModelShape, layout: Layout,
                      generation: str) -> float:
    """Per-device exposed ICI bytes from the ring-attention cp axis
    (double-buffered schedule — the shipped default; only the per-hop
    residual the attend cannot cover is exposed)."""
    if layout.cp < 2:
        return 0.0
    m = ring_attention_comms(
        generation, layout.cp, B=layout.microbatch_size,
        Hq=max(1, shape.num_heads // layout.tp),
        Hkv=max(1, shape.num_kv_heads // layout.tp),
        S=shape.seq_len, D=shape.head_dim)
    if m is None:
        return 0.0
    per_layer = m["exp_f_overlap"] + m["exp_b_overlap"]
    return (per_layer * (shape.num_layers / layout.pp)
            * layout.num_microbatches)


def _dp_exposed_bytes(shape: ModelShape, layout: Layout) -> float:
    """Gradient-sync bytes per device: fp32 grads ring-all-reduced over
    the data replicas (dp x ep x cp). The ZeRO layout moves the same
    total as its reduce-scatter + updated-param all-gather
    (`perf_model.allreduce_bytes`)."""
    replicas = layout.dp * layout.ep * layout.cp
    grad_bytes = 4.0 * memory.params_per_device(shape, layout)
    return allreduce_bytes(grad_bytes, replicas)


def _pp_exposed_bytes(shape: ModelShape, layout: Layout) -> float:
    """Pipeline boundary p2p: one SP-sharded boundary activation per
    microbatch per stage boundary, forward + backward."""
    if layout.pp < 2:
        return 0.0
    act = (shape.seq_len // (layout.cp * layout.tp)
           * layout.microbatch_size * shape.hidden_size * DTYPE_BYTES)
    return (2.0 * layout.num_microbatches * act
            * (layout.pp - 1) / layout.pp)


def _hbm_bytes_per_device(shape: ModelShape, layout: Layout) -> float:
    """First-order HBM traffic per device per step: stage weights
    re-streamed per microbatch (fwd + 2x bwd), the optimizer's fp32
    read-modify-write, and the residual-stream activation traffic."""
    p_dev = memory.params_per_device(shape, layout)
    weight_stream = (p_dev * DTYPE_BYTES * 3.0
                     * layout.num_microbatches)
    opt_rw = 28.0 * p_dev   # m/v/master read+write + grad read
    tok_dev = (shape.tokens_per_step
               / (layout.dp * layout.ep * layout.cp))
    act_stream = (tok_dev * shape.hidden_size * DTYPE_BYTES
                  * (shape.num_layers / layout.pp) * 12.0 / layout.tp)
    return weight_stream + opt_rw + act_stream


def calibration_factor(shape: ModelShape,
                       results_dir: Optional[str] = None) -> dict:
    """The banked slowdown to apply to this shape's analytic price.

    Preference order: the shape's OWN tpu step factor
    (``step:<shape.name>``), else the fleet geometric mean of every
    banked tpu step factor (an unmeasured config inherits the fleet's
    typical roofline shortfall rather than raw optimism), else 1.0.
    The provenance string rides into the plan so a consumer can see
    WHICH correction priced it."""
    from apex1_tpu.obs.calibrate import load_calibration

    doc = load_calibration(results_dir)
    if doc is None:
        return dict(slowdown=1.0, source="uncalibrated "
                    "(no banked calibration.json)")
    f = doc.get("factors", {}).get(f"step:{shape.name}")
    if isinstance(f, dict) and isinstance(f.get("slowdown"),
                                          (int, float)) \
            and f["slowdown"] > 0:
        return dict(slowdown=float(f["slowdown"]),
                    source=f"step:{shape.name} (n={f.get('n')}, "
                           f"banked calibration.json)")
    steps = [v["slowdown"] for k, v in
             sorted(doc.get("factors", {}).items())
             if k.startswith("step:") and isinstance(v, dict)
             and isinstance(v.get("slowdown"), (int, float))
             and v["slowdown"] > 0]
    if steps:
        geo = math.exp(sum(math.log(s) for s in steps) / len(steps))
        return dict(slowdown=geo,
                    source=f"fleet-geomean over {len(steps)} banked "
                           f"tpu step factors")
    return dict(slowdown=1.0,
                source="uncalibrated (no tpu step factors banked)")


def _sp_kernel_factor(layout: Layout,
                      results_dir: Optional[str] = None) -> dict:
    """TPU-backed kernel slowdown for the SP-boundary schedule the
    layout selected — PR 9's A/B data once a chip run banks it into
    the tuning tables and calibration fit. Today's tables are cpu-swept, so
    `kernel_slowdown` (tpu-only by contract) returns None and the
    boundary term stays analytic — labelled as such."""
    from apex1_tpu.obs.calibrate import kernel_slowdown

    # only the fused schedule runs a Pallas kernel with its own banked
    # factor; the overlap/serial schedules are XLA ppermute + dots,
    # already covered by the step-level calibration
    f = (kernel_slowdown("fused_collective_matmul", results_dir)
         if (layout.tp > 1 and layout.sp_mode == "fused") else None)
    if isinstance(f, dict) and isinstance(f.get("slowdown"),
                                          (int, float)):
        return dict(slowdown=float(f["slowdown"]),
                    source="kernel:fused_collective_matmul (banked "
                           "tpu A/B)")
    return dict(slowdown=1.0, source="analytic (no tpu kernel factor "
                "banked for the SP boundary)")


def price_layout(shape: ModelShape, layout: Layout, *,
                 generation: Optional[str] = None,
                 results_dir: Optional[str] = None,
                 use_calibration: bool = True,
                 calibration: Optional[dict] = None,
                 sp_kernel: Optional[dict] = None) -> dict:
    """Milliseconds per optimizer step for one layout, with the full
    breakdown and calibration provenance. Deterministic: same inputs
    (and same banked calibration.json) -> identical floats.

    ``calibration`` / ``sp_kernel``: precomputed factor docs
    (`calibration_factor` / `_sp_kernel_factor` output). The step
    factor is a property of the SHAPE and the fused-kernel factor of
    (tp>1, sp_mode) — constant across one search — so
    `search_layouts` loads the banked table ONCE and passes them
    down instead of re-reading calibration.json per candidate."""
    from apex1_tpu.core.capability import get_capability

    gen = generation or "v5e"
    cap = get_capability(gen)
    fl = step_flops(shape)
    shard = layout.dp * layout.ep * layout.cp * layout.tp
    # per-device compute: an equal stage slice of the layer stack, plus
    # the LM-head CE which rides the LAST stage (the critical one)
    flops_dev = ((fl["linear"] + fl["attn"]) / (shard * layout.pp)
                 + fl["ce"] / shard)
    bytes_dev = _hbm_bytes_per_device(shape, layout)
    sp = _sp_exposed_bytes(shape, layout, gen)
    cp = _cp_exposed_bytes(shape, layout, gen)
    dp = _dp_exposed_bytes(shape, layout)
    pp = _pp_exposed_bytes(shape, layout)
    kf = (sp_kernel if sp_kernel is not None
          else _sp_kernel_factor(layout, results_dir))
    exposed = sp * kf["slowdown"] + cp + dp + pp
    t, bound, mfu = roofline(flops_dev, bytes_dev, cap,
                             ici_exposed_bytes=exposed)
    bubble = ((layout.num_microbatches + layout.pp - 1)
              / layout.num_microbatches)
    step_ms = t * bubble * 1e3
    cal = (dict(slowdown=1.0, source="calibration disabled")
           if not use_calibration
           else calibration if calibration is not None
           else calibration_factor(shape, results_dir))
    calibrated_ms = step_ms * cal["slowdown"]
    tok_rate = (shape.tokens_per_step / (calibrated_ms * 1e-3)
                / layout.n_devices) if calibrated_ms > 0 else 0.0
    return dict(
        step_ms=step_ms, calibrated_step_ms=calibrated_ms,
        tokens_per_sec_per_chip=tok_rate,
        bound=bound, mfu=mfu, bubble_factor=bubble,
        flops_per_device=flops_dev, hbm_bytes_per_device=bytes_dev,
        ici_exposed_bytes=dict(sp_boundary=sp, cp_ring=cp,
                               dp_gradsync=dp, pp_p2p=pp),
        calibration=cal, sp_kernel=kf, generation=gen)
