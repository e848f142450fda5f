"""The ONE analytic performance-pricing library — rooflines, per-kernel
cost formulas, and ICI comms exposure models.

The planner must price thousands of candidate layouts per search, so
the formulas live in one importable library (as ``vmem_model`` holds
the VMEM formulas): ``apex1_tpu.planner.cost`` prices candidate layouts
through these functions, and `tools/tune_kernels.py` banks their
per-kernel figure beside each sweep. Fitted to nothing on the chip
(ROADMAP D7).

Everything here is jax-free at import (``core.capability`` is too):
the planner's legality/pricing path must run in light tools and the
``tools/lint.py``-style stub environments. The honesty contract on
every number: these are UPPER bounds on throughput (no bandwidth
derating, no scheduler gaps); calibration (``obs.calibrate``) is what
would correct them against banked silicon history.
"""

from __future__ import annotations

from typing import Optional

# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------


def roofline(flops, nbytes, cap, ici_exposed_bytes=0.0):
    """Predicted seconds + binding side for one program on one chip.

    ``ici_exposed_bytes``: ICI traffic NOT hidden behind compute — it
    ADDS to the roofline time (an overlapped transfer costs nothing
    here; an exposed one serializes). Priced at the conservative
    per-neighbor link rate (`core.capability.ici_link_gbps`). 0 for
    the single-chip bench rows."""
    from apex1_tpu.core.capability import ici_link_gbps

    t_mxu = flops / (cap.bf16_tflops * 1e12)
    t_hbm = nbytes / (cap.hbm_gbps * 1e9)
    t = max(t_mxu, t_hbm)
    bound = "MXU" if t_mxu >= t_hbm else "HBM"
    if ici_exposed_bytes:
        link = ici_link_gbps(cap.generation)
        t_ici = ici_exposed_bytes / (link * 1e9) if link else 0.0
        t = t + t_ici
        if t_ici > max(t_mxu, t_hbm):
            bound = "ICI"
    mfu = flops / (t * cap.bf16_tflops * 1e12) if t > 0 else 0.0
    return t, bound, mfu


# ---------------------------------------------------------------------------
# per-kernel analytic cases (the Pallas blind-spot table)
# ---------------------------------------------------------------------------


def flash_flops_bytes(B, Hq, Hkv, S, D, causal=True, grad=False):
    """Analytic (flops, min HBM bytes) for one flash-attention call —
    the formula block shared by `kernel_cases` and the planner's
    attention pricing (the factors: `kernel_cases`' docstring)."""
    f = 4 * B * Hq * S * S * D * (0.5 if causal else 1.0)
    if grad:
        # fwd (2 matmuls) + the SHIPPED two-pass backward: dq pass
        # recomputes p and dP then dq (3 matmuls), dkv pass
        # recomputes them again then dk, dv (4) — 7 bwd matmuls
        # total, NOT the fused-backward 5 an analytic count
        # assumes (Mosaic's output-revisiting rule forces the two
        # passes; see ops/attention.py). A
        # perfect kernel measured against the 5-matmul roofline
        # would read as ~0.78 and be mis-flagged as a tuning
        # target.
        f *= 4.5          # (2 + 7) / 2
    qb = B * Hq * S * D * 2
    kvb = 2 * B * Hkv * S * D * 2
    byt = qb + kvb + qb   # q, k, v in; o out
    if grad:
        byt += 2 * qb + kvb + qb   # dq out, dk/dv out, do in
    return f, byt


def elemwise_flops_bytes(n_elem, passes, itemsize, fpe):
    """Bandwidth-bound row kernels: flops-per-element x count, passes x
    element traffic."""
    return fpe * n_elem, passes * n_elem * itemsize


def kernel_cases():
    """ANALYTIC (flops, min HBM bytes) per Pallas kernel at its bench
    shape — shapes mirror tools/aot_check.py's kernel gate.

    Formulas (all counts: multiply-add = 2 flops; bytes = each operand
    and result crossing HBM once — the kernels are designed to touch
    operands once, so this IS the target):
    - flash attention fwd: 4*B*H*S^2*D matmul flops (QK^T + PV), x0.5
      causal skip; bwd = 2.5x fwd (dV/dP/dS/dQ/dK matmuls + the
      recomputed P the memory-efficient backward pays for). GQA K/V
      bytes scale by Hkv/Hq.
    - linear_xent f+b: 6*T*Hd*V (fwd logits + dX + dW); bytes 3 reads
      of W (fwd + recompute-bwd + dW stream) + x/dx/dw.
    - LN / RMS / softmax / rope / xentropy: bandwidth-bound, flops ~
      a few per element (counted as 5/elem fwd, 8/elem f+b — they
      never bind the roofline); bytes = per-pass element traffic
      (softmax f+b: x in, y out, then y + dy in, dx out; LN f+b: 2
      reads + 2 writes of x-sized arrays + stats).
    - int8 GEMM: 2*M*N*K flops; bytes dominated by the int8 weight
      (N*K) + scales + activations.
    """
    flash = flash_flops_bytes
    elemwise = elemwise_flops_bytes

    T, Hd, V = 16 * 1023, 768, 50432
    lx_f = linear_xent_flops(T, Hd, V)
    lx_b = 2 * (3 * V * Hd + 2 * T * Hd + V * Hd)  # W x3, x/dx, dW

    return [
        ("flash gpt2 (16,12,1024,64) fwd", *flash(16, 12, 12, 1024, 64)),
        ("flash gpt2 (16,12,1024,64) f+b",
         *flash(16, 12, 12, 1024, 64, grad=True)),
        ("flash longctx (1,32,16384,64) f+b",
         *flash(1, 32, 32, 16384, 64, grad=True)),
        ("flash GQA (Hq32/Hkv4,16k,64) f+b",
         *flash(1, 32, 4, 16384, 64, grad=True)),
        ("linear_xent gpt2 (16k,768,50k) f+b", lx_f, lx_b),
        ("layer_norm (16384,768) f+b",
         *elemwise(16384 * 768, 4, 2, 8)),
        ("rms_norm (16384,2048) f+b",
         *elemwise(16384 * 2048, 4, 2, 8)),
        ("causal softmax (16,12,1024,1024) f+b",
         *elemwise(16 * 12 * 1024 * 1024 // 2, 4, 4, 8)),
        ("xentropy (16368,50432) f+b",
         *elemwise(16368 * 50432, 3, 4, 8)),   # recompute-bwd: x, x, dx
        ("rope llama (1,16384,32,64) f+b",
         *elemwise(16384 * 32 * 64, 4, 2, 6)),
        ("int8 GEMM decode (8,4096)x(32000,4096)",
         2 * 8 * 32000 * 4096,
         32000 * 4096 * 1 + 32000 * 4 + 2 * 8 * (4096 + 32000) * 2),
    ]


def linear_xent_flops(T, Hd, V):
    """Fused LM-head CE fwd+bwd flops (logits + dX + dW) — the chunked
    kernel's arithmetic is the dense one's."""
    return 6 * T * Hd * V


# ---------------------------------------------------------------------------
# ICI comms exposure models
# ---------------------------------------------------------------------------


def ring_attention_comms(generation: str, n: int, *,
                         B: int = 1, Hq: int = 32, Hkv: int = 4,
                         S: int = 16384, D: int = 64
                         ) -> Optional[dict]:
    """Exposure model for the ring-attention CP path: per ring step the
    K/V shard transfer either serializes against the attend (the
    pre-overlap schedule) or hides behind it (the double-buffered
    schedule, hlo_probe-pinned). Returns None when the capability row
    carries no ICI figure. The planner prices candidate cp degrees
    through this math at its model's shape."""
    from apex1_tpu.core.capability import get_capability, ici_link_gbps

    cap = get_capability(generation)
    link = ici_link_gbps(generation)
    if not link:
        return None
    S_l = S // n
    kv_hop = 2 * B * Hkv * S_l * D * 2          # K+V bf16
    dkv_hop = 2 * B * Hkv * S_l * D * 4         # dK+dV fp32
    att = 4 * B * Hq * S_l * S_l * D * 0.5      # causal attend
    bwd = 2.5 * att
    t_hop_f = kv_hop / (link * 1e9)
    t_hop_b = (kv_hop + dkv_hop) / (link * 1e9)
    t_att = att / (cap.bf16_tflops * 1e12)
    t_bwd = bwd / (cap.bf16_tflops * 1e12)
    fwd_bytes = (n - 1) * kv_hop
    bwd_bytes = n * (kv_hop + dkv_hop)
    exp_f_overlap = (n - 1) * max(0.0, t_hop_f - t_att) * (link * 1e9)
    exp_b_overlap = n * max(0.0, t_hop_b - t_bwd) * (link * 1e9)
    return dict(
        generation=generation, cp=n, link_gbps=link,
        kv_hop=kv_hop, dkv_hop=dkv_hop,
        t_hop_f=t_hop_f, t_hop_b=t_hop_b, t_att=t_att, t_bwd=t_bwd,
        fwd_bytes=fwd_bytes, bwd_bytes=bwd_bytes,
        exp_f_overlap=exp_f_overlap, exp_b_overlap=exp_b_overlap)


def sp_boundary_comms(generation: str, n: int, *,
                      rows: int = 8192, local_k: Optional[int] = None,
                      out_width: int = 4096, ffn: int = 14336,
                      acc_bytes: int = 4,
                      hop_width: Optional[int] = None
                      ) -> Optional[dict]:
    """Exposure model for ONE Megatron-SP boundary matmul+collective
    (chunk-pipelined ppermute ring; docs/parallel.md "Fused
    comm-kernels"), across the three shipped schedules:

    - ``serial``   — every byte exposed (monolithic collective /
      rotate-then-dot negative control);
    - ``overlap``  — PR 4's ppermute ring AND the fused ppermute form:
      exposed = the per-hop residual the chunk dot cannot cover
      (BEST-case: assumes the scheduler hoists every permute);
    - ``fused``    — the single-kernel RDMA form: STRUCTURAL bound,
      exposed ≈ prologue hop (pipeline fill) + the same residual.

    Defaults are the llama-8B MLP row-parallel boundary
    (``predict_comms_fused``'s shape); the planner calls this per
    candidate layout with its own (rows, local_k, out_width).

    ``hop_width``: width of the TRAVELLING chunk. Default (None) =
    ``out_width`` — correct for matmul→reduce-scatter, where the fp32
    partial-result accumulator hops. For the all-gather→matmul dual
    the travelling data is the INPUT activation (width = the model
    dim, NOT the dot's output shard), so pass ``hop_width=E`` with
    ``acc_bytes`` = the activation dtype size.
    Returns None when the capability row carries no ICI figure."""
    from apex1_tpu.core.capability import get_capability, ici_link_gbps

    cap = get_capability(generation)
    link = ici_link_gbps(generation)
    if not link:
        return None
    if local_k is None:
        local_k = ffn // n
    chunk_rows = rows // n
    if hop_width is None:
        hop_width = out_width
    hop = chunk_rows * hop_width * acc_bytes      # travelling chunk
    dot = 2 * chunk_rows * local_k * out_width    # per-step MXU
    t_hop = hop / (link * 1e9)
    t_dot = dot / (cap.bf16_tflops * 1e12)
    total = n * hop
    resid = n * max(0.0, t_hop - t_dot) * (link * 1e9)
    fused_exposed = hop + resid                   # prologue hop
    return dict(
        generation=generation, tp=n, link_gbps=link,
        hop=hop, dot=dot, t_hop=t_hop, t_dot=t_dot,
        total=float(total),
        exposed_serial=float(total),
        exposed_overlap=float(resid),
        exposed_fused=float(fused_exposed))


def allreduce_bytes(nbytes: float, n: int) -> float:
    """Per-device ring all-reduce traffic for an ``nbytes`` buffer over
    ``n`` participants: reduce-scatter + all-gather, each moving
    (n-1)/n of the buffer through every device. The ZeRO split
    (reduce-scatter grads, all-gather updated params —
    `parallel.distributed_optimizer`) moves the same total, so one
    formula prices both the plain-dp and the zero layouts' gradient
    sync."""
    if n <= 1:
        return 0.0
    return 2.0 * nbytes * (n - 1) / n


# ---------------------------------------------------------------------------
# serving-config pricing (ISSUE 15: the goodput-multiplier arithmetic)
# ---------------------------------------------------------------------------


def kv_cache_bytes(num_layers: int, num_kv_heads: int, head_dim: int,
                   positions: int, batch: int = 1,
                   bytes_per_el: int = 2) -> int:
    """HBM bytes of a K/V cache pytree (`models.generate.init_cache`
    layout: K + V per layer, ``(batch, positions, Hkv * D)`` each) — the
    analytic mirror of `serving.KVPool.pool_bytes`, jax-free so the
    planner/bench can size pools without building one. ``bytes_per_el``
    2 = bf16 (the default compute dtype), 1 = the int8 capacity tier,
    4 = fp32 test configs."""
    return (2 * int(num_layers) * int(batch) * int(num_kv_heads)
            * int(positions) * int(head_dim) * int(bytes_per_el))


def serving_capacity(hbm_budget_bytes: float, num_layers: int,
                     num_kv_heads: int, head_dim: int, pool_len: int,
                     bytes_per_el: int = 2) -> int:
    """Resident batch (engine ``max_slots``) a KV-pool HBM budget buys:
    ``budget // bytes-per-slot``. The int8 tier's headline is this
    function at ``bytes_per_el=1`` — double the slots for the same
    budget — which is capacity, not correctness: the dtype-flip parity
    drills are what license flipping it on."""
    per_slot = kv_cache_bytes(num_layers, num_kv_heads, head_dim,
                              pool_len, 1, bytes_per_el)
    if per_slot <= 0:
        raise ValueError("per-slot KV bytes must be positive")
    return int(hbm_budget_bytes // per_slot)


def speculative_speedup(accept_rate: float, num_draft: int,
                        verify_cost: float = 1.0,
                        draft_cost: float = 0.0) -> float:
    """Expected decode-dispatch speedup of the engine's speculative
    mode: tokens emitted per verify round over its relative cost.

    Per-position independent accept probability ``r`` gives
    ``E[tokens/round] = 1 + r + r^2 + ... + r^K`` (the accepted prefix
    is geometric, truncated at K drafts, plus the always-emitted
    correction/bonus token). ``verify_cost`` is one (K+1)-wide chunk
    verify relative to one plain decode step (~1 on TPU decode, which
    is weight-streaming-bound: the same weights stream either way);
    ``draft_cost`` is the per-draft-token proposal cost (0 for the
    host-side n-gram default). An UPPER bound, like every number in
    this module — a measured accept rate is what calibrates it."""
    if not 0.0 <= accept_rate <= 1.0:
        raise ValueError(f"accept_rate must be in [0, 1], "
                         f"got {accept_rate}")
    if num_draft < 1:
        raise ValueError(f"num_draft must be >= 1, got {num_draft}")
    tokens = sum(accept_rate ** j for j in range(num_draft + 1))
    cost = float(verify_cost) + num_draft * float(draft_cost)
    if cost <= 0:
        raise ValueError("round cost must be positive")
    return tokens / cost
