"""Fused dense layers — reference ``apex/fused_dense/fused_dense.py ::
FusedDense, FusedDenseGeluDense`` (+ ``csrc/fused_dense*.cu``) and
``apex/mlp/mlp.py :: MLP`` (+ ``csrc/mlp*.cu``).

**Documented "XLA already fuses this" decision (SURVEY.md §7.0):** the
reference needs cuBLASLt epilogue fusion (``CUBLASLT_EPILOGUE_{BIAS,
GELU_AUX_BIAS,DGELU_BGRAD}``) and a bespoke GEMM-chain kernel because eager
torch launches matmul/bias/activation as separate kernels. Under XLA the
matmul lands on the MXU and the bias/GELU/ReLU epilogues are fused into its
output stage by the compiler — a hand-written Pallas GEMM would have to beat
XLA's own matmul emitter to win, which is expected not to happen for plain
dense shapes. No roofline A/B (achieved TFLOP/s against the MXU peak) has
run — the decision rests on the
architecture argument plus AOT lowering checks, not a measurement. So these
are thin modules with the reference's API over ``jnp`` compute, with fp32
MXU accumulation (``preferred_element_type``) matching the reference's
fp16-in/fp32-accumulate GEMMs. The backward (dgelu+bgrad, wgrad chain) is
jax AD, which XLA fuses the same way.

**The documented exception — gated MLPs (``fused_glu``):** llama-family
SwiGLU/GeGLU is ``act(x @ w_gate) * (x @ w_up)`` — TWO matmuls sharing one
``x`` whose outputs meet in an elementwise product. XLA schedules them as
two independent GEMMs, so ``x`` streams from HBM twice and the (T, F)
``gate`` product round-trips through HBM before the multiply. The Pallas
kernel below computes both dots and the glu product per (block_t, block_f)
tile in one pass over ``x`` — the arXiv 2502.17728 operation-fusion point.
H is deliberately NOT tiled (one MXU dot per operand per tile), so the
per-element reduction order matches the unfused XLA dot and the parity
check can be exact. The composite path IS the inline llama expression,
token-for-token, so routing `models/llama.py` through ``fused_glu`` is
bitwise-neutral on the CPU proxy (asserted in tests/test_fused_glu.py).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex1_tpu.ops._common import (interpret_mode, kernel_call, out_struct,
                                   pad_to, to_mosaic, use_pallas)

_LANES = 128


def fused_dense(x, weight, bias=None):
    """y = x @ Wᵀ + b. ``weight`` is (out, in) — torch convention, like the
    reference's ``FusedDenseFunc``."""
    y = jnp.matmul(x, weight.T, preferred_element_type=jnp.float32)
    if bias is not None:
        y = y + bias
    return y.astype(x.dtype)


def fused_dense_gelu_dense(x, w1, b1, w2, b2):
    """Linear+bias+GELU+Linear+bias in one traced region (reference
    ``FusedDenseGeluDenseFunc``); XLA fuses the epilogues."""
    h = fused_dense(x, w1, b1)
    h = jax.nn.gelu(h, approximate=True)
    return fused_dense(h, w2, b2)


class FusedDense(nn.Module):
    """``apex.fused_dense.FusedDense(in_features, out_features, bias)``."""

    in_features: int
    out_features: int
    bias: bool = True

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.lecun_normal(),
                       (self.out_features, self.in_features), jnp.float32)
        b = (self.param("bias", nn.initializers.zeros,
                        (self.out_features,), jnp.float32)
             if self.bias else None)
        return fused_dense(x, w.astype(x.dtype),
                           None if b is None else b.astype(x.dtype))


class FusedDenseGeluDense(nn.Module):
    """``apex.fused_dense.FusedDenseGeluDense(in, intermediate, out)``."""

    in_features: int
    intermediate_features: int
    out_features: int
    bias: bool = True

    @nn.compact
    def __call__(self, x):
        k = nn.initializers.lecun_normal()
        w1 = self.param("weight1", k, (self.intermediate_features,
                                       self.in_features), jnp.float32)
        w2 = self.param("weight2", k, (self.out_features,
                                       self.intermediate_features),
                        jnp.float32)
        b1 = b2 = None
        if self.bias:
            b1 = self.param("bias1", nn.initializers.zeros,
                            (self.intermediate_features,), jnp.float32)
            b2 = self.param("bias2", nn.initializers.zeros,
                            (self.out_features,), jnp.float32)
        cast = lambda t: None if t is None else t.astype(x.dtype)
        return fused_dense_gelu_dense(x, cast(w1), cast(b1), cast(w2),
                                      cast(b2))


_ACTIVATIONS: dict[str, Optional[Callable]] = {
    "none": None,
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
}


# ---------------------------------------------------------------------------
# Fused SwiGLU / GeGLU — the gated-MLP exception to "XLA already fuses this"
# ---------------------------------------------------------------------------

_GLU_ACTS: dict[str, Callable] = {
    "silu": jax.nn.silu,                                    # SwiGLU (llama)
    "gelu": functools.partial(jax.nn.gelu, approximate=True),  # GeGLU
}


def _ceil_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def check_glu_geometry(block_t: int, block_f: int, hidden: int, *,
                       es: int = 4) -> tuple[int, int]:
    """Validate a fused-glu tile LOUDLY at trace time (the
    `ops.paged_decode.check_paged_geometry` contract): misaligned or
    over-budget tiles raise with the priced estimate instead of falling
    back silently and OOMing Mosaic on silicon."""
    if block_t < 8 or block_t % 8:
        raise ValueError(
            f"fused_glu: block_t={block_t} must be a multiple of 8 "
            f"(sublane tiling)")
    if block_f < _LANES or block_f % _LANES:
        raise ValueError(
            f"fused_glu: block_f={block_f} must be a multiple of {_LANES}")
    from apex1_tpu.vmem_model import CHECKS, budget_bytes
    hp = _ceil_to(hidden, _LANES)
    ok, est = CHECKS["fused_swiglu"](
        {"block_t": block_t, "block_f": block_f}, {"Hp": hp}, es,
        budget_bytes())
    if not ok:
        raise ValueError(
            f"fused_glu: blocks ({block_t}, {block_f}) at Hp={hp} price "
            f"at ~{est} B of VMEM > budget {budget_bytes()} B; shrink the "
            f"tile or re-tune (tools/tune_kernels.py)")
    return block_t, block_f


def _auto_glu_blocks(T, F, hidden, block_t, block_f, dtype):
    """Explicit > tuning table > shrink-to-fit heuristic (docs/ops.md)."""
    es = jnp.dtype(dtype).itemsize
    if block_t is not None or block_f is not None:
        return check_glu_geometry(int(block_t or 128), int(block_f or 256),
                                  hidden, es=es)
    hp = _ceil_to(hidden, _LANES)
    from apex1_tpu import tuning
    hit = tuning.lookup("fused_swiglu", {"Hp": hp}, dtype)
    if hit is not None:
        try:
            return check_glu_geometry(int(hit["block_t"]),
                                      int(hit["block_f"]), hidden, es=es)
        except (KeyError, ValueError):
            pass  # fail-safe: stale table entries fall back to heuristic
    from apex1_tpu.vmem_model import CHECKS, budget_bytes
    bt = min(128, max(8, _ceil_to(T, 8)))
    bf = min(512, max(_LANES, _ceil_to(F, _LANES)))
    while bf > _LANES and not CHECKS["fused_swiglu"](
            {"block_t": bt, "block_f": bf}, {"Hp": hp}, es,
            budget_bytes())[0]:
        bf //= 2
    while bt > 8 and not CHECKS["fused_swiglu"](
            {"block_t": bt, "block_f": bf}, {"Hp": hp}, es,
            budget_bytes())[0]:
        bt //= 2
    return check_glu_geometry(bt, bf, hidden, es=es)


def _glu_kernel(x_ref, g_ref, u_ref, o_ref, *, activation):
    # ONE full-H dot per operand (H is never split across grid steps),
    # so each output element's reduction order matches the unfused dot.
    x = x_ref[...]
    g = jax.lax.dot_general(x, g_ref[...], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    u = jax.lax.dot_general(x, u_ref[...], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[...] = (_GLU_ACTS[activation](g) * u).astype(o_ref.dtype)


def _glu_call(x2, wg, wu, activation, bt, bf):
    T, H = x2.shape
    F = wg.shape[1]
    xm, wgm, wum = to_mosaic(x2, wg, wu)
    xp, _ = pad_to(xm, 0, bt)
    xp, _ = pad_to(xp, 1, _LANES)
    Hp = xp.shape[1]
    wgp, _ = pad_to(wgm, 0, Hp)
    wgp, _ = pad_to(wgp, 1, bf)
    wup, _ = pad_to(wum, 0, Hp)
    wup, _ = pad_to(wup, 1, bf)
    Tp, Fp = xp.shape[0], wgp.shape[1]
    out = kernel_call(
        functools.partial(_glu_kernel, activation=activation),
        name="glu",
        grid=(Tp // bt, Fp // bf),
        in_specs=[
            pl.BlockSpec((bt, Hp), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((Hp, bf), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((Hp, bf), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bt, bf), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=out_struct((Tp, Fp), xm.dtype, xm, wgm, wum),
        interpret=interpret_mode(),
    )(xp, wgp, wup)
    return out[:T, :F].astype(x2.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _glu_fused(x2, wg, wu, activation, bt, bf):
    return _glu_fwd(x2, wg, wu, activation, bt, bf)[0]


def _glu_fwd(x2, wg, wu, activation, bt, bf):
    return _glu_call(x2, wg, wu, activation, bt, bf), (x2, wg, wu)


def _glu_bwd(activation, bt, bf, res, dy):
    # Recompute-in-VJP: the fp32 gate/up activations are never saved —
    # the residuals are just the operands (the Liger/chunked-loss play).
    x2, wg, wu = res
    act = _GLU_ACTS[activation]
    xf = x2.astype(jnp.float32)
    wgf = wg.astype(jnp.float32)
    wuf = wu.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    g = xf @ wgf
    u = xf @ wuf
    a, act_vjp = jax.vjp(act, g)
    du = dyf * a
    dg = act_vjp(dyf * u)[0]
    dx = dg @ wgf.T + du @ wuf.T
    return (dx.astype(x2.dtype), (xf.T @ dg).astype(wg.dtype),
            (xf.T @ du).astype(wu.dtype))


_glu_fused.defvjp(_glu_fwd, _glu_bwd)


def fused_glu(x, w_gate, w_up, *, activation: str = "silu",
              block_t: int | None = None, block_f: int | None = None):
    """``act(x @ w_gate) * (x @ w_up)`` in one pass over ``x``.

    ``x`` (..., H); ``w_gate``/``w_up`` (H, F) — the (in, out) layout
    `models/llama.py` stores (NOT the torch (out, in) of `fused_dense`).
    ``activation``: "silu" (SwiGLU) | "gelu" (GeGLU, tanh approximation).
    Returns (..., F) in ``x.dtype``; the down projection stays an
    ordinary XLA matmul (a lone GEMM is exactly what the module
    docstring says not to hand-write).

    The XLA path is token-for-token the inline llama expression, so the
    `LlamaConfig.fused_mlp` flag is bitwise-neutral off-TPU; the Pallas
    path computes fp32 tiles with an XLA-identical reduction order.
    Differentiable via a recompute VJP (gate/up activations never saved).
    """
    if activation not in _GLU_ACTS:
        raise ValueError(f"fused_glu: activation must be one of "
                         f"{sorted(_GLU_ACTS)}, got {activation!r}")
    act = _GLU_ACTS[activation]
    if not use_pallas():
        return (act(x @ w_gate) * (x @ w_up)).astype(x.dtype)
    lead = x.shape[:-1]
    H = x.shape[-1]
    x2 = x.reshape(-1, H)
    bt, bf = _auto_glu_blocks(x2.shape[0], w_gate.shape[1], H,
                              block_t, block_f, x.dtype)
    out = _glu_fused(x2, w_gate, w_up, activation, bt, bf)
    return out.reshape(*lead, w_gate.shape[1])


class MLP(nn.Module):
    """``apex.mlp.MLP(mlp_sizes, bias=True, relu=True)`` equivalent.

    A stack of Linear(+bias)(+activation) layers evaluated as one traced
    region — the reference fuses the chain into one autograd node
    (``MlpFunction``) over cuBLAS calls; here the whole chain is one XLA
    fusion domain. ``activation``: "none" | "relu" | "sigmoid" (reference
    flags). No activation after the final layer, matching the reference.
    """

    mlp_sizes: Sequence[int]
    bias: bool = True
    activation: str = "relu"

    @nn.compact
    def __call__(self, x):
        if len(self.mlp_sizes) < 2:
            raise ValueError("mlp_sizes needs >= 2 entries")
        act = _ACTIVATIONS[self.activation]
        k = nn.initializers.lecun_normal()
        h = x
        for i, (fan_in, fan_out) in enumerate(
                zip(self.mlp_sizes[:-1], self.mlp_sizes[1:])):
            w = self.param(f"weight_{i}", k, (fan_out, fan_in),
                           jnp.float32)
            b = (self.param(f"bias_{i}", nn.initializers.zeros,
                            (fan_out,), jnp.float32)
                 if self.bias else None)
            h = fused_dense(h, w.astype(h.dtype),
                            None if b is None else b.astype(h.dtype))
            if act is not None and i < len(self.mlp_sizes) - 2:
                h = act(h)
        return h
