"""The lower-precision CONTROL of the benchmark's correctness check, shared
by the plain references: the same mathematics with every matmul operand
rounded through the next precision below the one the configurations state
(float8_e4m3fn below bfloat16), and the gradient reaching that operand
rounded through the type such a recipe keeps gradients in (float8_e5m2).
Plain `jax.numpy`; nothing of the program under test."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: the control's types: matmul operands on the way forward, and gradients
CONTROL_FORWARD = jnp.dtype(jnp.float8_e4m3fn)
CONTROL_GRAD = jnp.dtype(jnp.float8_e5m2)


def _round(x, quant):
    """`x` rounded through the floating type `quant`. A narrow type gets a
    per-tensor scale so the tensor fills its range, as a real fp8 matmul
    path does."""
    top = float(jnp.finfo(quant).max)
    scale = 1.0
    if top < 1e6:
        scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(quant).astype(jnp.float32) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _q_low(x, quant):
    return _round(x, quant)


def _q_low_fwd(x, quant):
    return _round(x, quant), None


def _q_low_bwd(quant, _, g):
    grad_type = CONTROL_GRAD if jnp.dtype(quant) == CONTROL_FORWARD else quant
    return (_round(g, grad_type),)


_q_low.defvjp(_q_low_fwd, _q_low_bwd)


def q(x, quant):
    """`x` unchanged where `quant` is None (the sound reference); else
    rounded through `quant` on the way forward, and the gradient reaching
    it rounded too (through CONTROL_GRAD beside CONTROL_FORWARD)."""
    return x if quant is None else _q_low(x, quant)
