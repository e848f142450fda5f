"""Plain reference optimizers, float32 `jax.numpy`, nothing imported from
the program under test. They follow the published update rules that the
reference library's fused optimizers document:

- ``adam``: Adam with decoupled weight decay (Loshchilov & Hutter 2019;
  apex `FusedAdam(adam_w_mode=True)`), bias correction on.
- ``lamb``: LAMB (You et al. 2020) as apex `FusedLAMB` states it: gradients
  first divided by max(1, ||g||_global / max_grad_norm), Adam moments with
  bias correction, decoupled weight decay added to the update, then each
  tensor's update scaled by ||w|| / ||update|| (1 where either is 0).

State is ``{"step", "m", "v"}``; ``update`` returns (new_params, state).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

tmap = jax.tree_util.tree_map


def init(params):
    zeros = tmap(jnp.zeros_like, params)
    return {"step": 0, "m": zeros, "v": tmap(jnp.zeros_like, params)}


def _moments(g, state, b1, b2):
    step = state["step"] + 1
    m = tmap(lambda m, g: b1 * m + (1 - b1) * g, state["m"], g)
    v = tmap(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], g)
    return step, m, v, 1 - b1 ** step, 1 - b2 ** step


def adam(params, grads, state, *, lr, b1=0.9, b2=0.999, eps=1e-8,
         weight_decay=0.0):
    step, m, v, bc1, bc2 = _moments(grads, state, b1, b2)

    def upd(p, m, v):
        u = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + weight_decay * p
        return p - lr * u

    return tmap(upd, params, m, v), {"step": step, "m": m, "v": v}


def lamb(params, grads, state, *, lr, b1=0.9, b2=0.999, eps=1e-6,
         weight_decay=0.01, max_grad_norm=1.0):
    gnorm = jnp.sqrt(sum(jnp.sum(g * g)
                         for g in jax.tree_util.tree_leaves(grads)))
    clip = jnp.maximum(1.0, gnorm / max_grad_norm)
    grads = tmap(lambda g: g / clip, grads)
    step, m, v, bc1, bc2 = _moments(grads, state, b1, b2)

    def upd(p, m, v):
        u = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + weight_decay * p
        wn, un = jnp.sqrt(jnp.sum(p * p)), jnp.sqrt(jnp.sum(u * u))
        ratio = jnp.where((wn > 0) & (un > 0), wn / un, 1.0)
        return p - lr * ratio * u

    return tmap(upd, params, m, v), {"step": step, "m": m, "v": v}


OPTIMIZERS = {"adam": adam, "lamb": lamb}


def first_gradient(state, b1=0.9):
    """The gradient as the optimizer got it in its first step, recovered
    from the state after that step: m_1 = (1 - b1) * g."""
    return tmap(lambda m: m / (1 - b1), state["m"])
