"""Mixture-of-Experts with expert parallelism over the ``ep`` mesh axis.

**Beyond-reference capability** (SURVEY.md §2.6 marks EP *[absent]* in
apex): provided because expert parallelism is a first-class distributed
strategy on TPU pods. The design is the canonical TPU MoE dataflow
(Mesh-TensorFlow / Switch-Transformer lineage, via PAPERS.md patterns):

- **Router**: dense gate → softmax → top-k (k ∈ {1, 2}); combine weights
  renormalized over the selected experts; Switch-style load-balance aux
  loss ``E · Σ_e f_e · p̄_e`` (fraction routed × mean prob).
- **Capacity-based dispatch**: each expert processes at most
  ``capacity = ceil(k · T / E · capacity_factor)`` tokens; overflow
  tokens are DROPPED from that expert (identity residual still carries
  them — Switch semantics). Dispatch/combine are one-hot einsum tensors,
  so the whole layer is static-shaped and MXU-friendly — no sorting, no
  dynamic shapes under jit.
- **Expert parallelism**: two forms, same math:
  1. **GSPMD**: stacked expert weights (E, ...) sharded over ``ep`` via
     `param_specs`; XLA inserts the all-to-alls.
  2. **Explicit shard_map** (`moe_shard_map_apply`): tokens sharded over
     ``ep``; ``jax.lax.all_to_all`` routes (expert, capacity) slots to
     the expert's device and back — the NCCL-alltoall dataflow the
     reference never had, on ICI.

**The dropless layer** (`dropless_route`, `expert_rows`, `held_experts_mlp`)
is the other dataflow, the one today's large sparse models are served with:
no capacity, no token dropped, rows of one batch independent of each
other. The router's form is DATA (`RouteConfig`: softmax or sigmoid
scores, an optional bias that chooses but never weighs, optional
normalisation of the kept weights, a scale), not a model's name. The
expert layer is TOLD which experts it holds (``held``, a range of expert
ids: this chip's share of an expert-parallel deployment): it routes over
all of them, keeps the (row, expert) pairs whose expert it holds, sorts
them by expert into one frame (`expert_rows`: a counting sort, groups
padded to `ops.moe_experts.ROW_TILE`), runs the grouped product
(`ops.moe_experts`) and adds each row's weighted results back. What the
experts held elsewhere would add is LEFT OUT: on one chip the layer runs
without its exchange, and nothing stands in for the absent chips. The sum
of the parts that every share gives is the whole layer
(`tests/test_moe_dropless.py`). A SHARED expert (`shared_expert_mlp`: one
that every token passes through, beside the routed ones) is not a share:
every chip holds it whole and computes it for its own tokens, so where
the shares' parts are summed it is counted ONCE (`tests/test_afmoe.py`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex1_tpu.core.mesh import AXIS_EP
from apex1_tpu.ops.moe_experts import ROW_TILE, moe_experts, padded_rows


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2                 # 1 = Switch, 2 = GShard-style
    capacity_factor: float = 1.25
    hidden_size: int = 64
    ffn_size: int = 256
    aux_loss_weight: float = 1e-2


def _capacity(cfg: MoEConfig, n_tokens: int) -> int:
    # ceil, per the docstring: capacity_factor=1.0 must not drop tokens
    # under perfectly balanced routing
    cap = math.ceil(cfg.capacity_factor * cfg.top_k * n_tokens
                    / cfg.num_experts)
    return max(1, cap)


def router(x2, wg, cfg: MoEConfig, token_mask=None, *, stats_axes=None):
    """Top-k routing for flat tokens ``x2`` (T, H) with gate ``wg`` (H, E).

    Returns ``(dispatch (T, E, C) bool-as-float, combine (T, E, C) float,
    aux_loss scalar)``. Everything static-shaped: position-in-expert is a
    masked cumsum, tokens beyond capacity get zero dispatch/combine.
    ``token_mask`` (T,) bool: False tokens (padding in packed batches)
    claim no capacity and are excluded from the load-balance statistics.

    ``stats_axes``: mesh axis name(s) that shard ONE logical batch's
    tokens across callers (tp sequence shards, ep/dp token subsets, cp
    sequence shards). The Switch aux statistics (assignment fraction f,
    mean router prob p) are then ``psum``-combined over those axes before
    forming ``Σ f·p``, so every rank returns the aux loss of the GLOBAL
    token set — matching the unpartitioned model exactly (Σ f·p is
    nonlinear in the per-shard means, so summing per-shard aux would
    not). Dispatch/combine stay local; capacity is per-shard.
    """
    T = x2.shape[0]
    E, k = cfg.num_experts, cfg.top_k
    C = _capacity(cfg, T)
    logits = (x2.astype(jnp.float32) @ wg.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)            # (T, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)      # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    mask = (jnp.ones((T,), jnp.float32) if token_mask is None
            else token_mask.astype(jnp.float32))

    # Switch aux loss over the TOP-1 assignment fraction (valid tokens)
    top1_hot = jax.nn.one_hot(gate_idx[:, 0], E) * mask[:, None]
    n_sum = jnp.sum(mask)
    f_sum = jnp.sum(top1_hot, axis=0)                  # count per expert
    p_sum = jnp.sum(probs * mask[:, None], axis=0)     # prob mass
    if stats_axes is not None:
        n_sum = jax.lax.psum(n_sum, stats_axes)
        f_sum = jax.lax.psum(f_sum, stats_axes)
        p_sum = jax.lax.psum(p_sum, stats_axes)
    n_valid = jnp.maximum(n_sum, 1.0)
    f = f_sum / n_valid                                # fraction per expert
    p = p_sum / n_valid                                # mean prob
    aux = cfg.aux_loss_weight * E * jnp.sum(f * p)

    dispatch = jnp.zeros((T, E, C), jnp.float32)
    combine = jnp.zeros((T, E, C), jnp.float32)
    # priority: k-th choices claim capacity after all (k-1)-th choices —
    # GShard ordering; positions via exclusive cumsum per expert
    used = jnp.zeros((E,), jnp.float32)
    for j in range(k):
        hot = jax.nn.one_hot(gate_idx[:, j], E) * mask[:, None]  # (T, E)
        pos = (jnp.cumsum(hot, axis=0) - hot) + used[None, :]  # (T, E)
        within = (pos < C) & (hot > 0)
        pos_c = jax.nn.one_hot(pos.astype(jnp.int32), C) * within[..., None]
        dispatch = dispatch + hot[..., None] * pos_c
        combine = combine + (gate_vals[:, j, None, None]
                             * hot[..., None] * pos_c)
        used = used + jnp.sum(hot * within, axis=0)
    return dispatch, combine, aux


class MoEMLP(nn.Module):
    """Dense-dispatch MoE FFN (GSPMD form): stacked expert weights
    (E, H, F)/(E, F, H); shard dim 0 over ``ep`` via `param_specs` and
    pjit does the rest. Returns ``(y, aux_loss)``."""

    cfg: MoEConfig
    dtype: jnp.dtype = jnp.float32
    act: Callable = jax.nn.gelu

    @nn.compact
    def __call__(self, x, token_mask=None):
        cfg = self.cfg
        lead = x.shape[:-1]
        H = x.shape[-1]
        x2 = x.reshape(-1, H)
        if token_mask is not None:
            token_mask = token_mask.reshape(-1)
        init = nn.initializers.normal(0.02)
        wg = self.param("router", init, (H, cfg.num_experts), jnp.float32)
        w1 = self.param("w1", init, (cfg.num_experts, H, cfg.ffn_size),
                        jnp.float32)
        w2 = self.param("w2", init, (cfg.num_experts, cfg.ffn_size, H),
                        jnp.float32)
        dispatch, combine, aux = router(x2, wg, cfg, token_mask)
        xe = jnp.einsum("tec,th->ech", dispatch.astype(self.dtype),
                        x2.astype(self.dtype))          # (E, C, H)
        h = self.act(jnp.einsum("ech,ehf->ecf", xe,
                                w1.astype(self.dtype)))
        ye = jnp.einsum("ecf,efh->ech", h, w2.astype(self.dtype))
        y = jnp.einsum("tec,ech->th", combine.astype(self.dtype), ye)
        return y.reshape(*lead, H).astype(x.dtype), aux


def param_specs(params, *, axis=AXIS_EP):
    """PartitionSpecs for a `MoEMLP` param tree: expert-stacked weights
    shard dim 0 over ``ep``; the router stays replicated."""
    from apex1_tpu.parallel.specs import specs_from_rules
    return specs_from_rules(
        params, ((r"w[12]$", P(axis, None, None)),), default=P())


def moe_shard_map_apply(x_local, wg, w1_local, w2_local, cfg: MoEConfig,
                        *, axis_name=AXIS_EP, act=jax.nn.gelu,
                        token_mask=None, stats_axes=None):
    """Explicit expert-parallel dataflow — call inside ``shard_map`` with
    tokens sharded over ``axis_name`` (x_local: (T_local, H)) and expert
    weights sharded over dim 0 (w1_local: (E_local, H, F)).

    Per device: route the LOCAL tokens against all E experts, build the
    local dispatch (T_l, E, C_l from the local token count), then
    ``all_to_all`` the (E, C_l, H) expert inputs so each device holds its
    own experts' slots from EVERY device — (E_l, ep·C_l, H) — runs its
    expert FFNs, and all_to_alls back. Two all-to-alls per layer over
    ICI, ≙ the NCCL alltoall in GPU MoE stacks.
    """
    ep = jax.lax.axis_size(axis_name)
    E = cfg.num_experts
    if E % ep:
        raise ValueError(f"num_experts {E} must divide by ep={ep}")
    dispatch, combine, aux = router(x_local, wg, cfg, token_mask,
                                    stats_axes=stats_axes)  # (T_l, E, C_l)
    dtype = x_local.dtype
    xe = jnp.einsum("tec,th->ech", dispatch.astype(dtype), x_local)
    # (E, C_l, H) -> split expert axis across devices, gather capacity:
    # each device ends with (E_l, ep*C_l, H)
    xe = jax.lax.all_to_all(xe, axis_name, split_axis=0, concat_axis=1,
                            tiled=True)
    h = act(jnp.einsum("ech,ehf->ecf", xe, w1_local.astype(dtype)))
    ye = jnp.einsum("ecf,efh->ech", h, w2_local.astype(dtype))
    # route results back: split capacity, gather experts
    ye = jax.lax.all_to_all(ye, axis_name, split_axis=1, concat_axis=0,
                            tiled=True)
    y = jnp.einsum("tec,ech->th", combine.astype(dtype), ye)
    # aux is a per-shard mean over local tokens; callers pmean it
    return y, aux


# ---- the dropless layer ---------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RouteConfig:
    """A dropless router, as data: ``top_k`` of ``num_experts``; scores
    ``softmax`` or ``sigmoid`` of the gate's outputs; the experts CHOSEN
    by score + bias where a bias is given (``select_bias``), the weights
    always the unbiased scores at the chosen; divided by their sum
    (``normalize``); times ``scale``."""

    num_experts: int
    top_k: int
    score: str = "softmax"
    select_bias: bool = False
    normalize: bool = True
    scale: float = 1.0

    def __post_init__(self):
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"score {self.score!r}: softmax or sigmoid")
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k {self.top_k} of {self.num_experts}")


def dropless_route(x2, wg, bias, cfg: RouteConfig):
    """``x2`` (T, H) rows, ``wg`` (H, E) the gate, ``bias`` (E,) or None:
    ``(experts (T, k) int32, weights (T, k) float32)``, the experts in
    the order `lax.top_k` gives them. All of it in float32, the gate's
    product at full precision: a TPU's default would round both operands
    to bfloat16, and a choice between two experts turns on the last
    bits."""
    if (bias is not None) != cfg.select_bias:
        raise ValueError("a selection bias is given exactly where the "
                         "router's form has one")
    logits = jnp.dot(x2.astype(jnp.float32), wg.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.sigmoid(logits) if cfg.score == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    chosen_by = scores if bias is None else scores + bias.astype(jnp.float32)
    _, experts = jax.lax.top_k(chosen_by, cfg.top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.normalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), weights * cfg.scale


def expert_rows(experts, held: range, live=None):
    """Where each (row, expert) pair of a routing lies in the grouped
    frame of the experts ``held``: a counting sort by expert, stable in
    the row.

    ``experts`` (T, k) from `dropless_route`; ``live`` (T,) bool or
    None: a row that is padding, or an idle lane, is not routed
    and claims no row. Returns ``(dest (T, k) int32, starts (E_held,),
    counts (E_held,))``: the frame's row of each pair, -1 for a pair that
    is not computed here; each held expert's first row, a multiple of
    `ROW_TILE`, and how many rows it has."""
    n = len(held)
    local = experts - held.start
    here = (local >= 0) & (local < n)
    if live is not None:
        here = here & live[:, None]
    group = jnp.where(here, local, n).reshape(-1)               # (T k,)
    hot = (group[:, None] == jnp.arange(n, dtype=jnp.int32)).astype(
        jnp.int32)                                              # (T k, n)
    rank = jnp.cumsum(hot, axis=0) - hot       # pairs of the group before
    counts = jnp.sum(hot, axis=0)
    padded = -(-counts // ROW_TILE) * ROW_TILE
    starts = jnp.cumsum(padded) - padded
    dest = jnp.sum(hot * (starts[None, :] + rank), axis=1)
    dest = jnp.where(here.reshape(-1), dest, -1).reshape(experts.shape)
    return dest.astype(jnp.int32), starts.astype(jnp.int32), \
        counts.astype(jnp.int32)


def held_experts_mlp(x2, experts, weights, w1, w3, w2, held: range,
                     live=None):
    """The part of a dropless mixture that the experts ``held`` give:
    ``y_t = sum over the chosen experts e of t that are held of weights[t,
    e] * W2[e] (silu(W1[e]^T x_t) * (W3[e]^T x_t))``. ``w1`` / ``w3``
    (E_held, H, F), ``w2`` (E_held, F, H): the held experts' matrices
    alone. Returns ``(y (T, H) of x2's dtype, counts (2,) int32)``: the
    pairs computed here, and the held experts with at least one.

    The sort is applied as a product with a 0/1 matrix (exact: one term a
    row), and so is the way back, which sums a token's up to k weighted
    rows in float32. That is R x T x H operations each way: microseconds
    at a serving batch's few hundred rows. A caller with many thousands
    of rows (a training step) wants a gather and a segment sum here."""
    dest, starts, counts = expert_rows(experts, held, live)
    # a token's chosen experts are distinct: at most min(k, held) here
    R = padded_rows(x2.shape[0] * min(experts.shape[1], len(held)),
                    len(held))
    place = dest[None, :, :] == jnp.arange(R, dtype=jnp.int32)[:, None, None]
    row_of = jnp.any(place, axis=-1)                            # (R, T)
    gains = jnp.sum(jnp.where(place, weights[None], 0.0), axis=(1, 2))
    # exact in the rows' own type: float32 rows want the full product
    exact = (jax.lax.Precision.HIGHEST if x2.dtype == jnp.float32 else None)
    x_rows = jnp.dot(row_of.astype(x2.dtype), x2, precision=exact,
                     preferred_element_type=jnp.float32).astype(x2.dtype)
    y_rows = moe_experts(x_rows, gains, w1, w3, w2, starts, counts)
    y = jnp.dot(row_of.T.astype(y_rows.dtype), y_rows, precision=exact,
                preferred_element_type=jnp.float32)
    return y.astype(x2.dtype), jnp.stack(
        [jnp.sum(counts), jnp.sum(counts > 0)]).astype(jnp.int32)


def shared_expert_mlp(x2, w1, w3, w2):
    """The expert every token passes through, unrouted and unweighted:
    ``W2 (silu(W1^T x) * (W3^T x))`` over ``x2`` (T, H), ``w1`` / ``w3``
    (H, F), ``w2`` (F, H). A layer's result is this plus the routed part
    (`held_experts_mlp`); in an expert-parallel deployment every chip
    holds the shared expert whole, so the sum of the chips' routed parts
    takes it once, not once a chip."""
    up = jax.nn.silu(jnp.dot(x2, w1, preferred_element_type=jnp.float32)) \
        * jnp.dot(x2, w3, preferred_element_type=jnp.float32)
    return jnp.dot(up.astype(x2.dtype), w2,
                   preferred_element_type=jnp.float32).astype(x2.dtype)
