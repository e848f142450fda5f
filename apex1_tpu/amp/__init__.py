"""Mixed-precision training services — reference ``apex/amp``.

The reference's ``amp.initialize(model, optimizer, opt_level)`` mutates a
torch model/optimizer in place (monkey-patching ops for O1, casting the
model + building fp32 master weights for O2) and ``amp.scale_loss`` wraps
``backward()``. In JAX the whole step is one traced function, so the same
capabilities become explicit state + a step builder:

    amp = Amp(tx=fused_adam(1e-4), opt_level="O2")
    state = amp.init(params)
    step = jax.jit(amp.make_train_step(loss_fn))
    state, metrics = step(state, batch)

Correspondence:
- fp32 master weights (O2)  → ``state.params`` are ALWAYS fp32 (policy
  ``param_dtype``); compute sees ``policy.cast_to_compute(params)`` inside
  the grad, so grads arrive in fp32 against the masters
  (``_process_optimizer.py :: _master_params_to_model_params`` has no
  equivalent code — the cast is re-traced each step, free under jit).
- op lists (O1)             → ``policy.fp32_fragile_ops`` consumed by
  `apex1_tpu.ops` kernels.
- ``scale_loss`` + overflow skip → ``loss_scale`` state threaded through;
  non-finite grads skip the update via ``select_tree`` (device-side, no
  host sync — ≙ ``amp_C`` noop_flag) and halve the scale.
- ``amp.state_dict()``      → ``state.loss_scale`` is part of the pytree
  and checkpoints with everything else.

Reference anchors: ``apex/amp/frontend.py :: initialize``,
``apex/amp/handle.py :: scale_loss``, ``apex/amp/_process_optimizer.py``,
``apex/amp/scaler.py :: LossScaler``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import chex
import jax
import jax.numpy as jnp
import optax

from apex1_tpu.core.loss_scale import (LossScaleState, all_finite,
                                       make_loss_scale, select_tree)
from apex1_tpu.core.policy import PrecisionPolicy, get_policy
from apex1_tpu.core.pytree import global_norm
from apex1_tpu.obs.regions import region


@chex.dataclass
class AmpState:
    """Train state: fp32 master params + optimizer state + loss-scale state.

    ≙ the (model, optimizer, amp.state_dict()) triple the reference
    checkpoints (README "checkpointing" recipe).
    """

    step: jnp.ndarray
    params: Any
    opt_state: Any
    loss_scale: Any  # LossScaleState, or a tuple of them (num_losses > 1)


class Amp:
    """Bundle of precision policy + optimizer transform.

    ``opt_level``/overrides mirror ``amp.initialize`` kwargs:
    ``Amp(tx, opt_level="O2", loss_scale=128.0, keep_norms_fp32=False)``.
    """

    def __init__(self, tx: optax.GradientTransformation,
                 opt_level: str | PrecisionPolicy = "O1",
                 max_grad_norm: float | None = None,
                 grad_psum_axes: tuple[str, ...] = (),
                 num_losses: int = 1,
                 cast_model_outputs=None,
                 min_loss_scale: float | None = None,
                 max_loss_scale: float | None = None,
                 **policy_overrides):
        self.tx = tx
        self.policy = get_policy(opt_level, **policy_overrides)
        self.scaler = make_loss_scale(self.policy.loss_scale)
        # ≙ amp.initialize(min_loss_scale=, max_loss_scale=) clamps
        if min_loss_scale is not None or max_loss_scale is not None:
            from apex1_tpu.core.loss_scale import DynamicLossScale
            if not isinstance(self.scaler, DynamicLossScale):
                raise ValueError("min/max_loss_scale require a dynamic "
                                 "loss scale")
            import copy
            self.scaler = copy.copy(self.scaler)  # never mutate a
            if min_loss_scale is not None:        # caller-supplied scaler
                self.scaler.min_loss_scale = float(min_loss_scale)
            if max_loss_scale is not None:
                self.scaler.max_loss_scale = float(max_loss_scale)
        self.max_grad_norm = max_grad_norm
        # mesh axes to pmean grads over (shard_map DDP; pjit needs none)
        self.grad_psum_axes = tuple(grad_psum_axes)
        # ≙ amp.initialize(num_losses=N): independent scaler state per
        # loss; steps pick one via loss_id (GAN D/G, multi-task)
        if num_losses < 1:
            raise ValueError("num_losses must be >= 1")
        self.num_losses = int(num_losses)
        # ≙ amp.initialize(cast_model_outputs=dtype) for make_forward
        self.cast_model_outputs = cast_model_outputs

    # -- setup (≙ amp.initialize) ------------------------------------------
    def init(self, params) -> AmpState:
        params = self.policy.cast_to_param(params)
        ls = (self.scaler.init() if self.num_losses == 1
              else tuple(self.scaler.init()
                         for _ in range(self.num_losses)))
        return AmpState(step=jnp.zeros([], jnp.int32),
                        params=params,
                        opt_state=self.tx.init(params),
                        loss_scale=ls)

    def _get_ls(self, state: AmpState, loss_id: int) -> LossScaleState:
        if self.num_losses == 1:
            return state.loss_scale
        return state.loss_scale[loss_id]

    def _set_ls(self, state_ls, loss_id: int, new: LossScaleState):
        if self.num_losses == 1:
            return new
        return tuple(new if i == loss_id else s
                     for i, s in enumerate(state_ls))

    # -- per-step (≙ scale_loss + optimizer.step) --------------------------
    def make_train_step(self, loss_fn: Callable, *,
                        has_aux: bool = False,
                        loss_id: int = 0,
                        accum_steps: int = 1) -> Callable:
        """``loss_fn(params_compute, *batch) -> loss`` (or ``(loss, aux)``).

        The returned function is pure — wrap it in ``jax.jit`` / ``pjit`` /
        ``shard_map``. Under data parallelism with pjit, gradient psums come
        from sharding; under shard_map pass ``grad_psum_axes=("dp",)``.
        ``loss_id`` selects the scaler when ``num_losses > 1``
        (≙ ``amp.scale_loss(loss, opt, loss_id=i)``).

        ``accum_steps > 1``: gradient accumulation — every batch leaf must
        lead with the accumulation axis (``(accum_steps, ...)``); the
        microbatch loop rides ONE ``lax.scan`` (grads averaged, one
        optimizer step — ≙ the reference's grad-accumulation recipe and
        ``fwd_bwd_no_pipelining``'s grad-sync-on-last semantics under jit;
        activation memory is one microbatch's).
        """
        if not 0 <= loss_id < self.num_losses:
            raise ValueError(f"loss_id {loss_id} outside num_losses="
                             f"{self.num_losses}")
        if accum_steps < 1:
            raise ValueError("accum_steps must be >= 1")
        policy, scaler = self.policy, self.scaler

        # graftlint: hot -- returned for the caller to jax.jit (the
        # examples' `jax.jit(amp.make_train_step(...), donate...)`);
        # the call graph can't see through the closure return
        def train_step(state: AmpState, *batch):
            ls = self._get_ls(state, loss_id)

            def scaled_loss_fn(master_params, *mb):
                with region("amp"):
                    compute_params = policy.cast_to_compute(master_params)
                out = loss_fn(compute_params, *mb)
                loss, aux = out if has_aux else (out, None)
                with region("amp"):
                    return scaler.scale(loss.astype(jnp.float32),
                                        ls), (loss, aux)

            if accum_steps == 1:
                grads, (loss, aux) = jax.grad(
                    scaled_loss_fn, has_aux=True)(state.params, *batch)
            else:
                def body(carry, mb):
                    gacc, lacc = carry
                    g, (l, aux_mb) = jax.grad(scaled_loss_fn,
                                              has_aux=True)(
                        state.params, *mb)
                    return (jax.tree_util.tree_map(jnp.add, gacc, g),
                            lacc + l), aux_mb

                zeros = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(jnp.shape(p), jnp.float32),
                    state.params)
                (grads, loss), aux = jax.lax.scan(
                    body, (zeros, jnp.zeros([], jnp.float32)), batch)
                inv = 1.0 / accum_steps
                # accumulate in fp32, then restore the accum_steps=1 dtype
                # contract (grads wrt masters carry the master dtype, which
                # is half under O3-style half-master policies)
                grads = jax.tree_util.tree_map(
                    lambda g, p: (g * inv).astype(p.dtype), grads,
                    state.params)
                loss = loss * inv
                if has_aux:
                    # keep metrics["aux"] shape-stable across accum_steps:
                    # float leaves average over microbatches, other dtypes
                    # (counters/flags) keep the LAST microbatch's value
                    aux = jax.tree_util.tree_map(
                        lambda a: (jnp.mean(a, axis=0)
                                   if jnp.issubdtype(a.dtype, jnp.floating)
                                   else a[-1]), aux)
                else:
                    aux = None
            for ax in self.grad_psum_axes:
                grads = jax.lax.pmean(grads, ax)
                loss = jax.lax.pmean(loss, ax)  # report the GLOBAL mean
            with region("amp"):
                grads = scaler.unscale(grads, ls)
                finite = all_finite(grads, axis_names=self.grad_psum_axes)
                gnorm = global_norm(grads)
                if self.max_grad_norm is not None:
                    from apex1_tpu.optim.clip_grad import clip_grad_norm
                    grads, _ = clip_grad_norm(grads, self.max_grad_norm)

            with region("optim"):
                updates, new_opt_state = self.tx.update(
                    grads, state.opt_state, state.params)
                new_params = optax.apply_updates(state.params, updates)
                # skip-on-overflow: keep old params/opt state (≙ noop_flag,
                # which the reference's optimizer kernels take themselves;
                # XLA fuses the update into these selects, so the fusion's
                # time, the state's traffic, is the optimizer's)
                new_params = select_tree(finite, new_params, state.params)
                new_opt_state = select_tree(finite, new_opt_state,
                                            state.opt_state)
            with region("amp"):
                new_ls = scaler.adjust(ls, finite)
                new_step = state.step + 1
            new_state = AmpState(
                step=new_step,
                params=new_params,
                opt_state=new_opt_state,
                loss_scale=self._set_ls(state.loss_scale, loss_id, new_ls),
            )
            metrics = {
                "loss": loss.astype(jnp.float32),
                "grad_norm": gnorm,
                "loss_scale": ls.scale,
                "grads_finite": finite,
                "skipped_steps": new_ls.overflow_count,
            }
            if has_aux:
                metrics["aux"] = aux
            return new_state, metrics

        return train_step

    # -- parity helpers ----------------------------------------------------
    def master_params(self, state: AmpState):
        """≙ ``amp.master_params(optimizer)`` — the fp32 weights."""
        return state.params

    def model_params(self, state: AmpState):
        """The compute-dtype view the model consumes (O2's fp16 model)."""
        return self.policy.cast_to_compute(state.params)

    def make_forward(self, forward_fn: Callable) -> Callable:
        """O2-style patched forward for eval/inference: casts params (and
        float inputs) to the compute dtype, and the outputs to
        ``cast_model_outputs`` if set
        (≙ ``_initialize.py :: patch_forward`` + ``cast_model_outputs``)."""
        policy = self.policy

        def fwd(state_or_params, *inputs):
            params = (state_or_params.params
                      if isinstance(state_or_params, AmpState)
                      else state_or_params)
            params = policy.cast_to_compute(params)
            inputs = jax.tree_util.tree_map(
                lambda x: (x.astype(policy.compute_dtype)
                           if hasattr(x, "dtype")
                           and jnp.issubdtype(x.dtype, jnp.floating)
                           else x), inputs)
            out = forward_fn(params, *inputs)
            if self.cast_model_outputs is not None:
                out = jax.tree_util.tree_map(
                    lambda x: x.astype(self.cast_model_outputs), out)
            return out

        return fwd

    # ≙ amp.half_function / float_function / promote_function, bound to
    # THIS Amp's policy (the module-level forms take the policy explicitly)
    def half_function(self, fn):
        return self.policy.half_function(fn)

    def float_function(self, fn):
        return self.policy.float_function(fn)

    def promote_function(self, fn):
        return self.policy.promote_function(fn)

    @staticmethod
    def _one_sd(ls: LossScaleState):
        return {"loss_scale": ls.scale,
                "growth_count": ls.growth_count,
                "overflow_count": ls.overflow_count,
                "hysteresis_left": ls.hysteresis_left}

    def _one_ls(self, sd) -> LossScaleState:
        return LossScaleState(
            scale=jnp.asarray(sd["loss_scale"], jnp.float32),
            growth_count=jnp.asarray(sd["growth_count"], jnp.int32),
            overflow_count=jnp.asarray(sd["overflow_count"], jnp.int32),
            hysteresis_left=jnp.asarray(
                sd.get("hysteresis_left",
                       getattr(self.scaler, "hysteresis", 1)),
                jnp.int32))

    def state_dict(self, state: AmpState):
        """≙ ``amp.state_dict()`` — loss-scaler state for checkpointing
        (``loss_scaler{i}`` sub-dicts when ``num_losses > 1``, like the
        reference's per-loss scalers)."""
        if self.num_losses == 1:
            return self._one_sd(state.loss_scale)
        return {f"loss_scaler{i}": self._one_sd(s)
                for i, s in enumerate(state.loss_scale)}

    def load_state_dict(self, state: AmpState, sd) -> AmpState:
        if self.num_losses == 1:
            return dataclasses.replace(state, loss_scale=self._one_ls(sd))
        ls = tuple(self._one_ls(sd[f"loss_scaler{i}"])
                   for i in range(self.num_losses))
        return dataclasses.replace(state, loss_scale=ls)


def initialize(params, tx, opt_level: str = "O1", **overrides):
    """One-call form mirroring ``amp.initialize(model, optimizer,
    opt_level)``: returns ``(amp, state)``."""
    amp = Amp(tx=tx, opt_level=opt_level, **overrides)
    return amp, amp.init(params)


def half_function(fn, policy):
    """≙ ``amp.half_function`` (O1 FP16_FUNCS registration): returns
    ``fn`` with float inputs cast to the policy's compute dtype. Pass the
    policy (or opt-level name) you train with — or use the bound form
    ``Amp.half_function`` which uses the Amp's own policy."""
    return get_policy(policy).half_function(fn)


def float_function(fn, policy="O0"):
    """≙ ``amp.float_function`` (FP32_FUNCS): float inputs cast fp32
    (policy-independent — fp32 is fp32 under every opt level)."""
    return get_policy(policy).float_function(fn)


def promote_function(fn, policy="O0"):
    """≙ ``amp.promote_function`` (CASTS): promote-widest inputs
    (policy-independent — promotion looks only at the input dtypes)."""
    return get_policy(policy).promote_function(fn)


def scale_loss(loss, loss_scale_state: LossScaleState):
    """Shape-parity helper for hand-rolled steps
    (≙ ``with amp.scale_loss(loss, opt) as scaled:``)."""
    return loss * loss_scale_state.scale.astype(loss.dtype)
