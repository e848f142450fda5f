"""DCGAN mixed-precision training — reference ``examples/dcgan/main_amp.py``
(the second canonical amp flow: TWO models and TWO optimizers sharing the
amp machinery, ``num_losses=3`` there — errD_real/errD_fake/errG).

TPU-native shape of the same thing: one `Amp` per network (generator and
discriminator each carry their own fp32 masters + loss-scale state, as the
reference allocates one loss-scaler per loss), NHWC conv stacks (TPU conv
layout), synthetic data. The literal-parity alternative — ONE ``Amp`` with
``num_losses=3`` and ``make_train_step(loss_fn, loss_id=i)`` per loss —
is also supported (see ``docs/amp.md``); separate Amps per network are the
cleaner functional design when the two nets have disjoint params.

``python examples/dcgan_amp.py [--opt-level O2] [--steps N]``
"""

import argparse
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from apex1_tpu.amp import Amp
from apex1_tpu.core.policy import get_policy
from apex1_tpu.optim.fused_adam import fused_adam


class Generator(nn.Module):
    """z (B, 1, 1, Z) -> image (B, 32, 32, C); ConvTranspose/BN/ReLU stack
    (BN stays fp32 under keep_norms_fp32 — amp keep_batchnorm_fp32)."""

    features: int = 64
    channels: int = 3
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, z, train=True):
        f, dt = self.features, self.dtype
        x = z.astype(dt)
        for i, (feat, stride) in enumerate(
                [(f * 4, 4), (f * 2, 2), (f, 2)]):
            x = nn.ConvTranspose(feat, (4, 4), (stride, stride),
                                 padding="SAME" if i else "VALID",
                                 use_bias=False, dtype=dt)(x)
            x = nn.BatchNorm(use_running_average=not train,
                             dtype=jnp.float32)(x)
            x = nn.relu(x)
        x = nn.ConvTranspose(self.channels, (4, 4), (2, 2), padding="SAME",
                             use_bias=False, dtype=dt)(x)
        return jnp.tanh(x)


class Discriminator(nn.Module):
    """image -> logit; strided Conv/LeakyReLU stack."""

    features: int = 64
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train=True):
        f, dt = self.features, self.dtype
        x = x.astype(dt)
        for i, feat in enumerate([f, f * 2, f * 4]):
            x = nn.Conv(feat, (4, 4), (2, 2), padding="SAME",
                        use_bias=False, dtype=dt)(x)
            if i:
                x = nn.BatchNorm(use_running_average=not train,
                                 dtype=jnp.float32)(x)
            x = nn.leaky_relu(x, 0.2)
        x = nn.Conv(1, (4, 4), (1, 1), padding="VALID", use_bias=False,
                    dtype=dt)(x)
        return x.reshape(x.shape[0])


def bce_logits(logits, target):
    """binary CE with logits, fp32 (≙ reference BCELoss on fp32 sigmoid)."""
    logits = logits.astype(jnp.float32)
    return jnp.mean(jnp.maximum(logits, 0) - logits * target
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--zdim", type=int, default=100)
    ap.add_argument("--opt-level", default="O2")
    args = ap.parse_args()

    policy = get_policy(args.opt_level)
    gen = Generator(dtype=policy.compute_dtype)
    disc = Discriminator(dtype=policy.compute_dtype)
    rng = np.random.default_rng(0)
    key = jax.random.key(0)

    z0 = jnp.zeros((args.batch, 1, 1, args.zdim), jnp.float32)
    img0 = jnp.zeros((args.batch, 32, 32, 3), jnp.float32)
    # distinct init keys: the same key for both nets would correlate
    # G's and D's initial weights (graftlint APX103 caught this)
    key_g, key_d = jax.random.split(key)
    gvars = jax.jit(gen.init)(key_g, z0)
    dvars = jax.jit(disc.init)(key_d, img0)

    # one Amp per (model, optimizer) pair — ≙ amp.initialize([netD, netG],
    # [optD, optG], num_losses=3); each keeps its own loss-scale state
    amp_g = Amp(tx=fused_adam(2e-4, b1=0.5, b2=0.999),
                opt_level=args.opt_level)
    amp_d = Amp(tx=fused_adam(2e-4, b1=0.5, b2=0.999),
                opt_level=args.opt_level)
    gstate = amp_g.init(gvars["params"])
    dstate = amp_d.init(dvars["params"])
    g_bn = gvars.get("batch_stats", {})
    d_bn = dvars.get("batch_stats", {})

    def d_loss_fn(d_params, batch):
        """errD = BCE(D(real), 1) + BCE(D(G(z)), 0) — two of the
        reference's three scaled losses."""
        real, fake, d_bn = batch
        logits_r, upd = disc.apply(
            {"params": d_params, "batch_stats": d_bn}, real,
            mutable=["batch_stats"])
        logits_f, upd = disc.apply(
            {"params": d_params, "batch_stats": upd["batch_stats"]}, fake,
            mutable=["batch_stats"])
        loss = bce_logits(logits_r, 1.0) + bce_logits(logits_f, 0.0)
        return loss, upd["batch_stats"]

    def g_loss_fn(g_params, batch):
        """errG = BCE(D(G(z)), 1)."""
        z, g_bn, d_params, d_bn = batch
        fake, upd = gen.apply(
            {"params": g_params, "batch_stats": g_bn}, z,
            mutable=["batch_stats"])
        logits = disc.apply(
            {"params": d_params, "batch_stats": d_bn}, fake, train=False)
        return bce_logits(logits, 1.0), upd["batch_stats"]

    d_step = jax.jit(amp_d.make_train_step(d_loss_fn, has_aux=True),
                     donate_argnums=0)
    g_step = jax.jit(amp_g.make_train_step(g_loss_fn, has_aux=True),
                     donate_argnums=0)

    @jax.jit
    def make_fake(g_params, g_bn, z):
        return gen.apply({"params": g_params, "batch_stats": g_bn}, z,
                         train=False)

    t0 = time.time()
    for i in range(args.steps):
        real = jnp.asarray(rng.normal(size=(args.batch, 32, 32, 3)),
                           jnp.float32)
        z = jnp.asarray(rng.normal(size=(args.batch, 1, 1, args.zdim)),
                        jnp.float32)
        fake = make_fake(gstate.params, g_bn, z)
        dstate, d_metrics = d_step(dstate, (real, fake, d_bn))
        d_bn = d_metrics["aux"]
        gstate, g_metrics = g_step(gstate, (z, g_bn, dstate.params, d_bn))
        g_bn = g_metrics["aux"]
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i}: errD={float(d_metrics['loss']):.4f} "
                  f"errG={float(g_metrics['loss']):.4f} "
                  f"scaleD={float(dstate.loss_scale.scale):.0f}")
    jax.block_until_ready(gstate.params)
    print(f"done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
