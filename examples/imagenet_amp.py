"""ResNet-50 "ImageNet" training — reference
``examples/imagenet/main_amp.py`` (amp O1/O2 + apex DDP + SyncBN +
prefetching loader), the canonical end-to-end flow (BASELINE config 3).

TPU-native shape of the same flow:
- amp opt-level      → `apex1_tpu.amp.Amp(tx, opt_level=...)`
- apex DDP allreduce → ``shard_map`` over the dp mesh axis +
                       ``grad_psum_axes=("dp",)`` (one fused psum)
- convert_syncbn     → model built with ``bn_axis_name="dp"``
- data_prefetcher    → `apex1_tpu.runtime.PrefetchLoader` with the native
                       u8→f32 normalize
Synthetic data (no dataset in the image); run with
``python examples/imagenet_amp.py [--steps N] [--opt-level O2]``.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from apex1_tpu import runtime
from apex1_tpu.amp import Amp
from apex1_tpu.core.mesh import make_mesh
from apex1_tpu.core.policy import get_policy
from apex1_tpu.models.resnet import ResNet, ResNetConfig
from apex1_tpu.ops import softmax_cross_entropy_loss
from apex1_tpu.optim.fused_sgd import fused_sgd
from apex1_tpu.utils.observability import MetricsLogger


def synthetic_loader(batch, image, steps, rng):
    for _ in range(steps):
        yield {
            "images": rng.integers(0, 256, (batch, image, image, 3),
                                   dtype=np.uint8),
            "labels": rng.integers(0, 1000, (batch,), dtype=np.int64),
        }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--image", type=int, default=64)
    ap.add_argument("--opt-level", default="O2")
    ap.add_argument("--tiny", action="store_true",
                    help="tiny model for smoke runs")
    args = ap.parse_args()

    n_dev = jax.device_count()
    mesh = make_mesh(dp=n_dev)
    policy = get_policy(args.opt_level)
    cfg = (ResNetConfig.tiny(bn_axis_name="dp", policy=policy)
           if args.tiny else
           ResNetConfig.resnet50(bn_axis_name="dp", policy=policy))
    model = ResNet(cfg)

    rng = np.random.default_rng(0)
    init_img = jnp.zeros((2, args.image, args.image, 3), jnp.float32)
    variables = jax.jit(model.init)(jax.random.key(0), init_img)
    amp = Amp(tx=fused_sgd(0.1, momentum=0.9), opt_level=args.opt_level,
              grad_psum_axes=("dp",))
    state = amp.init(variables["params"])
    bn_stats = variables["batch_stats"]

    def loss_fn(params, batch, bn_stats):
        logits, mutated = model.apply(
            {"params": params, "batch_stats": bn_stats},
            batch["images"], mutable=["batch_stats"])
        loss = jnp.mean(softmax_cross_entropy_loss(
            logits, batch["labels"], smoothing=0.1))
        # thread the updated running stats out through the aux channel
        return loss, mutated["batch_stats"]

    step = jax.jit(jax.shard_map(
        amp.make_train_step(loss_fn, has_aux=True), mesh=mesh,
        in_specs=(P(), {"images": P("dp"), "labels": P("dp")}, P()),
        out_specs=(P(), P()), check_vma=False))

    mean = (0.485, 0.456, 0.406)
    std = (0.229, 0.224, 0.225)
    loader = runtime.PrefetchLoader(
        synthetic_loader(args.batch * n_dev, args.image, args.steps, rng),
        transform=lambda b: {
            "images": runtime.normalize_images(b["images"], mean, std),
            "labels": b["labels"].astype(np.int32)})
    logger = MetricsLogger()
    t0 = time.time()
    for i, batch in enumerate(loader):
        state, metrics = step(state, batch, bn_stats)
        bn_stats = metrics.pop("aux")  # SyncBN running stats advance
        if i % 5 == 0 or i == args.steps - 1:
            logger.log(i, metrics, tokens=args.batch * n_dev)
    jax.block_until_ready(state.params)
    print(f"done: {args.steps} steps, "
          f"{args.steps * args.batch * n_dev / (time.time() - t0):.0f} "
          f"imgs/sec")


if __name__ == "__main__":
    main()
