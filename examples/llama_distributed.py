"""Distributed Llama training — BASELINE config 4's recipe end to end:
tensor parallelism via GSPMD param specs, optional ZeRO (fsdp) sharding
of params + optimizer state, data parallelism, amp-O2 mixed precision,
fused Adam. (The context-parallel forms — ring / Ulysses over a cp axis
— are shard_map programs; see `tests/test_ring_attention.py` and
`__graft_entry__.dryrun_multichip` for those flows.)

Runs on any device set — demonstrate on CPU with
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/llama_distributed.py --tp 2 --fsdp 2 --dp 2
or on a TPU slice with the same flags spelled by the topology.

The whole distributed story is specs + one jit: no process groups, no
wrappers, no collectives in user code (SURVEY.md §7.0) — at the price of
the Pallas kernels: GSPMD cannot partition a Mosaic kernel (with kernels
on, more than one TPU chip fails to compile with "Mosaic kernels cannot
be automatically partitioned. Please wrap the call in a shard_map"), so
this example selects the XLA composites itself, and says so. Multi-chip
training WITH the kernels is the shard_map forms:
`examples/distributed_data_parallel.py`, `examples/llama_3d.py`.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from apex1_tpu.amp import Amp
from apex1_tpu.core.mesh import make_mesh
from apex1_tpu.core.policy import get_policy
from apex1_tpu.models.llama import (Llama, LlamaConfig, llama_loss_fn,
                                    param_specs)
from apex1_tpu.ops import set_impl
from apex1_tpu.optim.fused_adam import fused_adam
from apex1_tpu.parallel import fsdp_param_specs, shard_opt_state_specs
from apex1_tpu.utils.observability import MetricsLogger


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--fsdp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--opt-level", default="O2")
    args = ap.parse_args()

    set_impl("xla")
    print("ops.set_impl('xla'): this GSPMD form runs the XLA composites "
          "— GSPMD cannot partition Mosaic kernels; the shard_map "
          "examples keep them", flush=True)
    mesh = make_mesh(dp=args.dp, fsdp=args.fsdp, tp=args.tp)
    cfg = LlamaConfig.tiny(policy=get_policy(args.opt_level),
                           max_seq_len=args.seq)
    model = Llama(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.seq)), jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), tokens)["params"]

    amp = Amp(tx=fused_adam(3e-4, weight_decay=0.1),
              opt_level=args.opt_level, max_grad_norm=1.0)
    state = amp.init(params)

    # TP from the model's regex rules; ZeRO by ALSO sharding any still-
    # replicated large params (and the optimizer moments, same dims)
    # over fsdp. GSPMD inserts every collective.
    tp_specs = param_specs(state.params)
    if args.fsdp > 1:
        zero = fsdp_param_specs(state.params, divisor=args.fsdp)
        tp_specs = jax.tree_util.tree_map(
            lambda t, z: z if t == P() else t, tp_specs, zero,
            is_leaf=lambda v: isinstance(v, P))
    opt_specs = shard_opt_state_specs(state.opt_state,
                                      param_specs=tp_specs)

    def put(tree, specs):
        return jax.device_put(tree, jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda v: isinstance(v, P)))

    import dataclasses
    state = dataclasses.replace(
        state,
        params=put(state.params, tp_specs),
        opt_state=put(state.opt_state, opt_specs))
    batch_spec = NamedSharding(mesh, P(("dp", "fsdp")))

    step = jax.jit(amp.make_train_step(llama_loss_fn(model)),
                   donate_argnums=0)
    logger = MetricsLogger()
    t0 = time.time()
    for i in range(args.steps):
        batch = jax.device_put(jnp.asarray(
            rng.integers(0, cfg.vocab_size, (args.batch, args.seq)),
            jnp.int32), batch_spec)
        state, metrics = step(state, batch)
        if i % 2 == 0 or i == args.steps - 1:
            logger.log(i, metrics, tokens=args.batch * args.seq)
    jax.block_until_ready(state.params)
    print(f"done in {time.time() - t0:.1f}s on mesh "
          f"{dict(mesh.shape)} — every collective GSPMD-inserted")


if __name__ == "__main__":
    main()
