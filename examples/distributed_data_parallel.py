"""Minimal DDP — reference ``examples/simple/distributed/
distributed_data_parallel.py`` (the 30-line apex-DDP hello world).

The reference: init NCCL process group, wrap a Linear in apex DDP, step.
TPU-native: the dp mesh axis IS the process group; one shard_map with
``grad_psum_axes=("dp",)`` is the whole of DDP.

``python examples/distributed_data_parallel.py`` (uses every visible
device; on CPU set XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from apex1_tpu.amp import Amp
from apex1_tpu.core.mesh import make_mesh
from apex1_tpu.optim.fused_sgd import fused_sgd


def main():
    mesh = make_mesh(dp=jax.device_count())
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    Y = jnp.asarray(rng.normal(size=(64, 4)), jnp.float32)
    params = {"w": jnp.zeros((16, 4), jnp.float32)}

    amp = Amp(tx=fused_sgd(0.1), opt_level="O0", grad_psum_axes=("dp",))
    state = amp.init(params)

    def loss_fn(p, x, y):
        return jnp.mean(jnp.square(x @ p["w"] - y))

    step = jax.jit(jax.shard_map(
        amp.make_train_step(loss_fn), mesh=mesh,
        in_specs=(P(), P("dp"), P("dp")), out_specs=(P(), P()),
        check_vma=False))

    for i in range(10):
        state, metrics = step(state, X, Y)
        print(f"step {i} loss {float(metrics['loss']):.4f}")


if __name__ == "__main__":
    main()
