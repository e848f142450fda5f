"""The glue to the system under test: one builder per model FAMILY, in a
file of its own, `benchmark/builders/<builder>.py`, found by the
configuration file's `builder` key (`get`), so a PR that brings a family
adds a file and edits none. What every family shares stays here: the
seed's key, the weight generator, the program's fused optimizers.

The protocol. The file holds a class `Builder`, made from the
configuration's dict (the published sizes), with imports of the program
inside its methods, so a cell imports only what its kind needs:

- `family`                  a name for the printed lines
- `vocab_size`              token ids are drawn below it
- `ref_cfg`                 the sizes the plain reference takes
- `model(opt_level)`        the system's own model, under that amp policy
- `param_shapes(model)`     the parameter tree as `ShapeDtypeStruct`s
- `loss_fn(model)`          `(params, batch) -> loss` (training cells)
- `make_batch(key, rows, seq_len, traffic)`   one batch of that family
- `decoder(model)`          what `serving.Engine` takes (serving cells)
- `train_flops_per_token(seq_len)`   logical operations, forward +
  backward, of the published model: no recomputation, a causal product
  counted once (`flops.mfu_pct` takes it)
- optional `shard_step(raw_step, devices, traffic) -> (step, replicated,
  split)`: how a step of this family is laid over several chips, and the
  shardings of its state and of its batch; where a builder has it,
  `train.make_step` calls it in place of its own data-parallel wrapping.

Weights are the benchmark's, not the program's: `make_params` fills the
program's parameter tree from `--seed` on the device in one jitted call,
and the plain reference is handed the same function's output, never an
array the program touched.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import manifest as mf


def seed_key(seed: int, impl=None):
    """`--seed` may exceed 31 bits: fold the high bits in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl=impl),
                              seed >> 31)


def _leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in path)


#: rows of 1024 numbers per draw of the weight generator
_DRAW_ROWS = 8192


def param_generator(shapes, dtype):
    """`gen(key) -> tree`: fills the tree of `jax.ShapeDtypeStruct`s from a
    key: matrices and embeddings normal(0, 0.02), norm scales 1 +
    0.1*normal, biases 0.02*normal, in `dtype`. One table of rows of 1024
    numbers, drawn chunk after chunk, each leaf cut from whole rows.

    Why so: a draw per leaf took 90 s to compile for the v5e; one draw of
    355M numbers wants 5.3 GiB of scratch, more than is free beside a
    serving engine's pool; and ANY 1-D array of this size is laid out by
    the v5e compiler in 2-wide rows that pad 64-fold and cannot be
    allocated, so nothing here is ever flat."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    sizes = [int(np.prod(s.shape)) for _, s in leaves]
    rows = [-(-n // 1024) for n in sizes]
    n_chunks = -(-sum(rows) // _DRAW_ROWS)

    def gen(key):
        table = jax.lax.map(
            lambda k: jax.random.normal(k, (_DRAW_ROWS, 1024), jnp.float32),
            jax.random.split(key, n_chunks)).reshape(-1, 1024)
        out, at = [], 0
        for (path, s), n, r in zip(leaves, sizes, rows):
            x = table[at:at + r].reshape(-1)[:n].reshape(s.shape)
            at += r
            if _leaf_name(path).endswith("scale"):
                x = 1.0 + 0.1 * x
            else:
                x = 0.02 * x
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return gen


def make_params(shapes, seed: int, dtype, sharding=None):
    """The weights, from `--seed`: one jitted call, on the device. The same
    seed gives the same weights. On several chips the draw is made on ONE
    (the same single-device program as a one-chip cell) and then placed."""
    gen = param_generator(shapes, dtype)
    devs = sorted(sharding.device_set, key=lambda d: d.id) if sharding \
        is not None else []
    if len(devs) <= 1:
        return jax.jit(gen, out_shardings=sharding)(seed_key(seed))
    one = jax.sharding.SingleDeviceSharding(devs[0])
    return jax.device_put(
        jax.jit(gen, out_shardings=one)(seed_key(seed)), sharding)


def get(cfg: dict):
    """The builder of the configuration's family: the `Builder` class of
    `benchmark/builders/<cfg["builder"]>.py`, found by name under the root
    the configuration was loaded from."""
    return mf.load_builder(cfg["builder"],
                           cfg.get("_root", mf.ROOT)).Builder(cfg)


def optimizer(spec: dict):
    """The program's fused optimizer for the traffic file's `optimizer`."""
    kw = {k: v for k, v in spec.items() if k not in ("name", "lr")}
    if spec["name"] == "adam":
        from apex1_tpu.optim.fused_adam import fused_adam
        return fused_adam(spec["lr"], **kw)
    if spec["name"] == "lamb":
        from apex1_tpu.optim.fused_lamb import fused_lamb
        return fused_lamb(spec["lr"], **kw)
    raise KeyError(f"unknown optimizer {spec['name']!r}")
