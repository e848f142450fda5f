"""The glue to the system under test: one builder per model FAMILY (named
by the configuration file's `builder` key). A builder takes the published
sizes, hands the system's own model, loss and decoder to the runners, and
says how a batch of that family is drawn. Imports of the program happen
inside the methods, so a cell imports only what its kind needs.

Weights are the benchmark's, not the program's: `make_params` fills the
program's parameter tree from `--seed` on the device in one jitted call,
and the plain reference is handed the same function's output, never an
array the program touched.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


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


class Gpt2:
    family = "gpt2"

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.vocab_size = cfg["vocab_size"]
        self.ref_cfg = {k: cfg[k] for k in ("n_layer", "n_head", "n_embd",
                                            "vocab_size")}

    def model(self, opt_level: str = "O2"):
        from apex1_tpu.core.policy import get_policy
        from apex1_tpu.models.gpt2 import GPT2, GPT2Config
        c = self.cfg
        if c["n_embd"] % c["n_head"]:
            raise ValueError("n_embd not divisible by n_head")
        return GPT2(GPT2Config(
            vocab_size=c["vocab_size"], max_seq_len=c["n_positions"],
            num_layers=c["n_layer"], num_heads=c["n_head"],
            hidden_size=c["n_embd"], dropout=c["resid_pdrop"],
            policy=get_policy(opt_level)))

    def param_shapes(self, model):
        probe = jax.ShapeDtypeStruct((1, 8), jnp.int32)
        return jax.eval_shape(model.init, jax.random.key(0), probe)["params"]

    def loss_fn(self, model):
        from apex1_tpu.models.gpt2 import gpt2_loss_fn
        f = gpt2_loss_fn(model)
        return lambda params, batch: f(params, batch["tokens"])

    def make_batch(self, key, rows: int, seq_len: int, traffic: dict):
        return {"tokens": jax.random.randint(
            key, (rows, seq_len), 0, self.vocab_size, jnp.int32)}

    def decoder(self, model):
        from apex1_tpu.models.generate import gpt2_decoder
        return gpt2_decoder(model)


class BertPretrain:
    family = "bert_pretrain"

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.vocab_size = cfg["vocab_size"]
        self.ref_cfg = {k: cfg[k] for k in (
            "num_hidden_layers", "num_attention_heads", "hidden_size",
            "vocab_size")}

    def model(self, opt_level: str = "O2"):
        from apex1_tpu.core.policy import get_policy
        from apex1_tpu.models.bert import BertConfig, BertPretrain as M
        c = self.cfg
        return M(BertConfig(
            vocab_size=c["vocab_size"],
            max_seq_len=c["max_position_embeddings"],
            type_vocab_size=c["type_vocab_size"],
            num_layers=c["num_hidden_layers"],
            num_heads=c["num_attention_heads"],
            hidden_size=c["hidden_size"],
            intermediate_size=c["intermediate_size"],
            dropout=c["hidden_dropout_prob"], policy=get_policy(opt_level)))

    def param_shapes(self, model):
        probe = jax.ShapeDtypeStruct((1, 8), jnp.int32)
        return jax.eval_shape(model.init, jax.random.key(0), probe)["params"]

    def loss_fn(self, model):
        from apex1_tpu.models.bert import bert_pretrain_loss_fn
        return bert_pretrain_loss_fn(model)

    def make_batch(self, key, rows: int, seq_len: int, traffic: dict):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        shape = (rows, seq_len)
        masked = jax.random.uniform(k2, shape) < traffic["mask_share"]
        return {
            "tokens": jax.random.randint(k1, shape, 0, self.vocab_size,
                                         jnp.int32),
            "mlm_labels": jnp.where(
                masked, jax.random.randint(k3, shape, 0, self.vocab_size,
                                           jnp.int32), -1),
            "nsp_labels": jax.random.randint(k4, (rows,), 0, 2, jnp.int32),
        }


BUILDERS = {"gpt2": Gpt2, "bert_pretrain": BertPretrain}


def get(cfg: dict):
    try:
        return BUILDERS[cfg["builder"]](cfg)
    except KeyError:
        raise KeyError(f"configuration {cfg.get('_name')} names builder "
                       f"{cfg.get('builder')!r}; known: {sorted(BUILDERS)}")


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
