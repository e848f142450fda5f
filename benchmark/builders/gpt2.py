"""The GPT-2 family's glue to the system under test (`builder: "gpt2"`):
`apex1_tpu.models.gpt2` for training, `models.generate.gpt2_decoder` for
serving, and the family's own count of logical training operations. The
protocol is written down in `benchmark/harness/builders.py`.

Training FLOPs per token = 6 x (parameters that sit in a matmul, applied
once per token) + the attention products: 12*S*H per layer (QK^T and PV,
2 FLOPs a multiply-add, forward + two backward), halved: causal, counted
once. Recomputed operations do not count.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def gpt2_matmul_params(cfg: dict) -> int:
    """qkv 3H^2 + proj H^2 + MLP 8H^2 per layer, plus the tied head V*H
    (the embedding LOOKUP is not a matmul and is not counted)."""
    h, n_layer, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    return 12 * n_layer * h * h + v * h


def gpt2_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    attn = 12 * seq_len * cfg["n_embd"] * cfg["n_layer"] / 2   # causal
    return 6.0 * gpt2_matmul_params(cfg) + attn


class Builder:
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

    def train_flops_per_token(self, seq_len: int) -> float:
        return gpt2_train_flops_per_token(self.cfg, seq_len)
