"""The BERT pre-training family's glue to the system under test
(`builder: "bert_pretrain"`): `apex1_tpu.models.bert.BertPretrain` with
its masked-LM + next-sentence loss, and the family's own count of logical
training operations. Training only: it has no `decoder`. The protocol is
written down in `benchmark/harness/builders.py`.

Training FLOPs per token = 6 x (parameters that sit in a matmul, applied
once per token) + the attention products: 12*S*H per layer (QK^T and PV,
2 FLOPs a multiply-add, forward + two backward), bidirectional: whole.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def bert_matmul_params(cfg: dict) -> int:
    """qkv 3E^2 + attn_out E^2 + ffn 2*E*I per layer; the masked-LM head
    (transform E^2 + tied decoder V*E) as the model applies it, to every
    position. The pooler and the 2-way head act once per SEQUENCE and are
    left out (E^2/S per token, under 0.001 %)."""
    e, i = cfg["hidden_size"], cfg["intermediate_size"]
    n_layer, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    return n_layer * (4 * e * e + 2 * e * i) + e * e + v * e


def bert_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    attn = 12 * seq_len * cfg["hidden_size"] * cfg["num_hidden_layers"]
    return 6.0 * bert_matmul_params(cfg) + attn


class Builder:
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

    def train_flops_per_token(self, seq_len: int) -> float:
        return bert_train_flops_per_token(self.cfg, seq_len)
