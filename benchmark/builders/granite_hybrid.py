"""The Granite 4.0-H family's glue to the system under test (`builder:
"granite_hybrid"`): `apex1_tpu.models.granite_hybrid` served through
`models.generate.granite_hybrid_decoder`. The protocol is written down in
`benchmark/harness/builders.py`. No cell trains this family (PERF.md §4
says why no cut of it fits a training step on one chip): the training
methods are the plain next-token loss over the model's uncached forward,
what the protocol asks of every builder, and nothing more.

Training FLOPs per token = 6 x (parameters that sit in a matmul, applied
once per token) + per state-space layer the state's two products (x (x) B
into the state and S C out of it: 2 x 2 x heads x head width x state width
forward, three times that with the backward pass) + per attention layer
12*S*hidden, halved: causal, counted once. Recomputed operations and the
chunked form's extra products do not count.

The configuration file holds the published `config.json` keys under their
published names. The model takes those it has a field for; what it does
NOT compute (experts, a bias, a position embedding, another activation or
norm, an untied head) is refused here by name, never ignored.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

#: published keys whose value says "this model has no such part"
_ABSENT = {"num_local_experts": 0, "num_experts_per_tok": 0,
           "attention_bias": False, "position_embedding_type": "nope",
           "hidden_act": "silu", "normalization_function": "rmsnorm",
           "tie_word_embeddings": True, "mamba_proj_bias": False}
#: what the plain reference takes
_REF_KEYS = ("vocab_size", "hidden_size", "layer_types",
             "num_attention_heads", "num_key_value_heads",
             "attention_multiplier", "embedding_multiplier",
             "residual_multiplier", "logits_scaling", "rms_norm_eps",
             "mamba_d_conv", "mamba_d_head", "mamba_d_state",
             "mamba_n_heads")


class Builder:
    family = "granite_hybrid"

    def __init__(self, cfg: dict):
        for key, want in _ABSENT.items():
            if cfg.get(key, want) != want:
                raise ValueError(
                    f"{key} = {cfg[key]!r}: this family's model computes "
                    f"only {want!r}")
        if cfg["shared_intermediate_size"] != cfg["intermediate_size"]:
            raise ValueError("the MLP's width is given twice and differs")
        self.cfg = cfg
        self.vocab_size = cfg["vocab_size"]
        self.ref_cfg = {k: cfg[k] for k in _REF_KEYS}

    def model(self, opt_level: str = "O2"):
        from apex1_tpu.core.policy import get_policy
        from apex1_tpu.models.granite_hybrid import (GraniteHybrid,
                                                     GraniteHybridConfig)
        fields = {f.name for f in dataclasses.fields(GraniteHybridConfig)}
        return GraniteHybrid(GraniteHybridConfig(
            **{k: v for k, v in self.cfg.items() if k in fields},
            policy=get_policy(opt_level)))

    def param_shapes(self, model):
        probe = jax.ShapeDtypeStruct((1, 8), jnp.int32)
        return jax.eval_shape(model.init, jax.random.key(0), probe)["params"]

    def decoder(self, model):
        from apex1_tpu.models.generate import granite_hybrid_decoder
        return granite_hybrid_decoder(model)

    def loss_fn(self, model):
        from apex1_tpu.ops import softmax_cross_entropy_loss

        def loss(params, batch):
            tokens = batch["tokens"]
            logits = model.apply({"params": params}, tokens)
            return jnp.mean(softmax_cross_entropy_loss(logits[:, :-1],
                                                       tokens[:, 1:]))
        return loss

    def make_batch(self, key, rows: int, seq_len: int, traffic: dict):
        return {"tokens": jax.random.randint(
            key, (rows, seq_len), 0, self.vocab_size, jnp.int32)}

    def train_flops_per_token(self, seq_len: int) -> float:
        c = self.cfg
        h, inner = c["hidden_size"], c["mamba_n_heads"] * c["mamba_d_head"]
        kv = h // c["num_attention_heads"] * c["num_key_value_heads"]
        mlp = 3 * h * c["intermediate_size"]
        mamba = (h * (2 * inner + 2 * c["mamba_d_state"]
                      + c["mamba_n_heads"]) + inner * h)
        n_attn = c["layer_types"].count("attention")
        n_mamba = len(c["layer_types"]) - n_attn
        matmul = (n_mamba * (mamba + mlp)
                  + n_attn * (2 * h * h + 2 * h * kv + mlp)
                  + c["vocab_size"] * h)
        state = 12 * inner * c["mamba_d_state"] * n_mamba
        return 6.0 * matmul + state + 12 * seq_len * h * n_attn / 2
