"""The LFM2-with-experts family's glue to the system under test (`builder:
"lfm2_moe"`): `apex1_tpu.models.lfm2` served through
`models.generate.lfm2_moe_decoder`. The protocol is written down in
`benchmark/harness/builders.py`. No cell trains this family (the grouped
expert product has no backward yet, ROADMAP R2 a): the training methods
are the plain next-token loss over the model's uncached forward, what the
protocol asks of every builder, and nothing more.

The configuration file holds the published `config.json` keys under their
published names, but for the one that counts the experts: ``num_experts``
is how many this chip HOLDS (the file's `reduced`), ``published.
num_experts`` what the router chooses among, and ``expert_parallel.
experts_held`` ``[first, count]`` which of them these are. The model and
the plain reference are handed the published count and the share; what the
model does NOT compute (a convolution's bias, an untied head) is refused
here by name, never ignored.

Training FLOPs per token = 6 x (parameters that sit in a matmul, applied
once per token: of a sparse layer the router and ``num_experts_per_tok``
experts) + per attention layer 12*S*hidden, halved: causal, counted once.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

#: published keys whose value says "this model has no such part"
_ABSENT = {"conv_bias": False, "tie_word_embeddings": True}
#: what the plain reference takes beside the share
_REF_KEYS = ("vocab_size", "hidden_size", "layer_types",
             "num_attention_heads", "num_key_value_heads",
             "num_dense_layers", "num_experts_per_tok", "norm_topk_prob",
             "use_expert_bias", "routed_scaling_factor", "conv_L_cache",
             "norm_eps", "rope_theta")


class Builder:
    family = "lfm2_moe"

    def __init__(self, cfg: dict):
        for key, want in _ABSENT.items():
            if cfg.get(key, want) != want:
                raise ValueError(
                    f"{key} = {cfg[key]!r}: this family's model computes "
                    f"only {want!r}")
        self.cfg = cfg
        self.vocab_size = cfg["vocab_size"]
        self.n_experts = cfg.get("published", {}).get("num_experts",
                                                      cfg["num_experts"])
        share = cfg.get("expert_parallel", {}).get("experts_held")
        self.held = tuple(share) if share else (0, self.n_experts)
        if self.held[1] != cfg["num_experts"]:
            raise ValueError(
                f"num_experts {cfg['num_experts']} is the experts held "
                f"here, experts_held says {self.held[1]}")
        self.ref_cfg = dict({k: cfg[k] for k in _REF_KEYS},
                            num_experts=self.n_experts,
                            held=list(self.held))

    def model(self, opt_level: str = "O2"):
        from apex1_tpu.core.policy import get_policy
        from apex1_tpu.models.lfm2 import Lfm2Moe, Lfm2MoeConfig
        fields = {f.name for f in dataclasses.fields(Lfm2MoeConfig)}
        return Lfm2Moe(Lfm2MoeConfig(**dict(
            {k: v for k, v in self.cfg.items() if k in fields},
            num_experts=self.n_experts, experts_held=self.held,
            policy=get_policy(opt_level))))

    def param_shapes(self, model):
        probe = jax.ShapeDtypeStruct((1, 8), jnp.int32)
        return jax.eval_shape(model.init, jax.random.key(0), probe)["params"]

    def decoder(self, model):
        from apex1_tpu.models.generate import lfm2_moe_decoder
        return lfm2_moe_decoder(model)

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
        h = c["hidden_size"]
        kv = h // c["num_attention_heads"] * c["num_key_value_heads"]
        n_attn = c["layer_types"].count("full_attention")
        n_conv = len(c["layer_types"]) - n_attn
        n_sparse = len(c["layer_types"]) - c["num_dense_layers"]
        matmul = (n_conv * 4 * h * h + n_attn * (2 * h * h + 2 * h * kv)
                  + c["num_dense_layers"] * 3 * h * c["intermediate_size"]
                  + n_sparse * (h * self.n_experts
                                + c["num_experts_per_tok"] * 3 * h
                                * c["moe_intermediate_size"])
                  + c["vocab_size"] * h)
        return 6.0 * matmul + 12 * seq_len * h * n_attn / 2
