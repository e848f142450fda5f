"""The AFMoE family's glue to the system under test (`builder: "afmoe"`):
`apex1_tpu.models.afmoe` served through `models.generate.afmoe_decoder`.
The protocol is written down in `benchmark/harness/builders.py`. No cell
trains this family (the grouped expert product has no backward and the
flash kernels no window yet, ROADMAP R2): the training methods are the
plain next-token loss over the model's uncached forward, what the
protocol asks of every builder, and nothing more.

The configuration file holds the published `config.json` keys under their
published names, but for the one that counts the experts: ``num_experts``
is how many this chip HOLDS (the file's `reduced`), ``published.
num_experts`` what the router chooses among, and ``expert_parallel.
experts_held`` ``[first, count]`` which of them these are. The model and
the plain reference are handed the published count and the share. What the
model does NOT compute (a group-limited choice, a tied head, scaled RoPE)
is refused by the model's own configuration, by name; a key that only
says HOW the published code computes (``use_grouped_mm``), what it was
trained with (``load_balance_coeff``) or repeats another
(``global_attn_every_n_layers``, ``num_expert_groups``,
``num_limited_groups``, ``hidden_act``) is held to the one value this
model has, here.

Training FLOPs per token = 6 x (parameters that sit in a matmul, applied
once per token: of a sparse layer the router, the shared expert and
``num_experts_per_tok`` experts) + per attention layer 12 x (positions
attended) x (heads x head width), halved: causal, counted once.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

#: published keys that say nothing the model could compute otherwise
_ONLY = {"hidden_act": "silu", "num_expert_groups": 1,
         "num_limited_groups": 1}
#: what the plain reference takes beside the share
_REF_KEYS = ("vocab_size", "hidden_size", "layer_types",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "sliding_window", "num_dense_layers", "num_experts_per_tok",
             "num_shared_experts", "score_func", "route_norm",
             "route_scale", "mup_enabled", "rms_norm_eps", "rope_theta")


class Builder:
    family = "afmoe"

    def __init__(self, cfg: dict):
        for key, want in _ONLY.items():
            if cfg.get(key, want) != want:
                raise ValueError(
                    f"{key} = {cfg[key]!r}: this family's model computes "
                    f"only {want!r}")
        every = cfg.get("global_attn_every_n_layers")
        if every and any((kind == "full_attention") != ((i + 1) % every == 0)
                         for i, kind in enumerate(cfg["layer_types"])):
            raise ValueError(
                f"layer_types and global_attn_every_n_layers = {every} "
                f"disagree")
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
        from apex1_tpu.models.afmoe import Afmoe, AfmoeConfig
        fields = {f.name for f in dataclasses.fields(AfmoeConfig)}
        return Afmoe(AfmoeConfig(**dict(
            {k: v for k, v in self.cfg.items() if k in fields},
            num_experts=self.n_experts, experts_held=self.held,
            policy=get_policy(opt_level))))

    def param_shapes(self, model):
        probe = jax.ShapeDtypeStruct((1, 8), jnp.int32)
        return jax.eval_shape(model.init, jax.random.key(0), probe)["params"]

    def decoder(self, model):
        from apex1_tpu.models.generate import afmoe_decoder
        return afmoe_decoder(model)

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
        h, f = c["hidden_size"], c["moe_intermediate_size"]
        q = c["num_attention_heads"] * c["head_dim"]
        kv = c["num_key_value_heads"] * c["head_dim"]
        n_layers = len(c["layer_types"])
        n_slide = c["layer_types"].count("sliding_attention")
        n_sparse = n_layers - c["num_dense_layers"]
        matmul = (n_layers * (3 * h * q + 2 * h * kv)
                  + c["num_dense_layers"] * 3 * h * c["intermediate_size"]
                  + n_sparse * (h * self.n_experts + 3 * h * f * (
                      c["num_experts_per_tok"] + c["num_shared_experts"]))
                  + c["vocab_size"] * h)
        seen = (n_slide * min(seq_len, 2 * c["sliding_window"])
                + (n_layers - n_slide) * seq_len)
        return 6.0 * matmul + 12 * q * seen / 2
