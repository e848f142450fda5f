"""Logical operation counts, computed from the configuration's sizes and
the cell's shapes — never from `cost_analysis()`, which cannot see inside
a `tpu_custom_call`. "Logical" = what the forward and backward passes of
the published model require: recomputed operations do not count, and a
causal attention is counted once (half the square).

Training FLOPs per token = 6 x (parameters that sit in a matmul, applied
once per token) + the attention products: 12*S*H per layer (QK^T and PV,
2 FLOPs a multiply-add, forward + two backward), halved where causal.
"""

from __future__ import annotations


def gpt2_matmul_params(cfg: dict) -> int:
    """qkv 3H^2 + proj H^2 + MLP 8H^2 per layer, plus the tied head V*H
    (the embedding LOOKUP is not a matmul and is not counted)."""
    h, n_layer, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    return 12 * n_layer * h * h + v * h


def gpt2_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    attn = 12 * seq_len * cfg["n_embd"] * cfg["n_layer"] / 2   # causal
    return 6.0 * gpt2_matmul_params(cfg) + attn


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


TRAIN_FLOPS_PER_TOKEN = {
    "gpt2": gpt2_train_flops_per_token,
    "bert_pretrain": bert_train_flops_per_token,
}


def mfu_pct(tokens_per_s_per_chip: float, flops_per_token: float,
            peak_flops: float) -> float:
    return 100.0 * tokens_per_s_per_chip * flops_per_token / peak_flops
