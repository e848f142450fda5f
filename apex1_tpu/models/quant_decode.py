"""Weight-only int8 quantized decode for `models.llama.Llama`.

Beyond-reference serving capability: autoregressive decode streams every
weight from HBM once per emitted token, so at batch sizes that don't
saturate the MXU the step time is weight-bytes / HBM-bandwidth — int8
storage halves it vs bf16. Weights are quantized ONCE
(:func:`quantize_llama_params`, per-out-channel symmetric int8 via
`ops.quantize_int8`) and every decode matmul runs through
`ops.int8_matmul`, whose Pallas kernel dequantizes inside VMEM tiles (the
bf16 weight matrix never exists in HBM).

This is a dedicated inference forward, not the flax module: it mirrors the
cached path of `models.llama.Llama.__call__` (same rms_norm / RoPE /
`generate.cached_attention` calls — the norm/rope/attention ops are shared
code, only the weight matmuls differ) and plugs into `generate` /
`beam_search` through the same ``apply_fn(params, tokens, cache,
cache_index)`` contract as `generate.llama_decoder`. Parity is pinned by
``tests/test_quantized.py``: with weights constructed exactly
representable in int8 the quantized decode must match the full-precision
model to bf16 rounding, and with real weights to quantization tolerance.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex1_tpu.models.generate import cached_attention, init_cache
from apex1_tpu.ops import (apply_rotary_pos_emb, int8_matmul, quantize_int8,
                           rms_norm, rope_tables)
from apex1_tpu.models.llama import is_moe_layer
from apex1_tpu.transformer.moe import MoEConfig, router


def quantize_llama_params(params, cfg):
    """Quantize a Llama param tree for decode. Embedding stays a bf16
    gather table; norms stay fp32; every matmul weight becomes
    ``{"q": int8 (out, in), "s": fp32 (out,)}`` (weights stored (in, out)
    in the flax tree are transposed into the kernel's (N, K) layout
    once, here).

    MoE layers (``cfg.moe_every > 0``): the stacked expert FFNs
    ``w1 (E, H, F)`` / ``w2 (E, F, H)`` quantize PER EXPERT per out
    channel — ``{"q": (E, out, in) int8, "s": (E, out) fp32}`` — since
    expert weights are the bulk of an MoE checkpoint's bytes, exactly
    the HBM-bound traffic int8 decode exists to halve. The router gate
    stays fp32 (tiny, and routing decisions feed top-k: quantizing it
    would flip near-tied expert choices for ~zero byte savings)."""
    dt = cfg.policy.compute_dtype

    def qt(w):  # (in, out) -> kernel layout (out, in)
        q, s = quantize_int8(jnp.asarray(w).T)
        return {"q": q, "s": s}

    def qt_experts(w):  # (E, in, out) -> (E, out, in) + (E, out)
        qs = [quantize_int8(jnp.asarray(w[e]).T)
              for e in range(w.shape[0])]
        return {"q": jnp.stack([q for q, _ in qs]),
                "s": jnp.stack([s for _, s in qs])}

    out = {"tok_embeddings": params["tok_embeddings"].astype(dt),
           "norm": params["norm"]}
    for i in range(cfg.num_layers):
        lp = params[f"layer{i}"]
        qlp = {
            "attn_norm": lp["attn_norm"],
            "mlp_norm": lp["mlp_norm"],
            "wq": qt(lp["wq"]), "wk": qt(lp["wk"]), "wv": qt(lp["wv"]),
            "wo": qt(lp["wo"]),
        }
        if is_moe_layer(cfg, i):
            qlp["moe"] = {
                "router": jnp.asarray(lp["moe"]["router"], jnp.float32),
                "w1": qt_experts(lp["moe"]["w1"]),
                "w2": qt_experts(lp["moe"]["w2"]),
            }
        else:
            qlp.update(w_gate=qt(lp["w_gate"]), w_up=qt(lp["w_up"]),
                       w_down=qt(lp["w_down"]))
        out[f"layer{i}"] = qlp
    # head is stored (vocab, hidden) = (N, K) already
    q, s = quantize_int8(jnp.asarray(params["output"]))
    out["output"] = {"q": q, "s": s}
    return out


def llama_quant_decoder(model, params):
    """(apply_fn, make_cache, qparams) for int8 decode of a `Llama`.

    ``apply_fn(qparams, tokens, cache, cache_index)`` has the
    `generate.llama_decoder` contract — pass it (with ``qparams`` as the
    params) to :func:`generate.generate` / :func:`generate.beam_search`.
    """
    cfg = model.cfg
    dt = cfg.policy.compute_dtype
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qparams = quantize_llama_params(params, cfg)

    def mm(x, qw):
        return int8_matmul(x, qw["q"], qw["s"]).astype(dt)

    def norm_g(g):
        return g if cfg.policy.keep_norms_fp32 else g.astype(dt)

    moecfg = (None if cfg.moe_every <= 0 else MoEConfig(
        num_experts=cfg.num_experts, top_k=cfg.moe_top_k,
        capacity_factor=cfg.moe_capacity_factor,
        aux_loss_weight=cfg.moe_aux_loss_weight,
        hidden_size=cfg.hidden_size, ffn_size=cfg.ffn_size))

    def moe_ffn(h, qm, segment_ids):
        """Dense-dispatch MoE FFN (the `transformer.moe.MoEMLP` decode
        math — same router, same capacity/drop semantics) with the
        expert matmuls through `ops.int8_matmul` per expert. Aux loss is
        computed-and-dropped: decode has no optimizer to feed it."""
        lead, H = h.shape[:-1], h.shape[-1]
        x2 = h.reshape(-1, H)
        mask = (None if segment_ids is None
                else (segment_ids >= 0).reshape(-1))
        dispatch, combine, _aux = router(x2, qm["router"], moecfg, mask)
        xe = jnp.einsum("tec,th->ech", dispatch.astype(dt),
                        x2.astype(dt))                    # (E, C, H)
        q1, s1 = qm["w1"]["q"], qm["w1"]["s"]             # (E, F, H)
        q2, s2 = qm["w2"]["q"], qm["w2"]["s"]             # (E, H, F)
        # vmap over the stacked expert axis (the layout qt_experts
        # already produces) — one batched Pallas GEMM per projection
        # instead of 2E unrolled dispatches (review r5: the unroll
        # bloated the HLO and serialized independent expert matmuls;
        # MoEMLP's bf16 form is one stacked einsum for the same reason)
        ye = jax.vmap(lambda xe_e, q1_e, s1_e, q2_e, s2_e: int8_matmul(
            jax.nn.silu(int8_matmul(xe_e, q1_e, s1_e).astype(dt)),
            q2_e, s2_e))(xe, q1, s1, q2, s2)              # (E, C, H)
        y = jnp.einsum("tec,ech->th", combine.astype(dt),
                       ye.astype(dt))
        return y.reshape(*lead, H)

    def apply_fn(qp, tokens, cache, cache_index, *, positions=None,
                 segment_ids=None, valid_start=None, chunk_decode=False):
        # the keyword-only args carry the RAGGED (left-padded) masking,
        # exactly as in `generate.llama_decoder` — so the int8 path
        # composes with generate(prompt_lens=...)
        B, S = tokens.shape
        idx = jnp.asarray(cache_index, jnp.int32)
        x = qp["tok_embeddings"][tokens].astype(dt)
        if positions is None:
            pos = idx + jnp.arange(S)
            cos, sin = rope_tables(pos, D, base=cfg.rope_base)
        else:  # (B, S) per-row positions -> per-row tables
            cos, sin = rope_tables(
                jnp.asarray(positions).reshape(-1), D, base=cfg.rope_base)
            cos = cos.reshape(B, S, -1)
            sin = sin.reshape(B, S, -1)
        new_cache = {}
        for i in range(cfg.num_layers):
            lp = qp[f"layer{i}"]
            h = rms_norm(x, norm_g(lp["attn_norm"]),
                         eps=cfg.norm_eps).astype(dt)
            q = mm(h, lp["wq"]).reshape(B, S, H, D)
            k = mm(h, lp["wk"]).reshape(B, S, Hkv, D)
            v = mm(h, lp["wv"]).reshape(B, S, Hkv, D)
            q = apply_rotary_pos_emb(q, cos, sin)
            k = apply_rotary_pos_emb(k, cos, sin)
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            attn, new_cache[f"layer{i}"] = cached_attention(
                q, k, v, cache[f"layer{i}"], cache_index,
                segment_ids=segment_ids, valid_start=valid_start,
                chunk_decode=chunk_decode)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, S, H * D)
            x = x + mm(attn, lp["wo"]).astype(x.dtype)
            h = rms_norm(x, norm_g(lp["mlp_norm"]),
                         eps=cfg.norm_eps).astype(dt)
            if is_moe_layer(cfg, i):
                y = moe_ffn(h, lp["moe"], segment_ids)
            else:
                y = mm(jax.nn.silu(mm(h, lp["w_gate"]))
                       * mm(h, lp["w_up"]), lp["w_down"])
            x = x + y.astype(x.dtype)
        x = rms_norm(x, norm_g(qp["norm"]), eps=cfg.norm_eps).astype(dt)
        logits = int8_matmul(x, qp["output"]["q"], qp["output"]["s"])
        return logits, new_cache

    def make_cache(batch: int, max_len: int, dtype=None, **form):
        return init_cache(cfg.num_layers, batch, Hkv, max_len, D,
                          dtype or dt, **form)

    return apply_fn, make_cache, qparams


def quantize_gpt2_params(params, cfg):
    """Quantize a GPT-2 param tree for decode. The tied ``wte`` is kept
    TWICE: as the bf16 gather table (embedding lookup is not a matmul)
    and as the int8 LM head (``(padded_vocab, hidden)`` is already the
    kernel's (N, K) layout). Dense kernels stored (in, out) transpose
    once, here; LayerNorm scale/bias and the dense biases stay fp32."""
    dt = cfg.policy.compute_dtype

    def qt(kernel):  # (in, out) -> (out, in)
        q, s = quantize_int8(jnp.asarray(kernel).T)
        return {"q": q, "s": s}

    out = {"wte": params["wte"].astype(dt),
           "wpe": params["wpe"].astype(dt),
           "lnf_scale": params["lnf_scale"],
           "lnf_bias": params["lnf_bias"]}
    for i in range(cfg.num_layers):
        lp = params[f"h{i}"]
        out[f"h{i}"] = {
            "ln1_scale": lp["ln1_scale"], "ln1_bias": lp["ln1_bias"],
            "ln2_scale": lp["ln2_scale"], "ln2_bias": lp["ln2_bias"],
            "qkv": qt(lp["qkv"]["kernel"]),
            "qkv_b": lp["qkv"]["bias"],
            "proj": qt(lp["proj"]["kernel"]),
            "proj_b": lp["proj"]["bias"],
            "fc_in": qt(lp["fc_in"]["kernel"]),
            "fc_in_b": lp["fc_in"]["bias"],
            "fc_out": qt(lp["fc_out"]["kernel"]),
            "fc_out_b": lp["fc_out"]["bias"],
        }
    q, s = quantize_int8(jnp.asarray(params["wte"]))
    out["head"] = {"q": q, "s": s}
    return out


def gpt2_quant_decoder(model, params):
    """(apply_fn, make_cache, qparams) for int8 decode of a `GPT2` —
    mirrors the flax module's cached path (LN with bias, fused qkv,
    causal cached attention at 1/sqrt(hd), GELU MLP, tied padded-vocab
    head) with every matmul through `ops.int8_matmul`. Same
    `generate.gpt2_decoder` apply_fn contract, ragged kwargs included."""
    import math

    from apex1_tpu.ops import layer_norm

    cfg = model.cfg
    dt = cfg.policy.compute_dtype
    nh = cfg.num_heads
    hd = cfg.hidden_size // nh
    qparams = quantize_gpt2_params(params, cfg)

    def mm(x, qw, b):
        y = int8_matmul(x, qw["q"], qw["s"])
        return (y + b.astype(jnp.float32)).astype(dt)

    def ln(x, g, b):
        if not cfg.policy.keep_norms_fp32:
            g, b = g.astype(dt), b.astype(dt)
        return layer_norm(x, g, b)

    def apply_fn(qp, tokens, cache, cache_index, *, positions=None,
                 segment_ids=None, valid_start=None, chunk_decode=False):
        B, S = tokens.shape
        idx = jnp.asarray(cache_index, jnp.int32)
        if positions is None:
            positions = jnp.broadcast_to((idx + jnp.arange(S))[None],
                                         (B, S))
        # mode="fill" NaN mirrors the flax model's loud out-of-range
        # positions (gpt2.py): a cache sized past max_seq_len must go
        # non-finite, not clamp to the last learned position
        x = (qp["wte"][tokens]
             + jnp.take(qp["wpe"], positions, axis=0, mode="fill",
                        fill_value=jnp.nan)).astype(dt)
        new_cache = {}
        for i in range(cfg.num_layers):
            lp = qp[f"h{i}"]
            h = ln(x, lp["ln1_scale"], lp["ln1_bias"]).astype(dt)
            qkv = mm(h, lp["qkv"], lp["qkv_b"])
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q, k, v = (t.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)
                       for t in (q, k, v))
            attn, new_cache[f"layer{i}"] = cached_attention(
                q, k, v, cache[f"layer{i}"], cache_index,
                sm_scale=1.0 / math.sqrt(hd),
                segment_ids=segment_ids, valid_start=valid_start,
                chunk_decode=chunk_decode)
            attn = attn.transpose(0, 2, 1, 3).reshape(B, S, nh * hd)
            x = x + mm(attn, lp["proj"], lp["proj_b"])
            y = ln(x, lp["ln2_scale"], lp["ln2_bias"]).astype(dt)
            y = jax.nn.gelu(mm(y, lp["fc_in"], lp["fc_in_b"]))
            x = x + mm(y, lp["fc_out"], lp["fc_out_b"])
        x = ln(x, qp["lnf_scale"], qp["lnf_bias"]).astype(dt)
        logits = int8_matmul(x, qp["head"]["q"], qp["head"]["s"])
        return logits, new_cache

    def make_cache(batch: int, max_len: int, dtype=None, **form):
        return init_cache(cfg.num_layers, batch, nh, max_len, hd,
                          dtype or dt, **form)

    return apply_fn, make_cache, qparams
