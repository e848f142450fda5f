"""Llama serving walkthrough — the inference half the reference never had
(apex accelerates training only; a complete framework serves the model it
just fine-tuned). Demonstrates, on one model, the whole decode stack:

1. greedy KV-cached generation (`models.generate`, one-dispatch scan);
2. RAGGED batching — mixed-length prompts served together via
   ``prompt_lens`` (left-aligned once; each row decodes exactly as if it
   were alone);
3. beam search with the GNMT length penalty;
4. int8 weight-only decode (`models.quant_decode`) — the same generate
   loop over per-out-channel int8 weights dequantized inside the Pallas
   GEMM's VMEM tiles (half the HBM weight traffic, the decode
   bottleneck);
5. speculative decoding — a small draft proposes, the target verifies a
   whole chunk per forward; output token-identical to the target's own
   greedy decode, with the per-row verify-round counts printed (the
   speedup observable);
6. prefix caching — a shared system prompt prefilled once, two user
   turns continued off it (`cache_start`), each token-exact vs the flat
   prompt.

``python examples/serving_llama.py [--tiny] [--batch 2] [--prompt-len 8]
                                   [--new 16] [--beams 4]``
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import dataclasses

from apex1_tpu.core.policy import get_policy
from apex1_tpu.models.generate import (beam_search, generate,
                                       llama_decoder,
                                       speculative_generate)
from apex1_tpu.models.llama import Llama, LlamaConfig
from apex1_tpu.models.quant_decode import llama_quant_decoder


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new", type=int, default=16)
    ap.add_argument("--beams", type=int, default=4)
    args = ap.parse_args()

    on_accel = jax.default_backend() not in ("cpu",)
    if args.tiny or not on_accel:
        cfg = LlamaConfig.tiny(policy=get_policy("O2"), max_seq_len=128)
    else:
        cfg = LlamaConfig(vocab_size=32000, max_seq_len=2048,
                          num_layers=16, num_heads=32, num_kv_heads=4,
                          hidden_size=2048, ffn_size=5632,
                          policy=get_policy("O2"))
    model = Llama(cfg)
    B, S0, N = args.batch, args.prompt_len, args.new
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, S0)),
                         jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), prompt)["params"]
    apply_fn, make_cache = llama_decoder(model)

    def timed(tag, fn):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        print(f"  {tag:34s} {time.perf_counter() - t0:7.2f}s "
              f"(incl. compile)")
        return out

    print(f"== serving {B}x{S0}+{N} on {jax.default_backend()} ==")
    toks = timed("greedy generate", lambda: generate(
        apply_fn, params, prompt, max_new_tokens=N,
        cache=make_cache(B, S0 + N), vocab_size=cfg.vocab_size))
    print(f"    row0: {np.asarray(toks[0])[:10]}...")

    # ragged: same rows at their true (mixed) lengths in ONE batch
    lens = jnp.asarray([S0] + [max(1, S0 // 2)] * (B - 1), jnp.int32)
    ragged = timed("ragged generate (mixed lens)", lambda: generate(
        apply_fn, params, prompt, max_new_tokens=N,
        cache=make_cache(B, S0 + N), vocab_size=cfg.vocab_size,
        prompt_lens=lens))
    print(f"    lens {np.asarray(lens)} -> row1: "
          f"{np.asarray(ragged[1])[:10]}...")

    beams, scores = timed(f"beam search K={args.beams}, lp=1.0",
                          lambda: beam_search(
        apply_fn, params, prompt, max_new_tokens=N,
        cache=make_cache(B * args.beams, S0 + N),
        num_beams=args.beams, length_penalty=1.0,
        vocab_size=cfg.vocab_size))
    print(f"    best scores: {np.asarray(scores).round(3)}")

    apply_q, make_cache_q, qparams = llama_quant_decoder(model, params)
    toks_q = timed("int8 weight-only generate", lambda: generate(
        apply_q, qparams, prompt, max_new_tokens=N,
        cache=make_cache_q(B, S0 + N), vocab_size=cfg.vocab_size))
    agree = float((np.asarray(toks_q) == np.asarray(toks)).mean())
    print(f"    token agreement with bf16: {agree:.2f} "
          f"(quantization shifts logits; ~1.0 expected at these sizes)")

    # speculative: a shallow draft of the same family; identical tokens,
    # fewer target forwards when the draft agrees
    draft_cfg = dataclasses.replace(
        cfg, num_layers=max(1, cfg.num_layers // 4))
    draft = Llama(draft_cfg)
    pd = jax.jit(draft.init)(jax.random.key(7), prompt)["params"]
    d_fn, make_cache_d = llama_decoder(draft)
    K = 4
    toks_s, rounds = timed("speculative (K=4, shallow draft)",
                           lambda: speculative_generate(
        apply_fn, params, d_fn, pd, prompt, max_new_tokens=N,
        target_cache=make_cache(B, S0 + N + K + 1),
        draft_cache=make_cache_d(B, S0 + N + K + 1),
        num_draft=K, vocab_size=cfg.vocab_size))
    assert (np.asarray(toks_s) == np.asarray(toks)).all(), \
        "speculative output must be token-identical to greedy"
    print(f"    verify rounds/row {np.asarray(rounds).tolist()} vs "
          f"{N - 1} greedy target forwards (untrained draft -> little "
          f"agreement; a distilled draft shrinks rounds toward "
          f"{(N - 1 + K) // (K + 1)})")

    # prefix caching: prefill the "system prompt" once, continue turns
    Ls = max(2, S0 // 2)
    cache_pre = make_cache(B, S0 + Ls + N)
    _, cache_pre = jax.jit(apply_fn)(params, prompt, cache_pre, 0)
    agrees = []
    for turn in range(2):
        user = jnp.asarray(
            np.random.default_rng(100 + turn).integers(
                1, cfg.vocab_size, (B, Ls)), jnp.int32)
        cont = timed(f"prefix-cached turn {turn}", lambda: generate(
            apply_fn, params, user, max_new_tokens=N, cache=cache_pre,
            cache_start=S0, vocab_size=cfg.vocab_size))
        flat = generate(apply_fn, params,
                        jnp.concatenate([prompt, user], 1),
                        max_new_tokens=N, cache=make_cache(B, S0 + Ls + N),
                        vocab_size=cfg.vocab_size)
        agrees.append(float(
            (np.asarray(cont) == np.asarray(flat)).mean()))
    # this walkthrough runs the O2 (bf16) policy: the chunk-decode
    # continuation prefill and the flat flash prefill round differently
    # in bf16, so a near-tie argmax can flip — exactness holds at fp32
    # (pinned in test_generate::TestPrefixCaching); report agreement
    # like the int8 section rather than asserting it
    print(f"    2 turns off one cached prefix; token agreement vs flat "
          f"{[round(a, 2) for a in agrees]} (exact under fp32; bf16 "
          f"rounds near-ties differently across the two prefill paths)")
    print("serving walkthrough done")


if __name__ == "__main__":
    main()
