"""Per-op and per-model TPU profiling harness.

Usage (on a machine with a live TPU):
    python tools/profile_ops.py [ops|gpt2|llama|all]

Prints ms per fwd / fwd+bwd for each Pallas kernel vs its XLA composite,
and model-level step breakdowns. Sync discipline: every measurement
fetches one fused scalar reduction over all outputs (see bench.py).
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _reduce_all(tree):
    return sum(jnp.sum(x.astype(jnp.float32)) for x in jax.tree.leaves(tree))


def sync(tree):
    float(_reduce_all(tree))


def bench(name, fn, *args, n=20):
    sync(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*args)
    sync(r)
    ms = (time.perf_counter() - t0) / n * 1e3
    print(f"{name:55s} {ms:8.2f} ms", flush=True)
    return ms


def profile_ops():
    from apex1_tpu.ops import (layer_norm, set_impl,
                               scaled_upper_triang_masked_softmax,
                               softmax_cross_entropy_loss)
    from apex1_tpu.ops.attention import flash_attention

    rng = np.random.default_rng(0)
    B, S, H, D, hid, V = 8, 1024, 12, 64, 768, 50304

    x3 = jnp.asarray(rng.normal(size=(B, S, hid)), jnp.bfloat16)
    gamma = jnp.ones((hid,), jnp.float32)
    beta = jnp.zeros((hid,), jnp.float32)
    for impl in ("auto", "xla"):
        set_impl(impl)
        f = jax.jit(jax.grad(lambda x: jnp.sum(
            layer_norm(x, gamma, beta).astype(jnp.float32))))
        bench(f"layernorm f+b (B{B} S{S} H{hid}) [{impl}]", f, x3)
    set_impl("auto")

    scores = jnp.asarray(rng.normal(size=(B, H, S, S)), jnp.float32)
    for impl in ("auto", "xla"):
        set_impl(impl)
        f = jax.jit(jax.grad(lambda s: jnp.sum(
            scaled_upper_triang_masked_softmax(s, scale=0.125))))
        bench(f"causal softmax f+b (B{B} H{H} S{S}) [{impl}]", f, scores)
    set_impl("auto")

    logits = jnp.asarray(rng.normal(size=(B * S, V)), jnp.float32)
    lbl = jnp.asarray(rng.integers(0, 50257, (B * S,)), jnp.int32)
    for impl in ("auto", "xla"):
        set_impl(impl)
        f = jax.jit(jax.grad(lambda l: jnp.sum(softmax_cross_entropy_loss(
            l, lbl, num_classes=50257))))
        bench(f"xentropy f+b ({B*S}x{V}) [{impl}]", f, logits)
    set_impl("auto")

    q = jnp.asarray(rng.normal(size=(B, H, S, 128)), jnp.bfloat16)
    f = jax.jit(jax.grad(lambda q: jnp.sum(
        flash_attention(q, q, q, causal=True).astype(jnp.float32))))
    bench(f"flash attn f+b (B{B} H{H} S{S} D128)", f, q)

    # fused LM-head+CE vs materialized logits+CE at GPT-2 head scale
    from apex1_tpu.ops import linear_cross_entropy
    h2 = jnp.asarray(rng.normal(size=(B * S, hid)) * 0.3, jnp.bfloat16)
    w2 = jnp.asarray(rng.normal(size=(V, hid)) * 0.3, jnp.bfloat16)
    f = jax.jit(jax.grad(lambda h, w: jnp.sum(linear_cross_entropy(
        h, w, lbl, num_classes=50257)), argnums=(0, 1)))
    bench(f"fused linear+CE f+b ({B*S}x{hid}x{V})", f, h2, w2)

    def unfused(h, w):
        logits = jnp.einsum("th,vh->tv", h, w,
                            preferred_element_type=jnp.float32)
        return jnp.sum(softmax_cross_entropy_loss(logits, lbl,
                                                  num_classes=50257))
    f = jax.jit(jax.grad(unfused, argnums=(0, 1)))
    bench(f"matmul+xentropy f+b ({B*S}x{hid}x{V})", f, h2, w2)


def profile_gpt2():
    from apex1_tpu.amp import Amp
    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn
    from apex1_tpu.optim.fused_adam import fused_adam

    for use_flash in (True, False):
        cfg = GPT2Config(policy=get_policy("O2"), use_flash=use_flash)
        model = GPT2(cfg)
        tokens = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (8, 1024)), jnp.int32)
        params = jax.jit(model.init)(jax.random.key(0), tokens)["params"]
        amp = Amp(tx=fused_adam(1e-4), opt_level="O2")
        state = amp.init(params)
        step = jax.jit(amp.make_train_step(gpt2_loss_fn(model)))
        ms = bench(f"gpt2-125M O2 step (flash={use_flash})", step, state,
                   tokens, n=10)
        toks = 8 * 1024 / (ms / 1e3)
        print(f"    -> {toks:,.0f} tokens/sec/chip")
        del state, params


def profile_llama():
    from apex1_tpu.amp import Amp
    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.llama import Llama, LlamaConfig, llama_loss_fn
    from apex1_tpu.optim.fused_adam import fused_adam

    # single-chip-sized llama (8B needs the pod); long-seq to exercise
    # flash + remat
    cfg = LlamaConfig(vocab_size=32128, max_seq_len=4096, num_layers=8,
                      num_heads=16, num_kv_heads=8, hidden_size=1024,
                      ffn_size=2816, remat=True,
                      policy=get_policy("O2"))
    model = Llama(cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 4096)), jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), tokens)["params"]
    amp = Amp(tx=fused_adam(1e-4), opt_level="O2")
    state = amp.init(params)
    step = jax.jit(amp.make_train_step(llama_loss_fn(model)))
    ms = bench("llama-0.2B long-ctx O2 remat step (S=4096)", step, state,
               tokens, n=5)
    print(f"    -> {1 * 4096 / (ms / 1e3):,.0f} tokens/sec/chip")


if __name__ == "__main__":
    what = sys.argv[1] if len(sys.argv) > 1 else "all"
    print("backend:", jax.default_backend(), flush=True)
    if what in ("ops", "all"):
        profile_ops()
    if what in ("gpt2", "all"):
        profile_gpt2()
    if what in ("llama", "all"):
        profile_llama()
