"""The train and decode steps that `tools/aot_check.py --steps` compiles
for a described TPU: one builder per family, most of which no benchmark
cell runs (BERT, ResNet, the Llama variants, T5, decode, int8 decode),
so that gate is the only Mosaic evidence those paths have.

Each builder returns ``(state, step, batch, units_per_step, iters, label,
unit, a100_row)``. The gate reads the first three; the rest is what the
measuring script these builders came from printed (the last is the
family's row of BASELINE.md's A100 table). Nothing here measures: the one
speed measurement is ``python3 -m benchmark.run``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _amp_state_step(model_loss_fn, params, lr=1e-4, opt_level="O2"):
    from apex1_tpu.amp import Amp
    from apex1_tpu.optim.fused_adam import fused_adam

    amp = Amp(tx=fused_adam(lr, weight_decay=0.01), opt_level=opt_level)
    return amp.init(params), amp.make_train_step(model_loss_fn)


def bench_gpt2(on_accel, batch=None, seq=None, fp16=False):
    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn

    # fp16=True: the O1_fp16 policy — fp16 compute, fp32 fragile ops,
    # DYNAMIC loss scaling with skip-on-overflow (half the reference's
    # reason to exist; VERDICT Weak #8 wanted hardware evidence with the
    # skip-step count and final loss-scale in the record)
    level = "O1_fp16" if fp16 else "O2"
    if on_accel:
        # B=16 AOT-verified on v5e (8.2 GiB incl. donated args; B=8 left
        # the MXU underfed — tools/aot_check.py sized both)
        B, S, iters = batch or 16, seq or 1024, 10
        cfg = GPT2Config(policy=get_policy(level),
                         max_seq_len=max(S, 1024))
    else:
        B, S, iters = batch or 2, seq or 128, 3
        cfg = GPT2Config.tiny(policy=get_policy(level),
                              max_seq_len=max(S, 128))
    model = GPT2(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (B, S)),
        jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), tokens)["params"]
    state, step = _amp_state_step(gpt2_loss_fn(model), params,
                                  opt_level=level)
    name = "GPT-2-125M" if on_accel else "GPT-2(tiny smoke)"
    return (state, step, (tokens,), B * S, iters,
            f"tokens/sec/chip {name} amp-{level} fused_adam",
            "tokens/sec/chip",
            145_000.0)   # BASELINE.md pinned A100 row: gpt2


def bench_bert(on_accel, large=False, dropout=0.0):
    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.bert import (BertConfig, BertPretrain,
                                       bert_pretrain_loss_fn)

    if on_accel:
        B, S, iters = (4, 512, 8) if large else (8, 512, 10)
        mk = BertConfig.bert_large if large else BertConfig.bert_base
        cfg = mk(policy=get_policy("O2"), dropout=dropout)
    else:
        B, S, iters = 2, 64, 3
        cfg = BertConfig.tiny(policy=get_policy("O2"), dropout=dropout)
    model = BertPretrain(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    mlm_labels = jnp.asarray(
        np.where(rng.random((B, S)) < 0.15,
                 rng.integers(0, cfg.vocab_size, (B, S)), -1), jnp.int32)
    batch = {"tokens": tokens, "mlm_labels": mlm_labels,
             "nsp_labels": jnp.asarray(rng.integers(0, 2, (B,)), jnp.int32)}
    if dropout > 0.0:
        # presence of the key ACTIVATES the in-kernel dropout paths
        # (flash attention-probability dropout + fused dropout-add-LN
        # epilogues). One fixed key per run: every timed step draws the
        # same masks — the PRNG work is identical per step, which is
        # what the throughput number prices; training would thread a
        # fresh key per step.
        batch["dropout_rng"] = jax.random.key(1234)
    params = jax.jit(model.init)(jax.random.key(0), tokens)["params"]
    state, step = _amp_state_step(bert_pretrain_loss_fn(model), params)
    name = (("BERT-large-pretrain" if large else "BERT-base-pretrain")
            if on_accel else "BERT(tiny smoke)")
    if dropout > 0.0:
        name += f"-dropout{dropout}"
    # BASELINE.md pinned A100 rows: bert_large / bert
    proxy = 57_500.0 if large else 173_000.0
    return (state, step, (batch,), B * S, iters,
            f"tokens/sec/chip {name} amp-O2 fused_adam", "tokens/sec/chip",
            proxy)


def bench_resnet(on_accel):
    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.resnet import ResNet, ResNetConfig
    from apex1_tpu.ops import softmax_cross_entropy_loss

    if on_accel:
        B, HW, iters = 64, 224, 10
        cfg = ResNetConfig.resnet50(policy=get_policy("O2"))
    else:
        B, HW, iters = 2, 32, 3
        cfg = ResNetConfig.tiny(policy=get_policy("O2"))
    model = ResNet(cfg)
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.normal(size=(B, HW, HW, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, cfg.num_classes, (B,)), jnp.int32)
    variables = jax.jit(model.init)(jax.random.key(0), images)
    bn0 = variables.get("batch_stats", {})

    def loss_fn(params, images, labels, bn):
        logits, upd = model.apply(
            {"params": params, "batch_stats": bn}, images,
            mutable=["batch_stats"])
        loss = jnp.mean(softmax_cross_entropy_loss(
            logits.astype(jnp.float32), labels))
        return loss, upd["batch_stats"]

    from apex1_tpu.amp import Amp
    from apex1_tpu.optim.fused_sgd import fused_sgd

    amp = Amp(tx=fused_sgd(0.1, momentum=0.9, weight_decay=1e-4),
              opt_level="O2")
    state = amp.init(variables["params"])
    inner = amp.make_train_step(loss_fn, has_aux=True)

    def step(carry, images, labels):
        st, bn = carry
        st, metrics = inner(st, images, labels, bn)
        return (st, metrics["aux"]), metrics

    name = "ResNet-50" if on_accel else "ResNet(tiny smoke)"
    return ((state, bn0), step, (images, labels), B, iters,
            f"images/sec/chip {name} amp-O2 fused_sgd", "images/sec/chip",
            2_900.0)   # BASELINE.md pinned A100 row: resnet (NGC-class)


def _bench_llama(on_accel, *, accel_cfg, accel_bsi, tiny_seq, name, proxy):
    """Shared scaffolding for the Llama-family configs below."""
    import dataclasses

    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.llama import Llama, LlamaConfig, llama_loss_fn

    if on_accel:
        B, S, iters = accel_bsi
        cfg = accel_cfg(get_policy("O2"), S)
    else:
        B, S, iters = 1, tiny_seq, 2
        cfg = dataclasses.replace(
            LlamaConfig.tiny(policy=get_policy("O2")), max_seq_len=S,
            remat=True)
        name = "Llama(tiny smoke)"
    model = Llama(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (B, S)),
        jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), tokens)["params"]
    state, step = _amp_state_step(llama_loss_fn(model), params)
    return (state, step, (tokens,), B * S, iters,
            f"tokens/sec/chip {name} amp-O2 remat", "tokens/sec/chip",
            proxy)


def bench_llama_longctx(on_accel):
    from apex1_tpu.models.llama import LlamaConfig

    # 16 layers: AOT memory analysis (tools/aot_check.py) showed the
    # 22-layer variant needs 18.7 GiB on a 15.75 GiB v5e (Adam state
    # dominates); 16 layers compiles at ~14.4 GiB with margin
    return _bench_llama(
        on_accel,
        accel_cfg=lambda pol, S: LlamaConfig(
            vocab_size=32000, max_seq_len=S, num_layers=16,
            num_heads=32, num_kv_heads=4, hidden_size=2048,
            ffn_size=5632, remat=True, policy=pol),
        accel_bsi=(1, 16384, 4), tiny_seq=512,
        name="Llama-0.8B-16k-flash",
        proxy=11_100.0)   # BASELINE.md pinned A100 row: llama_longctx


def bench_llama_block(on_accel):
    """BASELINE config 4's single-chip proxy (VERDICT r2 item 6): a
    Llama-3-8B-WIDTH decoder stack (hidden 4096, ffn 14336, 32 heads /
    8 KV, full flash + fused RoPE/RMSNorm/CE path) at the depth that fits
    one chip with full Adam state — tp=pp=1, remat. Times the exact
    per-layer fused stack the dp2×pp2×tp4 flagship runs per stage, so
    tokens/sec here × (depth ratio) bounds the full-model per-chip rate.
    3 layers + 32k-vocab embedding/head ≈ 0.9B params ≈ 11 GiB Adam
    state on a 16 GiB v5e."""
    from apex1_tpu.models.llama import LlamaConfig

    return _bench_llama(
        on_accel,
        accel_cfg=lambda pol, S: LlamaConfig(
            vocab_size=32000, max_seq_len=S, num_layers=3,
            num_heads=32, num_kv_heads=8, hidden_size=4096,
            ffn_size=14336, remat=True, policy=pol),
        accel_bsi=(2, 4096, 6), tiny_seq=256,
        name="Llama-8B-width-3L",
        proxy=20_800.0)   # BASELINE.md pinned A100 row: llama_block


def bench_t5(on_accel):
    """Beyond-BASELINE: T5-large-class encoder-decoder (the enc-dec family
    the reference's variable-shape pipeline machinery serves) — rel-pos
    bias on the Pallas fused-softmax path + flash cross-attention + fused
    tied-head CE. Sized to fit one v5e with full Adam state (12 enc + 12
    dec layers at d_model 1024 ≈ 0.4B params)."""
    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.t5 import T5, T5Config, t5_loss_fn

    if on_accel:
        B, S_enc, S_dec, iters = 8, 512, 512, 8
        cfg = T5Config.t5_large(policy=get_policy("O2"),
                                num_encoder_layers=12,
                                num_decoder_layers=12, remat=True)
    else:
        B, S_enc, S_dec, iters = 2, 32, 32, 3
        cfg = T5Config.tiny(policy=get_policy("O2"))
    model = T5(cfg)
    rng = np.random.default_rng(0)
    enc = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S_enc)),
                      jnp.int32)
    dec = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S_dec)),
                      jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), enc, dec)["params"]
    state, step = _amp_state_step(t5_loss_fn(model), params)
    name = "T5-0.4B-encdec" if on_accel else "T5(tiny smoke)"
    return (state, step, (enc, dec), B * (S_enc + S_dec), iters,
            f"tokens/sec/chip {name} amp-O2 fused_adam", "tokens/sec/chip",
            48_000.0)   # BASELINE.md pinned A100 row: t5


def bench_decode(on_accel, quant=False):
    """Serving-path decode throughput (beyond-BASELINE; the reference is
    training-only): KV-cached autoregressive generation through
    `models.generate` — prefill + a fixed number of single-dispatch
    decode steps per measured "step". ``quant=True`` times the int8
    weight-only path (`models.quant_decode`): decode is HBM-bound, so
    int8 weights should approach 2x the bf16 tokens/sec at small batch.

    Comparator: BASELINE.md pinned A100 decode rows — the 0.8B model's
    weight-streaming HBM roofline at B=8 x 0.6 achieved bandwidth
    (bf16 6.1k tok/s, int8 12.2k). Not a measured A100 run; the
    assumptions are stated in BASELINE.md and the int8 row credits the
    comparator with its own int8 path.
    """
    import functools as ft

    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.generate import generate, llama_decoder
    from apex1_tpu.models.llama import Llama, LlamaConfig
    from apex1_tpu.models.quant_decode import llama_quant_decoder

    if on_accel:
        B, S0, N, iters = 8, 128, 128, 3
        cfg = LlamaConfig(vocab_size=32000, max_seq_len=S0 + N + 8,
                          num_layers=16, num_heads=32, num_kv_heads=4,
                          hidden_size=2048, ffn_size=5632,
                          policy=get_policy("O2"))
        name = "Llama-0.8B-decode"
    else:
        B, S0, N, iters = 2, 8, 8, 2
        cfg = LlamaConfig.tiny(policy=get_policy("O2"), max_seq_len=32)
        name = "Llama(tiny smoke)-decode"
    model = Llama(cfg)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S0)),
                         jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), prompt)["params"]
    if quant:
        apply_fn, make_cache, decode_params = llama_quant_decoder(
            model, params)
        name += "-int8"
    else:
        apply_fn, make_cache = llama_decoder(model)
        decode_params = params

    gen = ft.partial(generate, apply_fn, max_new_tokens=N,
                     vocab_size=cfg.vocab_size)

    def step(state, prompt):
        (decode_params,) = state
        toks = gen(decode_params, prompt,
                   cache=make_cache(B, S0 + N + 1))
        # a finite scalar for the harness's loss check / full-tree sync
        metrics = {"loss": jnp.mean(toks.astype(jnp.float32))}
        return state, metrics

    # BASELINE.md pinned A100 rows: decode / decode_int8
    proxy = 12_200.0 if quant else 6_100.0
    return ((decode_params,), step, (prompt,), B * N, iters,
            f"decode tokens/sec/chip {name}", "tokens/sec/chip",
            proxy)


BENCHES = {
    "gpt2": bench_gpt2,
    "gpt2_fp16": functools.partial(bench_gpt2, fp16=True),
    "bert": bench_bert,
    "bert_dropout": functools.partial(bench_bert, dropout=0.1),
    "bert_large": functools.partial(bench_bert, large=True),
    "resnet": bench_resnet,
    "llama_longctx": bench_llama_longctx,
    "llama_block": bench_llama_block,
    "t5": bench_t5,
    "decode": bench_decode,
    "decode_int8": functools.partial(bench_decode, quant=True),
}
