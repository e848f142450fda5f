"""Headline benchmark — prints ONE JSON line, on a TPU only: with no TPU,
or on any failure, it prints no record and exits non-zero. Every record
carries ``device`` (platform, device_kind, count) as JAX reports it.

Default (``--config gpt2``, what the driver runs): tokens/sec/chip for a
GPT-2 125M training step under the amp-O2-equivalent policy (bf16 compute,
fp32 master weights) + fused Adam — BASELINE.json config 1's model under
the north-star's optimizer/precision recipe.

Other BASELINE configs are measurable with ``--config``:
  bert           config 2: BERT-base pretrain (MLM+NSP), fused LN + Adam
  bert_large     the north-star model size (BERT-large, 340M) at B=4
  resnet         config 3: ResNet-50 train step (BN; SyncBN's collective
                 parity is covered by tests — single-chip bench has dp=1)
  llama_longctx  config 5: long-context decoder, Pallas flash attention +
                 fused RoPE + remat, S=16k. Width is TinyLlama-class
                 (2048 hidden, 16 layers, ~0.8B) because Llama-3-8B +
                 Adam state does not fit one 16 GB chip (sizes verified
                 by tools/aot_check.py AOT memory analysis) — the
                 per-token attention/kernel work is the benchmarked path.

``vs_baseline``: the reference publishes no numbers (BASELINE.md); the
denominator is the PINNED A100 comparator from BASELINE.md "Pinned A100
comparator" — stated-assumption arithmetic (40%-MFU A100 for training,
0.6x HBM roofline for decode, NGC-class figure for ResNet). >= 1.0 is
the north-star "match A100" inequality; on the v5e bench chip, 0.63
(training) / 0.40 (decode) is already per-spec parity (see BASELINE.md
chip-context note).

Timing methodology: the measured run is ONE dispatch — iters steps ride a
``lax.fori_loop`` on device, so host→device dispatch latency cannot
pollute the steady state; warmup is
an identical (jit-cached) call; the sync is a full-tree readback-bearing
reduction.
"""

import argparse
import functools
import json
import math
import os
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def timed_steps(train_step, state, batch, iters, *, profile_dir=None):
    """(seconds/step, flops/step, final metrics, final state) with the
    loop in one dispatch.

    The many-step loop is AOT-lowered so ``cost_analysis`` can price one
    dispatch (→ MFU) without a second compile; the sync reduction covers
    every output leaf.

    ``profile_dir``: capture ONE extra (untimed) dispatch under
    ``jax.profiler.trace`` into this directory after the measured run —
    the ROADMAP-5 flywheel's trace-banking hook (a hardware window
    leaves a per-op breakdown artifact next to every record instead of
    a number alone). Profiling failure is swallowed: a trace must never
    cost the measurement."""

    def many_steps(state):
        def body(_, carry):
            st, _m = carry
            return train_step(st, *batch)
        return jax.lax.fori_loop(0, iters - 1, body,
                                 train_step(state, *batch))

    compiled = jax.jit(many_steps, donate_argnums=0).lower(state).compile()
    flops_per_step = None
    try:
        cost = compiled.cost_analysis()
        flops_per_step = float(cost["flops"]) / iters
    except Exception:
        pass  # cost model unavailable on some backends — MFU omitted

    @jax.jit
    def _reduce_all(tree):
        return sum(jnp.sum(leaf.astype(jnp.float32))
                   for leaf in jax.tree.leaves(tree))

    state, metrics = compiled(state)           # warmup (same executable)
    float(_reduce_all((state, metrics)))       # compiles the sync too

    # the spine StopWatch is the repo's ONE host-side timing primitive
    # (same machinery as utils.observability.Timers and the serving
    # clock); the full-tree float() reduction above IS the hard sync,
    # so no sync tree is passed here
    from apex1_tpu.obs import spine
    t0_ns = spine.monotonic_ns()
    sw = spine.StopWatch().start()
    state, metrics = compiled(state)           # n loop iters + 1 leading
    float(_reduce_all((state, metrics)))       # hard sync, full tree
    dt = sw.stop()
    spine.record_span("bench.timed_steps", t0_ns, spine.monotonic_ns(),
                      iters=iters, step_s=round(dt / iters, 6))
    loss = float(metrics["loss"])
    if not math.isfinite(loss):
        raise RuntimeError(f"benchmark loss is not finite: {loss}")
    if profile_dir:
        try:
            os.makedirs(profile_dir, exist_ok=True)
            # the profiled dispatch runs on a COPY (donate_argnums=0
            # would otherwise eat the state we return) and its outputs
            # are discarded — the returned metrics/state and any banked
            # checkpoint stay exactly the measured run's, profiled or
            # not
            state_copy = jax.tree_util.tree_map(jnp.copy, state)
            with jax.profiler.trace(profile_dir):
                prof_out = compiled(state_copy)
                float(_reduce_all(prof_out))
            del prof_out
        except Exception as e:
            print(f"WARNING: profile capture failed ({e}); record will "
                  f"carry no artifact", file=sys.stderr, flush=True)
    # final metrics + state ride along so configs can surface state
    # evidence (fp16 O1: skipped_steps + final loss_scale) and bank a
    # resume checkpoint of the trained state (--ckpt-dir)
    return dt / iters, flops_per_step, metrics, state


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


def bench_llama_3d(on_accel, plan=None):
    """The planner-driven 3D config: layout chosen by
    `apex1_tpu.planner` for THIS process's device count (or replayed
    from a banked plan via --plan), then the full
    `models.llama_3d.make_train_step` composition driven end-to-end
    from the emitted spec. On one CPU device the planner degenerates
    to the all-ones layout — the smoke proves the plan->mesh->specs->
    step path, the multi-chip number is the hardware queue's
    (`planner_ab`)."""
    import dataclasses

    from apex1_tpu import planner
    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.llama import LlamaConfig
    from apex1_tpu.models.llama_3d import make_train_step

    n = jax.device_count()
    if on_accel:
        # the llama_longctx-class 0.8B at trainable depth; global
        # batch sized so every dp split up to n stays feasible
        mcfg = LlamaConfig(vocab_size=32000, max_seq_len=2048,
                           num_layers=8, num_heads=32, num_kv_heads=4,
                           hidden_size=2048, ffn_size=5632, remat=True,
                           policy=get_policy("O2"))
        global_batch, iters = 4 * n, 6
    else:
        mcfg = dataclasses.replace(
            LlamaConfig.tiny(policy=get_policy("O2")), max_seq_len=128,
            remat=True)
        global_batch, iters = 4 * n, 2
    shape = planner.ModelShape.from_llama(mcfg, name="llama_3d",
                                          global_batch=global_batch)
    gen = None
    if on_accel:
        from apex1_tpu.core.capability import get_capability
        gen = get_capability().generation
    if plan is None:
        plan = planner.make_plan(shape, n, generation=gen,
                                 allow_zero=False)
    else:
        plan = planner.load_plan(plan)
        # a replayed plan must price THIS model and cover THIS mesh —
        # and the record's tokens/step must follow the PLAN's
        # schedule, not the live-derived default batch
        mismatch = planner.check_plan_model(plan, shape)
        if plan["n_devices"] != n:
            mismatch.append(f"n_devices: plan={plan['n_devices']} "
                            f"live={n}")
        if mismatch:
            raise ValueError(
                "--plan was searched for a different model/mesh than "
                "this bench builds: " + "; ".join(mismatch))
        shape = dataclasses.replace(
            shape, global_batch=plan["model"]["global_batch"])
    m = plan["mesh"]
    print(f"planner pick: dp={m['dp']} pp={m['pp']} cp={m['cp']} "
          f"ep={m['ep']} tp={m['tp']} "
          f"M={plan['schedule']['num_microbatches']} — "
          f"{plan['predicted']['calibrated_step_ms']:.2f} ms/step "
          f"calibrated", flush=True)
    cfg = planner.llama3d_config_from_plan(plan, mcfg)
    step, state, _ = make_train_step(cfg)
    rng = np.random.default_rng(0)
    dshape = (cfg.num_microbatches, mcfg.max_seq_len,
              cfg.microbatch_size * cfg.dp * cfg.ep)
    tokens = jnp.asarray(rng.integers(0, mcfg.vocab_size, dshape),
                         jnp.int32)
    labels = jnp.roll(tokens, -1, axis=1)

    def loss_step(state, tokens, labels):
        state, loss = step(state, tokens, labels)
        return state, {"loss": loss}

    tokens_per_step = shape.tokens_per_step
    return (state, loss_step, (tokens, labels), tokens_per_step // n,
            iters,
            f"tokens/sec/chip Llama-3D(planned x{n}) amp-O2 remat",
            "tokens/sec/chip",
            11_100.0)   # vs the pinned llama_longctx A100 row: the
    #                     nearest hand-tuned comparator until the
    #                     planner A/B banks its own


BENCHES = {
    "gpt2": bench_gpt2,
    "gpt2_fp16": functools.partial(bench_gpt2, fp16=True),
    "bert": bench_bert,
    "bert_dropout": functools.partial(bench_bert, dropout=0.1),
    "bert_large": functools.partial(bench_bert, large=True),
    "resnet": bench_resnet,
    "llama_longctx": bench_llama_longctx,
    "llama_block": bench_llama_block,
    "llama_3d": bench_llama_3d,
    "t5": bench_t5,
    "decode": bench_decode,
    "decode_int8": functools.partial(bench_decode, quant=True),
}

#: configs whose mesh comes from the planner + the LIVE device count:
#: excluded from tools/predict_perf.py's single-chip AOT table (the
#: planner's own cost engine prices them) so the banked
#: predicted_*.json rows stay byte-stable
PLANNED_BENCHES = {"llama_3d"}


def _emit(record, out_path=None):
    """The ONE JSON line the driver parses.

    ``out_path``: crash-safe partial banking for sweeps — the record is
    ALSO written to this file via temp-file + atomic rename, so a sweep
    killed between configs still banks every completed record (a
    half-written JSON file can never exist at ``out_path``)."""
    print(json.dumps(record), flush=True)
    if not out_path:
        return
    try:
        out_path = os.path.abspath(out_path)
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        tmp = f"{out_path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(record, f)
            f.write("\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, out_path)
    except OSError as e:   # banking is best-effort; stdout already has it
        print(f"WARNING: could not bank record to {out_path}: {e}",
              file=sys.stderr, flush=True)


def _predicted_row(config, results_dir=None):
    """The ``config`` step row of the newest banked prediction table
    (perf_results/predicted_*.json, written by tools/predict_perf.py),
    or None (never raises — the always-emit contract must not depend on
    this)."""
    import glob

    if results_dir is None:
        results_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "perf_results")
    paths = glob.glob(os.path.join(results_dir, "predicted_*.json"))
    if not paths:
        return None
    try:
        # newest by mtime — lexicographic order breaks at r10 vs r9
        path = max(paths, key=os.path.getmtime)
        with open(path) as f:
            doc = json.load(f)
        return next(r for r in doc.get("steps", [])
                    if r.get("name") == config and "flops" in r)
    except (StopIteration, OSError, KeyError, ValueError,
            json.JSONDecodeError):
        return None


def _predicted_rate(config, results_dir=None, generation=None):
    """Roofline-predicted units/sec for ``config`` from the newest banked
    prediction table, priced at ``generation``'s capability row (None =
    the attached chip's; with no chip `get_capability` raises). The
    comms term rides along: a row carrying ``ici_exposed_bytes`` (ICI
    traffic NOT hidden behind compute — tools/predict_perf.py's overlap
    model) ADDS that exposed transfer time, so `roofline_ratio` prices
    a serialized-collective program honestly instead of crediting the
    transfer as free. None when no prediction is banked."""
    row = _predicted_row(config, results_dir)
    if row is None:
        return None
    try:
        from apex1_tpu.core.capability import get_capability, ici_link_gbps
        cap = get_capability(generation)
        t_pred = max(row["flops"] / (cap.bf16_tflops * 1e12),
                     row["bytes"] / (cap.hbm_gbps * 1e9))
        exposed = row.get("ici_exposed_bytes", 0.0)
        if exposed:
            link = ici_link_gbps(cap.generation)
            if link:
                t_pred += exposed / (link * 1e9)
        if t_pred <= 0:
            return None
        return row["units_per_step"] / t_pred
    except (OSError, KeyError, ValueError, TypeError):
        return None


def _attach_roofline(record, config, results_dir=None, generation=None):
    """Add ``predicted`` (roofline units/sec) + ``roofline_ratio``
    (value / predicted — the localizer metric: < ~0.5 means a kernel or
    schedule is leaving real performance on the floor, see
    tools/predict_perf.py) to a record with a nonzero value. ON-SILICON
    records only: a cpu smoke run measures tiny auto-shrunk shapes, so
    a ratio against the accelerator-shape prediction would be noise
    dressed as a score.

    When a banked calibration table exists (``apex1_tpu.obs.calibrate``
    — perf_results/calibration.json, TPU-backed factors only), the
    record ALSO carries ``calibrated_predicted`` (the analytic rate
    corrected by the config's fitted slowdown) and
    ``calibrated_ratio`` (value / calibrated_predicted — ≈1.0 means
    "performing as banked silicon history says"; a drop below ~0.9 is
    a REGRESSION signal even when the raw ratio looks normal). The raw
    ``roofline_ratio`` keeps its absolute-localizer meaning."""
    try:
        metric = record.get("metric", "")
        if "[cpu]" in metric:
            return record
        pred = _predicted_rate(config, results_dir, generation)
        val = record.get("value")
        if pred and isinstance(val, (int, float)) and val > 0 \
                and math.isfinite(val):
            record["predicted"] = round(pred, 1)
            record["roofline_ratio"] = round(val / pred, 4)
            try:
                from apex1_tpu.obs.calibrate import step_slowdown
                cal = step_slowdown(config, results_dir)
                if cal:
                    cal_pred = pred / cal["slowdown"]
                    record["calibrated_predicted"] = round(cal_pred, 1)
                    record["calibrated_ratio"] = round(val / cal_pred, 4)
                    record["calibration"] = {
                        "slowdown": cal["slowdown"], "n": cal["n"]}
            except Exception:
                pass  # calibration is metadata on metadata
    except Exception:
        pass  # metadata only — never break the always-emit contract
    return record


def _try_resume(ckpt_dir, template):
    """--resume auto: restore the newest VALID checkpoint under
    ``ckpt_dir`` (integrity-verified, scans past corrupt ones). Returns
    ``(state, "step_N")`` or ``(template, None)`` when nothing usable is
    banked — a bench must measure, not die, on a stale/foreign dir."""
    try:
        from apex1_tpu.resilience import ResilientCheckpointer

        with ResilientCheckpointer(ckpt_dir) as ck:
            state, man = ck.restore(template=template)
        return state, f"step_{man.step}"
    except Exception as e:
        print(f"WARNING: --resume auto: no usable checkpoint under "
              f"{ckpt_dir} ({e}); starting fresh", file=sys.stderr,
              flush=True)
        return template, None


def _bank_ckpt(ckpt_dir, state, fallback_step):
    """Bank the trained bench state (synchronously) so the next
    ``--resume auto`` run continues from it."""
    from apex1_tpu.resilience import ResilientCheckpointer

    step_no = getattr(state, "step", None)
    if step_no is None and isinstance(state, tuple) and state:
        step_no = getattr(state[0], "step", None)
    step_no = (int(np.asarray(step_no)) if step_no is not None
               else int(fallback_step))
    with ResilientCheckpointer(ckpt_dir) as ck:
        ck.save_sync(step_no, state, meta={"source": "bench.py"})
    return step_no


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="gpt2", choices=sorted(BENCHES))
    ap.add_argument("--batch", type=int, default=None,
                    help="override batch size (gpt2 config only)")
    ap.add_argument("--seq", type=int, default=None,
                    help="override sequence length (gpt2 config only)")
    ap.add_argument("--plan", default=None,
                    help="banked plan.json for --config llama_3d "
                    "(default: the planner searches the live device "
                    "count)")
    ap.add_argument("--timeout", type=float, default=1500.0,
                    help="watchdog for build+compile+measure (seconds)")
    ap.add_argument("--out", default=None,
                    help="also bank the record to this file (temp-file + "
                    "atomic rename): an interrupted sweep keeps every "
                    "completed config's record")
    ap.add_argument("--ckpt-dir", default=None,
                    help="resilient checkpoint dir: the trained bench "
                    "state is banked here after measuring, and --resume "
                    "auto continues from the newest valid checkpoint")
    ap.add_argument("--resume", default="never", choices=("auto", "never"),
                    help="auto: restore the bench state from the newest "
                    "VALID checkpoint under --ckpt-dir (resilience."
                    "find_restorable) and stamp the record with "
                    "`resumed_from` provenance")
    args = ap.parse_args()

    # this process initialises the backend itself (a chip belongs to one
    # process: no probing child). No TPU, or any failure below, is a
    # non-zero exit with no record — a CPU run never prints a metric.
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"bench.py: device {json.dumps(device)}", file=sys.stderr,
          flush=True)
    if device["platform"] != "tpu":
        sys.exit("bench.py: no TPU (platform "
                 f"{device['platform']!r}) — nothing measured, no record")

    def _alarm(signum, frame):
        raise TimeoutError(f"watchdog: exceeded {args.timeout:.0f}s")

    signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(int(args.timeout))
    from apex1_tpu.testing import enable_persistent_compilation_cache

    # compile-once economics: the measured loop is timed AFTER warmup,
    # so a persistent cache only cuts re-run latency, never the number
    enable_persistent_compilation_cache()
    # headline auto-tune: with no explicit --batch, measure the
    # AOT-verified batch candidates and report the best (B=16 fits
    # at 8.2 GiB on v5e; 24 fits with margin — both sized by
    # tools/aot_check.py). A candidate that fails is the run's failure.
    if args.config in ("gpt2", "gpt2_fp16") and args.batch is None:
        cand_batches = [16, 24]
    else:
        cand_batches = [args.batch]

    best = None
    best_rate = -1.0
    bank_state = None
    bank_iters = 0
    resume_cache = None   # restore + digest-verify once per run,
    for b in cand_batches:  # not per candidate (batch-independent)
        kw = {}
        if args.config in ("gpt2", "gpt2_fp16"):
            kw = dict(batch=b, seq=args.seq)
        elif args.config == "llama_3d":
            kw = dict(plan=args.plan)
        (state, step, batch, units_per_step, iters, metric, unit,
         proxy) = BENCHES[args.config](True, **kw)
        resumed_from = None
        if args.ckpt_dir and args.resume == "auto":
            if resume_cache is None:
                restored, rf = _try_resume(args.ckpt_dir, state)
                if rf is not None:
                    # hold the restored state as HOST arrays:
                    # timed_steps donates its input buffers, so
                    # each candidate needs fresh device copies
                    restored = jax.device_get(restored)
                resume_cache = (restored, rf)
            host_restored, resumed_from = resume_cache
            if resumed_from is not None:
                state = jax.tree_util.tree_map(jnp.asarray,
                                               host_restored)
        # one untimed dispatch under jax.profiler.trace, its directory
        # stamped on the record as `profile_artifact`.
        # APEX1_BENCH_PROFILE=0 opts out.
        pdir = None
        if os.environ.get("APEX1_BENCH_PROFILE", "1") != "0":
            pdir = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "perf_results", "profiles",
                f"{args.config}_b{b}_{int(time.time())}")
        (per_step, flops_per_step, final_metrics,
         final_state) = timed_steps(step, state, batch, iters,
                                    profile_dir=pdir)
        rate = units_per_step / per_step
        if rate <= best_rate:   # unrounded comparison
            continue
        best_rate = rate
        bank_state, bank_iters = final_state, iters
        best = {
            "metric": f"{metric} [{device['platform']}]",
            "value": round(rate, 1),
            "unit": unit,
            "vs_baseline": round(rate / proxy, 4),
            "device": device,
        }
        if pdir is not None and os.path.isdir(pdir) \
                and os.listdir(pdir):
            best["profile_artifact"] = os.path.relpath(
                pdir, os.path.dirname(os.path.abspath(__file__)))
        if resumed_from:
            # provenance: this number continued from a banked
            # checkpoint, not a fresh init
            best["resumed_from"] = resumed_from
        if len(cand_batches) > 1:
            best["batch"] = b
        # dynamic-loss-scaling evidence (fp16 O1): the record
        # carries the skip count and where the scale settled
        for mk_ in ("loss_scale", "skipped_steps"):
            if mk_ in final_metrics:
                best[mk_] = float(np.asarray(final_metrics[mk_]))
        if flops_per_step is not None:
            from apex1_tpu.core.capability import get_capability
            peak = get_capability().bf16_tflops * 1e12
            # cost_analysis is blind inside tpu_custom_call, so its
            # number under-reports true utilization by the kernels'
            # flop share — name it what it is, and emit `mfu` from
            # logical flops: visible x the banked mfu_correction
            # (logical/visible flop ratio from
            # perf_results/predicted_*.json)
            vis = flops_per_step / per_step / peak
            best["xla_visible_mfu"] = round(vis, 4)
            best["step_ms"] = round(per_step * 1e3, 2)
            corr = (_predicted_row(args.config) or {}).get(
                "mfu_correction")
            if corr:
                best["mfu"] = round(vis * corr, 4)
    signal.alarm(0)
    if args.ckpt_dir and bank_state is not None:
        _bank_ckpt(args.ckpt_dir, bank_state, bank_iters)
    best = _attach_roofline(best, args.config)
    from apex1_tpu.obs import spine
    spine.emit("event", "bench.record", config=args.config, **best)
    _emit(best, args.out)


if __name__ == "__main__":
    main()
