"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user would call, at
the full width of GPT-2 125M (`GPT2Config()`: 12 x 768 x 12 heads, vocab
50257; weights random from a seed), both halves on the same weights:

- **train**: the README quick start — ``Amp(tx=fused_adam(...), "O2")`` →
  ``amp.make_train_step(gpt2_loss_fn(model))`` → ``jax.jit(...,
  donate_argnums=0)`` — B=16, S=1024, 8 steps on one fixed batch;
- **serve**: ``serving.Engine(*gpt2_decoder(model), params, EngineConfig)``
  sized for the chip, more requests than slots, mixed prompt lengths, 32
  new tokens each, through submit/step/pop_result until all finish — once
  dense, once paged (the `paged_attend` + `fused_sample` kernel path);
- **ddp** (only where ``jax.device_count() == 4``): the same train step
  as ``examples/distributed_data_parallel.py`` writes DDP —
  ``Amp(..., grad_psum_axes=("dp",))`` inside ``jax.shard_map`` over
  ``make_mesh(dp=4)``, 16 x 1024 per chip.

One process (a chip belongs to one process). It prints the device first
and exits non-zero before compiling anything unless the platform is
``tpu``; a phase's failure is the script's failure. The last line of
stdout is ``{"ok": true, "device": {...}}``.

The phases are importable functions that take their sizes as arguments
(``tests/test_smoke_chip.py`` runs them tiny on the virtual CPU mesh, and
``tools/aot_check.py`` compiles the same programs for the v5e without a
chip). The command line has no option that lets it pass without a TPU.

What holds on the chip (TPU v5 lite, PR 21; PERF.md has the numbers).
On CPU the repo pins paged, dense and solo-`generate` token streams
bit-identical. On the chip, in bf16 with compiled kernels, that does NOT
hold between differently fused programs: the paged kernel path and the
dense XLA path emitted identical streams for only some requests (the
logits of random weights are near-tied, and a flash-folded softmax is
not bitwise a composite one). What does hold, and is the pass condition:
every served token is the argmax of a float32-logit reference forward of
the same weights to within `ARGMAX_TOL` of that position's logit spread.
The exact-equality counts are printed, not asserted.
"""

from __future__ import annotations

import collections
import contextlib
import json
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from apex1_tpu import ops, runtime
from apex1_tpu.amp import Amp
from apex1_tpu.core.mesh import make_mesh
from apex1_tpu.core.policy import get_policy
from apex1_tpu.models.generate import generate, gpt2_decoder
from apex1_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn
from apex1_tpu.optim.fused_adam import fused_adam
from apex1_tpu.serving.engine import Engine, EngineConfig
from apex1_tpu.testing import enable_persistent_compilation_cache

TRAIN = dict(batch=16, seq=1024, steps=8)
DDP = dict(per_chip_batch=16, seq=1024, steps=4)
# sized for the chip, not EngineConfig's CPU defaults (8 / 256 / 16):
# 24 requests over 16 slots, prompts from 24 to 448 tokens
SERVE = dict(max_slots=16, max_len=512, prefill_chunk=128, n_requests=24,
             new_tokens=32, prompt_lens=(24, 100, 128, 200, 301, 448))
LR = 3e-4
SEED = 0

#: |step-0 loss (kernels) - step-0 loss (`force_impl("xla")`)|: both run
#: bf16 matmuls with fp32 accumulation and differ in fusion order only;
#: the loss is a mean over B*(S-1) tokens of magnitude ~ln(vocab).
#: Measured 2.8e-5 on the v5e (PR 21)
LOSS_TOL = 1e-3
#: a served token must be the argmax of a plain float32-logit forward of
#: the same weights under `force_impl("xla")` up to this fraction of that
#: position's logit spread (std over the vocabulary); bf16 rounding of
#: the hidden state moves a logit by well under 1% of the spread
#: (measured worst case 0.003 on the v5e, PR 21), a wrong cache index or
#: mask moves the argmax by ~4 spreads
ARGMAX_TOL = 0.05


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileMeter:
    """Seconds spent in backend compiles (a persistent-cache hit counts
    its retrieval time) and the cache's hit / write counts, read off
    jax.monitoring — so a cold and a warm run show the difference."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, h0, w0, t0 = self.seconds, self.hits, self.writes, time.time()
        print(f"== {name} ==", flush=True)
        yield
        print(f"== {name}: wall {time.time() - t0:.1f}s, compile "
              f"{self.seconds - c0:.1f}s, cache hits {self.hits - h0} "
              f"writes {self.writes - w0} ==", flush=True)


# ---- the programs (tools/aot_check.py lowers exactly these) -------------


def make_model(cfg: GPT2Config | None = None) -> GPT2:
    return GPT2(cfg or GPT2Config(policy=get_policy("O2")))


def make_tokens(cfg: GPT2Config, batch: int, seq: int, seed: int = SEED):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32)


def init_params(model: GPT2, seed: int = SEED):
    probe = jnp.zeros((1, 8), jnp.int32)
    return jax.jit(model.init)(jax.random.key(seed), probe)["params"]


def train_step(model: GPT2, lr: float = LR):
    """The README quick start's step: (amp, jitted donating step)."""
    amp = Amp(tx=fused_adam(lr, weight_decay=0.01), opt_level="O2")
    return amp, jax.jit(amp.make_train_step(gpt2_loss_fn(model)),
                        donate_argnums=0)


def ddp_step(model: GPT2, mesh, lr: float = LR):
    """`examples/distributed_data_parallel.py`'s form of the same step.
    ``check_vma=False``: under the vma check `linear_cross_entropy`'s
    custom VJP is rejected ("varying manual axes do not match")."""
    amp = Amp(tx=fused_adam(lr, weight_decay=0.01), opt_level="O2",
              grad_psum_axes=("dp",))
    step = jax.shard_map(
        amp.make_train_step(gpt2_loss_fn(model)), mesh=mesh,
        in_specs=(P(), P("dp")), out_specs=(P(), P()), check_vma=False)
    return amp, jax.jit(step, donate_argnums=0)


def engine_config(cfg: GPT2Config, *, paged: bool, max_slots: int,
                  max_len: int, prefill_chunk: int) -> EngineConfig:
    return EngineConfig(max_slots=max_slots, max_len=max_len,
                        prefill_chunk=prefill_chunk,
                        vocab_size=cfg.vocab_size, paged=paged)


def _kernel_census(lowered, compiled) -> tuple[int, dict]:
    """`tpu_custom_call` count in the compiled HLO (the assertion
    tools/aot_check.py makes) and the Pallas kernel names lowered."""
    count = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    names = collections.Counter(
        re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()))
    return count, dict(names)


def _check_steps(losses, metrics, what: str) -> None:
    if not all(np.isfinite(losses)):
        raise AssertionError(f"{what}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{what}: loss did not fall: {losses}")
    if int(metrics["grads_finite"]) != 1 or int(metrics["skipped_steps"]):
        raise AssertionError(
            f"{what}: grads_finite={int(metrics['grads_finite'])} "
            f"skipped_steps={int(metrics['skipped_steps'])}")


# ---- phases --------------------------------------------------------------


def phase_train(model: GPT2, *, batch: int, seq: int, steps: int,
                require_kernels: bool = True):
    """Train ``steps`` steps on one fixed batch; returns the trained
    compute-dtype params (what the serve phase loads)."""
    cfg = model.cfg
    tokens = jnp.asarray(make_tokens(cfg, batch, seq))
    amp, step = train_step(model)
    state = amp.init(init_params(model))

    def loss_under(impl):
        loss_fn = gpt2_loss_fn(model)

        def f(params, toks):   # one function object per impl: no shared
            with ops.force_impl(impl):     # jit-cache entry
                return loss_fn(amp.policy.cast_to_compute(params), toks)
        return jax.jit(f)

    ref_loss = float(loss_under("xla")(state.params, tokens))

    lowered = step.lower(state, tokens)
    compiled = lowered.compile()
    n_calls, names = _kernel_census(lowered, compiled)
    print(f"train: tpu_custom_call count {n_calls}, kernels {names}",
          flush=True)
    if require_kernels and n_calls == 0:
        raise AssertionError("train step holds no tpu_custom_call: the "
                             "composites ran, not the kernels")
    losses = []
    for i in range(steps):
        state, metrics = compiled(state, tokens)
        losses.append(float(metrics["loss"]))
        print(f"train: step {i} loss {losses[-1]:.4f} grad_norm "
              f"{float(metrics['grad_norm']):.3f}", flush=True)
    _check_steps(losses, metrics, "train")
    diff = abs(losses[0] - ref_loss)
    print(f"train: step-0 loss {losses[0]:.5f} vs force_impl('xla') "
          f"{ref_loss:.5f}: |diff| {diff:.2e} (tol {LOSS_TOL:.0e})",
          flush=True)
    if diff > LOSS_TOL:
        raise AssertionError(f"step-0 loss differs from the XLA "
                             f"composites by {diff:.3e} > {LOSS_TOL}")
    return amp.model_params(state)


def _serve(model: GPT2, params, prompts, *, paged: bool, max_slots: int,
           max_len: int, prefill_chunk: int, new_tokens: int):
    """Run every prompt through one Engine; returns {index: tokens}."""
    cfg = model.cfg
    tag = "paged" if paged else "dense"
    engine = Engine(*gpt2_decoder(model), params, engine_config(
        cfg, paged=paged, max_slots=max_slots, max_len=max_len,
        prefill_chunk=prefill_chunk))
    ids = {engine.submit(p, new_tokens, seed=1000 + i): i
           for i, p in enumerate(prompts)}
    pool_before = jax.tree_util.tree_leaves(
        engine.kv.pages if paged else engine.kv.cache)[0]
    out, n_steps = {}, 0
    while len(out) < len(prompts):
        engine.step()
        n_steps += 1
        for rid in [r for r in ids if r in engine.results]:
            res = engine.pop_result(rid)
            if res.status != "done" or res.tokens.size != new_tokens:
                raise AssertionError(
                    f"serve[{tag}]: request {ids[rid]} ended "
                    f"{res.status!r} ({res.reason}) with "
                    f"{res.tokens.size} tokens")
            if res.tokens.min() < 0 or res.tokens.max() >= cfg.vocab_size:
                raise AssertionError(
                    f"serve[{tag}]: token id outside the vocabulary")
            out[ids.pop(rid)] = res.tokens
        if n_steps > 100 * len(prompts) * new_tokens:
            raise AssertionError(f"serve[{tag}]: no progress")
    if engine.trace_counts != {"prefill": 1, "decode": 1}:
        raise AssertionError(f"serve[{tag}]: executables retraced: "
                             f"{engine.trace_counts}")
    if not pool_before.is_deleted():
        raise AssertionError(f"serve[{tag}]: the pool was not donated")
    print(f"serve[{tag}]: {len(prompts)} requests finished in {n_steps} "
          f"engine steps over {max_slots} slots; trace_counts "
          f"{engine.trace_counts}; pool donated", flush=True)
    return out


def _argmax_margin(model: GPT2, params, prompts, streams) -> float:
    """Teacher-forced reference: one plain forward (no cache, float32
    logits, `force_impl("xla")`) over prompt + served tokens; for every
    served token, how far below that position's reference argmax it sits,
    in units of the position's logit spread. Returns the worst case."""
    cfg = model.cfg
    n, new = len(prompts), len(streams[0])
    width = max(len(p) for p in prompts) + new
    batch = np.zeros((n, width), np.int32)
    for i, p in enumerate(prompts):
        batch[i, :len(p)] = p
        batch[i, len(p):len(p) + new] = streams[i]

    # position t's logits predict token t+1: the rows that produced each
    # served token, and how far below the row's argmax the token sits
    rows = np.stack([np.arange(len(p) - 1, len(p) - 1 + new)
                     for p in prompts])
    served = np.stack([streams[i] for i in range(n)])

    def margin(params, toks):
        with ops.force_impl("xla"):
            logits = model.apply({"params": params}, toks)
        picked = jnp.take_along_axis(
            logits[..., :cfg.vocab_size].astype(jnp.float32),
            jnp.asarray(rows)[..., None], axis=1)      # (n, new, vocab)
        got = jnp.take_along_axis(
            picked, jnp.asarray(served)[..., None], axis=-1)[..., 0]
        return jnp.max((picked.max(-1) - got) / picked.std(-1))

    return float(jax.jit(margin)(params, batch))


def phase_serve(model: GPT2, params, *, max_slots: int, max_len: int,
                prefill_chunk: int, n_requests: int, new_tokens: int,
                prompt_lens) -> None:
    cfg = model.cfg
    rng = np.random.default_rng(SEED + 1)
    prompts = [rng.integers(0, cfg.vocab_size,
                            prompt_lens[i % len(prompt_lens)]
                            ).astype(np.int32) for i in range(n_requests)]
    sizes = dict(max_slots=max_slots, max_len=max_len,
                 prefill_chunk=prefill_chunk, new_tokens=new_tokens)
    dense = _serve(model, params, prompts, paged=False, **sizes)
    paged = _serve(model, params, prompts, paged=True, **sizes)

    for tag, streams in (("dense", dense), ("paged", paged)):
        worst = _argmax_margin(model, params, prompts, streams)
        print(f"serve[{tag}]: worst served token sits {worst:.3f} logit "
              f"spreads below the reference argmax (tol {ARGMAX_TOL})",
              flush=True)
        if worst > ARGMAX_TOL:
            raise AssertionError(
                f"serve[{tag}]: a served token is {worst:.3f} spreads "
                f"below the float32 reference argmax (> {ARGMAX_TOL})")

    same = [np.array_equal(dense[i], paged[i]) for i in range(n_requests)]
    tok_same = np.mean([dense[i] == paged[i] for i in range(n_requests)])
    print(f"serve: dense vs paged — {sum(same)}/{n_requests} streams "
          f"identical, {tok_same:.3f} of tokens equal", flush=True)

    # solo `generate` (flash prefill + scanned decode), one batch per
    # prompt length — rows of a batch decode independently
    apply_fn, make_cache = gpt2_decoder(model)
    solo = {}
    for length in sorted(set(len(p) for p in prompts)):
        idx = [i for i, p in enumerate(prompts) if len(p) == length]
        toks = jax.jit(lambda pr, tk: generate(
            apply_fn, pr, tk, max_new_tokens=new_tokens,
            cache=make_cache(tk.shape[0], tk.shape[1] + new_tokens),
            vocab_size=cfg.vocab_size))(
                params, np.stack([prompts[i] for i in idx]))
        solo.update(zip(idx, np.asarray(toks)))
    for tag, streams in (("dense", dense), ("paged", paged)):
        same = sum(np.array_equal(solo[i], streams[i])
                   for i in range(n_requests))
        print(f"serve: {tag} vs solo generate — {same}/{n_requests} "
              f"streams identical", flush=True)


def phase_ddp(model: GPT2, devices, *, per_chip_batch: int, seq: int,
              steps: int, require_kernels: bool = True) -> None:
    cfg = model.cfg
    n = len(devices)
    mesh = make_mesh(dp=n, devices=list(devices))
    amp, step = ddp_step(model, mesh)
    tokens = jax.device_put(make_tokens(cfg, n * per_chip_batch, seq),
                            NamedSharding(mesh, P("dp")))
    homes = {s.device for s in tokens.addressable_shards}
    if len(homes) != n:
        raise AssertionError(f"ddp: batch shards sit on {len(homes)} "
                             f"devices, want {n}")
    state = jax.device_put(amp.init(init_params(model)),
                           NamedSharding(mesh, P()))
    lowered = step.lower(state, tokens)
    compiled = lowered.compile()
    text = compiled.as_text()
    n_calls, _ = _kernel_census(lowered, compiled)
    n_ar = len(re.findall(r"\ball-reduce(-start)?\(", text))
    print(f"ddp: {n} devices, batch shards on {len(homes)} devices, "
          f"tpu_custom_call count {n_calls}, all-reduce count {n_ar}",
          flush=True)
    if n_ar == 0:
        raise AssertionError("ddp: the program holds no all-reduce")
    if require_kernels and n_calls == 0:
        raise AssertionError("ddp: the program holds no tpu_custom_call")
    losses = []
    for i in range(steps):
        state, metrics = compiled(state, tokens)
        losses.append(float(metrics["loss"]))
        print(f"ddp: step {i} loss {losses[-1]:.4f}", flush=True)
    _check_steps(losses, metrics, "ddp")
    n_leaves = 0
    for leaf in jax.tree_util.tree_leaves(state.params):
        copies = [np.asarray(s.data) for s in leaf.addressable_shards]
        if len(copies) != n or not all(
                np.array_equal(copies[0], c) for c in copies[1:]):
            raise AssertionError("ddp: parameters differ across devices")
        n_leaves += 1
    print(f"ddp: {n_leaves} parameter leaves equal on all {n} devices",
          flush=True)


def main() -> None:
    dev = device_info()
    print(f"chip_smoke: platform={dev['platform']} "
          f"device_kind={dev['kind']!r} devices={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found platform "
                 f"{dev['platform']!r} — nothing compiled, no result")
    t0 = time.time()
    cache_dir = enable_persistent_compilation_cache()
    print(f"chip_smoke: jax {jax.__version__}, compile cache "
          f"{cache_dir or 'disabled'}, runtime.native_available() = "
          f"{runtime.native_available()}", flush=True)
    meter = CompileMeter()
    model = make_model()
    with meter.phase("train"):
        params = phase_train(model, **TRAIN)
    with meter.phase("serve"):
        phase_serve(model, params, **SERVE)
    if dev["count"] == 4:
        with meter.phase("ddp"):
            phase_ddp(model, jax.devices(), **DDP)
    print(f"chip_smoke: all phases passed in {time.time() - t0:.1f}s, "
          f"compile {meter.seconds:.1f}s (cache hits {meter.hits}, "
          f"writes {meter.writes})", flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
