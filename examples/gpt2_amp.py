"""GPT-2 mixed-precision training — the amp half of reference
``examples/imagenet/main_amp.py`` applied to BASELINE config 1 ("GPT-2
125M, amp O1 + Adam"): opt-level presets, dynamic loss scaling with
skip-on-overflow, fused Adam. Data rides the native runtime: a
memory-mapped `TokenDataset` (step-indexed, resumable) behind a
`PrefetchLoader` (host work + H2D transfer overlapped with device
compute — the reference prefetcher's side-stream overlap). Without
``--data`` a synthetic token file is generated.

``python examples/gpt2_amp.py [--opt-level O1|O1_fp16|O2] [--tiny]
                              [--data tokens.bin]``

With ``--ckpt-dir`` the loop runs under the resilient runtime
(`apex1_tpu.resilience`, docs/robustness.md): async integrity-checked
checkpoints every ``--ckpt-every`` steps, ``--resume auto`` continuing
EXACTLY from the newest valid checkpoint (step-indexed `TokenDataset`
⇒ the data position is just the step), a divergence sentinel
(skip → rollback → abort), and a SIGTERM/SIGINT preemption hook that
banks a final synchronous checkpoint and exits `EXIT_RESUMABLE` (75)
so a scheduler re-queues the job instead of recording a failure.
``APEX1_CHAOS_SIGTERM_STEP=<n>`` self-injects the preemption at step n
(the chaos harness's kill-and-resume drill).

``--obs-dir <dir>`` (or ``APEX1_OBS_DIR``) banks the run through the
telemetry spine (`apex1_tpu.obs`, docs/observability.md): every
`MetricsLogger` line, sentinel diagnostic, and checkpoint event lands
in one run-scoped JSONL file, joinable with bench/tuning/serving runs.
"""

import argparse
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from apex1_tpu.amp import Amp
from apex1_tpu.core.policy import get_policy
from apex1_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn
from apex1_tpu.optim.fused_adam import fused_adam
from apex1_tpu.runtime import PrefetchLoader, TokenDataset, write_token_file
from apex1_tpu.utils.observability import MetricsLogger


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--opt-level", default="O1")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--data", default=None,
                    help="flat uint16 token file (default: synthetic)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="enable the resilient runtime: async "
                    "checkpoints + sentinel + preemption hook")
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--resume", default="auto", choices=("auto", "never"),
                    help="auto: continue from the newest VALID "
                    "checkpoint under --ckpt-dir")
    ap.add_argument("--obs-dir", default=None,
                    help="bank run telemetry (metrics, sentinel "
                    "diagnostics) as JSONL through apex1_tpu.obs; "
                    "equivalent to setting APEX1_OBS_DIR")
    args = ap.parse_args()

    if args.obs_dir:
        # the spine's default run resolves this lazily at first emit,
        # so setting it before the loop wires every MetricsLogger line
        os.environ["APEX1_OBS_DIR"] = args.obs_dir

    policy = get_policy(args.opt_level)
    cfg = (GPT2Config.tiny(policy=policy) if args.tiny
           else GPT2Config(policy=policy))
    if args.seq > cfg.max_seq_len:   # --tiny keeps the default --seq
        args.seq = cfg.max_seq_len
    model = GPT2(cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.seq)), jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), tokens)["params"]

    amp = Amp(tx=fused_adam(3e-4, weight_decay=0.01),
              opt_level=args.opt_level, max_grad_norm=1.0)
    state = amp.init(params)
    step = jax.jit(amp.make_train_step(gpt2_loss_fn(model)),
                   donate_argnums=0)

    data_path = args.data
    if data_path is None:
        n_tok = max(args.batch * args.seq * 8, 1 << 18)
        data_path = os.path.join(
            tempfile.gettempdir(),
            f"gpt2_amp_synth_{cfg.vocab_size}_{n_tok}_{os.getuid()}.bin")
        if not os.path.exists(data_path):
            # write-then-rename: an interrupted write must never leave a
            # truncated file at the cached name
            tmp = f"{data_path}.tmp.{os.getpid()}"
            write_token_file(tmp, rng.integers(
                0, cfg.vocab_size, n_tok).astype(np.uint16))
            os.replace(tmp, data_path)

    logger = MetricsLogger()
    t0 = time.time()
    if args.ckpt_dir:
        state = _resilient_loop(args, amp, model, state, data_path, logger)
    else:
        with TokenDataset(data_path, seq_len=args.seq,
                          batch_size=args.batch) as ds:
            it = iter(PrefetchLoader(ds.iter_from(0), prefetch=2))
            try:
                for i, batch in zip(range(args.steps), it):
                    state, metrics = step(state, jnp.asarray(batch))
                    if i % 5 == 0 or i == args.steps - 1:
                        logger.log(i, metrics,
                                   tokens=args.batch * args.seq)
            finally:
                # stop the prefetch worker BEFORE the mmap goes away
                it.close()
    jax.block_until_ready(state.params)
    print(f"done in {time.time() - t0:.1f}s; final loss-scale "
          f"{float(state.loss_scale.scale)}, "
          f"skipped {int(state.loss_scale.overflow_count)} steps")


def _resilient_loop(args, amp, model, state, data_path, logger):
    """The --ckpt-dir path: the same train step under the resilient
    runtime. `TokenDataset.batch_at(step)` is a pure function of the
    step, so the data-iterator position in the checkpoint meta is just
    an int and resume/rollback are exact."""
    from apex1_tpu.resilience import (PreemptionHandler,
                                      ResilientCheckpointer, Sentinel,
                                      sentinel_init)
    from apex1_tpu.testing.chaos import sigterm_self_at
    from apex1_tpu.utils.debug import program_fingerprint

    sample = jnp.zeros((args.batch, args.seq), jnp.int32)
    plain_step = amp.make_train_step(gpt2_loss_fn(model))
    sent = Sentinel(None, check_every=max(1, args.ckpt_every),
                    rollback_after=2)
    guarded = jax.jit(sent.guard(plain_step), donate_argnums=0)
    fp = program_fingerprint(sent.guard(plain_step),
                             (state, sentinel_init()), sample)
    ck = ResilientCheckpointer(args.ckpt_dir, keep=3, fingerprint=fp)
    sent.checkpointer = ck
    chaos_at = os.environ.get("APEX1_CHAOS_SIGTERM_STEP")
    chaos_at = int(chaos_at) if chaos_at else None

    start = 0
    carry = (state, sentinel_init())
    if args.resume == "auto" and ck.latest_valid() is not None:
        restored, man = ck.restore(template=carry[0])
        start = int(man.meta.get("data_step", man.step))
        carry = (restored, sentinel_init())
        print(f"resumed from {man.step} (data step {start})", flush=True)

    with TokenDataset(data_path, seq_len=args.seq,
                      batch_size=args.batch) as ds, \
            PreemptionHandler() as pre, ck:
        i = start
        while i < args.steps:
            step_idx = i
            carry, metrics = guarded(carry, jnp.asarray(ds.batch_at(i)))
            i += 1
            action = sent.poll(carry[1])
            if action == "rollback":
                restored, man, s0 = sent.rollback(template=carry[0])
                i = int(man.meta.get("data_step", man.step))
                carry = (restored, s0)
                # This loss is deterministic, so the retry replays the
                # same trajectory on purpose: a TRANSIENT fault (SDC
                # bit flip) won't recur and training continues; a
                # LOGICAL NaN recurs and the ladder escalates to abort
                # with the diagnostics banked. A stochastic run would
                # additionally re-fold its dropout stream here —
                # resilience.refold_key(key, sent.rollbacks_done) — so
                # the retry draws different noise (docs/robustness.md).
                print(f"sentinel rollback to data step {i}", flush=True)
                continue
            if i % args.ckpt_every == 0 or i == args.steps:
                ck.save(int(carry[0].step), carry[0],
                        meta={"data_step": i})
            # same cadence as the plain loop: steps 0, 5, 10, ..., last
            if step_idx % 5 == 0 or step_idx == args.steps - 1:
                logger.log(step_idx, metrics,
                           tokens=args.batch * args.seq)
            sigterm_self_at(i, chaos_at)
            if pre.triggered:
                ck.wait()   # let the in-flight async save commit first
                ck.save_sync(int(carry[0].step), carry[0],
                             meta={"data_step": i, "preempted": True})
                pre.exit_resumable(f"preempted at data step {i}")
        ck.wait()
    return carry[0]


if __name__ == "__main__":
    main()
