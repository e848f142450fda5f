"""Deterministic chaos-injection harness — every recovery path in
`apex1_tpu.resilience` is EXERCISED in tier-1 on CPU, not just trusted
on silicon.

All injection is seed-keyed and pure-function-of-its-inputs: two runs
with the same seed inject the same faults at the same steps, which is
what makes "SIGTERM mid-run, resume, bit-identical to uninterrupted"
an assertable property instead of a flaky one.

Fault classes (one helper per class, composable):

- **NaN/Inf poisoning** (`poison_at_steps`, traced): multiply a loss /
  grad tree by a factor that is NaN exactly at the listed steps —
  drives the sentinel's skip/rollback/abort ladder from inside jit.
- **checkpoint corruption** (`truncate_checkpoint`,
  `bitflip_checkpoint`, host): deterministic file pick + deterministic
  byte, so `find_restorable`'s backward scan is tested against real
  on-disk damage.
- **simulated preemption** (`sigterm_self_at`, host): SIGTERM delivered
  to the current process at a step boundary, exercising
  `PreemptionHandler` + the resumable-exit contract.
- **transient backend errors** (`Flaky`): a callable that raises
  `resilience.TransientError` for its first N calls — verifies
  retry/backoff policies actually retry, back off, and give up on
  schedule.
- **serving faults** (`ServingFault` family, host): hooks the
  `serving.replica.ReplicaSupervisor` calls at its submit/step
  boundaries — `ReplicaKill` (crash at an exact step), `ReplicaHang`
  (stall past the watchdog), `SlowReplica` (straggler injecting
  per-step delay), `PoisonPill` (a marked request whose ADMISSION
  kills the replica, every time, on every replica — the quarantine
  fixture). `kill_schedule` derives (replica, step) picks from a seed
  for the bench's chaos-on mode. `toy_decoder` is the matching
  fixture model: a deterministic history-dependent cached decoder that
  compiles in milliseconds, so multi-replica drills stay cheap.

``python -m apex1_tpu.testing.chaos --smoke`` runs the two headline
TRAINING recoveries end-to-end (injected-NaN rollback +
corrupt-checkpoint fallback scan) in <30 s on CPU — the
``== chaos smoke ==`` step in ``tools/check_all.sh``;
``--serve-smoke`` runs the SERVING headline (2-replica frontend,
replica killed mid-stream → every request completes token-identical
to an uninterrupted run + poison-pill quarantine) in <10 s — the
``== serving chaos smoke ==`` step.
"""

from __future__ import annotations

import os
import signal
from typing import Callable, Optional, Sequence

import numpy as np

from apex1_tpu.resilience.manifest import read_manifest
from apex1_tpu.resilience.retry import TransientError, _mix32

__all__ = [
    "poison_at_steps", "poison_tree_at_steps", "truncate_checkpoint",
    "bitflip_checkpoint", "sigterm_self_at", "Flaky", "TransientError",
    "ServingFault", "ChaosSchedule", "ReplicaKill", "ReplicaHang",
    "SlowReplica", "PoisonPill", "HandoffWindowKill",
    "HandoffCorruption", "kill_schedule", "shrink_schedule",
    "toy_decoder",
]


# -- traced-side injection --------------------------------------------------

def poison_at_steps(value, step, steps: Sequence[int], *,
                    poison: float = float("nan")):
    """Return ``value`` except at the listed ``steps``, where every
    element becomes ``poison`` (NaN default, pass ``float('inf')`` for
    Inf). ``step`` may be traced (the train state's step counter);
    ``steps`` is static. Identity (and jit-cache-identical) when
    ``steps`` is empty."""
    import jax.numpy as jnp

    if not len(steps):
        return value
    v = jnp.asarray(value)
    hits = jnp.asarray(list(steps), jnp.int32)
    hit = jnp.any(hits == jnp.asarray(step, jnp.int32))
    bad = jnp.asarray(poison, v.dtype)
    return jnp.where(hit, jnp.full_like(v, bad), v)


def poison_tree_at_steps(tree, step, steps: Sequence[int], *,
                         poison: float = float("nan")):
    """`poison_at_steps` over every floating leaf of a pytree (poisoned
    grads, not just a poisoned loss)."""
    import jax
    import jax.numpy as jnp

    def leaf(x):
        x = jnp.asarray(x)
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        return poison_at_steps(x, step, steps, poison=poison)

    return jax.tree_util.tree_map(leaf, tree)


# -- on-disk corruption -----------------------------------------------------

def _pick_payload_file(ckpt_dir: str, seed: int) -> str:
    """Deterministic payload-file pick from the checkpoint's own
    manifest: the largest file (ties broken by path), rotated by seed —
    corruption always lands on bytes the integrity manifest covers."""
    m = read_manifest(ckpt_dir)
    files = sorted(m.files, key=lambda e: (-e["bytes"], e["path"]))
    if not files:
        raise ValueError(f"{ckpt_dir}: no payload files to corrupt")
    biggest = [e for e in files if e["bytes"] == files[0]["bytes"]]
    pick = biggest[_mix32(seed) % len(biggest)]
    return os.path.join(ckpt_dir, pick["path"])


def truncate_checkpoint(ckpt_dir: str | os.PathLike, *, seed: int = 0,
                        keep_fraction: float = 0.5) -> str:
    """Truncate a manifest-covered payload file to ``keep_fraction`` of
    its size (a killed writer / torn copy). Returns the damaged path."""
    path = _pick_payload_file(os.fspath(ckpt_dir), seed)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(int(size * keep_fraction))
    return path


def bitflip_checkpoint(ckpt_dir: str | os.PathLike, *, seed: int = 0
                       ) -> str:
    """XOR one deterministic byte of a payload file (cosmic-ray /
    bit-rot model). File size is unchanged — only the content digest can
    catch this. Returns the damaged path."""
    path = _pick_payload_file(os.fspath(ckpt_dir), seed)
    size = os.path.getsize(path)
    if size == 0:
        raise ValueError(f"{path}: empty file, nothing to flip")
    off = _mix32(seed ^ 0xB17F11B) % size
    with open(path, "r+b") as f:
        f.seek(off)
        b = f.read(1)
        f.seek(off)
        f.write(bytes([b[0] ^ 0xFF]))
    return path


# -- preemption + transient faults ------------------------------------------

def sigterm_self_at(step: int, at_step: Optional[int],
                    *, signum: int = signal.SIGTERM) -> bool:
    """Deliver ``signum`` to THIS process when ``step == at_step`` (the
    simulated mid-run preemption). Returns True when fired. A no-op
    (False) when ``at_step`` is None — training loops can leave the call
    in place, keyed off an env var the chaos test sets."""
    if at_step is None or int(step) != int(at_step):
        return False
    os.kill(os.getpid(), signum)
    return True


class Flaky:
    """Wrap ``fn`` to raise `TransientError` on its first ``fails``
    calls, then pass through — the backend-unreachable model. The call
    log (`attempts`, `failures`) is what retry/backoff tests assert."""

    def __init__(self, fn: Callable, *, fails: int = 2,
                 exc: type = TransientError):
        self.fn = fn
        self.fails = int(fails)
        self.exc = exc
        self.attempts = 0
        self.failures = 0

    def __call__(self, *args, **kwargs):
        self.attempts += 1
        if self.attempts <= self.fails:
            self.failures += 1
            raise self.exc(
                f"injected transient failure {self.failures}/{self.fails}")
        return self.fn(*args, **kwargs)


# -- serving faults ---------------------------------------------------------

class ServingFault:
    """Hook surface `serving.replica.ReplicaSupervisor` calls at its
    two fault boundaries. The base class is a no-op; subclasses raise
    or sleep at EXACT (replica, step) coordinates — deterministic, so
    "kill a replica mid-stream, every token bit-identical" is an
    assertable property, not a flaky one."""

    def on_step(self, replica_id: int, step: int) -> None:
        """Called once per serve iteration, before the engine step."""

    def on_submit(self, replica_id: int, sub) -> None:
        """Called just before a submission is admitted to the engine
        (``sub`` is a `serving.replica.Submission`)."""

    def on_handoff(self, replica_id: int, req_id: int, page) -> None:
        """Called by `serving.disagg.DisaggFrontend` in the handoff
        window — after a prefill replica extracted a KV page for
        ``req_id`` but BEFORE the decode pool acknowledged it
        (``page`` is a `serving.disagg.kv_transfer.KVPage`, mutable
        host copy). Raising `ReplicaKilled` here models the source
        dying mid-transfer; mutating ``page.lane`` models a torn/
        corrupt transfer the arrival re-digest must catch."""


class ChaosSchedule(ServingFault):
    """Compose several faults; each sees every hook."""

    def __init__(self, faults: Sequence[ServingFault]):
        self.faults = list(faults)

    def on_step(self, replica_id, step):
        for f in self.faults:
            f.on_step(replica_id, step)

    def on_submit(self, replica_id, sub):
        for f in self.faults:
            f.on_submit(replica_id, sub)

    def on_handoff(self, replica_id, req_id, page):
        for f in self.faults:
            f.on_handoff(replica_id, req_id, page)


class ReplicaKill(ServingFault):
    """Crash replica ``replica`` at its serve step ``at_step`` — once
    (the restarted generation starts its step count fresh but the
    fault has already fired; ``repeat=True`` kills every generation,
    the crash-loop fixture)."""

    def __init__(self, replica: int, at_step: int, *,
                 repeat: bool = False):
        self.replica = int(replica)
        self.at_step = int(at_step)
        self.repeat = bool(repeat)
        self.fired = 0

    def on_step(self, replica_id, step):
        if replica_id != self.replica or step != self.at_step:
            return
        if self.fired and not self.repeat:
            return
        self.fired += 1
        from apex1_tpu.serving.replica import ReplicaKilled
        raise ReplicaKilled(
            f"chaos: killed replica {replica_id} at step {step}")


class ReplicaHang(ServingFault):
    """Stall replica ``replica`` at step ``at_step`` for ``hang_s``
    (once) — the watchdog-path fixture: the step eventually returns,
    but past the supervision deadline, which is exactly the signature
    of a wedged-then-recovered decode the supervisor must NOT trust."""

    def __init__(self, replica: int, at_step: int, *,
                 hang_s: float = 0.2):
        self.replica = int(replica)
        self.at_step = int(at_step)
        self.hang_s = float(hang_s)
        self.fired = 0

    def on_step(self, replica_id, step):
        if (replica_id == self.replica and step == self.at_step
                and not self.fired):
            self.fired += 1
            import time
            time.sleep(self.hang_s)


class SlowReplica(ServingFault):
    """Straggler model: ``delay_s`` injected into every step of
    ``replica`` in ``[from_step, to_step)`` — below the watchdog
    threshold, so the replica stays 'healthy' while its latency blows
    hedging budgets (the hedged-dispatch fixture)."""

    def __init__(self, replica: int, *, delay_s: float = 0.02,
                 from_step: int = 0, to_step: Optional[int] = None):
        self.replica = int(replica)
        self.delay_s = float(delay_s)
        self.from_step = int(from_step)
        self.to_step = to_step

    def on_step(self, replica_id, step):
        if replica_id != self.replica or step < self.from_step:
            return
        if self.to_step is not None and step >= self.to_step:
            return
        import time
        time.sleep(self.delay_s)


class PoisonPill(ServingFault):
    """A request whose ADMISSION deterministically kills the replica —
    every admission, every replica, every restart: the fixture for the
    supervisor's quarantine ladder (resubmit -> kill again -> evicted
    as poisoned instead of crash-looping forever). Marked by a token:
    any request whose prompt contains ``poison_token`` is the pill."""

    def __init__(self, poison_token: int):
        self.poison_token = int(poison_token)
        self.fired = 0

    def on_submit(self, replica_id, sub):
        if self.poison_token in np.asarray(sub.tokens).tolist():
            self.fired += 1
            from apex1_tpu.serving.replica import PoisonedRequest
            raise PoisonedRequest(
                f"chaos: poison token {self.poison_token} in request "
                f"{sub.req_id}", req_id=sub.req_id)


class HandoffWindowKill(ServingFault):
    """Kill the SOURCE prefill replica in the handoff window — after
    its prefill completed but before the decode pool acknowledged the
    KV page (the ISSUE 16 regression fixture: the request must be
    re-routed, never stranded). Fires on the ``at_handoff``-th handoff
    overall (0 = the first); ``repeat=True`` kills every handoff from
    then on (the crash-loop form — bounded by the frontend's
    ``max_handoff_attempts``)."""

    def __init__(self, at_handoff: int = 0, *, repeat: bool = False):
        self.at_handoff = int(at_handoff)
        self.repeat = bool(repeat)
        self.seen = 0
        self.fired = 0

    def on_handoff(self, replica_id, req_id, page):
        k = self.seen
        self.seen += 1
        if k < self.at_handoff or (self.fired and not self.repeat):
            return
        self.fired += 1
        from apex1_tpu.serving.replica import ReplicaKilled
        raise ReplicaKilled(
            f"chaos: killed replica {replica_id} in the handoff window "
            f"of request {req_id} (handoff #{k})")


class HandoffCorruption(ServingFault):
    """Flip one byte of a transferred KV page AFTER its departure
    digests were taken (the torn/bit-rot transfer model) — the decode
    pool's arrival re-digest must surface a typed `HandoffError`, never
    silently garbage tokens. Fires on the ``at_handoff``-th handoff
    overall, once."""

    def __init__(self, at_handoff: int = 0):
        self.at_handoff = int(at_handoff)
        self.seen = 0
        self.fired = 0

    def on_handoff(self, replica_id, req_id, page):
        k = self.seen
        self.seen += 1
        if k != self.at_handoff or self.fired:
            return
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(page.lane)
        for i, leaf in enumerate(leaves):
            arr = np.array(leaf)         # np.asarray views of device
            #  arrays are read-only; a real copy is the writable
            #  "wire buffer" the flipped bit lands in
            flat = arr.reshape(-1).view(np.uint8)
            if flat.size:
                flat[0] ^= 0xFF
                leaves[i] = arr
                page.lane = jax.tree_util.tree_unflatten(treedef, leaves)
                self.fired += 1
                return


def shrink_schedule(seed: int, *, n_devices: int, lo: int, hi: int,
                    survivors: Optional[int] = None
                    ) -> tuple[int, int]:
    """Seed-keyed mid-run FLEET SHRINK pick for the elastic drill
    (`resilience.elastic`): ``(kill_step, n_survivors)`` — the step at
    which the training job dies, and the device count it must resume
    on. The step is avalanche-derived from the seed (same family as
    `kill_schedule`); survivors defaults to the largest proper divisor
    of ``n_devices`` (kill half an even fleet — the k-of-n drill's
    canonical k = n/2) so the planner always has a clean mesh product
    to re-plan onto. Deterministic: the drill's "kill mid-run" is an
    assertable property, not a flaky one."""
    if hi <= lo:
        raise ValueError(f"need hi > lo, got [{lo}, {hi})")
    step = lo + _mix32(seed ^ 0xE1A57C) % (hi - lo)
    if survivors is None:
        divs = [d for d in range(1, n_devices) if n_devices % d == 0]
        if not divs:
            raise ValueError(
                f"n_devices={n_devices} has no proper divisor to "
                "shrink onto")
        survivors = max(divs)
    if not 1 <= survivors < n_devices:
        raise ValueError(
            f"survivors={survivors} must be in [1, {n_devices})")
    return step, int(survivors)


def kill_schedule(seed: int, *, n_replicas: int, lo: int, hi: int
                  ) -> ReplicaKill:
    """Seed-derived `ReplicaKill`: replica and step picked by the same
    avalanche hash the rest of the chaos harness uses, so a bench's
    ``--chaos`` run is reproducible from its seed alone."""
    if hi <= lo:
        raise ValueError(f"need hi > lo, got [{lo}, {hi})")
    replica = _mix32(seed ^ 0xC0FFEE) % int(n_replicas)
    step = lo + _mix32(seed ^ 0xDEAD10C) % (hi - lo)
    return ReplicaKill(replica, step)


def toy_decoder(vocab_size: int = 61):
    """A deterministic cached toy decoder ``(apply_fn, make_cache,
    params)`` with the `models.generate` decoder contract — history-
    dependent logits (an avalanche hash of the causal prefix sum), so
    stale-cache and lost-stream bugs change tokens, but compiles in
    milliseconds: multi-replica chaos drills pay supervisor cost, not
    XLA cost. The cache stores one small integer per position, so the
    int8 ``cache_dtype`` profile is EXACT here (values < 128)."""
    import jax.numpy as jnp

    from apex1_tpu.models.generate import (cache_len, cache_write,
                                           init_cache)

    def make_cache(batch: int, max_len: int, dtype=None, **form):
        # one head of width 1, in `init_cache`'s stored form
        dt = jnp.float32 if dtype is None else dtype
        return {"toy": {"h": init_cache(1, batch, 1, max_len, 1, dt,
                                        **form)["layer0"]["k"]}}

    def apply_fn(params, tokens, cache, cache_index, positions=None,
                 chunk_decode=False):
        h = cache["toy"]["h"]                       # (B, Smax, 1)
        B, S = tokens.shape
        idx = jnp.asarray(cache_index, jnp.int32)
        vals = (tokens + 1).astype(h.dtype).reshape(B, 1, S, 1)
        # a scalar index or one per row, as `cached_attention` takes it
        h = cache_write(h, vals, idx)
        # causal-prefix sum per query: pos <= idx + j (the chunk-verify
        # horizon), over the UPDATED cache so each query sees itself —
        # pad/stale residue beyond the horizon never enters
        pos = jnp.arange(cache_len(h), dtype=jnp.int32)
        qpos = jnp.broadcast_to(
            idx[..., None] + jnp.arange(S, dtype=jnp.int32), (B, S))
        mask = (pos <= qpos[..., None]).astype(jnp.float32)
        hv = h[:, :, 0].astype(jnp.float32)
        s = jnp.einsum("bp,bsp->bs", hv, mask)      # (B, S)
        su = (s.astype(jnp.uint32) * params["w"].astype(jnp.uint32))
        v = jnp.arange(vocab_size, dtype=jnp.uint32)
        logits = -(((su[..., None] * jnp.uint32(2654435761)
                     + (v + 1) * jnp.uint32(40499))
                    % jnp.uint32(977)).astype(jnp.float32))
        return logits, {"toy": {"h": h}}

    params = {"w": jnp.ones((), jnp.uint32)}
    return apply_fn, make_cache, params


# -- smoke entry point (check_all.sh `== chaos smoke ==`) -------------------

def _smoke() -> int:
    """Two headline recoveries, tiny shapes, CPU, <30 s:
    (1) injected-NaN grads → device-side skip → second hit → rollback to
    last-good with a banked diagnostic; (2) newest checkpoint truncated
    AND the one before bit-flipped → `find_restorable` selects the older
    valid one and restore round-trips."""
    import tempfile

    from apex1_tpu.testing import force_virtual_cpu_devices

    force_virtual_cpu_devices(1)
    import jax
    import jax.numpy as jnp

    from apex1_tpu.amp import Amp
    from apex1_tpu.optim.fused_sgd import fused_sgd
    from apex1_tpu.resilience import (ResilientCheckpointer, Sentinel,
                                      find_restorable, sentinel_init)

    amp = Amp(tx=fused_sgd(0.1), opt_level="O0")
    state = amp.init({"w": jnp.ones((8,), jnp.float32)})

    def loss_fn(p, x, step):
        loss = jnp.sum(jnp.square(p["w"])) * x
        return poison_at_steps(loss, step, (3, 4))

    with tempfile.TemporaryDirectory() as d:
        ck = ResilientCheckpointer(d, keep=4)
        sent = Sentinel(ck, check_every=1, rollback_after=2)
        guarded = jax.jit(sent.guard(amp.make_train_step(loss_fn)))
        carry = (state, sentinel_init())
        rolled_back = False
        i = 0
        while i < 6 and not rolled_back:
            carry, _m = guarded(carry, jnp.float32(1.0),
                                carry[0].step)
            ck.save_sync(int(carry[0].step), carry[0],
                         meta={"data_step": i + 1})
            if sent.poll(carry[1]) == "rollback":
                good, manifest, s0 = sent.rollback(template=carry[0])
                carry = (good, s0)
                rolled_back = True
            i += 1
        assert rolled_back, "NaN injection never escalated to rollback"
        assert sent.records[-1]["action"] == "rollback"
        assert np.isfinite(np.asarray(carry[0].params["w"])).all()
        print(f"chaos smoke [1/2] OK: NaN@step3,4 -> skip -> rollback to "
              f"step {manifest.step}, diagnostic banked "
              f"({sent.records[-1].get('path', '<memory>')})")

        # (2) damage the two newest checkpoints two different ways
        dirs = sorted(p for p in os.listdir(d) if p.startswith("step_"))
        assert len(dirs) >= 3
        truncate_checkpoint(os.path.join(d, dirs[-1]))
        bitflip_checkpoint(os.path.join(d, dirs[-2]))
        best = find_restorable(d)
        assert best is not None and os.path.basename(best) == dirs[-3], \
            f"expected fallback to {dirs[-3]}, got {best}"
        restored, man = ck.restore(template=carry[0], path=best)
        assert int(man.step) == int(restored.step)
        ck.close()
        print(f"chaos smoke [2/2] OK: truncated {dirs[-1]} + bit-flipped "
              f"{dirs[-2]} -> find_restorable fell back to {dirs[-3]}")
    return 0


def _serve_smoke() -> int:
    """The serving headline recoveries, toy decoder, CPU, <10 s:
    (1) 2-replica frontend, replica killed mid-stream → restarted with
    a fresh engine (exactly two executables per generation), in-flight
    requests resubmitted → every request completes TOKEN-IDENTICAL to
    an uninterrupted single-engine run, at temperature > 0 (the pinned
    per-request seed, not greedy luck); (2) a poison-pill request that
    kills its replica on every admission is quarantined after the
    configured threshold instead of crash-looping."""
    from apex1_tpu.testing import (enable_persistent_compilation_cache,
                                   force_virtual_cpu_devices)

    force_virtual_cpu_devices(1)
    # every fresh engine (replica, restart, reference) re-traces the
    # same two tiny executables; the persistent cache collapses the
    # repeat XLA compiles so the drill's cost is supervision, not XLA
    enable_persistent_compilation_cache()

    from apex1_tpu.serving import (Engine, EngineConfig, FrontendConfig,
                                   ReplicaConfig, ServingFrontend)

    apply_fn, make_cache, params = toy_decoder()
    ecfg = EngineConfig(max_slots=3, max_len=48, prefill_chunk=4,
                        vocab_size=61, temperature=0.8, seed=7)

    def make_engine():
        return Engine(apply_fn, make_cache, params, ecfg)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 61, (n,)).astype(np.int32)
               for n in (3, 7, 5, 9, 4, 6)]

    kill = kill_schedule(seed=20260804, n_replicas=2, lo=4, hi=9)
    front = ServingFrontend(
        make_engine,
        FrontendConfig(n_replicas=2, capacity_per_replica=8,
                       hedge_after_s=None,
                       replica=ReplicaConfig(watchdog_s=30.0)),
        fault=kill)
    rids = [front.submit(p, max_new_tokens=6 + i % 4)
            for i, p in enumerate(prompts)]
    front.run_until_drained(timeout_s=60.0)

    ref = make_engine()
    for i, (p, rid) in enumerate(zip(prompts, rids)):
        sub = front._subs[rid]
        assert front.poll(rid).status == "done", front.poll(rid)
        rr = ref.submit(p, max_new_tokens=sub.max_new_tokens,
                        seed=sub.seed)
        ref.run(max_steps=100)
        got, want = front.poll(rid).tokens, ref.results[rr].tokens
        assert np.array_equal(got, want), \
            f"req {rid}: {got} != uninterrupted {want}"
    restarts = front.metrics.summary()["counters"]["replica_restarts"]
    assert kill.fired == 1 and restarts == 1, (kill.fired, restarts)
    for rep in front.replicas:
        assert rep.trace_counts() == {"prefill": 1, "decode": 1}, \
            (rep.replica_id, rep.trace_counts())
    print(f"serving chaos smoke [1/2] OK: replica {kill.replica} killed "
          f"at step {kill.at_step} -> restarted (fresh 2-executable "
          f"engine), {len(rids)} streams token-identical to the "
          f"uninterrupted run at temperature 0.8")

    # (2) poison-pill quarantine: admission kills the replica every
    # time; after poison_threshold deaths the request is evicted as
    # poisoned and the replica serves on
    pill = PoisonPill(poison_token=60)
    front2 = ServingFrontend(
        make_engine,
        FrontendConfig(n_replicas=1, capacity_per_replica=8,
                       hedge_after_s=None,
                       replica=ReplicaConfig(watchdog_s=30.0,
                                             max_restarts=5,
                                             poison_threshold=1)),
        fault=pill)
    good = front2.submit(prompts[0], max_new_tokens=5)
    bad = front2.submit(np.asarray([60, 1, 2], np.int32),
                        max_new_tokens=5)
    front2.run_until_drained(timeout_s=60.0)
    assert front2.poll(good).status == "done"
    res = front2.poll(bad)
    assert res.status == "evicted" and "poisoned" in res.reason, res
    assert pill.fired == 2, pill.fired      # threshold + 1 admissions
    print(f"serving chaos smoke [2/2] OK: poison pill killed its "
          f"replica {pill.fired}x -> quarantined ('{res.reason}'), "
          f"good request still served")
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="run the two headline training recovery paths "
                         "(CPU, <30s)")
    ap.add_argument("--serve-smoke", action="store_true",
                    help="run the serving recovery paths: replica-kill "
                         "token parity + poison quarantine (CPU, <10s)")
    args = ap.parse_args(argv)
    if args.smoke:
        return _smoke()
    if args.serve_smoke:
        return _serve_smoke()
    ap.print_help()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
