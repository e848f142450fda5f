"""Resilient training runtime — SURVEY §5.2's missing elastic-recovery
story, built as four cooperating pieces (see `docs/robustness.md`):

- `ResilientCheckpointer` / `find_restorable` (`.checkpointer`): async
  double-buffered snapshots with per-leaf integrity manifests, atomic
  ``latest`` promotion, ring keep-policy + milestone pins, and a
  backward scan past corrupt checkpoints to the newest valid one.
- `Sentinel` / `guard_train_step` (`.sentinel`): a device-side
  finite/divergence guard for ALL dtypes with a skip → rollback → abort
  escalation ladder and banked diagnostics.
- `PreemptionHandler` / `EXIT_RESUMABLE` (`.preemption`): SIGTERM grace
  hook → final sync checkpoint → the exit code a job scheduler
  re-queues instead of recording a failure.
- `retry_call` / `backoff_delays` / `TransientError` (`.retry`): the one
  bounded-exponential-backoff-with-deterministic-jitter policy, shared
  with `runtime.RequestFeeder`.
- `reshard_state` / `reshard_checkpoint` / `LayoutMismatch`
  (`.reshard`) + `elastic_resume` / `ElasticDecision` / the elastic
  drill (`.elastic`): plan-carrying checkpoints remapped onto a fresh
  planner layout when the fleet shrinks/grows — manifest-verified end
  to end, every decision banked as obs-spine events (ISSUE 14,
  docs/robustness.md § Elastic resume).

Every recovery path is exercised deterministically on CPU by the chaos
harness (`apex1_tpu.testing.chaos`) — injected NaNs, truncated and
bit-flipped checkpoints, simulated SIGTERM, transient backend errors.
"""

from apex1_tpu.resilience.checkpointer import (ResilientCheckpointer,
                                               find_restorable,
                                               is_valid_checkpoint,
                                               step_dir_name)
from apex1_tpu.resilience.elastic import ElasticDecision, elastic_resume
from apex1_tpu.resilience.manifest import (IntegrityError, Manifest,
                                           read_manifest, verify_files,
                                           verify_tree, write_manifest)
from apex1_tpu.resilience.preemption import EXIT_RESUMABLE, PreemptionHandler
from apex1_tpu.resilience.reshard import (LayoutMismatch, read_plan,
                                          reshard_checkpoint,
                                          reshard_state)
from apex1_tpu.resilience.retry import (TransientError, backoff_delays,
                                        retry_call)
from apex1_tpu.resilience.sentinel import (DivergenceError, Sentinel,
                                           SentinelState, guard_train_step,
                                           health_flag, refold_key,
                                           refold_seed, sentinel_init)

__all__ = [
    "ResilientCheckpointer", "find_restorable", "is_valid_checkpoint",
    "step_dir_name",
    "IntegrityError", "Manifest", "read_manifest", "verify_files",
    "verify_tree", "write_manifest",
    "EXIT_RESUMABLE", "PreemptionHandler",
    "ElasticDecision", "LayoutMismatch", "elastic_resume", "read_plan",
    "reshard_checkpoint", "reshard_state",
    "TransientError", "backoff_delays", "retry_call",
    "DivergenceError", "Sentinel", "SentinelState", "guard_train_step",
    "health_flag", "refold_key", "refold_seed", "sentinel_init",
]
