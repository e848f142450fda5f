"""Multi-process launcher — reference ``apex/parallel/multiproc.py`` (the
tiny pre-``torchrun`` launcher spawning ``world_size`` script copies with
``--rank i``).

JAX is multi-controller: one process per HOST (not per chip), each seeing
its local chips, joined by ``jax.distributed.initialize``. This module
provides both halves:

- `launch(script, num_processes)` — spawn N local processes wired with
  the JAX distributed env (coordinator address, process ids). With
  ``cpu_devices_per_process`` it builds a multi-process CPU cluster on one
  machine — the harness for multi-controller tests without a pod
  (SURVEY.md §4.2.4). ONE PROCESS PER HOST on an accelerator: a chip
  belongs to one process at a time, so several local processes that all
  want the host's TPU fail or hang — `launch` refuses that case.
- `init_distributed()` — in-process entry: call at the top of a training
  script on each host (reads the env `launch` sets, or GKE/TPU-pod env).

``python -m apex1_tpu.parallel.multiproc train.py ...`` mirrors the
reference's CLI shape.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Optional, Sequence


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """≙ ``torch.distributed.init_process_group`` at script top. On TPU
    pods with no args, jax auto-discovers topology from the environment."""
    import jax

    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)


def launch(script: str, args: Sequence[str] = (), *,
           num_processes: int = 2, coordinator_port: int = 12355,
           cpu_devices_per_process: int = 0,
           env: Optional[dict] = None) -> int:
    """Spawn ``num_processes`` copies of ``script``; returns the first
    nonzero exit code (0 if all succeeded). Each child gets
    ``APEX1_COORDINATOR/APEX1_NUM_PROCESSES/APEX1_PROCESS_ID`` plus the
    standard JAX distributed variables.

    More than one local process is only legal on the CPU backend
    (``cpu_devices_per_process``, or ``JAX_PLATFORMS=cpu`` in the child
    env): on an accelerator host the children would all claim the same
    chips — one process per host — so that case raises instead of
    hanging."""
    child_plat = {**os.environ, **(env or {})}.get("JAX_PLATFORMS", "")
    if (num_processes > 1 and not cpu_devices_per_process
            and child_plat.strip().lower() != "cpu"):
        raise ValueError(
            f"launch(num_processes={num_processes}) would start "
            f"{num_processes} local processes on the host's accelerator, "
            "which belongs to one process at a time (one process per "
            "host). Pass cpu_devices_per_process=N for a local CPU "
            "cluster, or run one process per host.")
    procs = []
    for rank in range(num_processes):
        child_env = dict(os.environ)
        child_env.update(env or {})
        child_env.update({
            "APEX1_COORDINATOR": f"127.0.0.1:{coordinator_port}",
            "APEX1_NUM_PROCESSES": str(num_processes),
            "APEX1_PROCESS_ID": str(rank),
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{coordinator_port}",
            "JAX_NUM_PROCESSES": str(num_processes),
            "JAX_PROCESS_ID": str(rank),
        })
        if cpu_devices_per_process:
            child_env["JAX_PLATFORMS"] = "cpu"
            child_env["XLA_FLAGS"] = (
                child_env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count="
                  f"{cpu_devices_per_process}")
        procs.append(subprocess.Popen(
            [sys.executable, script, *args], env=child_env))
    # poll rather than wait serially: if one rank dies, its peers may be
    # blocked in a collective forever — reap them instead of hanging
    import time as _time
    first_bad = 0
    while procs:
        alive = []
        for p in procs:
            code = p.poll()
            if code is None:
                alive.append(p)
            elif code and not first_bad:
                first_bad = code
        if first_bad and alive:
            deadline = _time.time() + 10  # grace for co-failing ranks
            while alive and _time.time() < deadline:
                alive = [p for p in alive if p.poll() is None]
                _time.sleep(0.1)
            for p in alive:
                p.terminate()
            for p in alive:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()
            return first_bad
        procs = alive
        if procs:
            _time.sleep(0.05)
    return first_bad


def init_from_env() -> None:
    """Child-side convenience: initialize from `launch`'s env vars."""
    init_distributed(
        coordinator_address=os.environ["APEX1_COORDINATOR"],
        num_processes=int(os.environ["APEX1_NUM_PROCESSES"]),
        process_id=int(os.environ["APEX1_PROCESS_ID"]))


def main(argv: Sequence[str] = ()) -> int:
    argv = list(argv) or sys.argv[1:]
    if not argv:
        print("usage: python -m apex1_tpu.parallel.multiproc [--nproc N] "
              "script.py [args...]", file=sys.stderr)
        return 2
    nproc = 2
    if argv[0] == "--nproc":
        nproc = int(argv[1])
        argv = argv[2:]
    return launch(argv[0], argv[1:], num_processes=nproc)


if __name__ == "__main__":
    sys.exit(main())
