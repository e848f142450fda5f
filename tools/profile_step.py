"""Capture a jax.profiler trace of one bench config's train step on the
current backend and print the per-op device-time breakdown.

Since PR 10 the parsing/attribution lives in `apex1_tpu.obs.xspace`
(a dependency-free XSpace wire-format walker — the old three-way
``xplane_pb2`` import-location roulette is gone) and the breakdown is
ALSO persisted as ``trace_report.json`` next to the trace, same format
as ``tools/trace_report.py`` banks for every bench `profile_artifact`.

Usage: python tools/profile_step.py [--config gpt2] [--top 40]
"""

import argparse
import os
import sys
import tempfile

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from apex1_tpu.obs import xspace  # noqa: E402


def build_step(config):
    import bench
    on_accel = jax.default_backend() not in ("cpu",)
    state, step, batch, units, iters, metric, unit, proxy = \
        bench.BENCHES[config](on_accel)
    jstep = jax.jit(step)
    # compile + warm
    out = jstep(state, *batch)
    jax.block_until_ready(out)
    return jstep, state, batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="gpt2")
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()

    # one process: the tracer must be the process that holds the chip
    print(f"backend={jax.default_backend()}", flush=True)
    jstep, state, batch = build_step(args.config)
    print("compiled; tracing...", flush=True)

    tmp = tempfile.mkdtemp(prefix="jaxprof_")
    with jax.profiler.trace(tmp):
        for _ in range(args.steps):
            out = jstep(state, *batch)
        jax.block_until_ready(out)

    try:
        report = xspace.build_report(tmp, steps=args.steps)
    except xspace.TraceError as e:
        print(f"trace unreadable: {e.reason}", flush=True)
        sys.exit(1)
    path = xspace.write_report(tmp, report=report)
    print(xspace.format_report(report, top=args.top), flush=True)
    print(f"report banked at {path}", flush=True)


if __name__ == "__main__":
    main()
