"""``python -m apex1_tpu.obs --smoke`` — the check_all ``== obs smoke ==``
gate: exercise spine, trace reader and calibration on the CPU backend.

1. spine: open a run in a temp dir, emit a counter and an event, time a
   span, read the file back through `read_events` — schema round-trip
   (the span reaches the file when the run closes).
2. trace -> report: capture a REAL ``jax.profiler.trace`` of one tiny
   jitted step, parse the xplane files with the dependency-free parser,
   build + persist the per-op report, assert it attributed ops.
3. calibrate: fit factors from the repo's banked corpus (bench logs +
   tuning tables) and assert the fit is non-empty — verified with no
   hardware attached.

Everything runs in a few seconds; failures exit non-zero with the
failing stage named.
"""

import argparse
import json
import os
import sys
import tempfile


def smoke() -> int:
    from apex1_tpu.obs import calibrate, spine, xspace

    # -- 1. spine round-trip ----------------------------------------------
    with tempfile.TemporaryDirectory(prefix="obs_smoke_") as tmp:
        with spine.ObsRun(dir=tmp, component="obs_smoke") as run:
            with spine.span("smoke/step", iters=1):
                pass
            run.counter("smoke.count", 2)
            run.event("smoke.note", detail="hello")
            path = run.path
        events = spine.read_events(path)
        kinds = [e["kind"] for e in events]
        assert kinds == ["run", "counter", "event", "span"], kinds
        assert events[0]["schema"] == spine.SCHEMA
        print(f"spine OK: {len(events)} events round-tripped", flush=True)

        # -- 2. trace -> per-op report ------------------------------------
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            return jnp.sum(x @ x)

        x = jnp.ones((256, 256), jnp.float32)
        step(x).block_until_ready()          # compile outside the trace
        tdir = os.path.join(tmp, "trace")
        with jax.profiler.trace(tdir):
            out = step(x)
            out.block_until_ready()
        report = xspace.build_report(tdir, steps=1)
        rpath = xspace.write_report(tdir, report=report)
        with open(rpath) as f:
            banked = json.load(f)
        assert banked["schema"] == xspace.REPORT_SCHEMA
        assert banked["n_ops"] > 0 and banked["total_op_ms"] > 0, banked
        assert set(banked["buckets"]) == set(xspace.BUCKETS)
        print(f"trace OK: {banked['n_ops']} ops attributed "
              f"({banked['plane_class']}), report at {rpath}", flush=True)

    # -- 3. calibration on the banked corpus ------------------------------
    doc = calibrate.build_calibration()
    n_factors = len(doc["factors"]) + len(doc["proxy_factors"])
    assert doc["n_pairs"] > 0 and n_factors > 0, (
        "calibration fitted nothing from the banked corpus "
        f"(pairs={doc['n_pairs']})")
    print(f"calibrate OK: {doc['n_pairs']} pairs -> "
          f"{len(doc['factors'])} tpu + {len(doc['proxy_factors'])} "
          f"cpu-proxy factors, {len(doc['excluded'])} excluded",
          flush=True)
    print("OBS SMOKE OK", flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="run the obs smoke (check_all gate)")
    args = ap.parse_args(argv)
    if args.smoke:
        return smoke()
    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
