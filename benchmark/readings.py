"""The readings a training cell's limits are set from, many seeds in ONE
process (set-up is long, and the contract asks for a dozen seeds of the
program and three of the control):

    python3 -m benchmark.readings --workload <cell> --seeds 1,2,3,... \\
        --control-seeds 1,2,3 --out chiprun_out/readings --deadline-s 400

Per seed: the cell's compiled step through its first steps exactly as
`benchmark.run` drives it, the plain reference over the same steps, and
for the control seeds the lower-precision control in the program's place.
Every per-leaf array goes to `<out>/<cell>.<seed>.npz`, so a statistic
over the leaves can be chosen afterwards without another chip run; one
`ROW` line per seed carries the numbers `check.compare_training` makes of
them. No window is measured and no result line is printed: the driver
never runs this. Control seeds go first; no new seed is started after
`--deadline-s` seconds.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_T0 = time.perf_counter()


def _numbers(rows) -> dict:
    return {name: value for name, value, _, _ in rows}


def main(argv=None, *, root=None, allow_cpu=False) -> int:
    """`root` and `allow_cpu` are for the tests' rehearsal, as in
    `benchmark.run.run_cell`."""
    ap = argparse.ArgumentParser(prog="benchmark.readings")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    ap.add_argument("--deadline-s", type=float, default=1e9)
    args = ap.parse_args(argv)

    from benchmark.harness import manifest as mf
    root = root or mf.ROOT
    man = mf.load_manifest(root)
    cell = mf.find(man, "workloads", args.workload)
    cfg = mf.load_config(man, cell["config"], root)
    traffic = mf.load_traffic(cell["traffic"], root)
    if traffic["kind"] != "train":
        print("benchmark.readings: training cells only (a serving cell's "
              "control is `benchmark.run --control 1`)", file=sys.stderr)
        return 3
    reference = mf.load_reference(cfg.get("reference", cell["config"]),
                                  root)

    import jax
    import numpy as np
    from benchmark.harness import check, device, train
    device.enable_cache()
    try:
        devices = device.require_chips(cell["chips"], allow_cpu=allow_cpu)
    except device.NoChip as e:
        print(f"benchmark.readings: {e}", file=sys.stderr)
        return 2
    train.import_program(cfg)
    limits = check.load_limits(cell["name"], root)
    os.makedirs(args.out, exist_ok=True)
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    seeds = control_seeds + [int(s) for s in args.seeds.split(",")
                             if s and int(s) not in control_seeds]

    pieces = compiled = None
    for seed in seeds:
        if time.perf_counter() - _T0 > args.deadline_s:
            print(f"deadline: seed {seed} and later not started", flush=True)
            break
        t0 = time.perf_counter()
        state, batches, pieces = train.build(cfg, traffic, devices, seed,
                                             pieces)
        if compiled is None:
            compiled = pieces["step"].lower(state, batches[0]).compile()
        state, prog = train.first_steps(compiled, state, batches, pieces,
                                        traffic, reference)
        host_batches = [jax.tree_util.tree_map(np.asarray, b)
                        for b in batches[:train.N_CHECKED_STEPS]]
        del state, batches
        res = train.follow(prog, pieces, traffic, host_batches, devices,
                           reference, seed in control_seeds)
        ref, ctl = res["reference"], res["control"]
        arrays = {f"{who}.{k}": np.asarray(v)
                  for who, d in (("prog", prog), ("ref", ref), ("ctl", ctl))
                  if d is not None for k, v in d.items()}
        np.savez(os.path.join(args.out, f"{cell['name']}.{seed}.npz"),
                 **arrays)
        row = {"cell": cell["name"], "seed": seed,
               "prog": _numbers(check.compare_training(prog, ref, limits)),
               "prog_losses": prog["losses"]}
        if ctl is not None:
            row["control"] = _numbers(
                check.compare_training(ctl, ref, limits))
        row["s"] = round(time.perf_counter() - t0, 1)
        row["at_s"] = round(time.perf_counter() - _T0, 1)
        print("ROW " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
