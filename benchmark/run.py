"""The benchmark's one command:

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run. It finds the cell in `BENCHMARK.json`, its
configuration, traffic mix, limits, plain reference and per-layer metric
files BY NAME, runs the cell's kind, and prints as the last line of
standard output one JSON object (`correct`, `attempted`, `failed`,
`metrics`, `device`, `breakdown` when traced, and last `compared`: every
number the check compared beside its limit, which are also the last lines
of standard error). Without a TPU holding
the chips the cell asks for it exits non-zero and prints no result.

`--control 1` (never passed by the driver) also computes the
lower-precision control of the correctness check, in the same process;
`python3 -m benchmark.readings` reads a training cell's check over many
seeds in one process.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402


class Phases:
    """Set-up, itemised on the benchmark's own clock from process start."""

    def __init__(self, meter=None):
        self.laps = {}
        self.meter = meter
        self.compile_s = 0.0
        self._last = _T_START

    def lap(self, name: str):
        now = time.perf_counter()
        self.laps[name] = self.laps.get(name, 0.0) + now - self._last
        self._last = now

    def total(self) -> float:
        if self.meter is not None:
            self.compile_s = self.meter.seconds
        return sum(self.laps.values())


class Profiler:
    """`jax.profiler` around the traced window: host annotations on, the
    Python call tracer off (it would dominate the trace and the host)."""

    def __init__(self, logdir: str):
        self.logdir = logdir

    def start(self):
        import jax
        shutil.rmtree(self.logdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.logdir, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()


def read_metric(spec: dict, ctx: dict):
    """The generic reader: a metric's own file says which scalar, or which
    series and reduction, of the run's context it reads. Returns None
    when there is nothing to read (the metric is then left out)."""
    if "_module" in spec:
        return spec["_module"].read(ctx)
    read = spec["read"]
    if "scalar" in read:
        value = ctx["scalars"].get(read["scalar"])
    else:
        from benchmark.harness import stats
        values = ctx["series"].get(read["series"]) or []
        if not values:
            return None
        how = read.get("reduce", "p50")
        if how == "mean":
            value = sum(values) / len(values)
        elif how == "max":
            value = max(values)
        else:
            value = stats.percentile(values, float(how.lstrip("p"))).value
    if value is None:
        return None
    return float(value) * float(read.get("scale", 1.0))


def run_cell(argv=None, *, root=None, allow_cpu=False):
    """(exit code, result dict or None). `root` and `allow_cpu` are for
    the tests' rehearsals: a run without a TPU never yields a result
    LINE (see `main`)."""
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import manifest as mf
    root = args.root = root or mf.ROOT
    # alone with BENCHMARK.json and the benchmark's own files there is no
    # system under test: fail before anything else
    if not os.path.isdir(os.path.join(mf.ROOT, "apex1_tpu")):
        print("benchmark.run: the system under test (apex1_tpu/) is not "
              "in this checkout", file=sys.stderr)
        return 3, None
    man = mf.load_manifest(root)
    mf.validate(man, root)
    cell = mf.find(man, "workloads", args.workload)
    cfg = mf.load_config(man, cell["config"], root)
    traffic = mf.load_traffic(cell["traffic"], root)
    args.reference = mf.load_reference(cfg.get("reference", cell["config"]),
                                       root)

    import jax
    from benchmark.harness import device
    cache = device.enable_cache()
    phases = Phases()
    phases.lap("import")
    try:
        devices = device.require_chips(cell["chips"], allow_cpu=allow_cpu)
    except device.NoChip as e:
        print(f"benchmark.run: {e} — nothing measured, no result",
              file=sys.stderr)
        return 2, None
    rehearsal = devices[0].platform != "tpu"
    meter = device.CompileMeter()
    phases.meter = meter
    phases.lap("client")
    dev = device.info(devices)
    print(f"benchmark.run: {cell['name']} = {cell['config']} x "
          f"{cell['traffic']} on {dev['count']} x {dev['kind']} "
          f"({dev['platform']}), seed {args.seed}, {args.seconds} s, trace "
          f"{args.trace}, compile cache {cache}", flush=True)

    kind = traffic["kind"]
    if kind == "train":
        from benchmark.harness import train as runner
    elif kind in ("serve_open", "serve_closed"):
        from benchmark.harness import serve as runner
    else:
        raise mf.ManifestError(f"unknown kind of cell {kind!r}")
    runner.import_program(cfg)     # only what this kind of cell needs
    phases.lap("import")
    profiler = None
    logdir = os.path.join(root, ".bench_profile", cell["name"])
    if args.trace:
        profiler = Profiler(logdir)
    out = runner.run(cell, cfg, traffic, args, phases, meter, devices,
                     profiler)

    # what a metric's own file may read (`layer_metrics/<metric>.py`,
    # `read(ctx)`): the run's scalars and series, the cell with its
    # configuration and traffic as loaded, the device with its row of
    # `harness/peaks.json` (None off a TPU), and in a traced run the whole
    # reduction of the trace (`trace`) and the trace file (`xplane`)
    ctx = {"scalars": dict(out["scalars"]), "series": out["series"],
           "cell": cell, "cfg": cfg, "traffic": traffic,
           "device": dict(dev, peaks=None if rehearsal
                          else device.peaks(dev["kind"]))}
    for name, secs in phases.laps.items():
        ctx["scalars"][f"setup.{name}_s"] = secs
    ctx["scalars"]["setup.compile_s"] = phases.compile_s
    ctx["scalars"]["peak_hbm_gib"] = out["memory_peak_bytes"] / 2 ** 30
    print("setup: " + ", ".join(
        f"{k} {v:.3f} s" for k, v in phases.laps.items())
        + f"; of which compiling or loading programs {phases.compile_s:.3f}"
        f" s; total {out['scalars']['setup_s']:.3f} s", flush=True)
    device_out = dict(dev, memory_peak_bytes=out["memory_peak_bytes"])
    breakdown = None
    if args.trace:
        from benchmark.harness import trace as tr
        ctx["xplane"] = tr.find_xplane(logdir)
        red = ctx["trace"] = tr.reduce(ctx["xplane"])
        for k, v in red.items():
            if isinstance(v, (int, float)):
                ctx["scalars"][f"trace.{k}"] = v
        device_out["busy_s"] = red["busy_s"]
        device_out["window_s"] = red["window_s"]
        breakdown = {"device_ops": red.get("device_ops", []),
                     "idle_gaps": red.get("idle_gaps", [])}
        print(f"trace: main program {red.get('main_module')}: n_steps "
              f"{red.get('n_steps', 0.0):.4f} inside the window "
              f"({red.get('n_executions', 0)} executions touch it), busy "
              f"{red['busy_s']:.4f} s of {red['window_s']:.4f} s",
              flush=True)
        print("trace: kernels by name (calls, s, ms a step): " + ", ".join(
            f"{k} {c} {s:.6f} {ms:.4f}"
            for k, (c, s, ms) in red.get("kernels", {}).items()),
            flush=True)
        print("trace: host spans read by their form: " + ", ".join(
            f"{k} x{n}" for k, n in red.get("host_spans", {}).items()),
            flush=True)

    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in mf.cell_metrics(man, cell["name"], section):
        if section == "end_to_end":
            value = ctx["scalars"].get(m["name"])
        else:
            value = read_metric(mf.load_layer_metric(m["name"], root), ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if args.trace:      # the metrics' own files have read what they read
        shutil.rmtree(logdir, ignore_errors=True)
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": device_out}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # every number compared beside its limit: the last key of the line
    result["compared"] = {
        name: {"value": float(value), "limit": float(limit),
               "ok": bool(ok)} for name, value, limit, ok in out["compared"]}
    # a CPU rehearsal proves control flow and counts, never a speed: its
    # result goes back to the test that asked, never onto a result line
    return (4 if rehearsal else 0), result


def main(argv=None) -> int:
    code, result = run_cell(argv)
    if code == 0:
        sys.stdout.flush()
        print(json.dumps(result), flush=True)
        # and the last lines of standard error
        for name, row in result["compared"].items():
            print(f"compared: {name} = {row['value']:.6g} (limit "
                  f"{row['limit']:.6g}) {'ok' if row['ok'] else 'FAIL'}",
                  file=sys.stderr)
        sys.stderr.flush()
    return code


if __name__ == "__main__":
    sys.exit(main())
