"""Turn banked profiler traces into per-op device-time breakdowns.

Every on-silicon ``bench.py`` record stamps a ``profile_artifact``
(PR 9): a ``perf_results/profiles/<config>_...`` directory holding the
``*.xplane.pb`` files of one untimed post-measurement dispatch. This
tool parses them with the dependency-free `apex1_tpu.obs.xspace`
walker (no TensorFlow import roulette) and persists a
``trace_report.json`` NEXT TO the trace it describes — Pallas-kernel /
collective / XLA-op buckets, so exposed-ICI time is directly readable,
and the device's time by the program's own regions (`obs.regions`:
``by region, ms an execution of <module>``, forward and backward, with
each region's largest ops and what no region reaches) — plus a human
table on stdout. A corrupt or truncated trace is a typed, named error
(`obs.xspace.TraceError`), never a traceback.

CPU-rehearsable end-to-end: ``jax.profiler.trace`` works on the CPU
backend (the report is then labelled ``host-xla-proxy`` — shares
meaningful, absolute times host wall-clock; docs/observability.md).

Usage:
    python tools/trace_report.py --trace perf_results/profiles/gpt2_...
    python tools/trace_report.py --log perf_results/bench_gpt2.log
    python tools/trace_report.py --all          # every banked artifact
"""

import argparse
import os
import sys

_TOOLS = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_TOOLS)
sys.path.insert(0, _REPO)

from apex1_tpu.obs import xspace  # noqa: E402
from apex1_tpu.obs.calibrate import json_lines  # noqa: E402


def _records_with_artifacts(results_dir):
    """[(log name, record)] for every banked JSON record carrying a
    ``profile_artifact`` pointer."""
    out = []
    for name in sorted(os.listdir(results_dir)):
        if not (name.startswith("bench_") and name.endswith(".log")):
            continue
        for rec in json_lines(os.path.join(results_dir, name)):
            if rec.get("profile_artifact"):
                out.append((name, rec))
    return out


def report_one(trace_dir, steps=None, top=25):
    """Build + persist + print one report. Returns the report dict."""
    report = xspace.build_report(trace_dir, steps=steps)
    path = xspace.write_report(trace_dir, report=report)
    print(f"== {trace_dir} ==")
    print(xspace.format_report(report, top=top))
    print(f"report banked at {path}")
    return report


def main():
    ap = argparse.ArgumentParser()
    g = ap.add_mutually_exclusive_group(required=True)
    g.add_argument("--trace", help="one trace directory (a banked "
                   "profile_artifact or any jax.profiler.trace output)")
    g.add_argument("--log", help="bench queue log: report the newest "
                   "record's profile_artifact")
    g.add_argument("--all", action="store_true",
                   help="report every banked profile_artifact in "
                   "--results")
    ap.add_argument("--results", default=os.path.join(_REPO,
                                                      "perf_results"))
    ap.add_argument("--steps", type=int, default=None,
                    help="steps the traced dispatch ran (adds ms/step)")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()

    targets = []
    if args.trace:
        targets = [args.trace]
    elif args.log:
        recs = [r for r in json_lines(args.log)
                if r.get("profile_artifact")]
        if not recs:
            print(f"no record with a profile_artifact in {args.log}")
            return 1
        targets = [recs[-1]["profile_artifact"]]
    else:
        arts = _records_with_artifacts(args.results)
        if not arts:
            print(f"no banked profile_artifact records under "
                  f"{args.results} (none stamped yet — they appear on "
                  f"on-silicon bench runs)")
            return 0   # an empty corpus is a state, not a failure
        targets = sorted({r["profile_artifact"] for _n, r in arts})

    failures = 0
    for t in targets:
        # profile_artifact paths are repo-relative (bench.py stamps
        # them that way so records survive checkout moves)
        tdir = t if os.path.isabs(t) else os.path.join(_REPO, t)
        try:
            report_one(tdir, steps=args.steps, top=args.top)
        except xspace.TraceError as e:
            print(f"SKIP {t}: {e.reason}")
            failures += 1
    if failures:
        print(f"{failures}/{len(targets)} artifact(s) unreadable")
    return 1 if failures == len(targets) else 0


if __name__ == "__main__":
    sys.exit(main())
