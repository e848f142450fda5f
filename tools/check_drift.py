#!/usr/bin/env python
"""check_drift — the perf-drift tripwire (ROADMAP item 4's last
clause): pin ``calibrated_ratio`` bands over the banked
``perf_results`` corpus so any drift on banked history fails LOUD.

The committed ``perf_results/calibration.json`` is the fleet's banked
performance memory: per-key slowdown factors fit from every joinable
(predicted, measured) pair (`apex1_tpu.obs.calibrate`). This gate
re-collects those pairs from the logs/tables as they exist NOW and
checks, for every banked measurement, its calibrated ratio

    calibrated_ratio = factor.slowdown / pair.slowdown
                     = measured_rate / (predicted_rate / factor)

against a stated band (default [0.70, 1.45] — outside PR 10's pinned
x1.35 residual envelope with margin). It also re-FITS the factors on
the current corpus and requires them within ``--refit-tol`` (default
5%) of the committed table, and requires the key sets to match
exactly. So ALL of these fail loud instead of rotting silently:

- a new banked record (hardware window, bad merge) whose
  calibrated_ratio says the fleet got slower/faster than banked
  history, enforced at CI time instead of eyeballed;
- an edited/corrupted log shifting a fitted factor;
- re-swept tuning tables or new logs without a calibration re-fit
  (run ``python -m apex1_tpu.obs.calibrate`` and commit);
- an unreadable calibration table or corpus file (exit 2,
  fail-closed: a gate that can't read its evidence must not pass).

jax-free by the same stub-parent import as tools/lint.py (the
capability table is jax-free when the generation is explicit, and the
generation comes from the committed table) — the gate costs ~1s in
check_all's ``== drift gate ==`` step.

Exit codes: 0 in-band, 1 drift, 2 fail-closed (unreadable evidence).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: default calibrated_ratio band: PR 10 pinned post-fit residuals
#: within x1.35 on the banked corpus; the gate allows a hair more so
#: it trips on NEW drift, not on the committed history re-checking
#: itself
BAND = (0.70, 1.45)
REFIT_TOL = 0.05


def _import_calibrate():
    """Import ``apex1_tpu.obs.calibrate`` without executing the
    package ``__init__`` (which imports jax) —
    the lint.py stub-parent recipe. ``apex1_tpu.core`` gets the same
    stub so the lazy capability lookups inside calibrate stay
    jax-free (explicit generation ⇒ no chip detection)."""
    for name, sub in (("apex1_tpu", ""), ("apex1_tpu.core", "core")):
        if name not in sys.modules:
            stub = types.ModuleType(name)
            stub.__path__ = [os.path.join(REPO, "apex1_tpu", sub)
                             if sub else os.path.join(REPO, "apex1_tpu")]
            sys.modules[name] = stub
    import apex1_tpu.obs.calibrate as calibrate
    return calibrate


def fail_closed(msg: str) -> int:
    print(f"DRIFT GATE FAIL-CLOSED: {msg}", file=sys.stderr, flush=True)
    return 2


def _check_corpus_readable(calibrate, results_dir: str,
                           tuning_dir: str) -> list:
    """Every evidence file that EXISTS must be readable and, for
    tables, parseable — the collectors deliberately degrade on damage
    (a decorating consumer must not die), but a GATE that silently
    skips damaged evidence is a gate that passes on corruption."""
    problems = []
    for logname in sorted(calibrate.LOG_TO_CONFIG):
        path = os.path.join(results_dir, logname)
        if not os.path.exists(path):
            continue
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                f.read()
        except OSError as e:
            problems.append(f"{path}: unreadable ({e})")
    if os.path.isdir(tuning_dir):
        for name in sorted(os.listdir(tuning_dir)):
            if not name.endswith(".json"):
                continue
            path = os.path.join(tuning_dir, name)
            try:
                with open(path, encoding="utf-8") as f:
                    json.load(f)
            except (OSError, json.JSONDecodeError) as e:
                problems.append(f"{path}: unreadable/corrupt ({e})")
    return problems


def run_gate(results_dir: str, *, calibration_path: str = None,
             band: tuple = BAND, refit_tol: float = REFIT_TOL,
             json_out: bool = False) -> int:
    calibrate = _import_calibrate()
    cal_path = calibration_path or os.path.join(results_dir,
                                                calibrate.CAL_NAME)
    # fail-closed table load: load_calibration's lenient None would
    # let a corrupt table pass the gate as "no factors, no drift"
    try:
        with open(cal_path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return fail_closed(f"cannot read calibration table "
                           f"{cal_path}: {e}")
    if not isinstance(doc, dict) or doc.get("schema") != calibrate.SCHEMA:
        return fail_closed(
            f"{cal_path}: schema {doc.get('schema')!r} != "
            f"{calibrate.SCHEMA!r}" if isinstance(doc, dict)
            else f"{cal_path}: not a JSON object")
    generation = str(doc.get("generation", "v5e"))
    # keyed by (key, backend): the same key can carry BOTH a tpu
    # factor and a cpu-proxy factor — one flat dict would let the
    # proxy entry shadow the tpu one and the gate would cry
    # UNCALIBRATED on a perfectly committed table
    banked = {(k, v.get("backend")): v
              for table in ("factors", "proxy_factors")
              for k, v in doc.get(table, {}).items()}

    env = os.environ.get("APEX1_TUNING_DIR", "").strip()
    tuning_dir = env or os.path.join(results_dir, "tuning")
    problems = _check_corpus_readable(calibrate, results_dir, tuning_dir)
    if problems:
        return fail_closed("; ".join(problems))

    pairs, _excluded = calibrate.collect_pairs(results_dir, generation,
                                               tuning_dir)
    rows, drifted = [], []
    for p in pairs:
        f = banked.get((p.key, p.backend))
        if f is None:
            drifted.append(p)
            rows.append((p, None, "UNCALIBRATED (re-fit + commit "
                                  "calibration.json)"))
            continue
        ratio = f["slowdown"] / p.slowdown
        ok = band[0] <= ratio <= band[1]
        if not ok:
            drifted.append(p)
        rows.append((p, ratio, "ok" if ok else
                     f"DRIFT (band [{band[0]}, {band[1]}])"))

    # re-fit drift: the committed factors must still be what the
    # corpus says (same keys, within tol) — new evidence requires a
    # recommitted table, not a silently stale one
    fresh_tpu, fresh_proxy = calibrate.fit(pairs)
    fresh = {(k, v.get("backend")): v
             for table in (fresh_tpu, fresh_proxy)
             for k, v in table.items()}
    refit_bad = []
    for key in sorted(set(banked) | set(fresh)):
        b, g = banked.get(key), fresh.get(key)
        if b is None or g is None:
            refit_bad.append((key, b, g, "key set changed"))
            continue
        rel = abs(g["slowdown"] - b["slowdown"]) / b["slowdown"]
        if rel > refit_tol:
            refit_bad.append((key, b, g, f"re-fit moved {rel:.1%} "
                                         f"(> {refit_tol:.0%})"))

    for p, ratio, verdict in rows:
        r = "      -" if ratio is None else f"{ratio:7.3f}"
        print(f"  [{p.backend:9s}] {p.key:28s} ratio {r}  "
              f"({p.source})  {verdict}")
        if verdict != "ok":
            # a failure must NAME the offending record so the fix is
            # one open() away, not a corpus-wide hunt
            print(f"              offending record: "
                  f"{os.path.join(results_dir, p.source)}")
    for (key, backend), b, g, why in refit_bad:
        bs = "-" if b is None else f"{b['slowdown']:.4f}"
        gs = "-" if g is None else f"{g['slowdown']:.4f}"
        print(f"  [refit    ] {f'{key} ({backend})':28s} banked {bs} "
              f"vs corpus {gs}  REFIT DRIFT: {why}")
        srcs = sorted(set((g or b or {}).get("sources", [])))
        if srcs:
            print("              offending record(s): "
                  + ", ".join(os.path.join(results_dir, s)
                              for s in srcs))
    n_bad = len(drifted) + len(refit_bad)
    print(f"drift gate: {len(rows)} banked measurement(s) vs "
          f"{len(banked)} committed factor(s), band "
          f"[{band[0]}, {band[1]}], refit tol {refit_tol:.0%} -> "
          f"{'OK' if n_bad == 0 else f'{n_bad} FAILURE(S)'}",
          flush=True)
    if json_out:
        print(json.dumps({
            "pairs": len(rows), "factors": len(banked),
            "band": list(band), "refit_tol": refit_tol,
            "drifted": [p.key for p in drifted],
            "refit_drift": [f"{k} ({b})" for (k, b), *_ in refit_bad]}))
    return 0 if n_bad == 0 else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--results", default=os.path.join(REPO,
                                                      "perf_results"))
    ap.add_argument("--calibration", default=None,
                    help="calibration table (default "
                         "<results>/calibration.json)")
    ap.add_argument("--band", nargs=2, type=float, default=list(BAND),
                    metavar=("LO", "HI"),
                    help=f"allowed calibrated_ratio band "
                         f"(default {BAND[0]} {BAND[1]})")
    ap.add_argument("--refit-tol", type=float, default=REFIT_TOL,
                    help="max relative movement of a re-fit factor "
                         "vs the committed one (default 0.05)")
    ap.add_argument("--json", action="store_true",
                    help="append a JSON verdict line")
    args = ap.parse_args(argv)
    return run_gate(args.results, calibration_path=args.calibration,
                    band=tuple(args.band), refit_tol=args.refit_tol,
                    json_out=args.json)


if __name__ == "__main__":
    sys.exit(main())
