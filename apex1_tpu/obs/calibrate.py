"""Calibration — fit (predicted → measured) correction factors from the
banked corpus, and feed them back to the predictors.

`apex1_tpu.perf_model` prices a step or a kernel with an analytic
roofline; uncorrected against measurement, every planner decision
would inherit its errors. This module closes the loop (the repo ships
NO corpus today, and since PR 52 nothing in it writes the
``bench_*.log`` records or ``predicted_*.json`` tables the step pairs
are joined from — the planner prices "uncalibrated"; ROADMAP D7):

- **pairs** — every banked measurement that can be joined to its own
  prediction: on-silicon ``perf_results/bench_*.log`` records against
  the newest ``predicted_*.json`` step rows, and tuning-table
  entries that carry the per-sweep analytic ``predicted.ms``
  `tools/tune_kernels.py` banks beside each ``time_ms``.
- **factors** — per key (``step:<config>`` / ``kernel:<name>``), the
  geometric-mean SLOWDOWN ``predicted_rate / measured_rate`` (equiv.
  ``measured_time / predicted_time``; > 1 = slower than the roofline).
  TPU-backed factors land in ``factors``; interpret/CPU-proxy pairs are
  fitted too but land in ``proxy_factors`` and are NEVER applied to
  on-silicon predictions — interpret-mode time is plumbing evidence,
  not silicon (docs/observability.md, "What CPU-proxy numbers mean").
- **feedback** — `step_slowdown` / `kernel_slowdown` are the lookup
  API (`apex1_tpu.planner.cost` reads them).

Exclusions are explicit and banked: the decode configs' predictions
are known-garbage (the HLO cost model counts a scanned loop's weight
buffers once, not once per decode step), so they are excluded with
that reason
rather than silently fitted into a meaningless factor.

The table (``perf_results/calibration.json``,
`resilience.manifest.atomic_write_json`) is a build product of the
corpus: re-run the CLI whenever the corpus changes.

CLI::

    python -m apex1_tpu.obs.calibrate [--results perf_results]
        [--out perf_results/calibration.json] [--generation v5e]
        [--dry-run]
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import math
import os
import time
from typing import Optional

SCHEMA = "apex1-calibration-v1"
CAL_NAME = "calibration.json"

#: step configs whose analytic prediction is structurally meaningless —
#: excluded from fitting WITH the reason banked in the table
EXCLUDED_STEP_CONFIGS = {
    "decode": "scanned-loop blind spot: cost model counts streamed "
              "weights once, not per decode step",
    "decode_int8": "scanned-loop blind spot (see decode)",
}

#: bench-log filename -> bench config
LOG_TO_CONFIG = {
    "bench_bert.log": "bert",
    "bench_bert_drop.log": "bert_dropout",
    "bench_bert_lg.log": "bert_large",
    "bench_decode.log": "decode",
    "bench_dec_int8.log": "decode_int8",
    "bench_gpt2.log": "gpt2",
    "bench_gpt2_b24.log": "gpt2",
    "bench_gpt2_fp16.log": "gpt2_fp16",
    # the planner-driven 3D config: joins no single-chip prediction
    # row (the planner prices it), so records land in `excluded` with
    # that reason rather than a bogus factor
    "bench_llama3d.log": "llama_3d",
    "bench_llama_blk.log": "llama_block",
    "bench_llama16k.log": "llama_longctx",
    "bench_resnet.log": "resnet",
    "bench_t5.log": "t5",
}


def default_results_dir() -> str:
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), "perf_results")


def roofline_ms(flops: float, nbytes: float,
                generation: Optional[str] = None) -> float:
    """Analytic roofline milliseconds for one kernel invocation at a
    capability row — what `tools/tune_kernels.py` banks as
    ``predicted.ms`` beside every sweep winner."""
    from apex1_tpu.core.capability import get_capability

    cap = get_capability(generation)
    t = max(flops / (cap.bf16_tflops * 1e12),
            nbytes / (cap.hbm_gbps * 1e9))
    return t * 1e3


# -- prediction-table resolution (the ONE newest-by-mtime rule) ------------

def newest_prediction_path(results_dir: Optional[str] = None
                           ) -> Optional[str]:
    """Newest banked ``predicted_*.json`` by mtime (lexicographic
    order breaks at r10 vs r9), so a new prediction round can never be
    silently scored against a stale table."""
    d = results_dir or default_results_dir()
    paths = glob.glob(os.path.join(d, "predicted_*.json"))
    if not paths:
        return None
    return max(paths, key=os.path.getmtime)


def newest_prediction(results_dir: Optional[str] = None) -> Optional[dict]:
    path = newest_prediction_path(results_dir)
    if path is None:
        return None
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    doc["_path"] = path
    return doc


def predicted_step_rate(row: dict, generation: str = "v5e"
                        ) -> Optional[float]:
    """Roofline units/sec for one prediction-step row at an EXPLICIT
    capability generation (bench._predicted_rate prices at the current
    chip; offline calibration must price at the chip the banked logs
    came from). Comms term included, same as bench."""
    from apex1_tpu.core.capability import get_capability, ici_link_gbps

    try:
        cap = get_capability(generation)
        t = max(row["flops"] / (cap.bf16_tflops * 1e12),
                row["bytes"] / (cap.hbm_gbps * 1e9))
        exposed = row.get("ici_exposed_bytes", 0.0)
        if exposed:
            link = ici_link_gbps(generation)
            if link:
                t += exposed / (link * 1e9)
        if t <= 0:
            return None
        return row["units_per_step"] / t
    except (KeyError, TypeError, ValueError):
        return None


# -- pair collection -------------------------------------------------------

@dataclasses.dataclass
class Pair:
    """One (predicted, measured) joinable observation."""

    key: str          # "step:<config>" | "kernel:<name>"
    predicted: float  # step: units/sec; kernel: ms
    measured: float   # same unit as predicted
    slowdown: float   # predicted_rate/measured_rate == meas_t/pred_t
    backend: str      # "tpu" | "cpu-proxy"
    source: str       # log / table file the measurement came from
    detail: dict      # free-form provenance

    def to_json(self) -> dict:
        return {"key": self.key, "predicted": self.predicted,
                "measured": self.measured,
                "slowdown": round(self.slowdown, 4),
                "backend": self.backend, "source": self.source,
                **({"detail": self.detail} if self.detail else {})}


def json_lines(path: str) -> list[dict]:
    """Lenient JSON-record scan of a bench queue log: every parseable
    one-line {...} object, in order; unreadable file -> []."""
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not (line.startswith("{") and line.endswith("}")):
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict):
                    out.append(rec)
    except OSError:
        pass
    return out


def collect_step_pairs(results_dir: Optional[str] = None,
                       generation: str = "v5e"
                       ) -> tuple[list[Pair], list[dict]]:
    """On-silicon bench records joined against the newest prediction
    table. Returns ``(pairs, excluded)`` — excluded rows carry their
    reason (decode blind spot, no prediction row, cpu-only record).

    The join is RATE-based (units/sec vs predicted units/sec), which
    tolerates batch-size overrides to first order — flops and time
    both scale ~linearly with B, so bench_gpt2_b24's record pairs
    fairly with the B=16 prediction row. A step_ms-based join would
    NOT."""
    d = results_dir or default_results_dir()
    pred = newest_prediction(d)
    rows = ({r.get("name"): r for r in pred.get("steps", [])
             if isinstance(r, dict) and "flops" in r} if pred else {})
    pairs: list[Pair] = []
    excluded: list[dict] = []
    for logname, config in sorted(LOG_TO_CONFIG.items()):
        path = os.path.join(d, logname)
        if not os.path.exists(path):
            continue
        for rec in json_lines(path):
            val = rec.get("value")
            if isinstance(val, bool) or not isinstance(val, (int, float)) \
                    or not math.isfinite(val) or val <= 0:
                continue
            if "[tpu]" not in rec.get("metric", ""):
                continue   # cpu smoke / unreachable records measure
                # nothing calibratable — skip silently, they are not
                # "excluded measurements", they are non-measurements
            if config in EXCLUDED_STEP_CONFIGS:
                excluded.append({
                    "key": f"step:{config}", "source": logname,
                    "reason": EXCLUDED_STEP_CONFIGS[config]})
                continue
            row = rows.get(config)
            if row is None:
                excluded.append({
                    "key": f"step:{config}", "source": logname,
                    "reason": "no prediction row in newest "
                              "predicted_*.json"})
                continue
            rate = predicted_step_rate(row, generation)
            if not rate:
                excluded.append({
                    "key": f"step:{config}", "source": logname,
                    "reason": "prediction row unpriceable"})
                continue
            pairs.append(Pair(
                key=f"step:{config}", predicted=round(rate, 1),
                measured=float(val), slowdown=rate / float(val),
                backend="tpu", source=logname,
                detail={k: rec[k] for k in ("batch", "step_ms")
                        if k in rec}))
    return pairs, excluded


def collect_kernel_pairs(tuning_dir: Optional[str] = None) -> list[Pair]:
    """Tuning-table winners that bank both ``time_ms`` and the analytic
    ``predicted.ms`` (tune_kernels writes both since PR 10). Interpret-
    timed entries become cpu-proxy pairs — fitted, labelled, never
    applied to silicon predictions."""
    if tuning_dir is None:
        from apex1_tpu.tuning import default_tuning_dir
        tuning_dir = default_tuning_dir()
    pairs: list[Pair] = []
    if not os.path.isdir(tuning_dir):
        return pairs
    for name in sorted(os.listdir(tuning_dir)):
        if not name.endswith(".json"):
            continue
        path = os.path.join(tuning_dir, name)
        try:
            with open(path) as f:
                doc = json.load(f)
            kernel = doc.get("kernel") or name[:-5]
            entries = doc.get("entries") or {}
        except (OSError, json.JSONDecodeError, AttributeError):
            continue   # corrupt table: lookup already degrades, so here
        if not isinstance(entries, dict):
            continue
        for key, entry in sorted(entries.items()):
            if not isinstance(entry, dict):
                continue
            t = entry.get("time_ms")
            p = (entry.get("predicted") or {}).get("ms") \
                if isinstance(entry.get("predicted"), dict) else None
            if not isinstance(t, (int, float)) or isinstance(t, bool) \
                    or not isinstance(p, (int, float)) \
                    or isinstance(p, bool) or t <= 0 or p <= 0:
                continue
            backend = ("tpu" if entry.get("timing") == "measured"
                       else "cpu-proxy")
            pairs.append(Pair(
                key=f"kernel:{kernel}", predicted=float(p),
                measured=float(t), slowdown=float(t) / float(p),
                backend=backend, source=os.path.join("tuning", name),
                detail={"entry": key, "blocks": entry.get("blocks")}))
    return pairs


def collect_pairs(results_dir: Optional[str] = None,
                  generation: str = "v5e",
                  tuning_dir: Optional[str] = None
                  ) -> tuple[list[Pair], list[dict]]:
    d = results_dir or default_results_dir()
    if tuning_dir is None:
        # the tuning corpus lives BESIDE the bench logs (never fall
        # back to the repo's tables when an explicit results dir lacks
        # them — a foreign corpus must not leak in); APEX1_TUNING_DIR
        # overrides, same as the tuning package itself
        env = os.environ.get("APEX1_TUNING_DIR", "").strip()
        tuning_dir = env or os.path.join(d, "tuning")
    step_pairs, excluded = collect_step_pairs(d, generation)
    return step_pairs + collect_kernel_pairs(tuning_dir), excluded


# -- fitting ---------------------------------------------------------------

def _geomean(xs: list[float]) -> float:
    return math.exp(sum(math.log(x) for x in xs) / len(xs))


def fit(pairs: list[Pair]) -> tuple[dict, dict]:
    """Per-key geometric-mean slowdown. Returns ``(factors,
    proxy_factors)``: tpu-backed keys in the first (the appliable
    ones), cpu-proxy-only evidence in the second."""
    by: dict[tuple, list[Pair]] = {}
    for p in pairs:
        by.setdefault((p.key, p.backend), []).append(p)
    factors: dict[str, dict] = {}
    proxy: dict[str, dict] = {}
    for (key, backend), ps in sorted(by.items()):
        geo = _geomean([p.slowdown for p in ps])
        residuals = [p.slowdown / geo for p in ps]
        doc = {"slowdown": round(geo, 4), "n": len(ps),
               "backend": backend,
               "residual_spread": [round(min(residuals), 4),
                                   round(max(residuals), 4)],
               "sources": sorted({p.source for p in ps})}
        (factors if backend == "tpu" else proxy)[key] = doc
    return factors, proxy


def build_calibration(results_dir: Optional[str] = None,
                      generation: str = "v5e",
                      tuning_dir: Optional[str] = None) -> dict:
    pairs, excluded = collect_pairs(results_dir, generation, tuning_dir)
    factors, proxy = fit(pairs)
    pred_path = newest_prediction_path(results_dir)
    return {"schema": SCHEMA,
            "generation": generation,
            "generated_unix": round(time.time(), 1),
            "prediction_table": (os.path.basename(pred_path)
                                 if pred_path else None),
            "n_pairs": len(pairs),
            "factors": factors,
            "proxy_factors": proxy,
            "excluded": excluded,
            "pairs": [p.to_json() for p in pairs]}


def save_calibration(doc: dict, path: Optional[str] = None,
                     results_dir: Optional[str] = None) -> str:
    from apex1_tpu.resilience.manifest import atomic_write_json

    if path is None:
        path = os.path.join(results_dir or default_results_dir(),
                            CAL_NAME)
    atomic_write_json(path, doc)
    return path


# -- lookup (the consumer API) ---------------------------------------------

def load_calibration(results_dir: Optional[str] = None,
                     path: Optional[str] = None) -> Optional[dict]:
    """Banked calibration table, or None. Fail-safe: a corrupt or
    foreign-schema file is a miss, never an exception — the consumers
    decorate measurement records and must not break them."""
    if path is None:
        path = os.path.join(results_dir or default_results_dir(),
                            CAL_NAME)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(doc, dict) or doc.get("schema") != SCHEMA:
        return None
    return doc


def _slowdown(key: str, results_dir: Optional[str] = None
              ) -> Optional[dict]:
    doc = load_calibration(results_dir)
    if doc is None:
        return None
    f = doc.get("factors", {}).get(key)
    if not isinstance(f, dict):
        return None
    s = f.get("slowdown")
    if not isinstance(s, (int, float)) or isinstance(s, bool) or s <= 0:
        return None
    return f


def step_slowdown(config: str, results_dir: Optional[str] = None
                  ) -> Optional[dict]:
    """TPU-backed factor doc for a bench config, or None. cpu-proxy
    factors are deliberately unreachable here — they must never
    recalibrate an on-silicon prediction."""
    return _slowdown(f"step:{config}", results_dir)


def kernel_slowdown(kernel: str, results_dir: Optional[str] = None
                    ) -> Optional[dict]:
    return _slowdown(f"kernel:{kernel}", results_dir)


# -- CLI -------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--results", default=None,
                    help="perf_results dir (default: the repo's)")
    ap.add_argument("--out", default=None,
                    help=f"output path (default <results>/{CAL_NAME})")
    ap.add_argument("--generation", default="v5e",
                    help="capability row the banked tpu logs came from")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the fit; don't write the table")
    args = ap.parse_args(argv)

    doc = build_calibration(args.results, args.generation)
    print(f"calibration: {doc['n_pairs']} pairs -> "
          f"{len(doc['factors'])} tpu factor(s), "
          f"{len(doc['proxy_factors'])} cpu-proxy factor(s), "
          f"{len(doc['excluded'])} excluded "
          f"(prediction table: {doc['prediction_table']})", flush=True)
    for label, fs in (("tpu", doc["factors"]),
                      ("cpu-proxy", doc["proxy_factors"])):
        for key, f in sorted(fs.items()):
            lo, hi = f["residual_spread"]
            print(f"  [{label}] {key:28s} slowdown {f['slowdown']:8.3f}  "
                  f"n={f['n']}  residual x{lo:.2f}..x{hi:.2f}")
    for e in doc["excluded"]:
        print(f"  [excluded] {e['key']:25s} {e['reason'][:80]}")
    if not args.dry_run:
        path = save_calibration(doc, args.out, args.results)
        print(f"wrote {path}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
