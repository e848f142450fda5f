"""Join on-silicon bench results against the offline roofline predictions.

Reads the JSON line each ``perf_results/bench_*.log`` ends with (written by
``bench.py`` — SURVEY.md §6 measurement obligation) and the NEWEST banked
prediction table (``predicted_*.json`` by mtime — the same resolution rule
``bench._predicted_rate`` applies, via
`apex1_tpu.obs.calibrate.newest_prediction_path`, so a new prediction
round can never be silently scored against a stale table; the r5 suffix
was previously HARDCODED here), and emits ``measured_<round>.md`` (round
taken from the resolved table's filename): measured step_ms / rate /
true-MFU next to the predicted roofline, with the achieved roofline
fraction that scores four rounds of tuning work (VERDICT r4 Missing
#1+#2). The same join, generalized and machine-readable, feeds
``perf_results/calibration.json`` — see `apex1_tpu.obs.calibrate`.

True MFU here is computed DIRECTLY as logical_flops / measured_time /
peak — the logical flop count comes from predicted_r5.json's
forced-composite compile, so it needs no per-executable correction factor
(bench.py's on-chip cost_analysis MFU is blind inside ``tpu_custom_call``
and its visible-flops denominator differs between the TPU executable and
the prediction compile, making the tabled ``mfu_correction`` factors
inapplicable to the measured number — this direct form sidesteps that).
HBM GB/s is the same direct form on the byte count (meaningful for the
HBM-bound rows; the cost model's byte count is an estimate, see roofline
fractions >1 where XLA fused better than it assumed).

Peak specs come from the same ``core/capability.py`` row
``tools/predict_perf.py`` uses, so prediction and scoring can never
normalize to different peaks.

Usage: python tools/measured_vs_predicted.py [--out perf_results/measured_rN.md]
"""
import argparse
import json
import os
import pathlib
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from apex1_tpu.core.capability import get_capability  # noqa: E402
from apex1_tpu.obs.calibrate import newest_prediction_path  # noqa: E402

# One log per config, NOT bench.py's _BANKED_LOGS (which lists every log
# a config lands in and picks the best value): the join below divides
# measured step time into the PREDICTION row's flops/bytes, so only the
# log whose shapes match that row may be used — e.g. bench_gpt2_b24.log
# is a B=24 run whose step_ms joined against the B=16 prediction would
# corrupt the MFU/roofline columns. Do not "deduplicate" these.
LOG_FOR_CONFIG = {
    "bert": "bench_bert.log",
    "bert_large": "bench_bert_lg.log",
    "decode": "bench_decode.log",
    "decode_int8": "bench_dec_int8.log",
    "gpt2": "bench_gpt2.log",
    "llama_block": "bench_llama_blk.log",
    "llama_longctx": "bench_llama16k.log",
    "resnet": "bench_resnet.log",
    "t5": "bench_t5.log",
}


def last_json_line(path):
    rec = None
    for line in path.read_text().splitlines():
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--results", default="perf_results")
    ap.add_argument("--out", default=None,
                    help="default: perf_results/measured_<round>.md with "
                         "<round> from the newest predicted_*.json")
    ap.add_argument("--generation", default="v5e",
                    help="capability row for peak specs (must match the "
                         "chip the bench logs came from)")
    args = ap.parse_args()
    cap = get_capability(args.generation)
    peak_flops = cap.bf16_tflops * 1e12
    peak_bw = cap.hbm_gbps * 1e9
    res = pathlib.Path(args.results)
    # newest-by-mtime, NOT a hardcoded round suffix: scoring the next
    # prediction round against r5 numbers would be silent staleness
    pred_path = newest_prediction_path(str(res))
    if pred_path is None:
        sys.exit(f"no predicted_*.json under {res} — run "
                 f"tools/predict_perf.py first")
    suffix = re.sub(r"^predicted_|\.json$", "",
                    os.path.basename(pred_path)) or "r?"
    if args.out is None:
        args.out = str(res / f"measured_{suffix}.md")
    pred = json.loads(pathlib.Path(pred_path).read_text())
    steps = {s["name"]: s for s in pred["steps"]}

    rows = []
    for name, logname in sorted(LOG_FOR_CONFIG.items()):
        p = res / logname
        rec = last_json_line(p) if p.exists() else None
        if rec is not None and not rec.get("value"):
            rec = None
        rows.append((name, rec, steps.get(name)))

    lines = [
        f"# Measured vs predicted — {suffix} "
        f"(single {cap.generation} chip)",
        "",
        "Generated by `tools/measured_vs_predicted.py` from the live-window",
        f"bench logs and `{os.path.basename(pred_path)}` (newest banked",
        "prediction table by mtime). `roofline frac` = predicted",
        "step time / measured step time — the fraction of the no-overlap",
        "roofline upper bound the program achieves (1.0 = at the bound;",
        ">1.0 = the cost model overcounted bytes; ≤~0.5 = localize with",
        "the per-op profile). `true MFU` = logical FLOPs (from the",
        "prediction's forced-composite compile) / measured time / peak —",
        "NOT bench.py's cost_analysis-visible MFU, which is blind inside",
        "tpu_custom_call. `HBM GB/s` = estimated bytes / measured time.",
        "",
        "| config | measured rate | unit | vs_baseline (pinned A100) | step ms | pred ms | roofline frac | true MFU | HBM GB/s |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for name, rec, s in rows:
        if rec is None:
            lines.append(f"| {name} | (no result) | | | | | | | |")
            continue
        pred_ms = ""
        ratio = ""
        true_mfu = ""
        hbm_gbs = ""
        step_ms = rec.get("step_ms")
        step_cell = f"{step_ms:,}" if step_ms is not None else ""
        if s:
            t_comp = s["flops"] / peak_flops
            t_hbm = s["bytes"] / peak_bw
            pms = max(t_comp, t_hbm) * 1e3
            pred_ms = f"{pms:.1f}"
            if step_ms:
                step_s = step_ms / 1e3
                ratio = f"{pms / step_ms:.2f}"
                true_mfu = f"{s['flops'] / step_s / peak_flops:.3f}"
                hbm_gbs = f"{s['bytes'] / step_s / 1e9:,.0f}"
        lines.append(
            f"| {name} | {rec['value']:,.0f} | {rec['unit']} | "
            f"{rec.get('vs_baseline', '')} | {step_cell} | "
            f"{pred_ms} | {ratio} | {true_mfu} | {hbm_gbs} |")
    lines += [
        "",
        "Flash-heavy-row context (gpt2 0.53, llama_block 0.53,",
        "llama_longctx 0.36): the prediction assumes BOTH 100% MXU peak",
        "AND the 5-matmul fused flash backward of the analytic flop",
        "count. The shipped backward is the TPU-standard TWO-PASS design",
        "(`ops/attention.py` `_bwd_dq_kernel` + `_bwd_dkv_kernel` — dq",
        "accumulates across the inner k grid axis, so a fused one-pass",
        "bwd cannot be expressed under Mosaic's output-revisiting rule),",
        "which performs 7 attention matmuls per backward. Correcting the",
        "attention share by 7/5, the flash-heavy rows sit at ~0.6-0.7 of",
        "an honest roofline — consistent with the non-attention rows —",
        "rather than the raw 0.36-0.53. The armed kern_all sweeps",
        "measure the kernels directly and settle this.",
        "",
        "Decode-row caveat: the HLO cost model counts the weight buffers",
        "of the scanned decode step ONCE, not once per decode step, so",
        "`pred ms` badly underestimates a 128-step KV-cached decode and",
        "its `roofline frac` is not meaningful. The right floor is the",
        "weight-streaming arithmetic in BASELINE.md: ~1.4-1.5 GiB of",
        "non-embedding bf16 weights x 128 steps ≈ 220-240 ms at the",
        "819 GB/s spec. Measured 208 ms/step sits at ≈100% of that floor",
        "(within the weight-byte estimate's error) — the decode path is",
        "bandwidth-saturated; further decode gains need int8 weights",
        "(queued) or more batch rows, not kernel work.",
        "",
    ]
    out = pathlib.Path(args.out)
    out.write_text("\n".join(lines) + "\n")
    print("\n".join(lines))


if __name__ == "__main__":
    main()
