"""A kernel's share of its roofline: the least time the chip could take
for the operations and bytes a call asks of it, over the device time the
trace shows. The counts are the metric's own (`benchmark/layer_metrics/
kernel.<name>.roofline_pct.<class>.py`: logical operations at the
PUBLISHED widths, never a padded one, each operand read once and each
result written once); the peaks are `harness/peaks.json`'s row of the
device; the time is the trace's (`trace.reduce`, `kernels`).

A share cannot pass 100 %: one that does has its count too high or its
time too short, and is never clipped.
"""

from __future__ import annotations


def share_pct(ops: float, bytes_: float, seconds: float,
              peaks: dict) -> float:
    return 100.0 * max(ops / peaks["bf16_flops"],
                       bytes_ / peaks["hbm_bytes_per_s"]) / seconds


def kernel_share(ctx: dict, kernel: str, ops: float, bytes_: float):
    """The share for `kernel` over one step of the main program, from the
    run's context: `ops` and `bytes_` are what ONE step asks of all the
    kernel's calls together. None where there is nothing to read: an
    untraced run, no such kernel in the trace, or a device without a row
    of peaks (a CPU rehearsal)."""
    row = (ctx.get("trace") or {}).get("kernels", {}).get(kernel)
    peaks = ctx["device"]["peaks"]
    if row is None or peaks is None or row[2] <= 0:
        return None
    calls, seconds, ms_per_step = row
    share = share_pct(ops, bytes_, 1e-3 * ms_per_step, peaks)
    by = "operations" if ops / peaks["bf16_flops"] >= \
        bytes_ / peaks["hbm_bytes_per_s"] else "bytes"
    print(f"roofline: {kernel} {ops / 1e9:.3f} GFLOP and "
          f"{bytes_ / 1e6:.3f} MB a step over {ms_per_step:.4f} ms "
          f"({calls} calls, {seconds:.6f} s in the window) = "
          f"{ops / ms_per_step / 1e9:.2f} TFLOP/s, {share:.2f} % of the "
          f"roofline, bound by {by}", flush=True)
    return share
