"""A kernel's device time inside the MAIN program's executions alone.

`trace.reduce`'s `kernels` sums a kernel's calls over every program the
window holds. Where a kernel runs in the step AND in the prefill program
(a sparse layer's expert product), a count of what the STEPS asked of it
must be held against the time it took in the steps: this reads the trace
once more and keeps the kernel's events that lie inside an execution of
the main module.
"""

from __future__ import annotations

import bisect
import functools
import re

from benchmark.harness import trace as tr


@functools.lru_cache(maxsize=8)
def _inside_main(path: str, main: str, kernel: str):
    """``(calls, seconds)`` of ``kernel``'s events on device 0 that lie
    inside an execution of ``main`` and inside the window (an event cut by
    the window's edge counts by its part inside), or None without device
    events. The window is `trace.reduce_events`' own: the host span where
    the device's ops fall inside it, else the extent of the ops. The
    result is kept, not the trace: two metrics ask."""
    raw = tr.load(path)
    planes = sorted(p for p in raw["devices"]
                    if raw["devices"][p].get("XLA Ops"))
    if not planes:
        return None
    lines = raw["devices"][planes[0]]
    ops = lines["XLA Ops"]
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    window = raw["host"].get(tr.WINDOW_SPAN)
    if window:
        w_lo, w_hi = window[0]
        inside = sum(1 for _, s, d in ops if s >= w_lo and s + d <= w_hi)
        if inside >= 0.5 * len(ops):
            lo, hi = w_lo, w_hi
    runs = sorted((s, s + d) for n, s, d in lines.get("XLA Modules", [])
                  if re.sub(r"\(.*$", "", n) == main)
    starts = [s for s, _ in runs]
    calls, secs = 0, 0.0
    for n, s, d in ops:
        if tr.kernel_name(n) != kernel:
            continue
        mid = s + 0.5 * d
        i = bisect.bisect_right(starts, mid) - 1
        part = max(0.0, min(s + d, hi) - max(s, lo)) * 1e-9
        if i >= 0 and runs[i][1] >= mid and part > 0:
            calls += 1
            secs += part
    return calls, secs


def in_main_module(ctx: dict, kernel: str):
    """``[calls, seconds, ms a step]`` of ``kernel`` inside the main
    module's executions inside the window, the row that
    `roofline.kernel_share` reads; None where there is no trace, no such
    kernel in it, or no module line to tell the programs apart."""
    red = ctx.get("trace") or {}
    main, n_steps = red.get("main_module"), red.get("n_steps") or 0
    if not ctx.get("xplane") or main is None or n_steps <= 0 \
            or kernel not in red.get("kernels", {}):
        return None
    got = _inside_main(ctx["xplane"], main, kernel)
    if got is None or not got[0]:
        return None
    calls, secs = got
    return [calls, secs, 1e3 * secs / n_steps]
