"""From a profiler trace (`.xplane.pb`) to numbers: the reduction every PR
shares, read with `jax.profiler.ProfileData` alone.

A device plane (`/device:TPU:<n>`) carries the lines "XLA Ops" (one event
per executed HLO instruction), "XLA Modules" (one per executed program)
and, where collectives run asynchronously, "Async XLA Ops". The host plane
carries the `TraceAnnotation` spans of the program (`serving/step`,
`serving/admit.register`, ...) and of the benchmark (`bench/window`,
`engine/step`, `loadgen`, `train/dispatch`) on the same clock. A span is
known by its FORM, `<word>/<word>[.<word>]` (and `loadgen`), not by a
list: one that a later PR adds to the program is read with no edit here.

- busy   = union of the "XLA Ops" intervals inside the window, per device,
           averaged over devices; idle share = 1 - busy / window.
- window = the host span `bench/window` where the device's ops fall inside
           it, else the extent of the device's own ops.
- an idle gap (no op running on device 0) is attributed to the innermost
  host span covering its midpoint (the window's own span apart), else to
  `host:other`.
- a Pallas kernel is a `custom-call` whose instruction carries the name
  the program gave it (`%apex1_flash_dq.7 = ... custom-call(...)`); it is
  labelled and summed under that name (`kernels`). The program's own
  report (`apex1_tpu/obs/xspace.py`, `op_key`) keeps the same rule; this
  is the benchmark's copy, which no PR that claims a gain can change.
- everything is counted INSIDE the window: an op or a program execution
  that straddles an edge counts by the part of it that lies inside, and
  `n_steps`, which every `*_per_step` divides by, is the sum of those
  shares over the main program's executions (5.00 where six executions
  touch a window that holds five steps of ops), not their number.
"""

from __future__ import annotations

import bisect
import collections
import functools
import glob
import os
import re
import statistics

SPAN_RE = re.compile(
    r"^(?:[A-Za-z0-9_]+/[A-Za-z0-9_]+(?:\.[A-Za-z0-9_]+)*|loadgen)$")
WINDOW_SPAN = "bench/window"
ENGINE_STEP_SPAN = "engine/step"
_MIN_GAP_NS = 2_000.0


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def _union(intervals):
    """Sorted, merged copy of [(start, end), ...]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(merged) -> float:
    return sum(e - s for s, e in merged)


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _subtract(a, b):
    """Parts of merged `a` not covered by merged `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@functools.lru_cache(maxsize=65536)
def _parse(name: str) -> tuple:
    """(opcode, first result shape, instruction name less its `.N`) of an
    HLO instruction text such as `%copy.3 = bf16[48,16]{1,0} copy(%p)` or
    `%x = (f32[8], s32[]) custom-call(...)`; a bare `%fusion.12` gives
    ("fusion", None, None)."""
    head, eq, rhs = name.partition(" = ")
    if not eq:
        base = name.strip().lstrip("%").split(" ")[0].split("(")[0]
        return re.sub(r"[.\-_]?\d+$", "", base) or name[:40], None, None
    rhs = rhs.lstrip()
    if rhs.startswith("("):
        depth, end = 0, len(rhs) - 1
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                end = i
                break
        shape_txt, rest = rhs[:end + 1], rhs[end + 1:]
    else:
        shape_txt, _, rest = rhs.partition(" ")
    instr = re.sub(r"\.\d+$", "", head.strip().lstrip("%"))
    m = re.match(r"\s*([\w\-]+)\(", rest)
    first = re.search(r"[a-z]+[0-9]*\[[^\]]*\]", shape_txt)
    return (m.group(1) if m else instr, first.group(0) if first else None,
            instr)


def kernel_name(name: str) -> str | None:
    """The name a Pallas kernel carries on its instruction
    (`%apex1_flash_dq.7 = ... custom-call(...)` -> `apex1_flash_dq`; tuple
    results alike), None for any other op and for a `custom-call` that XLA
    named itself (`%custom-call.7`)."""
    op, _, instr = _parse(name)
    if op == "custom-call" and instr and instr != "custom-call":
        return instr
    return None


def op_key(name: str) -> str:
    """A stable label for a device op: a named kernel's name, else opcode
    and first result shape, e.g. `copy_bf16_48_16_1151_64_`."""
    kernel = kernel_name(name)
    if kernel is not None:
        return kernel
    op, shape, _ = _parse(name)
    if shape is None:
        return op
    return f"{op}_" + re.sub(r"[^A-Za-z0-9]+", "_", shape).strip("_") + "_"


def opcode(name: str) -> str:
    return _parse(name)[0]


def _is_allreduce(name: str) -> bool:
    return opcode(name).startswith("all-reduce")


def load(path: str) -> dict:
    """{"devices": {plane: {line: [(name, start_ns, dur_ns)]}},
        "host": {span_name: [(start_ns, end_ns)]}} for every host event
    whose name has a span's form (`SPAN_RE`)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, host = {}, collections.defaultdict(list)
    is_span = functools.lru_cache(maxsize=None)(
        lambda name: SPAN_RE.match(name) is not None)
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                lines[line.name] = [(e.name, float(e.start_ns),
                                     float(e.duration_ns))
                                    for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if is_span(e.name):
                        host[e.name].append(
                            (float(e.start_ns),
                             float(e.start_ns) + float(e.duration_ns)))
    return {"devices": devices, "host": dict(host)}


def _covering(spans_by_name, t):
    best = None
    for name, (starts, spans) in spans_by_name.items():
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and spans[i][1] >= t:
            dur = spans[i][1] - spans[i][0]
            if best is None or dur < best[0]:
                best = (dur, name)
    return best[1] if best else "host:other"


def _inside(start: float, dur: float, lo: float, hi: float) -> float:
    """The part of [start, start + dur] that lies in [lo, hi], in ns."""
    return max(0.0, min(start + dur, hi) - max(start, lo))


def _shares(intervals, lo, hi) -> float:
    """Sum over (start, end) intervals of the share of each inside the
    window: whole ones count 1, one cut by an edge its part."""
    return sum(_inside(s, e - s, lo, hi) / (e - s)
               for s, e in intervals if e > s)


def reduce(path: str, *, top: int = 10) -> dict:
    return reduce_events(load(path), top=top)


def reduce_events(raw: dict, *, top: int = 10) -> dict:
    """Every number the per-layer readers take from a trace (`raw` as
    `load` gives it). Times in seconds unless the key says ms, counted
    inside the window; `*_per_step` divide by `n_steps`, the steps of the
    main program (the module with most device time) that the window
    holds."""
    planes = sorted(p for p in raw["devices"]
                    if raw["devices"][p].get("XLA Ops"))
    if not planes:
        return {"busy_s": 0.0, "window_s": 0.0, "n_devices": 0}
    host = raw["host"]
    out = {"n_devices": len(planes)}
    busy_s, windows = [], []
    for p in planes:
        ops = raw["devices"][p]["XLA Ops"]
        ivs = [(s, s + d) for _, s, d in ops]
        lo, hi = min(s for s, _ in ivs), max(e for _, e in ivs)
        if host.get(WINDOW_SPAN):
            w_lo, w_hi = host[WINDOW_SPAN][0]
            inside = sum(1 for s, e in ivs if s >= w_lo and e <= w_hi)
            if inside >= 0.5 * len(ivs):
                lo, hi = w_lo, w_hi
        windows.append((lo, hi))
        busy_s.append(_length(_union(_clip(ivs, lo, hi))) * 1e-9)
    out["busy_s"] = sum(busy_s) / len(busy_s)
    out["window_s"] = (windows[0][1] - windows[0][0]) * 1e-9
    out["idle_pct"] = 100.0 * (1.0 - out["busy_s"] / out["window_s"])

    # device 0 in detail
    lines = raw["devices"][planes[0]]
    lo, hi = windows[0]
    ops = [(n, s, d) for n, s, d in lines["XLA Ops"] if s + d > lo and s < hi]
    mods = [(n, s, d) for n, s, d in lines.get("XLA Modules", [])
            if s + d > lo and s < hi]
    by_mod = collections.defaultdict(list)
    for n, s, d in mods:
        by_mod[re.sub(r"\(.*$", "", n)].append((s, s + d))
    n_steps = 1.0
    if by_mod:
        main = max(by_mod, key=lambda k: sum(e - s for s, e in by_mod[k]))
        out["main_module"] = main
        out["n_executions"] = len(by_mod[main])
        n_steps = out["n_steps"] = _shares(by_mod[main], lo, hi) or 1.0
        out["step_device_ms_p50"] = statistics.median(
            e - s for s, e in by_mod[main]) * 1e-6
        out["modules"] = {k: [len(v), sum(e - s for s, e in v) * 1e-9]
                          for k, v in by_mod.items()}
    n_engine = _shares(host.get(ENGINE_STEP_SPAN, ()), lo, hi)
    out["n_engine_steps"] = n_engine

    totals = collections.defaultdict(float)
    kernels = {}
    cc = 0.0
    for n, s, d in ops:
        secs = _inside(s, d, lo, hi) * 1e-9
        totals[op_key(n)] += secs
        if opcode(n) == "custom-call":
            cc += secs
        kernel = kernel_name(n)
        if kernel is not None:
            row = kernels.setdefault(kernel, [0, 0.0])
            row[0] += 1
            row[1] += secs
    out["device_ops"] = [[k, v] for k, v in sorted(
        totals.items(), key=lambda kv: -kv[1])[:top]]
    out["kernels"] = {k: [calls, secs, 1e3 * secs / n_steps]
                      for k, (calls, secs) in sorted(kernels.items())}
    out["custom_call_s"] = cc
    out["custom_call_ms_per_step"] = 1e3 * cc / n_steps
    if n_engine:
        out["custom_call_ms_per_engine_step"] = 1e3 * cc / n_engine

    ar = [(s, s + d) for n, s, d in ops if _is_allreduce(n)]
    ar += [(s, s + d) for n, s, d in lines.get("Async XLA Ops", [])
           if _is_allreduce(n) and s + d > lo and s < hi]
    if ar:
        ar_u = _union(_clip(ar, lo, hi))
        other = _union(_clip([(s, s + d) for n, s, d in ops
                              if not _is_allreduce(n)], lo, hi))
        out["allreduce_ms_per_step"] = _length(ar_u) * 1e-6 / n_steps
        out["allreduce_exposed_ms_per_step"] = _length(
            _subtract(ar_u, other)) * 1e-6 / n_steps

    # idle gaps by what the host was doing
    busy = _union(_clip([(s, s + d) for _, s, d in ops], lo, hi))
    spans_by_name = {}
    for name, spans in host.items():
        if name != WINDOW_SPAN:
            spans = sorted(spans)
            spans_by_name[name] = ([s for s, _ in spans], spans)
    gaps = collections.defaultdict(float)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a >= _MIN_GAP_NS:
            gaps[_covering(spans_by_name, 0.5 * (a + b))] += (b - a) * 1e-9
    out["idle_gaps"] = [[k, v] for k, v in sorted(
        gaps.items(), key=lambda kv: -kv[1])[:top]]
    out["host_spans"] = {k: len(v) for k, v in sorted(host.items())}
    return out
