"""Tracing / profiling / metrics — SURVEY.md §5.1, §5.5.

Reference: ``apex.pyprof`` monkey-patched every torch callable with
``torch.cuda.nvtx.range_push(json_args)`` so nsys timelines carry op names,
and post-processed profiler SQLite into per-kernel FLOPs/bytes
(``pyprof/prof``). ``apex/transformer`` threads an optional ``timers``
callable through the pipeline schedules.

TPU-native equivalents:
- a region of DEVICE work is named by `obs.regions.region` (≙ nvtx
  ranges: the name lands in XLA's HLO metadata and, with the op, in the
  profiler's trace); a region of HOST time by `obs.spine.span`. Never
  both at once: a host span around traced code times the tracing, once.
- `trace` — context manager around ``jax.profiler.start_trace`` writing a
  TensorBoard-loadable trace (≙ running under nsys).
- `cost_analysis` — compile-time FLOPs/bytes attribution from XLA
  (≙ pyprof/prof's per-kernel FLOP counting, but exact and free).
- `Timers` — named wall-clock timers with device sync, the
  ``apex/transformer`` ``timers`` contract.
- `MetricsLogger` — per-step structured metrics (loss, grad-norm,
  loss-scale, skip-count, tokens/sec/chip — the BASELINE.json metric).

Since PR 10 both sit on the telemetry spine (`apex1_tpu.obs.spine`):
`Timers` is a thin adapter over the spine's `StopWatch` span primitive
(the ONE host-side timing implementation — serving and bench use the
same one), and `MetricsLogger` keeps its public surface but MIRRORS
every record into the run-scoped JSONL sink when ``APEX1_OBS_DIR`` is
set, so the examples' training loops join the same event stream as
bench/tuning/serving without touching their call sites.
"""

from __future__ import annotations

import contextlib
import json
import time
from typing import Any, Callable, Optional

import jax
import numpy as np

from apex1_tpu.obs import spine


@contextlib.contextmanager
def trace(logdir: str):
    """Capture a TensorBoard profiler trace of the enclosed block."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def cost_analysis(fn: Callable, *args, **kwargs) -> dict:
    """Compile ``fn`` (without running it) and return XLA's cost model:
    ``{"flops": ..., "bytes accessed": ..., "transcendentals": ...}``."""
    lowered = jax.jit(fn).lower(*args, **kwargs)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    return dict(ca) if ca else {}


def flops_per_step(fn: Callable, *args, **kwargs) -> float:
    return float(cost_analysis(fn, *args, **kwargs).get("flops", 0.0))


class Timers:
    """Named cumulative timers (``timers("fwd").start()/.stop()``) — the
    calling convention ``apex/transformer`` schedules expect. ``stop``
    blocks on ``sync`` trees so device work is attributed correctly.
    Each timer IS a spine `StopWatch` (same primitive as
    `bench.timed_steps` and the serving clock), and ``log`` mirrors the
    read-out as spine counters when ``APEX1_OBS_DIR`` is set."""

    #: the spine primitive, re-exported under the historical name
    _Timer = spine.StopWatch

    def __init__(self):
        self._timers: dict[str, spine.StopWatch] = {}

    def __call__(self, name: str) -> spine.StopWatch:
        return self._timers.setdefault(name, spine.StopWatch())

    def log(self, names=None, *, reset: bool = True) -> dict[str, float]:
        names = list(self._timers) if names is None else names
        out = {}
        for n in names:
            if n not in self._timers:
                continue
            t = self._timers[n]
            count = t.count
            out[n] = t.elapsed(reset=reset)
            spine.emit("counter", f"timer.{n}", value=round(out[n], 6),
                       count=count)
        return out


class MetricsLogger:
    """Structured per-step metrics with tokens/sec/chip derivation.

    ``log(step, metrics, tokens=...)`` fetches scalars (one small transfer)
    and emits a JSON line via ``print`` or a supplied writer. Every
    record is ALSO mirrored into the telemetry spine's run file when
    ``APEX1_OBS_DIR`` is set (kind ``event``, name ``metrics``) — the
    training loops, serving lifecycle, and bench records then share one
    joinable stream (docs/observability.md)."""

    def __init__(self, writer: Optional[Callable[[str], None]] = None,
                 n_chips: Optional[int] = None):
        self.writer = writer or print
        self.n_chips = n_chips or jax.device_count()
        self._last_t: Optional[float] = None
        self._last_step: Optional[int] = None

    def log(self, step: int, metrics: dict, *,
            tokens: Optional[int] = None,
            _obs_name: Optional[str] = "metrics") -> dict:
        # _obs_name: spine event name for the mirror; None = caller
        # already emitted a structured spine event for this record
        # (serving.ServingMetrics) — suppress the generic one
        now = time.perf_counter()
        rec = {"step": int(step)}
        for k, v in metrics.items():
            if isinstance(v, (str, bool)):
                rec[k] = v
                continue
            try:
                arr = np.asarray(jax.device_get(v))
                if arr.size == 1 and arr.dtype != object:
                    rec[k] = float(arr)
                elif arr.dtype != object:
                    rec[k] = arr.tolist()  # vectors go in whole
                else:
                    raise TypeError("non-array metric")
            except (TypeError, ValueError):
                # arbitrary pytrees (e.g. train-step aux) — keep a
                # readable form rather than crashing or dropping the key
                rec[k] = repr(v)[:500]
        if tokens is not None and self._last_t is not None:
            dt = now - self._last_t
            steps = step - (self._last_step or 0)
            if dt > 0 and steps > 0:
                rec["tokens_per_sec_per_chip"] = (
                    tokens * steps / dt / self.n_chips)
        self._last_t, self._last_step = now, step
        self.writer(json.dumps(rec))
        if _obs_name:
            spine.emit("event", _obs_name, **rec)
        return rec
