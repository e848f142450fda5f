"""Observability subsystem: what the program records about itself.

- `spine` — the ONE span recorder and the one run-scoped event schema.
  `spine.span(name, req=, wait=, **counts)` appends a record (id, parent,
  name, start/end on `spine.monotonic` in ns, request id, wait flag,
  counts) to a bounded in-memory buffer that is always on, and enters
  ``jax.profiler.TraceAnnotation(name)``, so the same span lies in a
  profiler trace on the device's clock whenever one is being taken.
  `spine.snapshot()` returns the buffer. ``APEX1_OBS_DIR`` is the one
  sink: with it set, counters and events (`MetricsLogger`,
  `ServingMetrics`, the resilience sentinel, the elastic episode) are
  appended to one JSONL file per process as they happen, and the spans
  of the buffer are written there when the run closes.
- `regions` — names for DEVICE time: `region("attn")` is a
  ``jax.named_scope`` of one fixed form (``~attn``) that lands in every
  instruction's ``op_name``, opened where the models, the Amp step and
  the engine's traced bodies write their work; `region_of(op_name)`
  reads it back. Metadata only: the lowered programs are the same text.
- `xspace` — reads the ``*.xplane.pb`` traces ``jax.profiler`` writes,
  with no dependency: per device the "XLA Ops" line alone, busy = the
  union of its intervals and idle = window − busy, a ``custom-call``
  keyed by its instruction name (the kernel, as `ops._common.kernel_call`
  named it), idle gaps put down to the innermost program span over their
  midpoint, and one module's device time by the program's regions,
  forward and backward (`by_region`: the path of an op comes from the
  program the profiler stored in the trace). `xspace.format_report`
  prints it.
- `calibrate` — fits correction factors from banked (predicted,
  measured) pairs; the repo ships no corpus, so every consumer prices
  "uncalibrated" (ROADMAP D1 removes it with the model it corrects).

See docs/observability.md for the span names, the schema and how to
read a chip trace.
"""

from apex1_tpu.obs import calibrate, regions, spine, xspace  # noqa: F401
from apex1_tpu.obs.spine import (ObsRun, StopWatch,  # noqa: F401
                                 default_run, emit, read_events)
from apex1_tpu.obs.xspace import (TraceError, build_report,  # noqa: F401
                                  parse_xspace, write_report)
