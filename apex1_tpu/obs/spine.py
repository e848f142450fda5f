"""Telemetry spine — one run-scoped event schema for every subsystem.

Before this module, each measuring subsystem invented its own JSON
shape (bench records, `tune_kernels` sweep logs, serving lifecycle
events, sentinel diagnostics), so nothing could be joined across a run.
The spine fixes the SCHEMA and the SINK:

- A **run** is one process-level measurement context. Its events land
  in one JSONL file ``<dir>/<component>_<pid>_<t0>.jsonl``.
- Line 1 is the run header::

    {"schema": "apex1-obs-v1", "kind": "run", "run": "<id>",
     "component": "<argv0>", "pid": 1234, "t0_unix": 1759...,
     "meta": {...}}

- Every following line is one event::

    {"kind": "counter", "name": ..., "t": <s since t0>, "value": ...}
    {"kind": "event",   "name": ..., "t": ..., **fields}
    {"kind": "span",    "name": ..., "t": ..., "dur_s": ..., "id": ...,
     "parent": <id or null>, "req": ..., "wait": true, **counts}

  Extra keyword fields ride along verbatim (JSON-safe scalars only —
  the emitter does not fetch device arrays; callers hand host scalars).

Spans are the ONE way the program names a region of host time. `span`
appends one record to a bounded in-memory buffer (`SPAN_CAPACITY`, the
oldest dropped; always on: there is no switch) and enters
``jax.profiler.TraceAnnotation(name)``, so the same span lies in the
profiler's host plane, on the device trace's clock, whenever a trace is
being taken. `snapshot` returns the buffer; a run writes the spans that
began during it as ``span`` lines when it CLOSES (in memory first,
written at the end — a span costs two clock reads and an append, never
a write).

Durability contract: counters and events are APPENDED and flushed per
line, so a crash keeps every line that printed and at most the LAST
line can be torn (`read_events` skips unparseable lines); spans reach
the file at `close` only. Derived artifacts (trace reports, calibration
tables) use `resilience.manifest.atomic_write_json` instead — those are
rewritten whole.

Activation: the module-level `emit`/`default_run` helpers are inert
(no file, no I/O beyond one getenv) until ``APEX1_OBS_DIR`` is set.
`StopWatch` is the ONE cumulative wall-clock timer; the
`utils.observability.Timers` surface and `bench.timed_steps` sit on it.
"""

from __future__ import annotations

import atexit
import collections
import itertools
import json
import os
import re
import sys
import threading
import time
from typing import Any, Optional

from jax.profiler import TraceAnnotation   # the package imports jax anyway

SCHEMA = "apex1-obs-v1"

#: event kinds `emit` admits; a run adds the "run" header line and, at
#: `close`, the "span" lines of the buffer
KINDS = ("counter", "event")

monotonic = time.monotonic   # the ONE clock origin helper (see ObsRun)
monotonic_ns = time.monotonic_ns   # the same clock, as spans stamp it

#: span records the process keeps; the oldest is dropped for the newest
SPAN_CAPACITY = 1 << 15


def obs_dir() -> Optional[str]:
    """``APEX1_OBS_DIR`` when set and non-empty, else None (spine off)."""
    d = os.environ.get("APEX1_OBS_DIR", "").strip()
    return d or None


class StopWatch:
    """Cumulative named-timer primitive: ``start()`` / ``stop(sync=...)``.

    ``stop(sync=tree)`` blocks on the tree first so device work is
    attributed to the timed region (the `apex/transformer` ``timers``
    contract). Attributes ``elapsed_`` / ``count`` / ``last_s`` are
    public; `elapsed(reset=True)` reads-and-clears.
    """

    def __init__(self):
        self.elapsed_ = 0.0
        self.count = 0
        self.last_s: Optional[float] = None
        self._t0: Optional[float] = None

    def start(self) -> "StopWatch":
        self._t0 = time.perf_counter()
        return self

    def stop(self, sync: Any = None) -> float:
        if sync is not None:
            import jax           # lazy: the spine imports without jax
            jax.block_until_ready(sync)
        dt = time.perf_counter() - self._t0
        self.elapsed_ += dt
        self.count += 1
        self.last_s = dt
        self._t0 = None
        return dt

    def elapsed(self, reset: bool = False) -> float:
        e = self.elapsed_
        if reset:
            self.elapsed_, self.count = 0.0, 0
        return e


# -- spans: the one recorder ------------------------------------------------

_SPANS: collections.deque = collections.deque(maxlen=SPAN_CAPACITY)
_SPAN_IDS = itertools.count(1)
_OPEN = threading.local()      # .stack: this thread's open spans


class Span:
    """One record of the buffer, and the context manager that fills it.
    ``parent`` is the id of the span open around it in the SAME thread;
    ``wait`` marks time the host spent blocked on the device; ``counts``
    may be set until the span closes (a step knows what it did at its
    end). `start_ns`/`end_ns` are `monotonic_ns`."""

    __slots__ = ("id", "parent", "name", "start_ns", "end_ns", "req",
                 "wait", "counts", "_ann")

    def __init__(self, name: str, req=None, wait: bool = False,
                 counts: Optional[dict] = None):
        self.id = next(_SPAN_IDS)
        self.parent = None
        self.name = name
        self.start_ns = self.end_ns = 0
        self.req = req
        self.wait = wait
        self.counts = counts or {}

    def __enter__(self) -> "Span":
        try:
            stack = _OPEN.stack
        except AttributeError:
            stack = _OPEN.stack = []
        if stack:
            self.parent = stack[-1].id
        stack.append(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start_ns = monotonic_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = monotonic_ns()
        self._ann.__exit__(*exc)
        _OPEN.stack.pop()
        _SPANS.append(self)
        return False


def span(name: str, req=None, wait: bool = False, **counts) -> Span:
    """``with span("serving/step") as sp: ...; sp.counts["n"] = 3`` —
    time the block as one record of the buffer and, when a profiler
    trace is being taken, as a host event of the same name."""
    return Span(name, req, wait, counts)


def record_span(name: str, start_ns: int, end_ns: int, *, req=None,
                **counts) -> Span:
    """A span whose ends were stamped apart (the wait in a queue). It
    has no parent and is not in the profiler's trace: nothing was open
    around all of it."""
    sp = Span(name, req, False, counts)
    sp.start_ns, sp.end_ns = int(start_ns), int(end_ns)
    _SPANS.append(sp)
    return sp


def snapshot() -> list:
    """The buffer's records, in the order they closed."""
    return list(_SPANS)


def _component() -> str:
    base = os.path.basename(sys.argv[0] or "") or "python"
    base = re.sub(r"\.py$", "", base)
    return re.sub(r"[^A-Za-z0-9_.-]", "_", base) or "python"


#: per-process sequence folded into run ids — two runs opened in the
#: same second must not append into one file
_RUN_SEQ = itertools.count()


class ObsRun:
    """One run's event sink. Thread-safe; every counter and event is
    flushed so the file tails live, and `close()` (or leaving the
    ``with``) adds the spans that began during the run. The file is
    usable after the process dies mid-run, less its spans."""

    def __init__(self, dir: Optional[str] = None, *,
                 run_id: Optional[str] = None,
                 component: Optional[str] = None,
                 meta: Optional[dict] = None,
                 path: Optional[str] = None):
        self.component = component or _component()
        t0_unix = time.time()
        self.run_id = run_id or (f"{self.component}_{os.getpid()}_"
                                 f"{int(t0_unix)}_{next(_RUN_SEQ)}")
        if path is None:
            d = dir or obs_dir()
            if d is None:
                raise ValueError("ObsRun needs dir=, path=, or "
                                 "APEX1_OBS_DIR")
            os.makedirs(d, exist_ok=True)
            path = os.path.join(d, self.run_id + ".jsonl")
        self.path = path
        self._t0_ns = monotonic_ns()
        self._t0 = self._t0_ns * 1e-9
        self._lock = threading.Lock()
        self._f = open(path, "a", encoding="utf-8")
        self._write({"schema": SCHEMA, "kind": "run", "run": self.run_id,
                     "component": self.component, "pid": os.getpid(),
                     "t0_unix": round(t0_unix, 3),
                     "meta": dict(meta or {})})

    # -- sink --------------------------------------------------------------

    def _write(self, rec: dict) -> None:
        line = json.dumps(rec)
        with self._lock:
            if self._f.closed:
                return
            self._f.write(line + "\n")
            self._f.flush()

    def emit(self, kind: str, name: str, *, t: Optional[float] = None,
             **fields) -> None:
        if kind not in KINDS:
            raise ValueError(f"unknown event kind {kind!r}; one of {KINDS}")
        t = (time.monotonic() - self._t0) if t is None else t
        self._write({"kind": kind, "name": str(name),
                     "t": round(float(t), 6), **fields})

    def counter(self, name: str, value, **fields) -> None:
        self.emit("counter", name, value=value, **fields)

    def event(self, name: str, **fields) -> None:
        self.emit("event", name, **fields)

    def _span_lines(self) -> str:
        out = []
        for sp in snapshot():
            if sp.start_ns < self._t0_ns:
                continue
            rec = {"kind": "span", "name": sp.name,
                   "t": round((sp.start_ns - self._t0_ns) * 1e-9, 6),
                   "dur_s": round((sp.end_ns - sp.start_ns) * 1e-9, 6),
                   "id": sp.id, "parent": sp.parent}
            if sp.req is not None:
                rec["req"] = sp.req
            if sp.wait:
                rec["wait"] = True
            rec.update(sp.counts)
            out.append(json.dumps(rec) + "\n")
        return "".join(out)

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.write(self._span_lines())
                self._f.flush()
                try:
                    os.fsync(self._f.fileno())
                except OSError:
                    pass
                self._f.close()

    def __enter__(self) -> "ObsRun":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# -- module-level default run (the zero-threading integration path) --------
#
# Subsystems call `spine.emit(...)` unconditionally; with APEX1_OBS_DIR
# unset that is a no-op, with it set the process lazily opens ONE run
# (keyed on (pid, dir) so forks and env changes get fresh files).

_DEFAULT: dict = {"run": None, "key": None}
_DEFAULT_LOCK = threading.Lock()


def default_run() -> Optional[ObsRun]:
    """The process-wide run (lazily created iff ``APEX1_OBS_DIR`` is
    set), or None. Never raises — a broken obs dir must not take down
    the instrumented subsystem."""
    d = obs_dir()
    key = (os.getpid(), d)
    if _DEFAULT["key"] == key:
        return _DEFAULT["run"]
    with _DEFAULT_LOCK:
        if _DEFAULT["key"] == key:
            return _DEFAULT["run"]
        old = _DEFAULT["run"]
        run = None
        if d is not None:
            try:
                run = ObsRun(dir=d)
                atexit.register(run.close)   # its spans are written then
            except OSError:
                run = None
        _DEFAULT.update(run=run, key=key)
    if old is not None:
        try:
            old.close()
        except Exception:
            pass
    return _DEFAULT["run"]


def set_default_run(run: Optional[ObsRun]) -> None:
    """Install an explicit run as the process default (tests, tools
    that own their run). Pass None to clear."""
    with _DEFAULT_LOCK:
        _DEFAULT.update(run=run,
                        key=(os.getpid(), obs_dir()) if run else None)


def emit(kind: str, name: str, **fields) -> None:
    """Fire-and-forget emission through the default run. No-op when the
    spine is off; swallows I/O errors — instrumentation must never cost
    the instrumented path its result."""
    run = default_run()
    if run is None:
        return
    try:
        run.emit(kind, name, **fields)
    except Exception:
        pass


# -- reader ----------------------------------------------------------------

def read_events(path: str, *, kinds: Optional[tuple] = None) -> list[dict]:
    """Parse one run file back into a list of dicts (header included).
    Unparseable lines — the torn tail a crash can leave — are skipped,
    not fatal: the durability contract is per-line."""
    out = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if not isinstance(rec, dict):
                continue
            if kinds is not None and rec.get("kind") not in kinds:
                continue
            out.append(rec)
    return out
