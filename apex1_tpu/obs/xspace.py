"""Dependency-free XSpace trace parser + per-op attribution.

``jax.profiler.trace`` banks ``*.xplane.pb`` files — XSpace protobufs.
The stock decoders (``tensorflow.tsl...xplane_pb2`` et al.) drag a
multi-second TensorFlow import through import-location roulette that
differs per image. The XSpace wire format itself is tiny, so this
module reads it directly: a ~100-line protobuf wire-format walker over
the four message types we need, validated field-for-field against the
``xplane_pb2`` parse on this image (PR 10). No imports beyond stdlib —
usable from tests, tools, and the check_all smoke without jax or TF.

Field numbers (tensorflow/tsl/profiler/protobuf/xplane.proto)::

    XSpace:  planes = 1
    XPlane:  id = 1, name = 2, lines = 3, event_metadata = 4 (map),
             stat_metadata = 5 (map)
    XLine:   id = 1, name = 2, timestamp_ns = 3, events = 4,
             display_name = 11
    XEvent:  metadata_id = 1, offset_ps = 2 (from the line's
             timestamp), duration_ps = 3, stats = 4,
             num_occurrences = 5
    XEventMetadata: id = 1, name = 2, stats = 5
    XStatMetadata:  id = 1, name = 2
    XStat:   metadata_id = 1, uint64_value = 3, int64_value = 4,
             str_value = 5, bytes_value = 6, ref_value = 7 (the id of
             a stat_metadata entry whose NAME is the value)

and, for the programs the profiler stores (`by_region`), of
xla/service/hlo.proto: HloProto.hlo_module = 1;
HloModuleProto.computations = 3; HloComputationProto.instructions = 2;
HloInstructionProto {name = 1, metadata = 7, id = 35, operand_ids =
36}; OpMetadata.op_name = 2.

Every malformed input path (truncated varint, over-long length prefix,
unknown wire type, bad gzip, empty dir) raises the typed `TraceError` —
a corrupt banked trace yields a diagnosable error, never a traceback
from the middle of a byte walker (and never a silently-empty report).

Attribution model:

- **device rows** — on TPU/GPU traces, per-op events live on device
  planes (``/device:...``) in the "XLA Ops" line, and ONLY that line
  counts: "XLA Modules" (one event per executed program) and "Async
  XLA Ops" cover the same time again. On CPU-backend traces there is no device plane; the XLA
  runtime's per-op events live on the host plane's
  ``tf_XLATfrtCpuClient/...`` executor lines instead, and the report
  is labelled ``plane_class: "host-xla-proxy"`` — op *shares* are
  meaningful there, absolute times are host wall-clock (see
  docs/observability.md, "What CPU-proxy numbers mean").
- **buckets** — each op name lands in exactly one of ``collective``
  (the ICI ops: exposed-collective time is directly readable),
  ``pallas`` (custom-call/Mosaic kernels — the HLO cost model's blind
  spot), or ``xla`` (everything else). Name-based and best-effort, the
  rules are in `bucket_of`.
- **keys** — a ``custom-call`` is keyed by its instruction's name
  (``%apex1_flash_fwd.3 = ... custom-call(...)`` → ``apex1_flash_fwd``:
  the kernel, as `ops._common.kernel_call` named it, summed over its
  call sites in the program); every other op by its event name.
- **regions** — `obs.regions.region` scopes land in each compiled
  instruction's ``op_name``; the profiler stores every program it saw
  run as the ``Hlo Proto`` of the line-less ``/host:metadata`` plane,
  one entry a program under the name its executions carry on the "XLA
  Modules" line; an op event is joined to it by its instruction's name
  and its time summed by region and by forward or backward
  (`by_region`), for the ops inside one module's executions.
- **busy and idle** — events keep their offsets, so per device busy =
  the union of the op intervals inside the window and idle = window −
  busy (averaged over devices). The window is the host span
  ``window_span`` when one is named and most of the device's ops fall
  inside it, else the extent of the device's own ops. Each idle gap of
  the first device is put down to the innermost PROGRAM span (a host
  event named ``layer/region``, as `obs.spine.span` names them) over
  its midpoint, else to ``host:other``.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import os
import re
import zlib
from typing import Iterator, Optional

REPORT_SCHEMA = "apex1-trace-report-v1"
REPORT_NAME = "trace_report.json"

BUCKETS = ("pallas", "collective", "xla")

_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast|ppermute|send|recv)\b", re.I)
_PALLAS_RE = re.compile(
    r"(custom-call|custom_call|tpu_custom_call|pallas|mosaic)", re.I)
#: the opcode of an HLO instruction's text after " = ": the first word
#: followed by "(" that comes after the result shape's closing bracket
_OPCODE_RE = re.compile(r"[\]})] ([a-z][\w\-]*)\(")
_CUSTOM_CALL_RE = re.compile(r"^%?(.+?)(?:\.\d+)? = .* custom-call\(")
#: a host event that is a span of the program: `layer/region`
_SPAN_RE = re.compile(r"^[A-Za-z0-9_]+/[A-Za-z0-9_./]+$")
_MIN_GAP_NS = 2_000.0
#: the plane where the profiler stores the programs it saw run
METADATA_PLANE = "/host:metadata"


class TraceError(RuntimeError):
    """Typed failure for unreadable/corrupt/empty traces — callers get
    ``.path`` and ``.reason``, never a byte-walker traceback."""

    def __init__(self, path: str, reason: str):
        self.path = os.fspath(path)
        self.reason = reason
        super().__init__(f"unreadable trace at {self.path}: {reason}")


# -- protobuf wire-format walker -------------------------------------------

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = 0
    val = 0
    n = len(buf)
    while True:
        if i >= n:
            raise ValueError("truncated varint")
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7
        if shift > 70:
            raise ValueError("varint overlong")


def _fields(buf: bytes) -> Iterator[tuple[int, int, object]]:
    """Yield ``(field_no, wire_type, value)`` over one message's bytes.
    Length-delimited values come back as bytes; varints as ints."""
    i = 0
    n = len(buf)
    while i < n:
        key, i = _varint(buf, i)
        fno, wt = key >> 3, key & 7
        if wt == 0:                      # varint
            val, i = _varint(buf, i)
        elif wt == 2:                    # length-delimited
            ln, i = _varint(buf, i)
            if i + ln > n:
                raise ValueError("truncated length-delimited field")
            val = buf[i:i + ln]
            i += ln
        elif wt == 5:                    # fixed32
            if i + 4 > n:
                raise ValueError("truncated fixed32")
            val = buf[i:i + 4]
            i += 4
        elif wt == 1:                    # fixed64
            if i + 8 > n:
                raise ValueError("truncated fixed64")
            val = buf[i:i + 8]
            i += 8
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield fno, wt, val


@dataclasses.dataclass
class Event:
    metadata_id: int
    duration_ps: int
    occurrences: int        # num_occurrences when aggregated, else 1
    offset_ps: int = 0      # start, from the line's timestamp_ns
    stats: tuple = ()       # raw XStat messages, kept on the host's XLA
    #                         executor lines alone (`Plane.stats_of`)


@dataclasses.dataclass
class Line:
    name: str
    events: list            # [Event]
    timestamp_ns: int = 0


@dataclasses.dataclass
class Plane:
    name: str
    lines: list             # [Line]
    event_names: dict       # metadata_id -> op name
    stat_names: dict = dataclasses.field(default_factory=dict)
    #: on the `/host:metadata` plane, which has no lines: a program's
    #: name (as on the "XLA Modules" line) -> its `Hlo Proto`
    programs: dict = dataclasses.field(default_factory=dict)

    def stats_of(self, event: Event) -> dict:
        """``{stat name: value}`` of an event that kept its stats."""
        out = {}
        for raw in event.stats:
            key = val = None
            for fno, wt, v in _fields(raw):
                if fno == 1 and wt == 0:
                    key = v
                elif fno in (3, 4) and wt == 0:
                    val = v
                elif fno == 5 and wt == 2:
                    val = v.decode("utf-8", "replace")
                elif fno == 7 and wt == 0:
                    val = self.stat_names.get(v, "")
            out[self.stat_names.get(key, str(key))] = val
        return out


def _parse_event(buf: bytes, keep_stats: bool = False) -> Event:
    mid = dur = off = 0
    occ = 1
    stats = []
    for fno, wt, val in _fields(buf):
        if wt != 0:
            if keep_stats and fno == 4 and wt == 2:
                stats.append(val)
            continue
        if fno == 1:
            mid = val
        elif fno == 2:
            off = val
        elif fno == 3:
            dur = val
        elif fno == 5:
            occ = val
    return Event(metadata_id=mid, duration_ps=dur, occurrences=occ,
                 offset_ps=off, stats=tuple(stats))


def _parse_line(buf: bytes) -> Line:
    name = ""
    events = []
    t0 = 0
    for fno, wt, val in _fields(buf):
        if fno == 2 and wt == 2:
            name = val.decode("utf-8", "replace")
        elif fno == 3 and wt == 0:
            t0 = val
        elif fno == 4 and wt == 2:
            # the name (field 2) is written before the events
            events.append(_parse_event(val, name.startswith("tf_XLA")))
    return Line(name=name, events=events, timestamp_ns=t0)


def _parse_map_entry(buf: bytes) -> tuple[int, str, Optional[bytes]]:
    """``(key, name, bytes stat or None)`` of one entry of a plane's
    `event_metadata` or `stat_metadata` map."""
    key = 0
    name = ""
    blob = None
    for fno, wt, val in _fields(buf):
        if fno == 1 and wt == 0:
            key = val
        elif fno == 2 and wt == 2:       # XEventMetadata / XStatMetadata
            for f2, w2, v2 in _fields(val):
                if f2 == 2 and w2 == 2:
                    name = v2.decode("utf-8", "replace")
                elif f2 == 5 and w2 == 2:            # XStat
                    for f3, w3, v3 in _fields(v2):
                        if f3 == 6 and w3 == 2:
                            blob = v3
    return key, name, blob


def _parse_plane(buf: bytes) -> Plane:
    name = ""
    lines = []
    emeta: dict[int, str] = {}
    smeta: dict[int, str] = {}
    programs: dict[str, bytes] = {}
    for fno, wt, val in _fields(buf):
        if fno == 2 and wt == 2:
            name = val.decode("utf-8", "replace")
        elif fno == 3 and wt == 2:
            lines.append(_parse_line(val))
        elif fno == 4 and wt == 2:
            k, v, blob = _parse_map_entry(val)
            emeta[k] = v
            # the name (field 2) is written before the maps
            if blob is not None and name == METADATA_PLANE:
                programs[v] = blob
        elif fno == 5 and wt == 2:
            k, v, _ = _parse_map_entry(val)
            smeta[k] = v
    return Plane(name=name, lines=lines, event_names=emeta,
                 stat_names=smeta, programs=programs)


def parse_xspace(path: str | os.PathLike) -> list[Plane]:
    """Parse one ``*.xplane.pb`` (``.gz`` transparently) into planes.
    Raises `TraceError` on any unreadable/corrupt input."""
    path = os.fspath(path)
    try:
        with open(path, "rb") as f:
            data = f.read()
        if path.endswith(".gz"):
            data = gzip.decompress(data)
    # zlib.error: a valid gzip HEADER over a corrupt deflate body —
    # BadGzipFile alone misses it and the typed-error contract breaks
    except (OSError, gzip.BadGzipFile, EOFError, zlib.error) as e:
        raise TraceError(path, f"cannot read: {e}") from e
    planes = []
    try:
        for fno, wt, val in _fields(data):
            if fno == 1 and wt == 2:
                planes.append(_parse_plane(val))
    except ValueError as e:
        raise TraceError(path, f"corrupt protobuf: {e}") from e
    if not planes:
        raise TraceError(path, "no XPlane messages (empty or foreign file)")
    return planes


def find_xplane_files(trace_dir: str | os.PathLike) -> list[str]:
    """Every ``*.xplane.pb[.gz]`` under ``trace_dir`` (the layout
    ``jax.profiler.trace`` writes: ``plugins/profile/<ts>/...``)."""
    trace_dir = os.fspath(trace_dir)
    out = []
    for pat in ("*.xplane.pb", "*.xplane.pb.gz"):
        out += glob.glob(os.path.join(trace_dir, "**", pat),
                         recursive=True)
    return sorted(out)


# -- attribution -----------------------------------------------------------

def bucket_of(op_name: str) -> str:
    """``collective`` | ``pallas`` | ``xla`` for one op name.
    Name-based, best-effort: collectives first (a fused
    collective-permute must read as ICI time even if spelled inside a
    custom call wrapper), then the custom-call/Mosaic family, then
    everything else. On a chip the name is the whole HLO instruction
    (``%x = shape opcode(operands), attributes``): only its name and
    opcode are looked at, since operands and attributes name OTHER
    instructions (a fusion fed by ``%custom-call.3`` is not a kernel)."""
    head, eq, rest = op_name.partition(" = ")
    if eq:
        m = _OPCODE_RE.search(rest)
        op_name = f"{head} {m.group(1) if m else ''}"
    if _COLLECTIVE_RE.search(op_name):
        return "collective"
    if _PALLAS_RE.search(op_name):
        return "pallas"
    return "xla"


def op_key(op_name: str) -> str:
    """What an op's time is summed under: a ``custom-call``'s
    instruction name (the kernel), any other op's event name."""
    m = _CUSTOM_CALL_RE.match(op_name)
    return m.group(1) if m else op_name


_SHAPE_RE = re.compile(r"[a-z]+[0-9]*\[[^\]]*\]")


def op_label(op_name: str) -> str:
    """A label that sums an op over its call sites in a program: a
    kernel's name, else opcode and first result shape
    (``fusion_bf16_8_1024_1024_``; the benchmark's `op_key` reads the
    same); of a bare name (a CPU trace's), the name less its ``.N``."""
    key = op_key(op_name)
    if key != op_name:
        return key
    head, eq, rest = op_name.partition(" = ")
    if not eq:
        return re.sub(r"[.\-_]?\d+$", "", head.strip().lstrip("%"))
    op = _OPCODE_RE.search(rest)
    shape = _SHAPE_RE.search(rest)
    return (op.group(1) if op else "op") + "_" + re.sub(
        r"[^A-Za-z0-9]+", "_", shape.group(0) if shape else "").strip(
            "_") + "_"


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:")


def _is_op_line(line_name: str, *, device: bool) -> bool:
    if device:
        return line_name == "XLA Ops"
    # CPU backend: the XLA executor threads carry the per-op events
    return line_name.startswith("tf_XLA")


def _op_events(planes: list) -> tuple[list, str]:
    """``[(plane name, [(op name, start_ns, dur_ns, occurrences,
    dur_ps)])]`` per device and the plane class (times in whole
    nanoseconds, as the profiler's own reader gives them; ``dur_ps`` is
    what the totals sum): ``"device"`` (accelerator planes,
    "XLA Ops" alone) or ``"host-xla-proxy"`` (CPU backend: the executor
    threads together stand for one device — shares meaningful, absolute
    times are host wall-clock)."""
    for device in (True, False):
        per = collections.defaultdict(list)
        for plane in planes:
            if _is_device_plane(plane.name) != device:
                continue
            for line in plane.lines:
                if not _is_op_line(line.name, device=device):
                    continue
                for ev in line.events:
                    per[plane.name if device else "host"].append((
                        plane.event_names.get(ev.metadata_id,
                                              str(ev.metadata_id)),
                        line.timestamp_ns + ev.offset_ps // 1000,
                        ev.duration_ps // 1000,
                        max(int(ev.occurrences), 1), ev.duration_ps))
        if per:
            return sorted(per.items()), ("device" if device
                                         else "host-xla-proxy")
    return [], "none"


def op_totals(planes: list) -> tuple[dict, str]:
    """Aggregate per-op ``{key: [total_ps, count]}`` over the op lines
    (`op_key`). Returns ``(totals, plane_class)``."""
    devices, plane_class = _op_events(planes)
    return _totals(ev for _, evs in devices for ev in evs), plane_class


def _totals(events) -> dict:
    totals: dict[str, list] = {}
    for name, _start, _dur, n, dur_ps in events:
        a = totals.setdefault(name, [0, 0])
        a[0] += dur_ps
        a[1] += n
    return totals


def _host_spans(planes: list) -> dict:
    """``{name: [(start_ns, end_ns)]}`` of the host planes' program
    spans (and of any other host event: the window span is looked up
    here too)."""
    out = collections.defaultdict(list)
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                start = line.timestamp_ns + ev.offset_ps // 1000
                out[plane.event_names.get(ev.metadata_id, "")].append(
                    (start, start + ev.duration_ps // 1000))
    return out


def _union(intervals, lo, hi) -> list:
    """Merged, sorted copy of the intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals
                       if e > lo and s < hi):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _innermost(spans: dict, t: float) -> str:
    best = None
    for name, (starts, ivs) in spans.items():
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and ivs[i][1] >= t:
            dur = ivs[i][1] - ivs[i][0]
            if best is None or dur < best[0]:
                best = (dur, name)
    return best[1] if best else "host:other"


# -- regions ------------------------------------------------------------------

def _sub(buf: bytes, field: int) -> list:
    return [v for f, wt, v in _fields(buf) if f == field and wt == 2]


def _ints(wt: int, val) -> list:
    """A repeated int64 field's values: one varint, or a packed run."""
    if wt == 0:
        return [val]
    out, i = [], 0
    while i < len(val):
        x, i = _varint(val, i)
        out.append(x)
    return out


def _nearest_path(uid, rows: dict, step) -> str:
    """The first path met walking from ``uid`` by ``step`` (an id's
    users, or its operands), level by level."""
    seen, level = {uid}, [uid]
    while level:
        level = [n for u in level for n in step(u)
                 if n in rows and n not in seen and not seen.add(n)]
        for n in level:
            if rows[n][1]:
                return rows[n][1]
    return ""


def instruction_paths(hlo_proto: bytes) -> dict:
    """``{instruction name: op_name}`` of one stored program
    (`Plane.programs`), every computation of it. An instruction the
    COMPILER made carries no path (a prefetch's ``copy-start`` /
    ``copy-done``, a sliced one's ``slice-done`` and the
    ``ConcatBitcast`` behind it, a layout ``copy``): it takes the path of
    what it is FOR, its nearest user with one, else of its nearest
    operand with one."""
    out = {}
    for module in _sub(hlo_proto, 1):
        for comp in _sub(module, 3):
            rows = {}                   # id -> (name, path, operand ids)
            for instr in _sub(comp, 2):
                name = path = ""
                uid, operands = None, []
                for f, wt, v in _fields(instr):
                    if f == 1 and wt == 2:
                        name = v.decode("utf-8", "replace")
                    elif f == 7 and wt == 2:
                        for f2, wt2, v2 in _fields(v):
                            if f2 == 2 and wt2 == 2:
                                path = v2.decode("utf-8", "replace")
                    elif f == 35 and wt == 0:
                        uid = v
                    elif f == 36:
                        operands += _ints(wt, v)
                # a path is the program's where a primitive lies under
                # a scope (`jit(step)/.../mul`); a layout copy named
                # after its parameter, a reducer's bare `add` are not
                rows[uid] = (name, path if "/" in path else "", operands)
            users = collections.defaultdict(list)
            for uid, (_, _, operands) in rows.items():
                for o in operands:
                    users[o].append(uid)
            for uid, (name, path, _) in rows.items():
                out[name] = (path
                             or _nearest_path(uid, rows, users.__getitem__)
                             or _nearest_path(uid, rows,
                                              lambda u: rows[u][2]))
    return out


def _module_of(name: str) -> str:
    return re.sub(r"\(.*$", "", name)


def _module_ops(planes: list, host: dict, window_span, module):
    """``(module, executions, [[(event name, program, start_ns, dur_ns)]
    a line], (lo, hi))``: the ops inside the window and inside an
    execution of ``module`` (None: the module of most device time). A
    device plane tells its programs apart by the "XLA Modules" line (an
    op belongs to the execution over its midpoint); a CPU trace's ops,
    on the host plane's ``tf_XLA`` lines, name their program themselves
    (``hlo_module``, ``program_id``) and their execution (``run_id``).
    None where the trace does neither."""
    lines, runs = [], []
    for plane in sorted((p for p in planes if _is_device_plane(p.name)),
                        key=lambda p: p.name):
        ops = [ln for ln in plane.lines if ln.name == "XLA Ops"]
        if not ops:
            continue
        ns = lambda ln, ev: (
            plane.event_names.get(ev.metadata_id, str(ev.metadata_id)),
            ln.timestamp_ns + ev.offset_ps // 1000, ev.duration_ps // 1000)
        runs = [ns(ln, ev) for ln in plane.lines
                if ln.name == "XLA Modules" for ev in ln.events]
        lines = [[ns(ln, ev) for ev in ln.events] for ln in ops]
        break                           # the first device, as idle gaps
    device = bool(lines)
    if not device:
        for plane in planes:
            if not plane.name.startswith("/host:"):
                continue
            for ln in plane.lines:
                if not _is_op_line(ln.name, device=False):
                    continue
                evs = []
                for ev in ln.events:
                    st = plane.stats_of(ev) if ev.stats else {}
                    if "hlo_op" in st and "program_id" in st:
                        evs.append((
                            plane.event_names.get(ev.metadata_id, ""),
                            ln.timestamp_ns + ev.offset_ps // 1000,
                            ev.duration_ps // 1000,
                            f"{st.get('hlo_module')}({st['program_id']})",
                            st.get("run_id")))
                if evs:
                    lines.append(evs)
    flat = [ev for evs in lines for ev in evs]
    if not flat or (device and not runs):
        return None
    lo = min(ev[1] for ev in flat)
    hi = max(ev[1] + ev[2] for ev in flat)
    if window_span and host.get(window_span):
        w_lo, w_hi = host[window_span][0]
        if sum(1 for ev in flat if ev[1] >= w_lo
               and ev[1] + ev[2] <= w_hi) >= 0.5 * len(flat):
            lo, hi = w_lo, w_hi
    inside = lambda s, d: s + d > lo and s < hi
    progs = ([(n, d) for n, s, d in runs if inside(s, d)] if device
             else [(ev[3], ev[2]) for ev in flat])
    if module is None:
        secs = collections.Counter()
        for n, d in progs:
            secs[_module_of(n)] += d
        module = secs.most_common(1)[0][0]
    if device:
        runs = sorted((s, s + d, n) for n, s, d in runs
                      if _module_of(n) == module and inside(s, d))
        starts = [s for s, _, _ in runs]
        executions = sum((min(e, hi) - max(s, lo)) / (e - s)
                         for s, e, _ in runs if e > s)
        kept = []
        for n, s, d in flat:
            i = bisect.bisect_right(starts, s + 0.5 * d) - 1
            if i >= 0 and runs[i][1] >= s + 0.5 * d and inside(s, d):
                kept.append((n, runs[i][2], s, d))
        return module, executions, [kept], (lo, hi)
    keep = lambda ev: _module_of(ev[3]) == module and inside(ev[1], ev[2])
    executions = float(len({ev[4] for ev in flat if keep(ev)}))
    return module, executions, [
        [(ev[0], ev[3], ev[1], ev[2]) for ev in evs if keep(ev)]
        for evs in lines], (lo, hi)


def _own_time(events: list, lo, hi) -> list:
    """``[(event, ns)]``: each event's part inside [lo, hi] less the
    parts of the events nested in it, over one line's events (apart or
    properly nested, as a device's or a thread's are), so that the sum is
    the line's busy time."""
    events = sorted(events, key=lambda ev: (ev[2], -ev[3]))
    parts = [max(0, min(ev[2] + ev[3], hi) - max(ev[2], lo))
             for ev in events]
    own = list(parts)
    stack = []                          # (end, index) of the events open
    for i, ev in enumerate(events):
        while stack and stack[-1][0] <= ev[2]:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= parts[i]
        stack.append((ev[2] + ev[3], i))
    return list(zip(events, own))


def by_region(planes: list, *, window_span: Optional[str] = None,
              module: Optional[str] = None, top: int = 3):
    """Device time of ONE module's executions inside the window, by the
    program's regions (`obs.regions`): ``{"module", "executions",
    "busy_s", "regions": {region: {"fwd_s", "bwd_s", "top": [[op key,
    s], ...]}}, "unattributed": {"s", "top"}}``, regions + unattributed
    = busy. ``module`` None: the one of most device time (a serving
    trace's decode step, a training trace's step). None where the trace
    does not tell its programs apart or stores no program of that name;
    a program that opened no scope gives every op unattributed."""
    from apex1_tpu.obs.regions import REGIONS, region_of
    # the host's spans are read for the window's alone
    got = _module_ops(planes, _host_spans(planes) if window_span else {},
                      window_span, module)
    if got is None:
        return None
    module, executions, lines, (lo, hi) = got
    paths = {name: instruction_paths(proto)
             for plane in planes for name, proto in plane.programs.items()
             if _module_of(name) == module}
    if not paths:
        return None
    secs = collections.defaultdict(lambda: [0.0, 0.0])
    tops = collections.defaultdict(collections.Counter)
    read = {}       # (program, event name) -> (region, backward?, label)
    for events in lines:
        for (name, prog, _, _), ns in _own_time(events, lo, hi):
            if (prog, name) not in read:    # once an instruction
                table = paths.get(prog) or next(iter(paths.values()))
                instr = name.partition(" = ")[0].strip().lstrip("%")
                region, phase = region_of(table.get(instr)) or (None, "fwd")
                read[prog, name] = region, phase == "bwd", op_label(name)
            region, bwd, label = read[prog, name]
            secs[region][bwd] += ns * 1e-9
            tops[region][label] += ns * 1e-9
    ranked = lambda r: [[k, v] for k, v in tops[r].most_common(top)]
    return {"module": module, "executions": executions,
            "busy_s": sum(a + b for a, b in secs.values()),
            "regions": {r: {"fwd_s": secs[r][0], "bwd_s": secs[r][1],
                            "top": ranked(r)}
                        for r in REGIONS if r in secs},
            "unattributed": {"s": sum(secs[None]), "top": ranked(None)}}


def build_report(trace_dir: str | os.PathLike, *,
                 steps: Optional[int] = None,
                 top: int = 200,
                 window_span: Optional[str] = None,
                 module: Optional[str] = None) -> dict:
    """Per-op device-time breakdown, busy and idle time, and the idle
    gaps by program span, for one banked trace directory (or one
    ``*.xplane.pb[.gz]`` file).

    ``by_region`` (None where the trace does not tell its programs
    apart) is `by_region` of ``module``, else of the module of most
    device time.

    Raises `TraceError` when the dir holds no xplane files, none
    parses, or no op events were found (an empty report would read as
    "nothing ran" when the truth is "nothing was attributable")."""
    trace_dir = os.fspath(trace_dir)
    paths = ([trace_dir] if os.path.isfile(trace_dir)
             else find_xplane_files(trace_dir))
    if not paths:
        raise TraceError(trace_dir, "no *.xplane.pb files under dir")
    planes = []
    for p in paths:
        planes += parse_xspace(p)
    devices, plane_class = _op_events(planes)
    if not devices:
        lines = sorted({(pl.name, ln.name)
                        for pl in planes for ln in pl.lines})
        raise TraceError(
            trace_dir, "no per-op events on any known op line; "
            f"planes/lines seen: {lines[:12]}")
    host = _host_spans(planes)

    # per device: the window, the ops inside it, busy = their union
    busy_ns, windows, inside = [], [], []
    for _, evs in devices:
        lo = min(ev[1] for ev in evs)
        hi = max(ev[1] + ev[2] for ev in evs)
        if window_span and host.get(window_span):
            w_lo, w_hi = host[window_span][0]
            n_in = sum(1 for ev in evs
                       if ev[1] >= w_lo and ev[1] + ev[2] <= w_hi)
            if n_in >= 0.5 * len(evs):
                lo, hi = w_lo, w_hi
        evs = [ev for ev in evs if ev[1] + ev[2] > lo and ev[1] < hi]
        windows.append((lo, hi))
        inside.append(evs)
        busy_ns.append(sum(e - s for s, e in _union(
            [(ev[1], ev[1] + ev[2]) for ev in evs], lo, hi)))
    busy_s = sum(busy_ns) / len(busy_ns) * 1e-9
    window_s = (windows[0][1] - windows[0][0]) * 1e-9

    # idle gaps of the first device, by the innermost program span
    lo, hi = windows[0]
    spans = {name: ([s for s, _ in ivs], ivs)
             for name, ivs in ((n, sorted(v)) for n, v in host.items()
                               if _SPAN_RE.match(n))}
    gaps = collections.defaultdict(float)
    edges = [lo] + [t for iv in _union(
        [(ev[1], ev[1] + ev[2]) for ev in inside[0]], lo, hi)
        for t in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a >= _MIN_GAP_NS:
            gaps[_innermost(spans, 0.5 * (a + b))] += (b - a) * 1e-9

    raw = _totals(ev for evs in inside for ev in evs)
    totals: dict[str, list] = {}
    bucket_by_key = {}
    for name, (ps, n) in raw.items():
        key = op_key(name)
        a = totals.setdefault(key, [0, 0])
        a[0] += ps
        a[1] += n
        bucket_by_key[key] = bucket_of(name)
    total_ps = sum(ps for ps, _n in totals.values())
    buckets = {b: 0 for b in BUCKETS}
    ops = []
    for key, (ps, n) in sorted(totals.items(), key=lambda kv: -kv[1][0]):
        b = bucket_by_key[key]
        buckets[b] += ps
        ops.append({"name": key, "bucket": b,
                    "ms": round(ps / 1e9, 6), "count": int(n),
                    "share": round(ps / total_ps, 4) if total_ps else 0.0})
    report = {
        "schema": REPORT_SCHEMA,
        "trace_dir": trace_dir,
        "plane_class": plane_class,
        "n_devices": len(devices),
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_s": window_s - busy_s,
        "idle_gaps": [[k, v] for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1])],
        "total_op_ms": round(total_ps / 1e9, 6),
        "buckets": {b: {"ms": round(buckets[b] / 1e9, 6),
                        "share": (round(buckets[b] / total_ps, 4)
                                  if total_ps else 0.0)}
                    for b in BUCKETS},
        "n_ops": len(ops),
        "ops": ops[:top],
        "by_region": by_region(planes, window_span=window_span,
                               module=module),
    }
    if steps:
        report["steps"] = int(steps)
        report["per_step_ms"] = round(total_ps / 1e9 / steps, 6)
    return report


def write_report(trace_dir: str | os.PathLike, *,
                 report: Optional[dict] = None,
                 steps: Optional[int] = None,
                 path: Optional[str] = None) -> str:
    """Build (unless given) and atomically persist the report NEXT TO
    the trace it describes (``<trace_dir>/trace_report.json``), so a
    banked ``profile_artifact`` directory carries its own breakdown."""
    from apex1_tpu.resilience.manifest import atomic_write_json

    if report is None:
        report = build_report(trace_dir, steps=steps)
    if path is None:
        path = os.path.join(os.fspath(trace_dir), REPORT_NAME)
    atomic_write_json(path, report)
    return path


def format_report(report: dict, top: int = 25) -> str:
    """Human-readable rendering."""
    lines = [f"plane class: {report['plane_class']}   "
             f"total op time: {report['total_op_ms']:.3f} ms"
             + (f"   ({report['per_step_ms']:.3f} ms/step x "
                f"{report['steps']})" if report.get("steps") else "")]
    lines.append(
        f"{report['n_devices']} device(s): busy {report['busy_s']:.6f} s, "
        f"idle {report['idle_s']:.6f} s of {report['window_s']:.6f} s; "
        "idle gaps: " + (", ".join(
            f"{k} {v:.6f} s" for k, v in report["idle_gaps"][:8])
            or "none"))
    bk = report["buckets"]
    lines.append("buckets: " + "  ".join(
        f"{b}={bk[b]['ms']:.3f}ms ({bk[b]['share'] * 100:.1f}%)"
        for b in BUCKETS))
    for op in report["ops"][:top]:
        lines.append(f"{op['ms']:10.3f} ms {op['count']:6d}x "
                     f"{op['share'] * 100:5.1f}%  [{op['bucket']:10s}] "
                     f"{op['name'][:100]}")
    reg = report.get("by_region")
    if reg:
        n = reg["executions"] or 1.0
        lines.append(
            f"by region, ms an execution of {reg['module']} "
            f"({reg['executions']:.4f} inside the window; busy "
            f"{reg['busy_s'] * 1e3 / n:.4f}):")
        rows = [(r, v["fwd_s"], v["bwd_s"], v["top"])
                for r, v in reg["regions"].items()]
        rows.append(("unattributed", reg["unattributed"]["s"], 0.0,
                     reg["unattributed"]["top"]))
        for r, fwd, bwd, ranked in rows:
            lines.append(
                f"  {r:13s} fwd {fwd * 1e3 / n:9.4f}  bwd "
                f"{bwd * 1e3 / n:9.4f}   " + ", ".join(
                    f"{k} {v * 1e3 / n:.4f}" for k, v in ranked))
    return "\n".join(lines)
