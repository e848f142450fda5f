"""The device's time by the PROGRAM's regions, forward and backward.

The program opens `jax.named_scope("~<region>")` where its work is
written (`apex1_tpu/obs/regions.py`); the scope lands in each compiled
instruction's `op_name`
(`jit(step)/transpose(jvp(GPT2))/h3/~attn/qkv/dot_general`). The
profiler stores every program it saw run, as the `Hlo Proto` of a plane
without lines, `/host:metadata`, one entry a program under the name the
"XLA Modules" line gives its executions (`jit_step(12)`): instruction
name -> `metadata.op_name` comes from there, and an "XLA Ops" event is
joined to it by its instruction's name (`%fusion.12 = ...`). This is the
benchmark's own copy of `region_of` and of the path's source, as
`trace.py` keeps its own `op_key`: no PR that claims a gain can change
what a region metric reads.

An op counts by its part INSIDE the window and INSIDE an execution of the
main module (`ctx["trace"]["main_module"]`), the rule of
`step_kernels.py`; where ops nest (a loop's body inside its `while`) each
counts its own time alone, so regions + unattributed = the main module's
busy time. A CPU trace has no device plane: its ops lie on the host's
`tf_XLA...` lines and say their program themselves (`program_id`), and
the reduction is the same, for the tests' rehearsals.
"""

from __future__ import annotations

import bisect
import collections
import functools
import re

from benchmark.harness import trace as tr

REGIONS = ("embed", "attn", "mixer", "ffn", "norm", "head", "amp", "optim",
           "engine")
_SEGMENT = re.compile(r"(?:^|[/(])~([a-z]+)(?=[/)]|$)")
METADATA_PLANE = "/host:metadata"


def region_of(op_name):
    """``(region, phase)``: the innermost `~<region>` segment of an
    instruction's path, `bwd` where the path went through `transpose(`,
    else `fwd`; None for a path without one."""
    found = _SEGMENT.findall(op_name or "")
    if not found or found[-1] not in REGIONS:
        return None
    return found[-1], ("bwd" if "transpose(" in op_name else "fwd")


# ---- the path's source: the programs the profiler stored -----------------

def _varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7


def _fields(buf):
    """``(field, wire type, value)`` over one protobuf message: a varint
    as an int, a length-delimited field as its bytes (not walked: a
    message of no interest costs its length prefix), fixed ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            val = bytes(buf[i:i + ln])
            i += ln
        elif wt in (1, 5):
            val = None
            i += 8 if wt == 1 else 4
        else:
            raise ValueError(f"wire type {wt}")
        yield key >> 3, wt, val


def _sub(buf, field):
    return [v for f, wt, v in _fields(buf) if f == field and wt == 2]


def _ints(wt, v):
    """A repeated int64 field's values: one varint, or a packed run."""
    if wt == 0:
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
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


def _instruction_paths(hlo_proto: bytes) -> dict:
    """``{instruction name: op_name}`` of one stored program, every
    computation of it (a fusion's body too: names are unique in a
    module). An instruction the COMPILER made carries no path (a
    prefetch's `copy-start` / `copy-done`, a sliced one's `slice-done`
    and the `ConcatBitcast` behind it, a layout `copy`): it takes the
    path of what it is FOR, its nearest user with one, else of its
    nearest operand with one. `HloProto.hlo_module` = 1,
    `HloModuleProto.computations` = 3, `HloComputationProto.instructions`
    = 2, `HloInstructionProto` {name 1, metadata 7, id 35, operand_ids
    36}, `OpMetadata.op_name` = 2."""
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


def stored_programs(path: str) -> dict:
    """``{"jit_step(12)": bytes of its Hlo Proto}`` from the trace file's
    `/host:metadata` plane (`XSpace.planes` = 1; `XPlane` {name 2,
    event_metadata 4: a map entry {key 1, value 2}}; `XEventMetadata`
    {name 2, stats 5}; `XStat.bytes_value` = 6)."""
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            data = f.read()
    else:
        with open(path, "rb") as f:
            data = f.read()
    out = {}
    for plane in _sub(data, 1):
        entries, name = [], ""
        for f, wt, v in _fields(plane):
            if f == 2 and wt == 2:
                name = v.decode("utf-8", "replace")
            elif f == 4 and wt == 2:
                entries.append(v)
        if name != METADATA_PLANE:
            continue
        for entry in entries:
            for meta in _sub(entry, 2):
                prog, proto = "", None
                for f, wt, v in _fields(meta):
                    if f == 2 and wt == 2:
                        prog = v.decode("utf-8", "replace")
                    elif f == 5 and wt == 2:
                        proto = next(iter(_sub(v, 6)), proto)
                if prog and proto:
                    out[prog] = proto
    return out


# ---- the ops of the main module ------------------------------------------

def _module(name: str) -> str:
    return re.sub(r"\(.*$", "", name)


def _instruction(event_name: str) -> str:
    """`fusion.12` of `%fusion.12 = bf16[8]{0} fusion(...)` or of the
    bare `fusion.12` a CPU trace gives."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def _heaviest(programs) -> str:
    """The module of most time among ``(program name, duration)``."""
    secs = collections.Counter()
    for name, dur in programs:
        secs[_module(name)] += dur
    return secs.most_common(1)[0][0]


def _window(ops, host):
    """`trace.reduce_events`' window: the host span where the ops fall
    inside it, else the extent of the ops. ``ops``: (start, dur)."""
    lo = min(s for s, _ in ops)
    hi = max(s + d for s, d in ops)
    if host.get(tr.WINDOW_SPAN):
        w_lo, w_hi = host[tr.WINDOW_SPAN][0]
        if sum(1 for s, d in ops
               if s >= w_lo and s + d <= w_hi) >= 0.5 * len(ops):
            lo, hi = w_lo, w_hi
    return lo, hi


def _device_ops(raw: dict, main):
    """``(main, n_steps, [[(event name, program, start, dur)] a line],
    (lo, hi))`` from the first device plane of ``raw`` (as `trace.load`
    gives it), ops inside an execution of ``main`` (None: the module of
    most device time); None without a device plane that tells its
    programs apart."""
    planes = sorted(p for p in raw["devices"]
                    if raw["devices"][p].get("XLA Ops"))
    if not planes:
        return None
    lines = raw["devices"][planes[0]]
    ops = lines["XLA Ops"]
    lo, hi = _window([(s, d) for _, s, d in ops], raw["host"])
    mods = [(n, s, d) for n, s, d in lines.get("XLA Modules", [])
            if s + d > lo and s < hi]
    if not mods:
        return None
    main = main or _heaviest((n, d) for n, _, d in mods)
    runs = sorted((s, s + d, n) for n, s, d in mods if _module(n) == main)
    starts = [s for s, _, _ in runs]
    n_steps = tr._shares([(s, e) for s, e, _ in runs], lo, hi)
    inside = []
    for n, s, d in ops:
        mid = s + 0.5 * d
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and runs[i][1] >= mid and s + d > lo and s < hi:
            inside.append((n, runs[i][2], s, d))
    return main, n_steps, [inside], (lo, hi)


def _host_ops(path: str, main):
    """The same from a CPU trace: the ops on the host plane's `tf_XLA`
    lines, each with its own `hlo_module` and `program_id`; a line is a
    thread, so ops nest within a line alone; ``n_steps`` is the main
    module's executions (`run_id`) the trace holds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    per_line, host = [], {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            xla = line.name.startswith("tf_XLA")
            evs = []
            for e in line.events:
                if e.name == tr.WINDOW_SPAN:
                    host.setdefault(e.name, []).append(
                        (float(e.start_ns),
                         float(e.start_ns) + float(e.duration_ns)))
                if not xla:
                    continue
                st = dict(e.stats)
                if "hlo_op" in st and "program_id" in st:
                    evs.append((e.name, f"{st.get('hlo_module')}"
                                f"({st['program_id']})", float(e.start_ns),
                                float(e.duration_ns), st.get("run_id")))
            if evs:
                per_line.append(evs)
    flat = [ev for evs in per_line for ev in evs]
    if not flat:
        return None
    lo, hi = _window([(s, d) for _, _, s, d, _ in flat], host)
    main = main or _heaviest((ev[1], ev[3]) for ev in flat)
    keep = lambda ev: (_module(ev[1]) == main and ev[2] + ev[3] > lo
                       and ev[2] < hi)
    n_steps = len({ev[4] for ev in flat if keep(ev)})
    return main, float(n_steps), [[ev[:4] for ev in evs if keep(ev)]
                                  for evs in per_line], (lo, hi)


def _own_time(events, lo, hi):
    """``[(event, ns)]``: each event's part inside [lo, hi] less the
    parts of the events nested in it, over one line's events (properly
    nested or apart, as a device's or a thread's are)."""
    events = sorted(events, key=lambda ev: (ev[2], -ev[3]))
    own = [tr._inside(ev[2], ev[3], lo, hi) for ev in events]
    parts = list(own)
    stack = []                      # (end, index) of the events open
    for i, ev in enumerate(events):
        while stack and stack[-1][0] <= ev[2]:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= parts[i]
        stack.append((ev[2] + ev[3], i))
    return list(zip(events, own))


@functools.lru_cache(maxsize=4)
def by_region(path: str, main=None):
    """``{"module", "n_steps", "busy_s", "regions": {region: {"fwd_s",
    "bwd_s", "top": [[op_key, s], ...]}}, "unattributed": {"s", "top"}}``
    of the main module's ops inside the window; None where the trace does
    not tell its programs apart or stores none of the main module's. A
    program without scopes (a parent commit's) gives every op
    unattributed and no region. Prints the line `regions: ...` once a
    trace."""
    got = _device_ops(tr.load(path), main) or _host_ops(path, main)
    if got is None:
        return None
    main = got[0]
    out = reduce_ops(got, {
        prog: _instruction_paths(proto)
        for prog, proto in stored_programs(path).items()
        if _module(prog) == main})
    if out is None:
        return None
    ms = 1e3 / out["n_steps"]
    print(f"regions: {main}, ms a step of {out['n_steps']:.4f}: "
          + ", ".join(
              f"{r} fwd/bwd {v['fwd_s'] * ms:.4f}/{v['bwd_s'] * ms:.4f}"
              for r, v in out["regions"].items())
          + f", unattributed {out['unattributed']['s'] * ms:.4f} ("
          + ", ".join(f"{k} {v * ms:.4f}"
                      for k, v in out["unattributed"]["top"])
          + f"); busy {out['busy_s'] * ms:.4f}", flush=True)
    return out


def reduce_ops(got, paths: dict):
    """`by_region`'s dict from the main module's ops (`_device_ops` /
    `_host_ops`) and its stored programs' ``{program: {instruction:
    op_name}}``; None without a program or a step."""
    main, n_steps, lines, (lo, hi) = got
    if not paths or n_steps <= 0:
        return None
    secs = collections.defaultdict(lambda: [0.0, 0.0])
    tops = collections.defaultdict(collections.Counter)
    busy = 0.0
    read = {}       # (program, event name) -> (region, backward?, label)
    for events in lines:
        for (name, prog, _, _), ns in _own_time(events, lo, hi):
            if (prog, name) not in read:    # once an instruction, not an
                #                             event: a trace holds ~1 M
                table = paths.get(prog) or next(iter(paths.values()))
                region, phase = region_of(
                    table.get(_instruction(name))) or (None, "fwd")
                read[prog, name] = region, phase == "bwd", tr.op_key(name)
            region, bwd, label = read[prog, name]
            secs[region][bwd] += ns * 1e-9
            tops[region][label] += ns * 1e-9
            busy += ns * 1e-9
    top = lambda r: [[k, v] for k, v in tops[r].most_common(3)]
    return {"module": main, "n_steps": n_steps, "busy_s": busy,
            "regions": {r: {"fwd_s": secs[r][0], "bwd_s": secs[r][1],
                            "top": top(r)} for r in REGIONS if r in secs},
            "unattributed": {"s": sum(secs[None]), "top": top(None)}}


# ---- what the metrics' own files call ------------------------------------

def _reduced(ctx: dict):
    """`by_region` of the run's trace under the main module
    `trace.reduce` chose (a CPU rehearsal's trace names none: the module
    of most op time); None without a trace or without a single region in
    it: a program that opens no scope has nothing a region metric
    reads."""
    if not ctx.get("xplane"):
        return None
    got = by_region(ctx["xplane"], (ctx.get("trace") or {}).get(
        "main_module"))
    return got if got and got["regions"] else None


def region_ms(ctx: dict, region: str):
    """Device ms a step of the main program in ``region``, forward and
    backward together; 0.0 where the program has regions and no op of
    the step lies in this one; None where there is nothing to read."""
    got = _reduced(ctx)
    if got is None:
        return None
    row = got["regions"].get(region, {"fwd_s": 0.0, "bwd_s": 0.0})
    return 1e3 * (row["fwd_s"] + row["bwd_s"]) / got["n_steps"]


def unattributed_pct(ctx: dict):
    """100 x device time of the main program's ops without a region /
    its busy time inside the window: the tracing's own gauge."""
    got = _reduced(ctx)
    if got is None or got["busy_s"] <= 0:
        return None
    return 100.0 * got["unattributed"]["s"] / got["busy_s"]
