"""The program's own spans (`apex1_tpu.obs.spine.snapshot()`), reduced over
the measured window: what the per-layer metrics of source `program_span`
read, in the benchmark's own process.

The runner calls `Engine.step()` no more once the window has closed, so
the LAST `window.steps` spans named `serving/step` are the window's steps;
their descendants (by `parent`) are what the engine did inside them. A
layer's time is its span; its HOST time is the span less its descendants
marked `wait` (the host blocked on the device). Every reading states the
number of samples it stands on. Where the program keeps no spans (a parent
commit from before PR 25) there is nothing to read and every reader
returns None.
"""

from __future__ import annotations

import collections
from statistics import fmean

from benchmark.harness import stats

STEP = "serving/step"


def _ms(sp) -> float:
    return (sp.end_ns - sp.start_ns) * 1e-6


def _reduce(records: list, n_steps: int) -> dict | None:
    steps = [r for r in records if r.name == STEP][-n_steps:]
    if not steps or n_steps <= 0:
        return None
    steps.sort(key=lambda r: r.start_ns)
    children = collections.defaultdict(list)
    for r in records:
        if r.parent is not None:
            children[r.parent].append(r)

    def descendants(sp):
        out, todo = [], [sp]
        while todo:
            kids = children.get(todo.pop().id, ())
            out += kids
            todo += kids
        return out

    def host_ms(sp, below):
        return _ms(sp) - sum(_ms(d) for d in below if d.wait)

    inside = {sp.id: descendants(sp) for sp in steps}
    named = collections.defaultdict(list)
    for below in inside.values():
        for d in below:
            named[d.name].append(d)
    admits = named["serving/admit"]
    n = len(steps)
    return {
        "step_ms": [_ms(sp) for sp in steps],
        "host_ms": [host_ms(sp, inside[sp.id]) for sp in steps],
        "wait_ms": [sum(_ms(d) for d in inside[sp.id] if d.wait)
                    for sp in steps],
        "read_wait_ms": [_ms(d) for d in named["serving/read_tokens"]],
        "admit_host_ms": [host_ms(a, descendants(a)) for a in admits],
        "admit_wait_ms": [_ms(d)
                          for d in named["serving/admit.first_read"]],
        "retire_ms": [_ms(d) for d in named["serving/retire"]],
        "between_steps_ms": [(b.start_ns - a.end_ns) * 1e-6
                             for a, b in zip(steps, steps[1:])],
        "control_dispatches": [sp.counts.get("control_dispatches", 0)
                               for sp in steps],
        "steps_with_admission": sum(
            1 for sp in steps if sp.counts.get("admitted", 0)),
        "by_name_ms": {k: sum(_ms(d) for d in v)
                       for k, v in sorted(named.items())},
        "n_steps": n,
    }


def window(ctx: dict) -> dict | None:
    """The reduction for this run, made once and kept on `ctx`; prints
    the inside view beside the outside one when it is first made."""
    if "_spans" in ctx:
        return ctx["_spans"]
    from apex1_tpu.obs import spine
    snapshot = getattr(spine, "snapshot", None)
    n_steps = int(ctx["scalars"].get("window.steps") or 0)
    red = _reduce(snapshot(), n_steps) if snapshot else None
    ctx["_spans"] = red
    if red is None:
        return None
    inside = stats.percentile(red["step_ms"], 50)
    outside = stats.percentile(ctx["series"].get("engine_step_ms") or [],
                               50)
    print(f"spans: serving/step p50 {inside.value:.3f} ms (n={inside.n}) "
          f"beside the benchmark's clock around Engine.step() "
          f"{outside.value:.3f} ms (n={outside.n}): differ by "
          f"{abs(inside.value - outside.value):.3f} ms", flush=True)
    print(f"spans: mean step {fmean(red['step_ms']):.3f} ms = host "
          f"{fmean(red['host_ms']):.3f} + waiting for the device "
          f"{fmean(red['wait_ms']):.3f}; {red['steps_with_admission']} of "
          f"{red['n_steps']} steps hold an admission; queue waits "
          f"n={len(ctx['series'].get('queue_wait_ms') or [])}", flush=True)
    print("spans: ms inside the window's steps by span: " + ", ".join(
        f"{k} {v:.2f}" for k, v in red["by_name_ms"].items()), flush=True)
    return red


def read(ctx: dict, series: str, how: str = "p50"):
    """One number from the window's spans, or None where there are none;
    prints the number with its sample count."""
    red = window(ctx)
    if red is None or not red[series]:
        return None
    values = red[series]
    if how == "mean":
        value = fmean(values)
    else:
        value = stats.percentile(values, float(how.lstrip("p"))).value
    print(f"spans: {series} {how} {value:.4f} (n={len(values)})",
          flush=True)
    return value
