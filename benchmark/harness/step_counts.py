"""Counts the program puts on its `serving/step` spans, summed over the
measured window's steps: what a per-layer metric of source
`program_counter` reads when its quantity is a count a step."""

from __future__ import annotations

from benchmark.harness import spans


def window_sums(ctx: dict, *names: str):
    """``(number of steps, {name: sum})`` over the LAST `window.steps` step
    spans (the window's, as `harness/spans.py` takes them), or None where
    the program keeps no spans or its step spans carry none of the counts
    (a commit from before the count existed; a decoder without the part
    that is counted)."""
    from apex1_tpu.obs import spine
    snapshot = getattr(spine, "snapshot", None)
    n_steps = int(ctx["scalars"].get("window.steps") or 0)
    if snapshot is None or n_steps <= 0:
        return None
    steps = [r for r in snapshot() if r.name == spans.STEP][-n_steps:]
    if not any(name in sp.counts for sp in steps for name in names):
        return None
    return len(steps), {name: sum(sp.counts.get(name, 0) for sp in steps)
                        for name in names}
