"""engine.launch_ms_p50.chat: the serving/decode_step span inside the window's steps, median in ms: what one launch of the step executable costs the host; printed beside it, the span's count operands (the arrays the launch hands over)."""

from statistics import median

from benchmark.harness import spans

LAUNCH = "serving/decode_step"


def read(ctx):
    """The median over the launches of the LAST `window.steps` step spans
    (the window's, as `harness/spans.py` takes them), or None where the
    program keeps no spans or launched nothing under that name. The
    count `operands` is printed where the spans carry it (a commit from
    before PR 35 does not) and is no part of the value."""
    from apex1_tpu.obs import spine
    snapshot = getattr(spine, "snapshot", None)
    n_steps = int(ctx["scalars"].get("window.steps") or 0)
    if snapshot is None or n_steps <= 0:
        return None
    records = snapshot()
    steps = set([r.id for r in records if r.name == spans.STEP][-n_steps:])
    launches = [r for r in records
                if r.name == LAUNCH and r.parent in steps]
    if not launches:
        return None
    value = median((r.end_ns - r.start_ns) * 1e-6 for r in launches)
    operands = sorted({r.counts["operands"] for r in launches
                       if "operands" in r.counts})
    print(f"spans: {LAUNCH} p50 {value:.4f} ms (n={len(launches)}), "
          f"operands a launch {operands or 'not counted'}", flush=True)
    return value
