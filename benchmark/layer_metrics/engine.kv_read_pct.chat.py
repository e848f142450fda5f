"""engine.kv_read_pct.chat: 100 x the serving/step span's count kv_blocks_read over its count kv_blocks_pool, each summed over the window's steps: how much of the pool the step's attention has to move."""

from benchmark.harness import spans


def read(ctx):
    """The share over the LAST `window.steps` step spans (the window's, as
    `harness/spans.py` takes them), or None where the program keeps no
    spans or its step spans carry no such counts (a parent commit)."""
    from apex1_tpu.obs import spine
    snapshot = getattr(spine, "snapshot", None)
    n_steps = int(ctx["scalars"].get("window.steps") or 0)
    if snapshot is None or n_steps <= 0:
        return None
    steps = [r for r in snapshot() if r.name == spans.STEP][-n_steps:]
    moved = sum(sp.counts.get("kv_blocks_read", 0) for sp in steps)
    pool = sum(sp.counts.get("kv_blocks_pool", 0) for sp in steps)
    if not pool:
        return None
    print(f"spans: kv_blocks_read {moved} of kv_blocks_pool {pool} over "
          f"{len(steps)} steps", flush=True)
    return 100.0 * moved / pool
