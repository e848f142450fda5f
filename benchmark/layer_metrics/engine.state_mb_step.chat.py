"""engine.state_mb_step.chat: the serving/step span's count state_bytes (each live lane's recurrent leaves, read once and written once), its mean over the window's steps, in MB."""

from benchmark.harness import step_counts


def read(ctx):
    got = step_counts.window_sums(ctx, "state_bytes", "state_lanes")
    if got is None:
        return None
    n, sums = got
    print(f"spans: state_bytes {sums['state_bytes']} for state_lanes "
          f"{sums['state_lanes']} over {n} steps", flush=True)
    return sums["state_bytes"] / n / 1e6
