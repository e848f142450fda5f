"""attn.window_read_pct.chat: 100 x the serving/step spans' count kv_blocks_read_window (the blocks the sliding-window layers' attention reads: those that hold each live lane's window) over kv_blocks_read (every attention layer's), both summed over the window's steps."""

from benchmark.harness import step_counts


def read(ctx):
    got = step_counts.window_sums(ctx, "kv_blocks_read_window",
                                  "kv_blocks_read")
    if got is None or not got[1]["kv_blocks_read_window"] \
            or not got[1]["kv_blocks_read"]:
        return None
    return 100.0 * got[1]["kv_blocks_read_window"] / got[1]["kv_blocks_read"]
