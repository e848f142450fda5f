"""moe.tokens_per_expert.chat: the serving/step spans' count moe_rows (the (row, expert) pairs a launch computed here, summed over the sparse layers) over moe_experts_touched (the held experts with at least one), both summed over the window's steps."""

from benchmark.harness import step_counts


def read(ctx):
    got = step_counts.window_sums(ctx, "moe_rows", "moe_experts_touched")
    if got is None or not got[1]["moe_experts_touched"]:
        return None
    return got[1]["moe_rows"] / got[1]["moe_experts_touched"]
