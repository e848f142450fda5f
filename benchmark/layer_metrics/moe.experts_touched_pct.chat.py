"""moe.experts_touched_pct.chat: 100 x the serving/step spans' count moe_experts_touched over moe_expert_slots (the held experts a launch could touch: those held, times the sparse layers), both summed over the window's steps."""

from benchmark.harness import step_counts


def read(ctx):
    got = step_counts.window_sums(ctx, "moe_experts_touched",
                                  "moe_expert_slots")
    if got is None or not got[1]["moe_expert_slots"]:
        return None
    return 100.0 * got[1]["moe_experts_touched"] / got[1]["moe_expert_slots"]
