"""kernel.moe_experts.roofline_pct.chat.

The grouped expert product (`apex1_moe_experts`, `ops/moe_experts.py`)
runs once a sparse layer. For every held expert that at least one row
chose it reads the expert's three matrices (hidden x expert width, bfloat16)
once, and for every (row, expert) pair it reads the row and writes the
result (hidden, bfloat16 each) and computes three products (2 operations
a weight each). An expert that no row chose costs nothing and asks nothing.

How many pairs and how many touched experts comes from the program, as
COUNTED and not assumed: the `serving/step` span's counts ``moe_rows``
and ``moe_experts_touched`` (both summed over the sparse layers), summed
over the window's steps and divided by the launches they are of
(``moe_expert_slots`` over the slots of one launch). The widths are the
configuration's, as published. The time is the kernel's inside the step
program (`harness/step_kernels.py`): the prefill program calls it too, and
the steps' counts say nothing of those calls.
"""

from benchmark.harness import roofline, step_counts, step_kernels

KERNEL = "apex1_moe_experts"


def count(cfg: dict, rows: float, touched: float) -> tuple:
    """(operations, bytes) of one step with ``rows`` pairs over
    ``touched`` experts, both summed over the sparse layers."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ops = rows * 6 * h * f
    bytes_ = touched * 3 * h * f * 2 + rows * 2 * h * 2
    return ops, bytes_


def read(ctx):
    cfg = ctx["cfg"]
    got = step_counts.window_sums(ctx, "moe_rows", "moe_experts_touched",
                                  "moe_expert_slots")
    row = step_kernels.in_main_module(ctx, KERNEL)
    if got is None or row is None or not got[1]["moe_expert_slots"]:
        return None
    sums = got[1]
    slots = cfg["num_experts"] * (len(cfg["layer_types"])
                                  - cfg["num_dense_layers"])
    launches = sums["moe_expert_slots"] / slots
    rows, touched = (sums[k] / launches
                     for k in ("moe_rows", "moe_experts_touched"))
    print(f"spans: moe_rows {sums['moe_rows']}, moe_experts_touched "
          f"{sums['moe_experts_touched']} of moe_expert_slots "
          f"{sums['moe_expert_slots']} over {got[0]} step spans = "
          f"{launches:.0f} launches: {rows:.2f} pairs over {touched:.2f} "
          f"experts a launch", flush=True)
    step_ctx = dict(ctx, trace=dict(ctx["trace"], kernels={KERNEL: row}))
    return roofline.kernel_share(step_ctx, KERNEL,
                                 *count(cfg, rows, touched))
