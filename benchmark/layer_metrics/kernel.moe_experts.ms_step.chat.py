"""kernel.moe_experts.ms_step.chat: device time of `apex1_moe_experts` (`ops/moe_experts.py`), its calls inside the step program, per step of the main program."""

from benchmark.harness import step_kernels

KERNEL = "apex1_moe_experts"


def read(ctx):
    row = step_kernels.in_main_module(ctx, KERNEL)
    return None if row is None or row[2] <= 0 else float(row[2])
