"""kernel.decode_attend.ms_step.chat: device time of `apex1_decode_attend` (`ops/decode_attend.py`), its calls inside the step program (one an attention layer), per step of the main program."""

from benchmark.harness import step_kernels

KERNEL = "apex1_decode_attend"


def read(ctx):
    row = step_kernels.in_main_module(ctx, KERNEL)
    return None if row is None or row[2] <= 0 else float(row[2])
