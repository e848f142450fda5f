"""kernel.ssm_step.ms_step.chat: device time of `apex1_ssm_step` (`ops/ssm.py`), all its calls, per step of the main program."""

KERNEL = "apex1_ssm_step"


def read(ctx):
    row = (ctx.get("trace") or {}).get("kernels", {}).get(KERNEL)
    return None if row is None or row[2] <= 0 else float(row[2])
