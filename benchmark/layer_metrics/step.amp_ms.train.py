"""step.amp_ms.train: device ms a step of the main program inside the program's region `amp` (`apex1_tpu/obs/regions.py`), forward and backward, read by `harness/regions.py`."""

from benchmark.harness import regions


def read(ctx):
    return regions.region_ms(ctx, "amp")
