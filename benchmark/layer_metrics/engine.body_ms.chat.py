"""engine.body_ms.chat: device ms a step of the main program inside the program's region `engine` (`apex1_tpu/obs/regions.py`), forward and backward, read by `harness/regions.py`."""

from benchmark.harness import regions


def read(ctx):
    return regions.region_ms(ctx, "engine")
