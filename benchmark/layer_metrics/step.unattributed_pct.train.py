"""step.unattributed_pct.train: share of the main program's device time whose ops carry no region of the program's (`harness/regions.py`)."""

from benchmark.harness import regions


def read(ctx):
    return regions.unattributed_pct(ctx)
