"""engine.retire_ms_p50.chat: The serving/retire span, median."""

from benchmark.harness import spans


def read(ctx):
    return spans.read(ctx, "retire_ms", "p50")
