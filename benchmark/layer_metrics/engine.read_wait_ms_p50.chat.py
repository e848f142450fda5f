"""engine.read_wait_ms_p50.chat: The serving/read_tokens span, median: a decode step's device time as the host sees it."""

from benchmark.harness import spans


def read(ctx):
    return spans.read(ctx, "read_wait_ms", "p50")
