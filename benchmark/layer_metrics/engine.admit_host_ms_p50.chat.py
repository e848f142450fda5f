"""engine.admit_host_ms_p50.chat: Per admission, the serving/admit span minus its descendants marked wait, median."""

from benchmark.harness import spans


def read(ctx):
    return spans.read(ctx, "admit_host_ms", "p50")
