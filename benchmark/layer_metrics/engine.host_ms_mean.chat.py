"""engine.host_ms_mean.chat: Mean over the window's steps of the serving/step span minus its descendants marked wait: host time on the critical path per step."""

from benchmark.harness import spans


def read(ctx):
    return spans.read(ctx, "host_ms", "mean")
