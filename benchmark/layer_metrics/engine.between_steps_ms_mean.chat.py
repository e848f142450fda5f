"""engine.between_steps_ms_mean.chat: Mean gap from one serving/step span's end to the next one's start: the caller's share."""

from benchmark.harness import spans


def read(ctx):
    return spans.read(ctx, "between_steps_ms", "mean")
