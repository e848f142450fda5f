"""engine.control_dispatches.chat: The serving/step span's count control_dispatches (device programs launched outside the two executables), summed over the window's steps and divided by the steps."""

from benchmark.harness import spans


def read(ctx):
    return spans.read(ctx, "control_dispatches", "mean")
