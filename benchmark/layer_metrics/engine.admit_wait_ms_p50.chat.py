"""engine.admit_wait_ms_p50.chat: Per admission, the serving/admit.first_read span: the host waiting for the prefill chain on the device, median."""

from benchmark.harness import spans


def read(ctx):
    return spans.read(ctx, "admit_wait_ms", "p50")
