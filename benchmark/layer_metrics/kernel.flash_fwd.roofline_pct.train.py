"""kernel.flash_fwd.roofline_pct.train.

The forward flash-attention kernel (`apex1_flash_fwd`, `ops/attention.py`
`_fwd_kernel`) computes two products a layer: the scores QK^T and PV. Each
is 2*B*h*S*S*d operations as a square; the cell's attention is causal, so
each is counted ONCE, half the square (what the kernel's block skipping
can reach). d is the PUBLISHED head width (n_embd / n_head = 64), not the
128 lanes the kernel pads it to. Bytes: q, k, v read and o written in
bfloat16, the log-sum-exp written in float32. `step.mfu_pct.train` counts
the same two products.
"""

from benchmark.harness import roofline

KERNEL = "apex1_flash_fwd"


def _sizes(cfg, traffic):
    """rows, heads, positions, published head width of one chip's step."""
    return (int(traffic["per_chip_batch"]), cfg["n_head"],
            int(traffic["seq_len"]), cfg["n_embd"] // cfg["n_head"])


def count(cfg: dict, traffic: dict) -> tuple:
    """(operations, bytes) one training step asks of the kernel."""
    b, h, s, d = _sizes(cfg, traffic)
    product = 2 * b * h * s * s * d / 2           # causal: counted once
    ops = 2 * product
    bytes_ = 4 * b * h * s * d * 2 + b * h * s * 4
    return cfg["n_layer"] * ops, cfg["n_layer"] * bytes_


def read(ctx):
    return roofline.kernel_share(ctx, KERNEL,
                                 *count(ctx["cfg"], ctx["traffic"]))
