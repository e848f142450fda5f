"""kernel.linear_xent_fwd.roofline_pct.train.

The forward kernel of the fused LM head + cross entropy
(`apex1_linear_xent_fwd`, `ops/linear_xent.py` `_fwd_kernel`) computes ONE
product, the logits x W^T tile by tile: 2*N*H*V operations (N tokens of
the step, H hidden, V the PUBLISHED vocabulary), once a step. Bytes: x and
W read in bfloat16, the targets read and the loss and log-sum-exp written
at 4 bytes a token. `step.mfu_pct.train` counts the same product.
"""

from benchmark.harness import roofline

KERNEL = "apex1_linear_xent_fwd"


def _sizes(cfg, traffic):
    """tokens of one chip's step, hidden width, PUBLISHED vocabulary (the
    program stores 50304 rows for GPT-2's 50257; the rows past the
    published ones take no part in the loss)."""
    return (int(traffic["per_chip_batch"]) * int(traffic["seq_len"]),
            cfg["n_embd"], cfg["vocab_size"])


def count(cfg: dict, traffic: dict) -> tuple:
    """(operations, bytes) one training step asks of the kernel."""
    n, h, v = _sizes(cfg, traffic)
    return 2 * n * h * v, (n * h + v * h) * 2 + 3 * n * 4


def read(ctx):
    return roofline.kernel_share(ctx, KERNEL,
                                 *count(ctx["cfg"], ctx["traffic"]))
