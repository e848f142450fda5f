"""kernel.linear_xent_dw.roofline_pct.train.

The backward kernel for dW of the fused LM head + cross entropy
(`apex1_linear_xent_dw`, `ops/linear_xent.py` `_bwd_dw_kernel`) computes
TWO products ITSELF: the logits tile x W^T again (the logits are never
stored) and dW = g^T x. Each is 2*N*H*V operations at the PUBLISHED
vocabulary. Bytes: x and W read and dW written in bfloat16; targets,
log-sum-exp and the upstream gradient read at 4 bytes a token.
`step.mfu_pct.train` counts ONE of the two: no recomputation.
"""

from benchmark.harness import roofline

KERNEL = "apex1_linear_xent_dw"


def _sizes(cfg, traffic):
    """tokens of one chip's step, hidden width, PUBLISHED vocabulary (the
    program stores 50304 rows for GPT-2's 50257; the rows past the
    published ones take no part in the loss)."""
    return (int(traffic["per_chip_batch"]) * int(traffic["seq_len"]),
            cfg["n_embd"], cfg["vocab_size"])


def count(cfg: dict, traffic: dict) -> tuple:
    """(operations, bytes) one training step asks of the kernel."""
    n, h, v = _sizes(cfg, traffic)
    return 2 * (2 * n * h * v), (n * h + 2 * v * h) * 2 + 3 * n * 4


def read(ctx):
    return roofline.kernel_share(ctx, KERNEL,
                                 *count(ctx["cfg"], ctx["traffic"]))
