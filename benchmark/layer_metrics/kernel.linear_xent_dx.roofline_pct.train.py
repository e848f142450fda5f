"""kernel.linear_xent_dx.roofline_pct.train.

The backward kernel for dx of the fused LM head + cross entropy
(`apex1_linear_xent_dx`, `ops/linear_xent.py` `_bwd_dx_kernel`) computes
TWO products ITSELF: the logits tile x W^T again (the logits are never
stored, so no call with these operands can do without) and dx = g W.
Each is 2*N*H*V operations at the PUBLISHED vocabulary. Bytes: x and W
read and dx written in bfloat16; targets, log-sum-exp and the upstream
gradient read at 4 bytes a token. `step.mfu_pct.train` counts ONE of the
two: no recomputation.
"""

from benchmark.harness import roofline

KERNEL = "apex1_linear_xent_dx"


def _sizes(cfg, traffic):
    """tokens of one chip's step, hidden width, PUBLISHED vocabulary (the
    program stores 50304 rows for GPT-2's 50257; the rows past the
    published ones take no part in the loss)."""
    return (int(traffic["per_chip_batch"]) * int(traffic["seq_len"]),
            cfg["n_embd"], cfg["vocab_size"])


def count(cfg: dict, traffic: dict) -> tuple:
    """(operations, bytes) one training step asks of the kernel."""
    n, h, v = _sizes(cfg, traffic)
    return 2 * (2 * n * h * v), (2 * n * h + v * h) * 2 + 3 * n * 4


def read(ctx):
    return roofline.kernel_share(ctx, KERNEL,
                                 *count(ctx["cfg"], ctx["traffic"]))
