"""kernel.flash_dq.roofline_pct.train.

The backward kernel for dQ (`apex1_flash_dq`, `ops/attention.py`
`_bwd_dq_kernel`) computes three products a layer ITSELF: the scores QK^T
again (flash attention keeps no S x S matrix, so no call with these
operands can do without), dP = dO V^T, and dQ = dS K. Each is counted
causal, once: B*h*S*S*d operations at the PUBLISHED head width (64, not
the padded 128). Bytes: q, k, v, dO read and dQ written in bfloat16, the
log-sum-exp and the row sums (delta) read in float32.
`step.mfu_pct.train` counts NO recomputation: of the backward pass's
seven products here and in `flash_dkv` it counts four.
"""

from benchmark.harness import roofline

KERNEL = "apex1_flash_dq"


def _sizes(cfg, traffic):
    """rows, heads, positions, published head width of one chip's step."""
    return (int(traffic["per_chip_batch"]), cfg["n_head"],
            int(traffic["seq_len"]), cfg["n_embd"] // cfg["n_head"])


def count(cfg: dict, traffic: dict) -> tuple:
    """(operations, bytes) one training step asks of the kernel."""
    b, h, s, d = _sizes(cfg, traffic)
    product = 2 * b * h * s * s * d / 2           # causal: counted once
    ops = 3 * product
    bytes_ = 5 * b * h * s * d * 2 + 2 * b * h * s * 4
    return cfg["n_layer"] * ops, cfg["n_layer"] * bytes_


def read(ctx):
    return roofline.kernel_share(ctx, KERNEL,
                                 *count(ctx["cfg"], ctx["traffic"]))
