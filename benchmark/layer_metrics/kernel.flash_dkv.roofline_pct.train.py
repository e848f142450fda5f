"""kernel.flash_dkv.roofline_pct.train.

The backward kernel for dK and dV (`apex1_flash_dkv`, `ops/attention.py`
`_bwd_dkv_kernel`) computes four products a layer ITSELF: the scores QK^T
again, dV = P^T dO, dP = dO V^T, and dK = dS^T Q. Each is counted causal,
once: B*h*S*S*d operations at the PUBLISHED head width (64, not the
padded 128). Bytes: q, k, v, dO read and dK, dV written in bfloat16, the
log-sum-exp and the row sums (delta) read in float32.
`step.mfu_pct.train` counts NO recomputation (see `flash_dq`).
"""

from benchmark.harness import roofline

KERNEL = "apex1_flash_dkv"


def _sizes(cfg, traffic):
    """rows, heads, positions, published head width of one chip's step."""
    return (int(traffic["per_chip_batch"]), cfg["n_head"],
            int(traffic["seq_len"]), cfg["n_embd"] // cfg["n_head"])


def count(cfg: dict, traffic: dict) -> tuple:
    """(operations, bytes) one training step asks of the kernel."""
    b, h, s, d = _sizes(cfg, traffic)
    product = 2 * b * h * s * s * d / 2           # causal: counted once
    ops = 4 * product
    bytes_ = 6 * b * h * s * d * 2 + 2 * b * h * s * 4
    return cfg["n_layer"] * ops, cfg["n_layer"] * bytes_


def read(ctx):
    return roofline.kernel_share(ctx, KERNEL,
                                 *count(ctx["cfg"], ctx["traffic"]))
