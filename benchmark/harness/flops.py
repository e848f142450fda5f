"""Model FLOP/s utilisation. The count of logical operations per token is
the family's own (`benchmark/builders/<builder>.py`,
`train_flops_per_token`): computed from the configuration's sizes and the
cell's shapes, never from `cost_analysis()`, which cannot see inside a
`tpu_custom_call`. "Logical" = what the forward and backward passes of
the published model require: recomputed operations do not count, and a
causal attention is counted once (half the square).
"""

from __future__ import annotations


def mfu_pct(tokens_per_s_per_chip: float, flops_per_token: float,
            peak_flops: float) -> float:
    return 100.0 * tokens_per_s_per_chip * flops_per_token / peak_flops
