"""kernel.decode_attend.roofline_pct.chat.

The step's attention (`apex1_decode_attend`, `ops/decode_attend.py`) runs
once an attention layer. For every live lane it reads the blocks of 128
positions that hold what the lane attends, K and V, each position's
``num_key_value_heads * head_dim`` numbers of 2 B (bfloat16): a global
layer's up to the lane's horizon, a sliding layer's that hold its window.
For each position read it computes q.k and p.v for every query head: 4
operations a head a number of the head's width. The query, the output and
the rows appended (a few KB a lane) are left out.

How many blocks comes from the program, as COUNTED and not assumed: the
`serving/step` span's count ``kv_blocks_read``, which for a decoder with
K/V leaves of several lengths is the sum over its attention layers
(``kv_layers`` of them a launch), summed over the window's steps and
divided by the launches they are of. A program whose spans carry no
``kv_layers`` (a commit from before the count; a decoder with one length,
whose ``kv_blocks_read`` is a lane's and not a layer's) gives nothing to
read. The time is the kernel's inside the step program
(`harness/step_kernels.py`).
"""

from benchmark.harness import roofline, step_counts, step_kernels

KERNEL = "apex1_decode_attend"
BLOCK = 128


def count(cfg: dict, blocks: float) -> tuple:
    """(operations, bytes) of one step that reads ``blocks`` blocks of K
    and of V, summed over the layers and the lanes."""
    positions = blocks * BLOCK
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    ops = positions * 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    return ops, positions * 2 * kv * 2


def read(ctx):
    cfg = ctx["cfg"]
    got = step_counts.window_sums(ctx, "kv_blocks_read", "kv_layers")
    row = step_kernels.in_main_module(ctx, KERNEL)
    if got is None or row is None or not got[1]["kv_layers"]:
        return None
    sums = got[1]
    launches = sums["kv_layers"] / len(cfg["layer_types"])
    blocks = sums["kv_blocks_read"] / launches
    print(f"spans: kv_blocks_read {sums['kv_blocks_read']} over kv_layers "
          f"{sums['kv_layers']} in {got[0]} step spans = {launches:.0f} "
          f"launches: {blocks:.1f} blocks a launch", flush=True)
    step_ctx = dict(ctx, trace=dict(ctx["trace"], kernels={KERNEL: row}))
    return roofline.kernel_share(step_ctx, KERNEL, *count(cfg, blocks))
