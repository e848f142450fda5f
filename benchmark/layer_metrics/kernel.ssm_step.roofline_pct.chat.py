"""kernel.ssm_step.roofline_pct.chat.

The decode step's state-update kernel (`apex1_ssm_step`, `ops/ssm.py`)
runs once a state-space layer. For every LIVE lane it reads the layer's
float32 state (heads x head width x state width), computes two products
over it (the outer product ``dt x (x) B`` summed into the decayed state,
and ``S C``: 2 operations a state element each), and writes the state
back; beside it the lane's ``dt x`` and decay (a head-width x heads tile
each), ``B``, ``C`` and the output ``y``. An idle lane costs the kernel
time and asks nothing of it.

How many lanes were live comes from the program: the `serving/step`
span's count ``state_lanes``, summed over the window's steps and divided
by the steps of the main program that the trace holds (the divisor of the
kernel's ms a step). The widths are the configuration's, as published.
(`state_bytes`, which `engine.state_mb_step.chat` reads, also holds the
convolution's inputs, 1.2 % more, which XLA moves and this kernel does
not: counting them here would count too high.)
"""

from benchmark.harness import roofline, step_counts

KERNEL = "apex1_ssm_step"


def count(cfg: dict, lanes: float) -> tuple:
    """(operations, bytes) of one decode step with ``lanes`` live lanes."""
    heads, width = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    state = heads * width * cfg["mamba_d_state"]
    layers = sum(kind == "mamba" for kind in cfg["layer_types"])
    ops = 4 * state
    bytes_ = 4 * (2 * state + 3 * heads * width + 2 * cfg["mamba_d_state"])
    return layers * lanes * ops, layers * lanes * bytes_


def read(ctx):
    n_steps = (ctx.get("trace") or {}).get("n_steps") or 0
    got = step_counts.window_sums(ctx, "state_lanes")
    if got is None or n_steps <= 0:
        return None
    lanes = got[1]["state_lanes"] / n_steps
    print(f"spans: state_lanes {got[1]['state_lanes']} over {got[0]} step "
          f"spans, {n_steps:.4f} steps of the main program in the trace: "
          f"{lanes:.3f} live lanes a step", flush=True)
    return roofline.kernel_share(ctx, KERNEL, *count(ctx["cfg"], lanes))
