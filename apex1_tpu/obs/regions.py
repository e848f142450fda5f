"""The program's regions: names for DEVICE time.

A span (`obs.spine`) names host time. A region names what a compiled
instruction is FOR: `region("attn")` is `jax.named_scope("~attn")`, the
scope lands in the instruction's `op_name`
(`jit(step)/transpose(jvp(GPT2))/h3/~attn/qkv/dot_general`), the
profiler keeps that path with the op, and a trace reader sums the
device's time by region and by forward or backward. Metadata only: the
lowered program is the same text with the scopes or without, so they are
always there, with no switch.

One form, `~<name>` as a whole segment of the path: no module name
takes it. (Not `@`: XLA's export of a location cuts a name at its first
`@`, and everything behind it, the primitive too, is gone from
`op_name`.) `REGIONS` is the closed list; what each covers is in
docs/observability.md. A region opened inside another wins: a norm
inside the mixer is `norm`.
"""

from __future__ import annotations

import re
from typing import Optional

import jax

#: the closed list, the same names in every model family
REGIONS = ("embed", "attn", "mixer", "ffn", "norm", "head", "amp", "optim",
           "engine")
MARK = "~"

#: a region's segment, also as the first scope under a transform, which
#: wraps it: `jvp(~attn)`, `transpose(jvp(~attn))`
_SEGMENT = re.compile(r"(?:^|[/(])" + re.escape(MARK) + r"([a-z]+)(?=[/)]|$)")


def region(name: str):
    """`jax.named_scope` of the region's segment: a context manager, or
    a decorator of a function whose whole body is the region's."""
    if name not in REGIONS:
        raise ValueError(f"no region {name!r}: one of {REGIONS}")
    return jax.named_scope(MARK + name)


def region_of(op_name: str) -> Optional[tuple]:
    """``(region, phase)`` of an instruction's path: the INNERMOST
    region segment, and ``"bwd"`` where the path went through a
    transposition (`transpose(jvp(...))`: the backward pass keeps the
    scopes of the forward ops it transposes), else ``"fwd"``. None for a
    path without a region."""
    found = _SEGMENT.findall(op_name or "")
    if not found or found[-1] not in REGIONS:
        return None
    return found[-1], ("bwd" if "transpose(" in op_name else "fwd")
