"""TPU-generation capability table — the gating layer that replaces the
reference's build-time flag registry.

Reference: ``setup.py`` (≈800 lines) is apex's de-facto feature-flag
system — every native extension is an opt-in ``--flag`` build gated on the
CUDA version and compute capability (sm70/80/90 lists per extension), and
kernels check ``torch.cuda.get_device_capability`` at runtime
(e.g. fmha requires sm80, head-dim 64). On TPU there is nothing to build —
Pallas kernels ship with the package and lower through Mosaic for whatever
chip is attached — so the *capability* that survives is the per-generation
hardware table: block-shape heuristics read VMEM size, precision policies
check native-dtype support, and ``require()`` gives contrib modules the
same "this kernel needs sm80" style guard (as data, not compiled-out code).

The generation is read from the attached device's ``device_kind``. A
compile-only tool that lowers for a chip which is not attached names its
target with `target_generation`; block planners on the CPU backend (no
chip, no target) plan for `CPU_PLANNING_GENERATION`. A peak used to
report utilisation never comes from that default: `get_capability()`
raises when there is neither a chip nor a named generation.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import re


@dataclasses.dataclass(frozen=True)
class TpuCapability:
    """Public per-generation facts that gate or tune framework behavior."""

    generation: str           # canonical name: "v4", "v5e", "v5p", "v6e"
    mxu: tuple[int, int]      # systolic array shape
    vmem_bytes: int           # per-core VMEM the kernel block planner sees
    hbm_bytes: int            # per-chip HBM
    hbm_gbps: float           # per-chip HBM bandwidth (GB/s)
    bf16_tflops: float        # peak dense bf16 TFLOP/s per chip
    cores_per_chip: int       # TensorCores per chip (megacore counts as 1)
    ici_axes: int             # torus dimensionality (2 = 2D, 3 = 3D)
    native_fp8: bool          # fp8 matmul support
    sparsecore: bool          # embedding SparseCore present
    ici_gbps: float = 0.0     # per-chip aggregate ICI bandwidth (GB/s,
    #                           spec-sheet "interchip interconnect BW"
    #                           converted from Gbit/s; /ici_axes/2 ≈ one
    #                           link — the ring-neighbor transfer rate
    #                           the overlap roofline comms term prices)


_TABLE = {
    # Public spec-sheet numbers (cloud.google.com/tpu/docs system specs);
    # vmem_bytes is the conservative planning figure, not a spec claim.
    # ici_gbps: spec "interchip interconnect BW" per chip, Gbit/s -> GB/s
    # (v2 496 / v3 656 / v4 2400 / v5e 1600 / v5p 4800 / v6e 3584 Gbps).
    "v2": TpuCapability("v2", (128, 128), 16 * 2**20, 16 * 2**30, 600.0,
                        45.0, 2, 2, False, False, 62.0),
    "v3": TpuCapability("v3", (128, 128), 16 * 2**20, 32 * 2**30, 900.0,
                        123.0, 2, 2, False, False, 82.0),
    "v4": TpuCapability("v4", (128, 128), 32 * 2**20, 32 * 2**30, 1200.0,
                        275.0, 1, 3, False, True, 300.0),
    "v5e": TpuCapability("v5e", (128, 128), 32 * 2**20, 16 * 2**30, 819.0,
                         197.0, 1, 2, False, False, 200.0),
    "v5p": TpuCapability("v5p", (128, 128), 64 * 2**20, 95 * 2**30, 2765.0,
                         459.0, 1, 3, False, True, 600.0),
    "v6e": TpuCapability("v6e", (256, 256), 64 * 2**20, 32 * 2**30, 1640.0,
                         918.0, 1, 2, False, True, 448.0),
}

# `device_kind` as jax reports it: "TPU v5 lite" (v5e), "TPU v5" (v5p),
# "TPU v6 lite" (v6e), "TPU v4" — the lite forms must match first.
_KIND_PATTERNS = [
    (re.compile(r"v6 ?lite|v6e|trillium", re.I), "v6e"),
    (re.compile(r"v5 ?lite|v5e", re.I), "v5e"),
    (re.compile(r"v5p|v5\b", re.I), "v5p"),
    (re.compile(r"v4", re.I), "v4"),
    (re.compile(r"v3", re.I), "v3"),
    (re.compile(r"v2", re.I), "v2"),
]

#: what Pallas block planners size for on the CPU backend (interpret-mode
#: tests, tuning-table lookups): the chip this repo is measured on
CPU_PLANNING_GENERATION = "v5e"

_target: str | None = None


class CapabilityError(RuntimeError):
    """≙ the reference's '<ext> requires compute capability >= sm80'."""


def _canonical(kind: str) -> str | None:
    for pat, gen in _KIND_PATTERNS:
        if pat.search(kind):
            return gen
    return None


@contextlib.contextmanager
def target_generation(generation: str):
    """Plan for ``generation`` instead of the attached device — for
    compile-only tools (``tools/aot_check.py``) that lower for a TPU
    topology from a host that has no chip."""
    global _target
    if generation not in _TABLE:
        raise ValueError(f"unknown TPU generation {generation!r}; "
                         f"known: {sorted(_TABLE)}")
    prev, _target = _target, generation
    try:
        yield
    finally:
        _target = prev


@functools.cache
def _attached_generation() -> str | None:
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    gen = _canonical(dev.device_kind)
    if gen is None:
        raise CapabilityError(
            f"no capability row for {dev.platform} device_kind "
            f"{dev.device_kind!r}; known generations: {sorted(_TABLE)}")
    return gen


def detect_generation() -> str | None:
    """Generation this process plans for: the active `target_generation`,
    else the attached accelerator's; None on the CPU backend. An
    accelerator whose ``device_kind`` is not in the table raises."""
    return _target if _target is not None else _attached_generation()


def get_capability(generation: str | None = None) -> TpuCapability:
    """Capability row for ``generation`` (default: `detect_generation`).
    With no chip attached and no generation named this raises — a peak
    must not come from a default."""
    gen = generation or detect_generation()
    if gen is None:
        raise CapabilityError(
            "no TPU attached: name the generation explicitly "
            "(get_capability('v5e'))")
    try:
        return _TABLE[gen]
    except KeyError:
        raise ValueError(
            f"unknown TPU generation {gen!r}; known: {sorted(_TABLE)}"
        ) from None


def require(feature: str, *, generation: str | None = None) -> None:
    """Assert the attached chip supports ``feature`` — the runtime analog
    of setup.py's per-extension sm gating. Features: "fp8", "sparsecore",
    "ici_3d", "megacore"."""
    cap = get_capability(generation)
    ok = {
        "fp8": cap.native_fp8,
        "sparsecore": cap.sparsecore,
        "ici_3d": cap.ici_axes >= 3,
        "megacore": cap.cores_per_chip == 1,
    }
    if feature not in ok:
        raise ValueError(f"unknown feature {feature!r}; known: {sorted(ok)}")
    if not ok[feature]:
        raise CapabilityError(
            f"feature {feature!r} requires a newer TPU generation than "
            f"{cap.generation} (≙ apex setup.py sm-arch gate)")


def vmem_budget(generation: str | None = None) -> int:
    """VMEM bytes the Pallas block planners should assume (leaves headroom
    for Mosaic's own double buffering). On the CPU backend with no
    generation named this plans for `CPU_PLANNING_GENERATION`."""
    gen = generation or detect_generation() or CPU_PLANNING_GENERATION
    return get_capability(gen).vmem_bytes // 2


def ici_link_gbps(generation: str | None = None) -> float:
    """Conservative per-neighbor ICI rate (GB/s): the aggregate per-chip
    spec figure split across the torus's ``2 * ici_axes`` links. This is
    the rate a ring ppermute hop (ONE neighbor transfer) sees — the
    denominator of the roofline comms term (`apex1_tpu.perf_model`'s
    ``ici_exposed_bytes`` pricing). 0.0 when the generation row carries
    no ICI figure."""
    cap = get_capability(generation)
    if not cap.ici_gbps:
        return 0.0
    return cap.ici_gbps / (2 * cap.ici_axes)
