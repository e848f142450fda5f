"""Shared kernel-dispatch machinery for `apex1_tpu.ops`.

Every op ships two implementations:

- a **Pallas TPU kernel** (the ``csrc/`` equivalent), used on TPU backends;
- an **XLA composite** (pure jnp; also the parity "gold"), used on CPU/GPU
  and wherever profiling shows XLA's fusion already wins (the reference's
  ``is_kernel_available`` fallback pattern,
  ``apex/transformer/functional/fused_softmax.py :: FusedScaleMaskSoftmax``).

Dispatch is controllable for tests/benchmarks via ``set_impl`` /
``force_impl`` ("auto" | "pallas" | "xla"). On non-TPU backends "pallas"
runs the kernel in interpreter mode so kernel logic is testable on the CPU
mesh harness.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_IMPL = "auto"  # "auto" | "pallas" | "xla"


def set_impl(mode: str) -> None:
    global _IMPL
    if mode not in ("auto", "pallas", "xla"):
        raise ValueError(f"impl must be auto|pallas|xla, got {mode!r}")
    _IMPL = mode


def get_impl() -> str:
    return _IMPL


@contextlib.contextmanager
def force_impl(mode: str):
    prev = _IMPL
    set_impl(mode)
    try:
        yield
    finally:
        set_impl(prev)


@functools.cache
def _default_backend() -> str:
    # a backend that fails to initialise RAISES here: mapping the failure
    # to "cpu" would silently select the composites and interpret mode
    # for the life of the process
    return jax.default_backend()


def on_tpu() -> bool:
    return _default_backend() == "tpu"


def use_pallas() -> bool:
    if _IMPL == "pallas":
        return True
    if _IMPL == "xla":
        return False
    return on_tpu()


def interpret_mode() -> bool:
    """Interpret Pallas kernels when not on a real TPU."""
    return not on_tpu()


def mosaic_dtype(dtype):
    """The dtype a COMPILED Pallas kernel runs for ``dtype`` operands.

    Mosaic has no IEEE float16 ("Unsupported type: 'f16'" at lowering),
    so under the fp16 AMP policies fp16 is a STORAGE dtype only: kernel
    entry points cast f16 operands to bf16 on the compiled-TPU path and
    cast results back (XLA itself upcasts f16 dots on TPU — neither path
    computes IEEE-f16 products). Identity everywhere else: interpret
    mode and the XLA composites take f16 directly, so CPU tier-1
    behavior is unchanged. The cast is a plain convert_element_type —
    autodiff transposes it, so custom_vjp kernels only ever see bf16."""
    if dtype == jnp.float16 and not interpret_mode():
        return jnp.bfloat16
    return dtype


def to_mosaic(*arrays):
    """Cast each array to its `mosaic_dtype` (f16 -> bf16 on the
    compiled-TPU path, identity otherwise). ``None`` passes through;
    one array in -> one array out. Kernel entry points run EVERY
    floating-point operand through this so per-operand coverage is
    auditable at the call site."""
    out = tuple(a if a is None or a.dtype == mosaic_dtype(a.dtype)
                else a.astype(mosaic_dtype(a.dtype)) for a in arrays)
    return out[0] if len(out) == 1 else out


#: what every kernel's name starts with on its compiled instruction
KERNEL_PREFIX = "apex1_"


def kernel_call(kernel, *, name: str, **kwargs):
    """``pl.pallas_call`` under the name the kernel carries onto its
    compiled instruction (``%apex1_<name>.N = ... custom-call(...)``) and
    so into the device trace, where a bare call takes the innermost jax
    scope's (``%layer0.7``, ``%transpose_jvp___.5``) and forward, dq and
    dkv cannot be told apart. Every Pallas call of `ops/` goes through
    here, each site under a name of its own (``tests/test_kernel_names``
    pins both). Metadata only: the kernel, its blocks and its operands
    are ``kwargs``, passed on as they are."""
    call = pl.pallas_call(kernel, name=KERNEL_PREFIX + name, **kwargs)

    def named(*args):
        # a transform wraps the FIRST scope under it (`jvp(kernel)/
        # apex1_x`): without this one it would wrap the kernel's own
        # name where no module scope lies between (`jvp_apex1_x_`)
        with jax.named_scope("kernel"):
            return call(*args)

    return named


def out_struct(shape, dtype, *like):
    """``ShapeDtypeStruct`` for a ``pallas_call`` output whose ``vma``
    (varying-across-mesh-axes set) is the union of the ``like`` inputs'.

    Under ``jax.shard_map(..., check_vma=True)`` — the default — every
    pallas_call output must declare its vma or tracing fails with
    "`vma` on `jax.ShapeDtypeStruct` must not be `None`" (review r5:
    this made the Pallas path of ring/Ulysses attention untraceable in
    the shipped TPU configuration while the CPU/XLA fallback hid it
    from the suite). A kernel output varies exactly like the inputs it
    is computed from, so the union is the right declaration; outside
    shard_map every vma is the empty frozenset, which pallas_call
    accepts in plain jit.
    """
    vma = frozenset()
    for x in like:
        vma |= jax.typeof(x).vma
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def vary(x, axis_name):
    """Mark ``x`` as device-varying over ``axis_name`` (ring/scan carry
    typing under ``check_vma``). The sibling of `out_struct`'s vma
    declaration, shared by parallel.ring_attention and
    tensor_parallel.mappings."""
    return jax.lax.pcast(x, axis_name, to="varying")


def pad_to(x: jnp.ndarray, axis: int, multiple: int, value=0.0):
    """Pad ``axis`` up to a multiple; returns (padded, original_size).

    Client-side neutral-element padding keeps kernels free of ragged-edge
    masking (XLA fuses the pad/slice into the surrounding program).
    """
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x, size
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads, constant_values=value), size


def as_rows(x: jnp.ndarray) -> tuple[jnp.ndarray, tuple[int, ...]]:
    """Collapse leading dims: (..., H) -> (R, H)."""
    shape = x.shape
    return x.reshape(-1, shape[-1]), shape


NEG_INF = -1e30  # finite mask value, reference kernels use -10000/-inf


def row_block(lanes: int, *, rows: int | None = None,
              budget_bytes: int = 1 << 20, lo: int = 8,
              hi: int = 512) -> int:
    """Rows per grid step for row-wise kernels (LN, softmax, xentropy…).

    Tiny fixed blocks make the grid huge and per-step DMA/launch overheads
    dominate (round-1 on-device profile attributed ~5× to small tiles on
    GPT-2 shapes; the raw trace was not retained and the block sweep
    was never re-measured); this targets ``budget_bytes``
    of fp32 per row-block operand (keep it ≤1 MiB — Pallas double-buffers
    every operand and bwd kernels carry 3+ row blocks), clamped to
    [``lo``, ``hi``] and — when ``rows`` is given — to the actual row
    count (8-aligned) so small inputs aren't padded up to dead work.
    ``lanes`` is the RAW last-dim size; rounded to 128 internally."""
    lanes_p = max(128, ((lanes + 127) // 128) * 128)
    br = max(lo, min(hi, budget_bytes // (4 * lanes_p) // 8 * 8))
    if rows is not None:
        br = min(br, max(lo, ((rows + 7) // 8) * 8))
    return br
