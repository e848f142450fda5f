"""What `serving.Engine` hands its executables in place of the caller's
parameter tree: the same values in fewer buffers.

A launch through `jax.jit` costs the host a fixed part and a part for
every array it is handed, whatever the array's size (PERF.md, PR 35: on a
v5e's host ~0.14 ms + ~3.2 us an operand). Most leaves of a decoder's
tree are tiny: biases, norm weights, per-head scalars, convolution taps,
hundreds of them in a handful of shapes. `PackedParams` stacks the SMALL
leaves (`SMALL_BYTES`) that agree in shape, dtype and sharding on a new
leading axis, once, and passes every other leaf through as the very
buffer the caller gave: no copy of a matrix. `unpack`, at the head of a
traced body, cuts the stacks apart again at static indices and rebuilds
the caller's tree, so the model is called exactly as before.

A stack is plain: ``(n, *shape)``. Keeping a vector's own shape behind
two leading axes, ``(n, 1, 1024)``, so that a slab would be whole tiles,
buys nothing: the TPU compiler gives that shape the layout ``{2,0,1}``,
the unit axis outermost, which IS the plain stack in memory, and the two
compiled steps agree instruction for instruction (PERF.md, PR 35).

Cutting a stack apart costs the DEVICE a little every step (a fusion of
~2 us for every 19 leaves), so packing pays only where the launch is
what the step waits for. A decode step streams every parameter it uses
once and lasts at least their bytes over the memory's bandwidth; where
that alone outlasts the launch and the host's loop (`launch_is_hidden`:
granite-4.0-h-micro's 6.4 GB are 7.8 ms on a v5e against a launch of
1.9), the launch lies under the step in flight, fewer operands buy
nothing, and the tree is handed over as it is. A decoder with experts
streams the TOUCHED experts' matrices, not all it holds: the rule counts
the whole tree, which is the step's bytes where every held expert is
touched every step (a serving batch of a dozen rows an expert:
lfm2-8b-a1b's share of 5.05 GB, 6.2 ms, handed over unpacked) and an
overestimate for a batch so small that most experts idle; there the
launch may not be hidden after all, and the rule wants the step's counts
(`moe_experts_touched`) before it is trusted.

The same answer decides a second thing (`Layout.hidden`, read by
`serving.Engine` at construction): how many launches the plain decode
loop keeps in flight behind the read. Hidden, the host waits for the
device whatever the launch costs, and one launch in flight is all that
can help; a second would cost a step of every token's way out and a
second overrun lane-step at every ``eos``. Not hidden, the device stands
idle for part of every launch while one step is in flight, and the loop
keeps two (`Engine._decode_step`).

Nothing here is an option: what is packed, and how far the loop runs
ahead, follow from what the tree holds and the device it is served from.
A tree with no small leaves, or with one leaf of a kind, passes through
unchanged.
"""

from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp
import numpy as np

from apex1_tpu.core import capability
from apex1_tpu.obs.regions import region

#: a leaf of at most this many bytes is small: a launch pays for it what
#: it pays for a matrix, and a stack of hundreds is still a few MB
SMALL_BYTES = 64 * 1024
#: what a launch through `jax.jit` costs a v5e's host: a fixed part and a
#: part an operand (PERF.md, PR 35: 0.85 ms at 154 operands, 1.20 at 259,
#: 1.24 at 345, 1.9 at 551), and what the serving loop adds a step (its
#: own work and a token's way to the host)
LAUNCH_FIXED_S = 0.55e-3
LAUNCH_OPERAND_S = 2.2e-6
LOOP_S = 0.7e-3


def _is_array(leaf) -> bool:
    return hasattr(leaf, "shape") and hasattr(leaf, "dtype")


def _group_key(leaf):
    """What two leaves must agree in to share a stack, or None for a
    leaf that is handed over as it is."""
    if not _is_array(leaf):
        return None
    shape = tuple(leaf.shape)
    if math.prod(shape) * np.dtype(leaf.dtype).itemsize > SMALL_BYTES:
        return None
    return (shape, np.dtype(leaf.dtype), getattr(leaf, "sharding", None),
            isinstance(leaf, jax.ShapeDtypeStruct))


def _device_bytes(leaf) -> int:
    """The bytes of ``leaf`` that one device holds."""
    shape = tuple(leaf.shape)
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None:
        shape = sharding.shard_shape(shape)
    return math.prod(shape) * np.dtype(leaf.dtype).itemsize


def launch_is_hidden(leaves: list, n_other: int) -> bool:
    """Whether a launch of ``leaves`` and ``n_other`` operands more ends
    before the device can have streamed ``leaves`` once: the step in
    flight then outlasts the launch of the next, and the host waits for
    the device whatever the launch costs. (Every leaf counts, a sparse
    layer's experts too, though a step streams only those its rows
    touch: the module's docstring says what that assumes.) Off an
    accelerator, or on one with no row in `core.capability`, nothing is
    known of the memory, and nothing is hidden."""
    try:
        generation = capability.detect_generation()
    except capability.CapabilityError:
        generation = None
    if generation is None:
        return False
    stream_s = (sum(_device_bytes(x) for x in leaves if _is_array(x))
                / (capability.get_capability(generation).hbm_gbps * 1e9))
    launch_s = LAUNCH_FIXED_S + LAUNCH_OPERAND_S * (len(leaves) + n_other)
    return stream_s > launch_s + LOOP_S


def _stack(leaves: list):
    """``leaves`` as one array ``(n, *shape)``; of shapes alone where the
    leaves are shapes (an engine built to be lowered, not run)."""
    first = leaves[0]
    if isinstance(first, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct((len(leaves), *first.shape),
                                    first.dtype, sharding=first.sharding)
    return jnp.stack(leaves)


class Layout:
    """Where each leaf of a tree lies among the operands: static, holds
    no array. ``slots[i]`` is ``(operand, row)`` for the tree's i-th
    leaf, ``row`` None for a leaf that is an operand of its own.
    ``hidden`` is what `launch_is_hidden` said of the tree."""

    def __init__(self, tree, n_other: int = 0):
        leaves, self.treedef = jax.tree_util.tree_flatten(tree)
        groups = collections.defaultdict(list)
        self.hidden = launch_is_hidden(leaves, n_other)
        if not self.hidden:
            for i, leaf in enumerate(leaves):
                key = _group_key(leaf)
                if key is not None:
                    groups[key].append(i)
        #: the stacks, in the order their first leaf comes in the tree:
        #: the indices of the leaves each holds
        self.groups = [m for m in groups.values() if len(m) > 1]
        self.slots = [None] * len(leaves)
        for g, members in enumerate(self.groups):
            for row, i in enumerate(members):
                self.slots[i] = (g, row)
        self.passed = [i for i, s in enumerate(self.slots) if s is None]
        for k, i in enumerate(self.passed):
            self.slots[i] = (len(self.groups) + k, None)
        self.n_operands = len(self.groups) + len(self.passed)

    def pack(self, tree) -> tuple:
        """The flat tuple of operands for ``tree``, which has the
        structure this layout was made from: the stacks, then every
        other leaf, itself."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if treedef != self.treedef:
            raise ValueError(
                f"a tree of another structure than the engine's: "
                f"{treedef} against {self.treedef}")
        return (*(_stack([leaves[i] for i in members])
                  for members in self.groups),
                *(leaves[i] for i in self.passed))

    def unpack(self, operands):
        """The caller's tree again, inside a traced body: a stacked leaf
        is cut out at its static row, and the cut leaves pass one
        `optimization_barrier` together, so each is a buffer of its own
        from there on, as the caller's leaf was, and the compiler plans
        the step it planned before. Left to fuse the cuts into their
        readers it planned another: on a v5e it kept the stacks in fast
        memory, spent the prefetch slots that freed on a second weight
        matrix a layer, streamed under the attention kernel, and left
        that kernel's query in HBM: the kernel, bound by the latency of
        its own small reads, ran 2.6 times as long and the device's step
        22 % (PERF.md, PR 35). With the barrier the cuts are a few
        fusions at the head of the program, each with up to 19 results
        (a dozen for GPT-2 medium's 194 leaves, ~2 us each on the chip),
        and the rest is instruction for instruction the program of the
        unpacked tree."""
        cut = [jax.lax.index_in_dim(operands[op], row, keepdims=False)
               for op, row in self.slots if row is not None]
        cut = iter(jax.lax.optimization_barrier(cut) if cut else ())
        return self.treedef.unflatten([
            operands[op] if row is None else next(cut)
            for op, row in self.slots])


class PackedParams:
    """``tree``, the caller's own, beside its ``operands``, made once;
    ``n_other`` is what a launch hands over besides."""

    def __init__(self, tree, n_other: int = 0):
        self.tree = tree
        self.layout = Layout(tree, n_other)
        self.operands = self.layout.pack(tree)

    def operands_of(self, tree) -> tuple:
        """The operands made at construction for the engine's own tree;
        packed anew for any other (a tool that lowers an executable for
        shapes of its own)."""
        if tree is self.tree:
            return self.operands
        return self.layout.pack(tree)


class Executable:
    """``fn(params, *args)`` under `jax.jit`, called with the caller's
    tree as before and launched with its packed operands: the traced
    body rebuilds the tree at its head. ``args[0]``, the pool, is
    donated."""

    def __init__(self, fn, packed: PackedParams):
        layout = packed.layout      # the body holds no array

        def body(operands, *args):
            # what the engine adds around the decoder (the cuts, the
            # control vectors, the lane moved in and out) is `engine`;
            # the model's own regions, opened inside, win
            with region("engine"):
                return fn(layout.unpack(operands), *args)

        body.__name__ = fn.__name__     # the module's name in a trace
        self._operands_of = packed.operands_of
        self._jit = jax.jit(body, donate_argnums=1)

    def __call__(self, params, *args):
        return self._jit(self._operands_of(params), *args)

    def lower(self, params, *args):
        return self._jit.lower(self._operands_of(params), *args)
