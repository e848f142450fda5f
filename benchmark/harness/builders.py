"""The glue to the system under test: one builder per model FAMILY, in a
file of its own, `benchmark/builders/<builder>.py`, found by the
configuration file's `builder` key (`get`), so a PR that brings a family
adds a file and edits none. What every family shares stays here: the
seed's key, the weight generator, the program's fused optimizers.

The protocol. The file holds a class `Builder`, made from the
configuration's dict (the published sizes), with imports of the program
inside its methods, so a cell imports only what its kind needs:

- `family`                  a name for the printed lines
- `vocab_size`              token ids are drawn below it
- `ref_cfg`                 the sizes the plain reference takes
- `model(opt_level)`        the system's own model, under that amp policy
- `param_shapes(model)`     the parameter tree as `ShapeDtypeStruct`s
- `loss_fn(model)`          `(params, batch) -> loss` (training cells)
- `make_batch(key, rows, seq_len, traffic)`   one batch of that family
- `decoder(model)`          what `serving.Engine` takes (serving cells)
- `train_flops_per_token(seq_len)`   logical operations, forward +
  backward, of the published model: no recomputation, a causal product
  counted once (`flops.mfu_pct` takes it)
- optional `shard_step(raw_step, devices, traffic) -> (step, replicated,
  split)`: how a step of this family is laid over several chips, and the
  shardings of its state and of its batch; where a builder has it,
  `train.make_step` calls it in place of its own data-parallel wrapping.

Weights are the benchmark's, not the program's: `make_params` fills the
program's parameter tree from `--seed` on the device, and the plain
reference is handed the same function's output, never an array the program
touched. A leaf whose NAME ends in `scale` gets 1 + 0.1*normal, every
other 0.02*normal: a family names its norm weights so (`ln1_scale`,
`final_norm_scale`), or they start near 0. The weights are made a window
of the table at a time (`SCRATCH_BYTES`), so a tree as large as one chip
serves is made beside nothing but itself: 3.41 B and 4.92 B parameters
in bfloat16 on one v5e (PERF.md, PR 28, has the seconds and the peaks).

The plain reference (`benchmark/references/<name>.py`): `loss(params,
batch, cfg, quant=None)` for a training cell; for a serving cell
`logits(params, tokens, cfg, quant=None)` -> `(B, S, V)` or, better,
`logits(params, tokens, cfg, quant=None, positions=None)`: with
`positions` `(B, n)` it returns `(B, n, V)`, the logits at those positions
alone, and the serving check (`check.serve_gaps`) then never holds a full
`(B, S, V)`. The check hands it the weights AS STORED (the served type,
bfloat16), once: a reference upcasts what it multiplies, and one for a
large tree upcasts a layer or an expert at a time and blocks its attention
over heads and queries, so that its scratch stays small beside the weights.

The check refuses a reference whose sound path (`quant` None) has a
product with an operand below float32, or logits below it: the yardstick
is float32 whatever type the weights are stored in. It calls the
reference with 4 rows of `max_len` at a time: a reference bounds its own
scratch (a row or a block of rows at a time, `jax.lax.map`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import manifest as mf


def seed_key(seed: int, impl=None):
    """`--seed` may exceed 31 bits: fold the high bits in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF, impl=impl),
                              seed >> 31)


def _leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in path)


#: rows of 1024 numbers per draw of the weight generator
_DRAW_ROWS = 8192
#: draws (chunks) of the table that `make_params` holds at a time: 16 x
#: 32 MiB of float32. The only scratch that does not shrink with the tree
_WINDOW_CHUNKS = 16
#: what one program of `make_params` may take beside its arguments and
#: its outputs (`memory_analysis().temp_size_in_bytes`; the tests hold
#: every program to it): one window
SCRATCH_BYTES = _WINDOW_CHUNKS * _DRAW_ROWS * 1024 * 4


def _draw(key):
    return jax.random.normal(key, (_DRAW_ROWS, 1024), jnp.float32)


def _rows(shape) -> int:
    """Whole rows of the table that a leaf of this shape is cut from."""
    return -(-int(np.prod(shape)) // 1024)


def _cut(rows, shape, scale: bool, dtype):
    """A leaf from its whole rows of the table: cut, scaled, cast."""
    x = rows.reshape(-1)[:int(np.prod(shape))].reshape(shape)
    return (1.0 + 0.1 * x if scale else 0.02 * x).astype(dtype)


def _table_rows(shapes) -> tuple:
    """(leaves with their paths, first table row of each, n_chunks)."""
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    first, at = [], 0
    for _, s in leaves:
        first.append(at)
        at += _rows(s.shape)
    return leaves, first, -(-at // _DRAW_ROWS)


def param_generator(shapes, dtype):
    """`gen(key) -> tree`: fills the tree of `jax.ShapeDtypeStruct`s from a
    key: matrices and embeddings normal(0, 0.02), norm scales 1 +
    0.1*normal, biases 0.02*normal, in `dtype`. A leaf is a norm scale
    where its NAME ends in `scale`, so a family names its norm weights so.

    This DEFINES the values. One table of rows of 1024 numbers: row `i`
    belongs to chunk `i // 8192`, chunk `c` is `normal(split(key,
    n_chunks)[c], (8192, 1024))` with `n_chunks` counted over the whole
    tree, each leaf is cut from whole rows in tree order. Traceable (the
    training check regenerates the seed's weights inside a reduction), and
    it holds the WHOLE table at once, 4 B a parameter beside its outputs:
    `make_params` gives the same values in bounded scratch.

    Why a table: a draw per leaf took 90 s to compile for the v5e; one
    draw of 355M numbers wants 5.3 GiB of scratch; and ANY 1-D array of
    this size is laid out by the v5e compiler in 2-wide rows that pad
    64-fold and cannot be allocated, so nothing here is ever flat."""
    leaves, first, n_chunks = _table_rows(shapes)
    treedef = jax.tree_util.tree_structure(shapes)

    def gen(key):
        table = jax.lax.map(_draw, jax.random.split(key, n_chunks)
                            ).reshape(-1, 1024)
        out = []
        for (path, s), at in zip(leaves, first):
            out.append(_cut(table[at:at + _rows(s.shape)], s.shape,
                            _leaf_name(path).endswith("scale"), dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return gen


def param_windows(shapes) -> tuple:
    """(windows, n_chunks): how `make_params` walks the table. A window is
    `(chunk, n, pieces)`: the table's chunks `chunk ... chunk + n`, at
    most `_WINDOW_CHUNKS` of them, and the pieces cut from them in tree
    order. A piece is `(leaf, lead, shape, row, scale)`: the slice `[lead,
    lead + shape[0])` of leaf number `leaf` along its leading axis (`lead`
    None: the whole leaf), cut from the table's rows from `row` on. A
    piece takes at most `_WINDOW_CHUNKS - 1` chunks of rows, so it lies
    inside one window wherever it starts; a larger leaf is cut along its
    leading axis where a piece ends on a whole row, into equal pieces (one
    program) and a rest."""
    leaves, first, n_chunks = _table_rows(shapes)
    most = (_WINDOW_CHUNKS - 1) * _DRAW_ROWS * 1024
    pieces = []
    for i, ((path, s), at) in enumerate(zip(leaves, first)):
        name = _leaf_name(path)
        scale = name.endswith("scale")
        n = int(np.prod(s.shape))
        if n <= most:
            pieces.append((i, None, tuple(s.shape), at, scale))
            continue
        m = n // s.shape[0]                  # numbers a leading index
        whole = 1024 // int(np.gcd(m, 1024))  # of them end on a whole row
        per = most // m // whole * whole
        if per >= 1024:                      # and on a whole tile
            per = per // 1024 * 1024
        if per == 0:
            raise ValueError(
                f"leaf {name} {tuple(s.shape)}: {whole} of its leading "
                f"slices are {whole * m} numbers, more than the {most} "
                f"that the weight generator cuts at a time")
        for lead in range(0, s.shape[0], per):
            pieces.append((i, lead, (min(per, s.shape[0] - lead),
                                     *s.shape[1:]), at + lead * m // 1024,
                           scale))
    windows = []
    for piece in pieces:
        end = piece[3] + _rows(piece[2])
        if not windows or end > (windows[-1][0] + _WINDOW_CHUNKS) \
                * _DRAW_ROWS:
            windows.append([piece[3] // _DRAW_ROWS, 0, []])
        w = windows[-1]
        w[1] = -(-end // _DRAW_ROWS) - w[0]
        w[2].append(piece)
    return windows, n_chunks


@jax.jit
def _draw_window(keys, chunk, n):
    """The table's chunks `chunk ... chunk + n` as one window's first
    rows; its other rows are never read."""
    def body(i, window):
        return jax.lax.dynamic_update_slice_in_dim(
            window, _draw(keys[chunk + i]), i * _DRAW_ROWS, 0)
    return jax.lax.fori_loop(0, n, body, jnp.zeros(
        (_WINDOW_CHUNKS * _DRAW_ROWS, 1024), jnp.float32))


@functools.partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _cut_piece(window, row, *, shape, scale, dtype):
    """One piece from the window's rows `row ...`. The row is an operand:
    leaves alike, in whatever layer, share this program."""
    return _cut(jax.lax.dynamic_slice_in_dim(window, row, _rows(shape)),
                shape, scale, dtype)


@functools.partial(jax.jit, donate_argnums=0)
def _place_piece(leaf, piece, lead):
    return jax.lax.dynamic_update_slice_in_dim(leaf, piece, lead, 0)


def make_params(shapes, seed: int, dtype, sharding=None):
    """The weights, from `--seed`, on the device: leaf for leaf and bit for
    bit `jax.jit(param_generator(shapes, dtype))(seed_key(seed))`, made in
    bounded scratch. The table is never whole: a window of it
    (`SCRATCH_BYTES`) is drawn, the pieces that lie in it are cut, and
    the next window starts at the chunk of the next piece. The peak is the
    leaves made so far and a constant, whatever the tree's size. On
    several chips the weights are made on ONE (the same single-device
    programs as a one-chip cell) and then placed."""
    devs = sorted(sharding.device_set, key=lambda d: d.id) if sharding \
        is not None else []
    one = jax.sharding.SingleDeviceSharding(devs[0]) if devs else None
    leaves = jax.tree_util.tree_leaves(shapes)
    windows, n_chunks = param_windows(shapes)
    keys = jax.device_put(jax.random.split(seed_key(seed), n_chunks), one)
    out = [None] * len(leaves)
    for chunk, n, pieces in windows:
        window = None                        # let go before the next is made
        window = _draw_window(keys, chunk, n)
        for leaf, lead, shape, row, scale in pieces:
            piece = _cut_piece(window, row - chunk * _DRAW_ROWS, shape=shape,
                               scale=scale, dtype=jnp.dtype(dtype))
            if lead is None:
                out[leaf] = piece
                continue
            if lead == 0:
                out[leaf] = jnp.zeros(leaves[leaf].shape, dtype, device=one)
            out[leaf] = _place_piece(out[leaf], piece, lead)
        # a call's outputs are allocated when it is enqueued: a host that
        # runs ahead of the device holds window upon window (4.7 GiB over
        # the outputs, my chip run, PR 28). So it waits for this window's
        # pieces before it draws the next
        jax.block_until_ready(out[leaf])
    tree = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(shapes), out)
    return tree if sharding is None else jax.device_put(tree, sharding)


def get(cfg: dict):
    """The builder of the configuration's family: the `Builder` class of
    `benchmark/builders/<cfg["builder"]>.py`, found by name under the root
    the configuration was loaded from."""
    return mf.load_builder(cfg["builder"],
                           cfg.get("_root", mf.ROOT)).Builder(cfg)


def optimizer(spec: dict):
    """The program's fused optimizer for the traffic file's `optimizer`."""
    kw = {k: v for k, v in spec.items() if k not in ("name", "lr")}
    if spec["name"] == "adam":
        from apex1_tpu.optim.fused_adam import fused_adam
        return fused_adam(spec["lr"], **kw)
    if spec["name"] == "lamb":
        from apex1_tpu.optim.fused_lamb import fused_lamb
        return fused_lamb(spec["lr"], **kw)
    raise KeyError(f"unknown optimizer {spec['name']!r}")
