"""The weight generator: `builders.make_params` makes, in bounded scratch,
bit for bit what `jax.jit(builders.param_generator(...))` defines; no
program of it takes more than one window of the table beside its
arguments and outputs; layers alike share a compiled program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_testlib as lib
from benchmark.harness import builders, device


def _f(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _layers(n, kernel, wide=None):
    """A tree whose leaves do not end on chunk borders: ragged last rows
    (1000 numbers, 7 x 333), norm weights named `scale`, `n` layers
    alike."""
    tree = {f"h{i}": {"ln_scale": _f(1000), "w": {"kernel": _f(*kernel),
                                                  "bias": _f(kernel[1])},
                      "odd": _f(7, 333)} for i in range(n)}
    tree["lnf_scale"] = _f(1000)
    if wide:
        tree["wte"] = _f(*wide)
    return tree


def _assert_same_bits(got, want):
    got = jax.tree_util.tree_flatten_with_path(got)[0]
    want = jax.tree_util.tree_flatten_with_path(want)[0]
    assert len(got) == len(want)
    for (path, a), (_, b) in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        assert np.array_equal(np.asarray(a).view(np.uint8),
                              np.asarray(b).view(np.uint8)), path


@pytest.fixture()
def small_window(monkeypatch):
    """Chunks of 16 rows, windows of 4: a tree of a megabyte then turns
    many windows over and has leaves too large for one. The jitted
    programs read the two numbers as they are traced."""
    monkeypatch.setattr(builders, "_DRAW_ROWS", 16)
    monkeypatch.setattr(builders, "_WINDOW_CHUNKS", 4)
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_make_params_equals_the_generator_bit_for_bit(dtype):
    shapes = _layers(8, (1000, 3072))
    windows, n_chunks = builders.param_windows(shapes)
    assert n_chunks >= 3
    # leaves lie across chunk borders, and the last row is ragged
    rows = sorted(p[3] for w in windows for p in w[2])
    assert any(a // 8192 != (b - 1) // 8192 for a, b in zip(rows, rows[1:]))
    seed = 2 ** 31 + 5
    got = builders.make_params(shapes, seed, dtype)
    want = jax.jit(builders.param_generator(shapes, dtype))(
        builders.seed_key(seed))
    _assert_same_bits(got, want)
    scale = np.asarray(got["h0"]["ln_scale"], np.float32)
    assert abs(scale.mean() - 1.0) < 0.02 and 0.05 < scale.std() < 0.2
    kernel = np.asarray(got["h0"]["w"]["kernel"], np.float32)
    assert abs(kernel.mean()) < 1e-3 and 0.018 < kernel.std() < 0.022


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_windows_turn_over_and_a_large_leaf_is_made_in_pieces(
        small_window, dtype):
    shapes = _layers(3, (1000, 3072), wide=(600, 1024))
    windows, n_chunks = builders.param_windows(shapes)
    assert len(windows) > 50 and all(1 <= n <= 4 for _, n, _ in windows)
    wte = [jax.tree_util.keystr(path) for path, _ in
           jax.tree_util.tree_flatten_with_path(shapes)[0]].index("['wte']")
    leads = [p[1] for w in windows for p in w[2] if p[0] == wte]
    assert leads[:3] == [0, 48, 96] and leads[-1] == 576
    got = builders.make_params(shapes, 7, dtype)
    want = jax.jit(builders.param_generator(shapes, dtype))(
        builders.seed_key(7))
    _assert_same_bits(got, want)


def test_pieces_end_on_whole_rows_and_cover_every_leaf(small_window):
    shapes = _layers(2, (1000, 3072), wide=(77, 5, 512))
    windows, _ = builders.param_windows(shapes)
    leaves = jax.tree_util.tree_leaves(shapes)
    covered = [0] * len(leaves)
    for chunk, n, pieces in windows:
        for leaf, lead, shape, row, _ in pieces:
            rows = -(-int(np.prod(shape)) // 1024)
            assert rows <= 3 * 16
            assert chunk * 16 <= row and row + rows <= (chunk + n) * 16
            covered[leaf] += shape[0] if lead is not None else \
                leaves[leaf].shape[0]
            if lead:        # a later piece starts where a row starts
                assert lead * int(np.prod(shape[1:])) % 1024 == 0
    assert covered == [s.shape[0] for s in leaves]


def test_a_leaf_that_cannot_be_cut_into_windows_is_refused(small_window):
    with pytest.raises(ValueError, match="h0/w/kernel"):
        builders.param_windows(_layers(1, (3, 50001)))


def test_weights_on_four_chips_are_those_of_one():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    shapes = _layers(2, (64, 3072))
    repl = NamedSharding(Mesh(np.array(jax.devices()[:4]), ("dp",)), P())
    got = builders.make_params(shapes, 11, jnp.float32, repl)
    assert all(len(a.sharding.device_set) == 4
               for a in jax.tree_util.tree_leaves(got))
    _assert_same_bits(got, builders.make_params(shapes, 11, jnp.float32))


def test_layers_alike_share_a_compiled_program():
    meter = device.CompileMeter()
    counts = []
    for n in (2, 6):
        jax.clear_caches()
        mark = meter.mark()
        jax.block_until_ready(builders.make_params(
            _layers(n, (96, 3072)), 3, jnp.bfloat16))
        counts.append(meter.since(mark)["compiles"])
    jax.clear_caches()
    assert counts[0] == counts[1] > 0, counts


def test_every_program_of_a_100m_tree_fits_the_scratch():
    """Counts of the CPU's compiler, no run: what each program of
    `make_params` takes beside its arguments and its outputs."""
    shapes = lib.decoder_shapes(dict(
        lib.BIG_DECODER, hidden=1024, expert_width=1024, dense_width=4096,
        heads=16, q_rank=512, vocab=8192))
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert n > 100e6
    programs = lib.weight_programs(shapes, jnp.bfloat16)
    assert 10 < len(programs) < 30      # not one per leaf (there are 83)
    for label, lowered in programs:
        mem = lowered.compile().memory_analysis()
        assert mem.temp_size_in_bytes <= builders.SCRATCH_BYTES, label
        assert mem.output_size_in_bytes <= builders.SCRATCH_BYTES, label


@pytest.mark.parametrize("experts,billions", [(8, 3.41), (16, 4.92)])
def test_the_large_trees_are_the_sizes_the_issue_counted(experts, billions):
    shapes = lib.decoder_shapes(dict(lib.BIG_DECODER, experts=experts))
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert round(n / 1e9, 2) == billions
    windows, n_chunks = builders.param_windows(shapes)
    drawn = sum(w[1] for w in windows)
    assert n_chunks <= drawn < 1.1 * n_chunks       # little is drawn twice
