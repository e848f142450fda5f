"""The dense engine's step appends its K/V rows WITHOUT copying the pool:
`Engine._decode` and `Engine._verify`, built at the `gpt2m_serve_chat`
cell's real shapes (48 slots x 1152 positions, GPT-2 medium, bfloat16) and
compiled for a described v5e:2x2, hold

- no `copy` in the entry computation whose result has a pool leaf's
  element count or more,
- no `while` anywhere,
- the whole pool aliased to its donated input,
- less than one leaf (113 MB) of temporaries,
- no instruction that computes a result of a leaf's element count but
  the 24 `apex1_decode_attend` kernels, one a layer, whose two leaves
  are aliased in and out (PR 29: nothing rewrites a leaf), and
- arguments of pool + weights to within 1 % (the stored form is not
  padded: a head of 64 is not a row of 128 lanes), and
- no more parameters than the operands the engine reckons it hands over
  (PR 35: the 194 vectors of the tree in 3 stacks), with no copy of a
  matrix or of a pool leaf on the way to the model.

`Engine._prefill` holds no leaf-sized copy either: it moves one lane.

Before PR 26 the same assertions read, for both executables: 96 such
copies (two layout copies of each of the 48 leaves), 48 loops (XLA's
expansion of the scatter that a batched `dynamic_update_slice` is, 48
iterations each), and 0.235 GiB of temporaries for `_decode` (6.15 GiB
for `_verify`); on the chip that was 50 + 8 ms of every 69 ms step
(PERF.md, PR 26). From PR 26 to PR 29 the step's two attention fusions a
layer each rewrote their leaf whole with the new row selected in: 48
leaf-sized results a step, 16.8 ms of 20 (PERF.md, PR 29).
`test_the_scatter_it_replaced_still_loops` keeps the detector honest:
the old write, compiled the same way, is seen.

The topology is described inside a fixture, so only the worker that is
given this file loads the TPU compiler; where it cannot be described the
tests skip. The cell's files are read, never written. A compile that
passes is not a chip run and is never reported as one.
"""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "gpt2m_serve_chat"

RESULT_RE = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = \(?\w+\[([\d,]*)\]")
#: an instruction: its name, its result type (one array or a tuple of
#: them) and its opcode
INSTR_RE = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = "
    r"(\((?:[^=]|/\*index=\d+\*/)*?\)|\S+) ([\w\-]+)\(")
#: opcodes that name or regroup a buffer and move nothing
NO_DATA = {"parameter", "tuple", "get-tuple-element", "bitcast"}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def mosaic(topo):
    """The real (non-interpret) kernels for the described chip, with the
    persistent cache off around the compiles (its entries cannot be read
    back without a chip), as `tests/benchmark/test_benchmark_aot.py`
    sets them."""
    import jax
    import apex1_tpu.ops._common as common
    from apex1_tpu.core import capability
    from jax.experimental.compilation_cache import compilation_cache
    saved = (common.on_tpu, common.interpret_mode,
             jax.config.jax_enable_compilation_cache)
    common.on_tpu = lambda: True
    common.interpret_mode = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with capability.target_generation("v5e"):
        yield
    common.on_tpu, common.interpret_mode = saved[:2]
    jax.config.update("jax_enable_compilation_cache", saved[2])
    compilation_cache.reset_cache()


def _census(compiled, leaf_elems):
    """(copies of >= ``leaf_elems`` elements in the entry computation,
    `while` instructions anywhere) of a compiled module's text."""
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    copies = 0
    for line in entry.splitlines():
        if " copy(" not in line:
            continue
        n = 1
        for d in RESULT_RE.match(line).group(1).split(","):
            n *= int(d or 1)
        copies += n >= leaf_elems
    return copies, len(re.findall(r" while\(", text))


def _leaf_sized(compiled, leaf_elems):
    """Names of the instructions, anywhere in a compiled module, that
    compute a result of ``leaf_elems`` elements or more (an array, or
    one of a tuple)."""
    found = []
    for line in compiled.as_text().splitlines():
        m = INSTR_RE.match(line)
        if not m or m.group(3) in NO_DATA:
            continue
        for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(2)):
            n = 1
            for d in dims.split(","):
                n *= int(d or 1)
            if n >= leaf_elems:
                found.append(m.group(1))
                break
    return found


def _cell_engine(topo, num_draft):
    """(engine, params, `place`) at the cell's shapes: shapes with the
    described chip's sharding, never arrays (but the pool of zeros the
    engine makes for itself, on the CPU)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from apex1_tpu.serving.engine import Engine, EngineConfig
    from benchmark.harness import builders
    from benchmark.harness import manifest as mf
    man = mf.load_manifest(ROOT)
    cell = mf.find(man, "workloads", CELL)
    cfg = mf.load_config(man, cell["config"], ROOT)
    traffic = mf.load_traffic(cell["traffic"], ROOT)
    s1 = SingleDeviceSharding(topo.devices[0])

    def place(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype,
                                           sharding=s1), tree)

    b = builders.get(cfg)
    model = b.model("O2")
    params = place(jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
        b.param_shapes(model)))
    eng = Engine(*b.decoder(model), params, EngineConfig(
        vocab_size=b.vocab_size, num_draft=num_draft, **traffic["engine"]))
    leaves = jax.tree_util.tree_leaves(eng.kv.cache)
    assert (len(leaves), leaves[0].shape) == (48, (48, 1152, 1024))
    # 0.71 GB stream in 0.87 ms, less than their launch takes the host:
    # not hidden, so the tree is packed and the plain loop keeps two
    # launches in flight behind the read
    assert not eng._packed.layout.hidden and eng._depth == 2
    return eng, params, place


@pytest.mark.parametrize("num_draft", [0, 4], ids=["decode", "verify"])
def test_step_appends_without_copying_the_pool(topo, mosaic, num_draft):
    import jax
    import jax.numpy as jnp
    eng, params, place = _cell_engine(topo, num_draft)
    leaves = jax.tree_util.tree_leaves(eng.kv.cache)
    leaf_elems = leaves[0].size
    leaf_bytes = leaves[0].nbytes
    pool_bytes = sum(x.nbytes for x in leaves)
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(params))
    args = (params, place(eng.kv.cache),
            *place((eng._d_toks, eng._d_idxs, eng._d_active,
                    eng._d_seeds, eng._d_pos)))
    if num_draft:
        drafts = jax.ShapeDtypeStruct(
            (eng.cfg.max_slots, num_draft), jnp.int32,
            sharding=jax.tree_util.tree_leaves(params)[0].sharding)
        lowered = eng._verify.lower(*args, drafts)
    else:
        lowered = eng._decode.lower(*args)
    del eng                                   # the 5 GiB pool of zeros
    compiled = lowered.compile()
    copies, loops = _census(compiled, leaf_elems)
    big = _leaf_sized(compiled, leaf_elems)
    mem = compiled.memory_analysis()
    print(f"AOT {CELL} num_draft={num_draft}: pool-sized copies {copies}, "
          f"while {loops}, leaf-sized results {len(big)}, alias "
          f"{mem.alias_size_in_bytes / 2 ** 30:.3f} GiB, temp "
          f"{mem.temp_size_in_bytes / 2 ** 30:.3f} GiB, arguments "
          f"{mem.argument_size_in_bytes / 2 ** 30:.3f} GiB (pool "
          f"{pool_bytes / 2 ** 30:.3f} + weights "
          f"{weight_bytes / 2 ** 30:.3f})")
    assert copies == 0
    assert loops == 0
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < leaf_bytes
    # nothing but the kernel, one a layer, computes a leaf-sized result
    assert len(big) == 24, big
    assert all(re.fullmatch(r"apex1_decode_attend(\.\d+)?", n)
               for n in big), big
    assert abs(mem.argument_size_in_bytes - pool_bytes - weight_bytes) \
        < 0.01 * (pool_bytes + weight_bytes)


#: an entry parameter: its name and its type
PARAM_RE = re.compile(r"^\s*%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* parameter\(")
#: a copy, plain or asynchronous: its result type and its operand
COPY_RE = re.compile(
    r"^\s*%[\w.\-]+ = (\([^=]*?\)|\S+) (copy|copy-start)\(%([\w.\-]+)\)")


def _entry_of_decode(topo):
    """(the engine's reckoned operands, the tree's leaves, the compiled
    decode step's entry computation) at the cell's shapes."""
    import jax
    eng, params, place = _cell_engine(topo, 0)
    lowered = eng._decode.lower(
        params, place(eng.kv.cache),
        *place((eng._d_toks, eng._d_idxs, eng._d_active, eng._d_seeds,
                eng._d_pos)))
    reckoned = eng._n_operands["step"]
    del eng
    text = lowered.compile().as_text()
    return (reckoned, len(jax.tree_util.tree_leaves(params)),
            text[text.index("\nENTRY "):])


def _opcodes(entry):
    import collections
    return collections.Counter(
        m.group(3) for m in map(INSTR_RE.match, entry.splitlines()) if m)


def test_decode_is_launched_with_the_reckoned_operands_and_copies_none(
        topo, mosaic, monkeypatch):
    """`Engine._decode` at the cell's shapes is launched with the packed
    operands (`serving.packing`: 98 matrices as they are, the 194 vectors
    in 3 stacks, 48 pool leaves, 5 control vectors), the compiled program
    has no more parameters than that, and cutting the stacks apart costs
    no copy of a matrix or of a pool leaf: no `copy` reads a large
    parameter, and a `copy-start` of one is the compiler's prefetch into
    fast memory (`S(1)`), as before the stacks. Behind its head the step
    is the step of the unpacked tree: the same kernels, the same
    prefetches of weight matrices (`slice-start`), the same copies, and
    the fusions that cut the stacks apart besides, each with up to 19
    results: a dozen for 194 leaves, ~2 us each on the chip (without
    `Layout.unpack`'s barrier the compiler prefetched twice the matrices
    under the attention kernels, and the chip ran the step 22 % longer:
    PERF.md, PR 35)."""
    from apex1_tpu.serving import packing
    SMALL_BYTES = packing.SMALL_BYTES
    reckoned, n_leaves, entry = _entry_of_decode(topo)
    assert reckoned == 98 + 3 + 48 + 5
    assert n_leaves == 292
    monkeypatch.setattr(packing, "SMALL_BYTES", -1)      # nothing is small
    unpacked, _, plain = _entry_of_decode(topo)
    assert unpacked == 292 + 48 + 5
    ops, was = _opcodes(entry), _opcodes(plain)
    print(f"AOT {CELL} decode, packed: {dict(ops)}; unpacked: {dict(was)}")
    for opcode in ("custom-call", "slice-start", "copy", "while"):
        assert ops[opcode] == was[opcode], opcode
    assert ops["copy-start"] <= was["copy-start"]
    cutters = sum(
        bool(re.search(r" fusion\(%operands_[012]_\.\d+\)", line))
        for line in entry.splitlines())
    assert ops["fusion"] == was["fusion"] + cutters
    assert 3 <= cutters <= 194 // 16
    large = set()
    n_params = 0
    for line in entry.splitlines():
        m = PARAM_RE.match(line)
        if not m:
            continue
        n_params += 1
        n = 1
        for d in m.group(3).split(","):
            n *= int(d or 1)
        # every large parameter is bfloat16: a matrix, a pool leaf or
        # a stack
        if n * 2 > SMALL_BYTES and m.group(2) == "bf16":
            large.add(m.group(1))
    # a parameter the step never reads (the seeds of a greedy step) is
    # dropped by `jit`
    assert reckoned - 2 <= n_params <= reckoned
    assert len(large) == 98 + 48 + 3
    bad = []
    for line in entry.splitlines():
        m = COPY_RE.match(line)
        if m and m.group(3) in large and not (
                m.group(2) == "copy-start"
                and re.match(r"\(\w+\[[\d,]*\]\{[^}]*S\(1\)\}", m.group(1))):
            bad.append(line.strip()[:200])
    assert not bad, bad


def test_prefill_moves_one_lane_not_a_leaf(topo, mosaic):
    """A prefill chunk's attention is the composite on one batch-1 lane
    (scalar index, chunk 128): sliced out of the pool, written back in
    place, with no copy of a leaf."""
    import jax
    import jax.numpy as jnp
    eng, params, place = _cell_engine(topo, 0)
    leaves = jax.tree_util.tree_leaves(eng.kv.cache)
    leaf_elems, leaf_bytes = leaves[0].size, leaves[0].nbytes
    pool_bytes = sum(x.nbytes for x in leaves)
    s1 = jax.tree_util.tree_leaves(params)[0].sharding
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=s1)
    lowered = eng._prefill.lower(
        params, place(eng.kv.cache), i32, place(eng.kv.zeros_lane),
        jax.ShapeDtypeStruct((), jnp.bool_, sharding=s1),
        jax.ShapeDtypeStruct((1, eng.cfg.prefill_chunk), jnp.int32,
                             sharding=s1), i32, i32, i32)
    del eng
    compiled = lowered.compile()
    copies, loops = _census(compiled, leaf_elems)
    big = _leaf_sized(compiled, leaf_elems)
    mem = compiled.memory_analysis()
    print(f"AOT {CELL} prefill: pool-sized copies {copies}, while {loops}, "
          f"leaf-sized results {len(big)}, alias "
          f"{mem.alias_size_in_bytes / 2 ** 30:.3f} GiB, temp "
          f"{mem.temp_size_in_bytes / 2 ** 30:.3f} GiB")
    assert copies == 0
    assert loops == 0
    assert mem.alias_size_in_bytes >= pool_bytes
    # a leaf-sized result is the leaf itself with the lane put back in
    # place, never a leaf computed anew; the temporaries are lanes (48
    # of 2.4 MB sliced out, 48 updated)
    assert all(re.search(r"dynamic[_-]update[_-]slice", n)
               for n in big), big
    assert mem.temp_size_in_bytes < 2 * leaf_bytes


def test_the_scatter_it_replaced_still_loops(topo, mosaic):
    """The control: the write as it was before PR 26 (a
    `dynamic_update_slice` batched over its index by `vmap`), compiled
    the same way at a small pool, is still a loop between copies of the
    cache, and `_census` sees both; the step's kernel with the same
    per-row index shows neither, and is the one leaf-sized result."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from apex1_tpu.models.generate import cache_write
    from apex1_tpu.ops.decode_attend import decode_attend
    s1 = SingleDeviceSharding(topo.devices[0])
    cache = jax.ShapeDtypeStruct((16, 1152, 1024), jnp.bfloat16,
                                 sharding=s1)
    new = jax.ShapeDtypeStruct((16, 16, 1, 64), jnp.bfloat16, sharding=s1)
    idx = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=s1)
    leaf_elems = 16 * 1152 * 1024

    def old(c, n, i):
        c = jax.vmap(lambda c1, n1, i1: cache_write(
            c1[None], n1[None], i1)[0])(c, n, i)
        return jnp.einsum("bhsd,bkhd->bhsk", n,
                          c.reshape(16, 1152, 16, 64)), c

    def kernel(c, n, i):
        attn, c, _ = decode_attend(n, n, n, c, c + 1, i)
        return attn, c

    compiled = {f.__name__: jax.jit(f, donate_argnums=0).lower(
        cache, new, idx).compile() for f in (old, kernel)}
    copies, loops = _census(compiled["old"], leaf_elems)
    assert copies >= 1 and loops == 1
    assert _census(compiled["kernel"], leaf_elems) == (0, 0)
    big = _leaf_sized(compiled["kernel"], leaf_elems)
    assert [re.sub(r"\.\d+$", "", n) for n in big if "apex1" in n] == [
        "apex1_decode_attend"]


def _hw_numerics():
    """`tools/hw_numerics.py` as a module (its imports at the top are the
    standard library's and numpy): the ONE table of the serving cells'
    step-attention geometries, which the chip's parity check walks."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hw_numerics.py")
    spec = importlib.util.spec_from_file_location("hw_numerics", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_DECODE_CELLS = _hw_numerics().DECODE_CELLS


@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify"])
@pytest.mark.parametrize("cell", sorted(_DECODE_CELLS))
def test_the_kernels_queue_compiles_inside_the_vmem_budget(topo, mosaic,
                                                           cell, s):
    """The step kernel with its queue of fetches (PR 50), at each serving
    cell's own geometry, through Mosaic for the described v5e: the depth
    the row's bytes derive (4 at GPT-2's rows of 1024 lanes, 8 at 512),
    that many K and V buffers in a frame `vmem_model` prices under its
    budget, both leaves aliased in place, and nothing leaf-sized beside
    them. What the chip's compiler accepts, not what the chip computes:
    `tools/hw_numerics.py --only decode_attend` is that."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from apex1_tpu import vmem_model
    from apex1_tpu.ops.decode_attend import (check_decode_geometry,
                                             decode_attend, fetch_depth)
    B, Hq, Hkv, D, L, window = _DECODE_CELLS[cell]
    HD = Hkv * D
    depth = fetch_depth(HD, jnp.bfloat16, L)
    assert depth == (4 if HD == 1024 else 8)
    blk, win, rp = check_decode_geometry(L, HD, Hq * s, s, jnp.bfloat16,
                                         window)
    fits, est = vmem_model.CHECKS["decode_attend"](
        {"block_l": blk, "depth": depth}, {"HD": HD, "Rq": rp, "W": win},
        2, vmem_model.budget_bytes())
    assert fits and est >= 2 * depth * blk * HD * 2
    s1 = SingleDeviceSharding(topo.devices[0])
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                              sharding=s1)
    kw = {} if window is None else {"window": window}
    compiled = jax.jit(
        lambda q, kn, vn, kp, vp, ix: decode_attend(q, kn, vn, kp, vp, ix,
                                                    **kw),
        donate_argnums=(3, 4)).lower(
            sds(B, Hq, s, D), sds(B, Hkv, s, D), sds(B, Hkv, s, D),
            sds(B, L, HD), sds(B, L, HD),
            jax.ShapeDtypeStruct((B,), jnp.int32, sharding=s1)).compile()
    leaf_elems = B * L * HD
    assert _census(compiled, leaf_elems) == (0, 0)
    assert compiled.memory_analysis().temp_size_in_bytes < leaf_elems // 4
