"""The dense engine's step appends its K/V rows WITHOUT copying the pool:
`Engine._decode` and `Engine._verify`, built at the `gpt2m_serve_chat`
cell's real shapes (48 slots x 1151 positions, GPT-2 medium, bfloat16) and
compiled for a described v5e:2x2, hold

- no `copy` in the entry computation whose result has a pool leaf's
  element count or more,
- no `while` anywhere,
- the whole pool aliased to its donated input, and
- less than one leaf (113 MB) of temporaries.

Before PR 26 the same assertions read, for both executables: 96 such
copies (two layout copies of each of the 48 leaves), 48 loops (XLA's
expansion of the scatter that a batched `dynamic_update_slice` is, 48
iterations each), and 0.235 GiB of temporaries for `_decode` (6.15 GiB
for `_verify`); on the chip that was 50 + 8 ms of every 69 ms step
(PERF.md, PR 26). `test_the_scatter_it_replaced_still_loops` keeps the
detector honest: the old write, compiled the same way, is seen.

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


@pytest.mark.parametrize("num_draft", [0, 4], ids=["decode", "verify"])
def test_step_appends_without_copying_the_pool(topo, mosaic, num_draft):
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
    leaf_elems = leaves[0].size
    leaf_bytes = leaves[0].nbytes
    pool_bytes = sum(x.nbytes for x in leaves)
    assert (len(leaves), leaves[0].shape) == (48, (48, 16, 1151, 64))
    args = (params, place(eng.kv.cache),
            *place((eng._d_toks, eng._d_idxs, eng._d_active,
                    eng._d_seeds, eng._d_pos)))
    if num_draft:
        drafts = jax.ShapeDtypeStruct((eng.cfg.max_slots, num_draft),
                                      jnp.int32, sharding=s1)
        lowered = eng._verify.lower(*args, drafts)
    else:
        lowered = eng._decode.lower(*args)
    del eng                                   # the 5 GiB pool of zeros
    compiled = lowered.compile()
    copies, loops = _census(compiled, leaf_elems)
    mem = compiled.memory_analysis()
    print(f"AOT {CELL} num_draft={num_draft}: pool-sized copies {copies}, "
          f"while {loops}, alias {mem.alias_size_in_bytes / 2 ** 30:.3f} "
          f"GiB, temp {mem.temp_size_in_bytes / 2 ** 30:.3f} GiB")
    assert copies == 0
    assert loops == 0
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < leaf_bytes


def test_the_scatter_it_replaced_still_loops(topo, mosaic):
    """The control: the write as it was (a `dynamic_update_slice` batched
    over its index by `vmap`), compiled the same way at a small pool, is
    still a loop between copies of the cache, and `_census` sees both;
    `cache_write` with the same per-row index shows neither."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from apex1_tpu.models.generate import cache_write
    s1 = SingleDeviceSharding(topo.devices[0])
    cache = jax.ShapeDtypeStruct((16, 16, 1151, 64), jnp.bfloat16,
                                 sharding=s1)
    new = jax.ShapeDtypeStruct((16, 16, 1, 64), jnp.bfloat16, sharding=s1)
    idx = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=s1)

    def attend(write):
        def f(c, n, i):
            c = write(c, n, i)
            return jnp.einsum("bhsd,bhkd->bhsk", n, c), c
        return jax.jit(f, donate_argnums=0).lower(cache, new, idx).compile()

    old = attend(lambda c, n, i: jax.vmap(
        lambda c1, n1, i1: cache_write(c1[None], n1[None], i1)[0])(c, n, i))
    copies, loops = _census(old, 16 * 16 * 1151 * 64)
    assert copies >= 1 and loops == 1
    assert _census(attend(cache_write), 16 * 16 * 1151 * 64) == (0, 0)
