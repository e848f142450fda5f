"""Every cell's step programs, compiled at REAL size for a described
v5e:2x2 (no chip attached): the Pallas kernels must be in the program
(`tpu_custom_call`) and the program must fit one chip's 16 GiB.

The topology is described inside a fixture (on-chip-measurement guide §2):
only the worker that is given this file loads the TPU compiler, and where
it cannot be described the tests skip. A compile that passes is not a chip
run and is never reported as one.
"""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HBM = 16 * 2 ** 30


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
    """Steer the program's dispatch to the real (non-interpret) kernels for
    the described chip, as `tools/aot_check.py` does; the persistent cache
    is off around these compiles (its entries cannot be read back without
    a chip)."""
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


def _cell(name):
    from benchmark.harness import manifest as mf
    man = mf.load_manifest(ROOT)
    cell = mf.find(man, "workloads", name)
    return (cell, mf.load_config(man, cell["config"], ROOT),
            mf.load_traffic(cell["traffic"], ROOT))


def _report(name, compiled):
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    row = {"program": name,
           "argument_gib": mem.argument_size_in_bytes / 2 ** 30,
           "temp_gib": mem.temp_size_in_bytes / 2 ** 30,
           "alias_gib": mem.alias_size_in_bytes / 2 ** 30,
           "total_gib": total / 2 ** 30,
           "tpu_custom_call": text.count(
               'custom_call_target="tpu_custom_call"'),
           "all_reduce": text.count(" all-reduce(")
           + text.count(" all-reduce-start(")}
    print("AOT " + json.dumps(row), flush=True)
    return row, total


#: the four-chip DDP step has no cell yet (PERF.md, open question 1b): its
#: configuration and traffic files are kept, and so is this guard of them
DDP_FILES = ("bert-large", "mlm_512_ddp4", 4)


def _train_cells():
    from benchmark.harness import manifest as mf
    man = mf.load_manifest(ROOT)
    cells = [w["name"] for w in man["workloads"]
             if mf.load_traffic(w["traffic"], ROOT)["kind"] == "train"]
    if not any(w["traffic"] == DDP_FILES[1] for w in man["workloads"]):
        cells.append("files:" + DDP_FILES[1])
    return cells


def _train_cell(name):
    if not name.startswith("files:"):
        return _cell(name)
    from benchmark.harness import manifest as mf
    config, traffic, chips = DDP_FILES
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        return {"chips": chips}, json.load(f), mf.load_traffic(traffic, ROOT)


def _serve_cells():
    from benchmark.harness import manifest as mf
    man = mf.load_manifest(ROOT)
    return [w["name"] for w in man["workloads"]
            if mf.load_traffic(w["traffic"], ROOT)["kind"] != "train"]


@pytest.mark.parametrize("name", _train_cells())
def test_train_step_compiles_for_v5e(name, topo, mosaic):
    from benchmark.harness import train
    cell, cfg, traffic = _train_cell(name)
    devices = list(topo.devices)[:cell["chips"]]
    pieces = train.make_step(cfg, traffic, devices)
    state, batch = train.abstract_args(pieces)
    compiled = pieces["step"].lower(state, batch).compile()
    row, total = _report(name, compiled)
    assert row["tpu_custom_call"] > 0, "the composites, not the kernels"
    assert total < HBM, f"{total / 2 ** 30:.2f} GiB does not fit 16 GiB"
    if traffic.get("ddp"):
        assert row["all_reduce"] > 0, "no all-reduce in the DDP step"


@pytest.mark.parametrize("name", _serve_cells())
def test_engine_programs_compile_for_v5e(name, topo, mosaic):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from apex1_tpu.serving.engine import Engine, EngineConfig
    from benchmark.harness import builders
    cell, cfg, traffic = _cell(name)
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
        vocab_size=b.vocab_size, **traffic["engine"]))
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=s1)
    chunk = jax.ShapeDtypeStruct((1, traffic["engine"]["prefill_chunk"]),
                                 jnp.int32, sharding=s1)
    pool = place(eng.kv.cache)
    ctl = place((eng._d_toks, eng._d_idxs, eng._d_active, eng._d_seeds,
                 eng._d_pos))
    pre = (pool, i32, place(eng.kv.zeros_lane),
           jax.ShapeDtypeStruct((), jnp.bool_, sharding=s1), chunk, i32,
           i32, i32)
    weights = sum(s.size * 2 for s in jax.tree_util.tree_leaves(params))
    for tag, lowered in (
            ("prefill", eng._prefill.lower(params, *pre)),
            ("decode", eng._decode.lower(params, pool, *ctl))):
        row, total = _report(f"{name}/{tag}", lowered.compile())
        assert total < HBM, f"{tag}: {total / 2 ** 30:.2f} GiB"
    assert weights < HBM


def test_weight_programs_of_a_3b_tree_fit_their_scratch_on_v5e(topo, mosaic):
    """`builders.make_params` on "a 5-layer decoder of hidden 7680 with 8
    experts of width 2048" (3.41 B parameters, 6.8 GB in bfloat16): no
    program of it takes more than one window of the table beside its
    arguments and outputs, and there are tens of programs, not one per
    leaf."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    import benchmark_testlib as lib
    from benchmark.harness import builders
    shapes = lib.decoder_shapes(lib.BIG_DECODER)
    programs = lib.weight_programs(shapes, jnp.bfloat16,
                                   SingleDeviceSharding(topo.devices[0]))
    assert len(programs) < len(jax.tree_util.tree_leaves(shapes)) / 2
    worst = 0
    for label, lowered in programs:
        mem = lowered.compile().memory_analysis()
        worst = max(worst, mem.temp_size_in_bytes)
        assert mem.temp_size_in_bytes <= builders.SCRATCH_BYTES, label
    print("AOT " + json.dumps({"program": "make_params/3.41B",
                               "programs": len(programs),
                               "worst_temp_gib": worst / 2 ** 30}))
