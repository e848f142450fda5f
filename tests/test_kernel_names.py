"""Every Pallas kernel carries a name of its own onto its compiled
instruction (`%apex1_<name>.N = ... custom-call(...)`), and so into the
device trace.

- Statically: `ops._common.kernel_call` holds the repo's only
  `pl.pallas_call`, and every site that goes through it names itself with
  a literal that no other site uses.
- Compiled: the tiny GPT-2 training step and the engine's two executables
  (dense and paged pool), lowered for a described v5e as
  `tests/benchmark/test_benchmark_aot.py` does, hold no `custom-call` to
  Mosaic without such a name — also where a transform (`jvp`,
  `transpose`) sits right above the kernel. A compile is not a chip run.
"""

import ast
import collections
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPS = os.path.join(ROOT, "apex1_tpu", "ops")
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))

#: a Mosaic custom-call of a compiled module, by its instruction's name
KERNEL_RE = re.compile(
    r'%([\w.\-]+?)(?:\.\d+)? = [^\n]*? custom-call\([^\n]*'
    r'custom_call_target="tpu_custom_call"')


def _calls(path):
    tree = ast.parse(open(path).read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            f = node.func
            yield (f.attr if isinstance(f, ast.Attribute)
                   else getattr(f, "id", None)), node


def _sites():
    """[(file, line, name literal or None)] of every `kernel_call(...)`."""
    out = []
    for fn in sorted(os.listdir(OPS)):
        if not fn.endswith(".py"):
            continue
        for called, node in _calls(os.path.join(OPS, fn)):
            if called == "kernel_call":
                kw = {k.arg: k.value for k in node.keywords}
                name = kw.get("name")
                out.append((fn, node.lineno, name.value if isinstance(
                    name, ast.Constant) else None))
    return out


def test_one_pallas_call_and_it_is_the_helpers():
    where = [fn for fn in sorted(os.listdir(OPS)) if fn.endswith(".py")
             for called, _ in _calls(os.path.join(OPS, fn))
             if called == "pallas_call"]
    assert where == ["_common.py"]


def test_every_site_names_itself_and_no_two_alike():
    sites = _sites()
    assert len(sites) == 30, sites
    unnamed = [s for s in sites if not s[2]]
    assert not unnamed, unnamed
    twice = [n for n, c in collections.Counter(
        s[2] for s in sites).items() if c > 1]
    assert not twice, twice
    assert all(re.fullmatch(r"[a-z0-9_]+", s[2]) for s in sites)
    names = {s[2] for s in sites}
    assert {"flash_fwd", "flash_dq", "flash_dkv", "flash_dbias",
            "linear_xent_stats", "linear_xent_pack", "linear_xent_dx",
            "linear_xent_dw", "linear_xent_fwd", "layer_norm_fwd",
            "layer_norm_bwd", "paged_attend", "fused_sample",
            "decode_attend", "ssm_step", "moe_experts"} <= names


def test_name_reaches_the_jaxpr_under_interpret_mode():
    """On the CPU the kernels run interpreted; the name is on the
    `pallas_call` equation all the same, prefixed."""
    import jax
    import jax.numpy as jnp
    from apex1_tpu.ops import force_impl
    from apex1_tpu.ops.layer_norm import layer_norm
    x = jnp.ones((16, 128), jnp.float32)
    g = jnp.ones((128,), jnp.float32)
    with force_impl("pallas"):
        jaxpr = jax.make_jaxpr(
            lambda x: layer_norm(x, g, jnp.zeros_like(g)))(x)
    assert "apex1_layer_norm_fwd" in str(jaxpr)


# -- compiled for a described v5e -------------------------------------------

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
    persistent cache off, as `test_benchmark_aot.py` sets them."""
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


def _tiny():
    import benchmark_testlib as lib
    files = lib.tiny_files()
    return (lib, dict(files["benchmark/configs/gpt2-tiny.json"],
                      _name="gpt2-tiny"),
            files["benchmark/traffic/tiny_train.json"])


def _kernels(compiled) -> collections.Counter:
    found = collections.Counter(KERNEL_RE.findall(compiled.as_text()))
    assert found, "the composites, not the kernels"
    bare = [n for n in found if not re.fullmatch(r"apex1_[a-z0-9_]+", n)]
    assert not bare, f"Pallas custom-calls without a kernel's name: {bare}"
    return found


def test_training_step_names_every_kernel(topo, mosaic):
    from benchmark.harness import train
    _, cfg, traffic = _tiny()
    pieces = train.make_step(cfg, traffic, list(topo.devices)[:1])
    state, batch = train.abstract_args(pieces)
    found = _kernels(pieces["step"].lower(state, batch).compile())
    layers = cfg["n_layer"]
    assert found["apex1_flash_fwd"] == found["apex1_flash_dq"] \
        == found["apex1_flash_dkv"] == layers
    assert found["apex1_layer_norm_fwd"] == found["apex1_layer_norm_bwd"] \
        == 2 * layers + 1
    # right under `jvp` / `transpose(jvp)`, with no module scope between
    assert {"apex1_linear_xent_fwd", "apex1_linear_xent_dx",
            "apex1_linear_xent_dw"} <= set(found)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_engine_executables_name_every_kernel(topo, mosaic, paged):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from apex1_tpu.serving.engine import Engine, EngineConfig
    from benchmark.harness import builders
    lib, cfg, _ = _tiny()
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
        vocab_size=b.vocab_size, paged=paged, **lib.TINY_ENGINE))
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=s1)
    chunk = jax.ShapeDtypeStruct((1, lib.TINY_ENGINE["prefill_chunk"]),
                                 jnp.int32, sharding=s1)
    ctl = place((eng._d_toks, eng._d_idxs, eng._d_active, eng._d_seeds,
                 eng._d_pos))
    if paged:
        pool = (place(eng.kv.pages), place(eng._d_bt))
        pre = (*pool, i32, chunk, i32, i32, i32)
    else:
        pool = (place(eng.kv.cache),)
        pre = (*pool, i32, place(eng.kv.zeros_lane),
               jax.ShapeDtypeStruct((), jnp.bool_, sharding=s1), chunk,
               i32, i32, i32)
    for lowered in (eng._prefill.lower(params, *pre),
                    eng._decode.lower(params, *pool, *ctl)):
        found = _kernels(lowered.compile())
        assert found["apex1_layer_norm_fwd"] == 2 * cfg["n_layer"] + 1
        if paged:
            assert found["apex1_paged_attend"] == cfg["n_layer"]
            assert found["apex1_fused_sample"] == 1
