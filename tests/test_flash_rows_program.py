"""The programs around the flash kernels' ROWS layout (`ops.attention.
flash_form`), beside `tests/test_kernel_names.py`.

- Compiled for a described v5e: the training step of a tiny GPT-2 with
  heads of 64 holds one `apex1_flash_fwd` / `_dq` / `_dkv` a layer and,
  inside the `~attn` region, NO pad, NO split and NO turn of a per-head
  array: the kernels read the qkv product's output as it lies and write
  where `proj` and the qkv product's backward read. A model whose heads do
  not fill a lane block (one head of 64) takes the HEADS layout and shows
  every one of those ops: the check can fail. A compile is not a chip run.
- Lowered here: the prefill and decode programs of the three served
  families are the programs of the commit before the rows layout (PR 41),
  by the sha256 of their lowered text, with the composites and with the
  kernels forced: no serving program runs a flash kernel, and none moved.
"""

import collections
import hashlib
import os
import re
import sys

import pytest

# the described v5e and the custom-calls' names are `test_kernel_names.py`'s
from test_kernel_names import KERNEL_RE, topo  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))

#: `%name = <shape> <opcode>(` of one compiled instruction
INSTR_RE = re.compile(r'^\s+(?:ROOT )?%[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(')


# -- the served families' programs are the parent's -------------------------

def _served_models():
    from apex1_tpu.models.generate import (gpt2_decoder,
                                           granite_hybrid_decoder,
                                           lfm2_moe_decoder)
    from apex1_tpu.models.gpt2 import GPT2, GPT2Config
    from apex1_tpu.models.granite_hybrid import (GraniteHybrid,
                                                 GraniteHybridConfig)
    from apex1_tpu.models.lfm2 import Lfm2Moe, Lfm2MoeConfig
    return {
        "gpt2": (GPT2(GPT2Config.tiny()), gpt2_decoder),
        "granite_hybrid": (GraniteHybrid(GraniteHybridConfig.tiny()),
                           granite_hybrid_decoder),
        "lfm2_moe": (Lfm2Moe(Lfm2MoeConfig.tiny()), lfm2_moe_decoder),
    }


def served_program_hashes() -> dict:
    """{"<family>/<prefill|decode>/<xla|pallas>": sha256 of the lowered
    text}: a chunk of 16 tokens into lane 0 of a fresh cache, and one
    token a lane for 4 lanes at their own depths."""
    import jax
    import jax.numpy as jnp
    from apex1_tpu.ops import force_impl
    out = {}
    for name, (model, decoder) in _served_models().items():
        apply_fn, make_cache = decoder(model)
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32)))["params"]
        sds = jax.ShapeDtypeStruct
        args = {
            "prefill": (sds((1, 16), jnp.int32),
                        jax.eval_shape(lambda: make_cache(1, 64)),
                        sds((), jnp.int32)),
            "decode": (sds((4, 1), jnp.int32),
                       jax.eval_shape(lambda: make_cache(4, 64)),
                       sds((4,), jnp.int32)),
        }
        for impl in ("xla", "pallas"):
            for prog in args:
                # a function of its own a lowering: jit's trace cache
                # does not key on the forced implementation
                def fn(p, t, c, i, decode=prog == "decode"):
                    return apply_fn(p, t, c, i, chunk_decode=decode)
                with force_impl(impl):
                    text = jax.jit(fn).lower(params, *args[prog]).as_text()
                out[f"{name}/{prog}/{impl}"] = hashlib.sha256(
                    text.encode()).hexdigest()[:16]
    return out


#: `served_program_hashes()` at d76ddfc, the commit before PR 41 (run with
#: that tree on the path: `PYTHONPATH=<tree> python -c "import sys;
#: sys.path.insert(0, 'tests'); import test_flash_rows_program as t;
#: print(t.served_program_hashes())"`). A later PR that changes a serving
#: program on purpose computes them anew, and says so: PR 50 read the
#: three `decode/pallas` entries anew (the step kernel inside them keeps a
#: queue of fetches; `ops/decode_attend.py`), the other nine are d76ddfc's.
PARENT_HASHES = {
    "gpt2/prefill/xla": "328f7e3503aaa4ea",
    "gpt2/decode/xla": "20a95c8604206260",
    "gpt2/prefill/pallas": "251c8d9df97e4377",
    "gpt2/decode/pallas": "7a33415212ecc78e",
    "granite_hybrid/prefill/xla": "7b5b4026640f4ba2",
    "granite_hybrid/decode/xla": "42b168ec3a7fb121",
    "granite_hybrid/prefill/pallas": "0f097d116873aa9e",
    "granite_hybrid/decode/pallas": "87be7b03ff7dc6ec",
    "lfm2_moe/prefill/xla": "bdd5faba9a14df1b",
    "lfm2_moe/decode/xla": "f39f47cdab8001c4",
    "lfm2_moe/prefill/pallas": "b2ad05aa6a7759a3",
    "lfm2_moe/decode/pallas": "3061e50a9b12c894",
}


def test_served_programs_are_the_parents():
    import apex1_tpu.ops._common as common
    assert not common.on_tpu() and common.interpret_mode(), \
        "lowered under a fixture that swaps the interpreter out"
    got = served_program_hashes()
    assert len(got) == 12
    assert got == PARENT_HASHES


# -- compiled for a described v5e -------------------------------------------

@pytest.fixture()
def mosaic(topo):
    """`test_kernel_names.py`'s fixture, the real kernels for the
    described chip with the persistent cache off, for ONE test: the
    programs lowered above must never meet it."""
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


def _compiled_step(topo, n_head, n_embd):
    import benchmark_testlib as lib
    from benchmark.harness import train
    files = lib.tiny_files()
    cfg = dict(files["benchmark/configs/gpt2-tiny.json"], _name="gpt2-tiny",
               n_head=n_head, n_embd=n_embd)
    traffic = files["benchmark/traffic/tiny_train.json"]
    pieces = train.make_step(cfg, traffic, list(topo.devices)[:1])
    state, batch = train.abstract_args(pieces)
    return cfg, pieces["step"].lower(state, batch).compile().as_text()


def _attn_moves(text):
    """Instructions of the `~attn` region that only MOVE a per-head array:
    a pad, a slice, or a copy / transpose of an array of four axes or
    more, by opcode and shape."""
    found = collections.Counter()
    for line in text.splitlines():
        m = INSTR_RE.match(line)
        if not m or "~attn" not in line:
            continue
        shape, opcode = m.groups()
        dims = re.match(r"\w+\[([\d,]*)\]", shape)
        rank = len(dims.group(1).split(",")) if dims else 0
        if opcode in ("pad", "slice") or (
                opcode in ("copy", "transpose") and rank >= 4):
            found[f"{opcode} {re.sub(r'{.*', '', shape)}"] += 1
    return found


def test_rows_layout_leaves_nothing_to_move_in_the_attn_region(topo, mosaic):
    """Two heads of 64 fill a 128-lane block: `fmha` takes the ROWS
    layout, and XLA has nothing left to pad, split or turn."""
    from apex1_tpu.ops.attention import flash_form
    cfg, text = _compiled_step(topo, n_head=2, n_embd=128)
    assert flash_form(2, 2, 64, 64, 64, packed=True)["layout"] == "rows"
    kernels = collections.Counter(KERNEL_RE.findall(text))
    assert kernels["apex1_flash_fwd"] == kernels["apex1_flash_dq"] \
        == kernels["apex1_flash_dkv"] == cfg["n_layer"]
    assert "~attn" in text, "no regions in the compiled text"
    assert not _attn_moves(text), _attn_moves(text)


def test_heads_layout_still_pads_and_turns(topo, mosaic):
    """The control: ONE head of 64 cannot fill a lane block, `fmha` turns
    its array and `flash_attention` pads each head to 128 lanes — the ops
    the test above must not find."""
    from apex1_tpu.ops.attention import flash_form
    cfg, text = _compiled_step(topo, n_head=1, n_embd=64)
    assert flash_form(1, 1, 64, 64, 64, packed=True)["layout"] == "heads"
    kernels = collections.Counter(KERNEL_RE.findall(text))
    assert kernels["apex1_flash_fwd"] == kernels["apex1_flash_dq"] \
        == kernels["apex1_flash_dkv"] == cfg["n_layer"]
    moves = _attn_moves(text)
    assert any(k.startswith("pad ") for k in moves), moves
