"""What `serving.Engine` hands its executables (`serving.packing`): the
small leaves of the caller's tree stacked by shape, dtype and sharding,
every other leaf the caller's own buffer.

- an engine launched with packed operands serves, token for token, what
  `generate` serves from the caller's tree: GPT-2 dense, paged and
  speculative, a hybrid decoder with recurrent state, a Llama with LoRA;
- the count `operands` on the launch's span is the arrays the launch is
  handed, and for a 2-layer GPT-2 the number reckoned by hand;
- a tree with nothing to stack, and one whose equal-shaped leaves differ
  in dtype (or sharding), is served unpacked and right;
- `engine.params` is the caller's tree and no large leaf is copied;
- an engine still goes with its last reference under a frozen collector.

CPU: counts and tokens, never a time.
"""

import dataclasses
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex1_tpu.core.policy import get_policy
from apex1_tpu.models.generate import (generate, gpt2_decoder,
                                       granite_hybrid_decoder,
                                       llama_decoder)
from apex1_tpu.models.gpt2 import GPT2, GPT2Config
from apex1_tpu.models.granite_hybrid import (GraniteHybrid,
                                             GraniteHybridConfig)
from apex1_tpu.models.llama import Llama, LlamaConfig
from apex1_tpu.obs import spine
from apex1_tpu.serving import packing
from apex1_tpu.serving.engine import Engine, EngineConfig

#: a 2-layer GPT-2 whose matrices are large (256 KiB and up) and whose
#: vectors are small, as a published one's are
GPT2_CFG = dataclasses.replace(
    GPT2Config.tiny(policy=get_policy("O0"), max_seq_len=64),
    hidden_size=256, vocab_size=512)
HYBRID_CFG = GraniteHybridConfig.tiny(embedding_multiplier=1.0)
LLAMA_CFG = LlamaConfig(vocab_size=97, hidden_size=32, num_layers=2,
                        num_heads=4, num_kv_heads=2, ffn_size=64,
                        max_seq_len=64)


def _seeded(shapes, seed):
    """0.1 * normal, a leaf named `*scale` 1 + that: every layer weighs
    in the logits, so a leaf handed over wrong changes a token."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    out = []
    for (path, s), k in zip(leaves, keys):
        noise = 0.1 * jax.random.normal(k, s.shape, jnp.float32)
        name = str(getattr(path[-1], "key", path[-1]))
        out.append((1.0 + noise if name.endswith("scale") else noise)
                   .astype(s.dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def _family(name):
    """(decoder pair, seeded params, vocabulary) of a tiny model."""
    model, decoder, vocab = {
        "gpt2": (GPT2(GPT2_CFG), gpt2_decoder, GPT2_CFG.vocab_size),
        "hybrid": (GraniteHybrid(HYBRID_CFG), granite_hybrid_decoder,
                   HYBRID_CFG.vocab_size),
        "llama": (Llama(LLAMA_CFG), llama_decoder, LLAMA_CFG.vocab_size),
    }[name]
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    return decoder(model), _seeded(shapes, 7), vocab


@pytest.fixture(scope="module")
def families():
    return {name: _family(name) for name in ("gpt2", "hybrid", "llama")}


def _solo(dec, params, vocab, prompt, n_new):
    apply_fn, make_cache = dec
    return np.asarray(generate(
        apply_fn, params, jnp.asarray(prompt, jnp.int32)[None],
        max_new_tokens=n_new, cache=make_cache(1, 64),
        vocab_size=vocab))[0]


def _serve(eng, vocab, seed=0):
    """Five requests over three slots, joining while others decode."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, n).astype(np.int32)
               for n in (5, 16, 21, 9, 12)]
    outs = [7, 5, 9, 6, 8]
    ids = [eng.submit(p, o) for p, o in zip(prompts[:2], outs[:2])]
    eng.step()
    ids += [eng.submit(p, o) for p, o in zip(prompts[2:], outs[2:])]
    eng.run(max_steps=200)
    return [(p, o, eng.results[rid].tokens)
            for p, o, rid in zip(prompts, outs, ids)]


CASES = {
    "gpt2-dense": ("gpt2", {}),
    "gpt2-paged": ("gpt2", dict(paged=True)),
    "gpt2-speculative": ("gpt2", dict(num_draft=2)),
    "hybrid-dense": ("hybrid", dict(prefix_cache=False)),
    "llama-lora": ("llama", dict(lora_rank=2)),
}


@pytest.mark.parametrize("case", CASES)
def test_served_from_packed_operands_is_generate_from_the_tree(families,
                                                               case):
    family, asked = CASES[case]
    dec, params, vocab = families[family]
    extra = {"lora_head": params["output"]} if "lora_rank" in asked else {}
    eng = Engine(*dec, params, EngineConfig(
        max_slots=3, max_len=48, prefill_chunk=8, vocab_size=vocab,
        **asked), **extra)
    layout = eng._packed.layout
    assert layout.groups, "nothing was stacked: the case proves nothing"
    assert layout.n_operands < len(jax.tree_util.tree_leaves(params))
    streams = set()
    for prompt, n_new, got in _serve(eng, vocab):
        np.testing.assert_array_equal(
            got, _solo(dec, params, vocab, prompt, n_new))
        streams.add(tuple(got))
    assert len(streams) == 5
    step = "verify" if "num_draft" in asked else "decode"
    assert eng.trace_counts == {"prefill": 1, step: 1}


def _handed_over(executable):
    """Wrap an engine executable's jitted body: the array count of
    every call lands in the returned list."""
    seen = []
    inner = executable._jit

    def counting(*args):
        seen.append(len(jax.tree_util.tree_leaves(args)))
        return inner(*args)

    executable._jit = counting
    return seen


@pytest.mark.parametrize("asked,reckoned", [
    # 9 matrices + wpe (alone of its shape) + the stacks of (256,),
    # (768,) and (1024,) vectors; 2 layers x K, V; 5 control vectors
    ({}, 13 + 4 + 5),
    (dict(paged=True), 13 + 4 + 1 + 5),
    (dict(num_draft=2), 13 + 4 + 5 + 1),
], ids=["dense", "paged", "speculative"])
def test_the_span_counts_the_arrays_the_launch_hands_over(families, asked,
                                                          reckoned):
    dec, params, vocab = families["gpt2"]
    assert len(jax.tree_util.tree_leaves(params)) == 28
    eng = Engine(*dec, params, EngineConfig(
        max_slots=3, max_len=48, prefill_chunk=8, vocab_size=vocab,
        eos_id=vocab - 1, **asked))
    spec = "num_draft" in asked
    step_seen = _handed_over(eng._verify if spec else eng._decode)
    prefill_seen = _handed_over(eng._prefill)
    t0 = spine.monotonic_ns()
    _serve(eng, vocab)
    spans = [r for r in spine.snapshot() if r.start_ns >= t0]
    launch = "serving/verify_step" if spec else "serving/decode_step"
    on_step = {r.counts["operands"] for r in spans if r.name == launch}
    on_prefill = {r.counts["operands"] for r in spans
                  if r.name == "serving/prefill"}
    assert step_seen and on_step == set(step_seen) == {reckoned}
    assert prefill_seen and on_prefill == set(prefill_seen)
    # a dense prefill is also handed the lane to install (4 leaves) and
    # six scalars; a paged one the block table and five
    assert on_prefill == {13 + 4 + (1 + 5 if "paged" in asked else 4 + 6)}


def _unalike(params, how):
    """``(tree, restore)``: ``params`` with no two leaves alike, and the
    map that gives the model its own leaves back. The k-th leaf of a
    shape is padded by k rows (``how`` "shapes") or stored in the k-th
    of three dtypes ("dtypes")."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    seen, out = {}, []
    for x in leaves:
        k = seen[x.shape] = seen.get(x.shape, -1) + 1
        if how == "shapes":
            x = jnp.pad(x, [(0, k)] + [(0, 0)] * (x.ndim - 1))
        else:
            x = x.astype([jnp.float32, jnp.bfloat16, jnp.float16][k])
        out.append(x)

    def restore(tree):
        return jax.tree_util.tree_map(
            lambda x, own: x[tuple(slice(0, d) for d in own.shape)]
            .astype(own.dtype), tree, params)

    return treedef.unflatten(out), restore


@pytest.mark.parametrize("how", ["shapes", "dtypes"])
def test_a_tree_with_no_two_leaves_alike_is_served_unpacked(how):
    """A 1-layer Llama has at most three leaves of a shape (its norms).
    With one leaf of each shape, or with equal-shaped leaves that differ
    in dtype, nothing shares a stack: the operands ARE the caller's
    leaves, and the tokens are `generate`'s from the same tree."""
    cfg = dataclasses.replace(LLAMA_CFG, num_layers=1)
    model = Llama(cfg)
    apply_fn, make_cache = llama_decoder(model)
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    tree, restore = _unalike(_seeded(shapes, 5), how)

    def apply_restored(params, *args, **kw):
        return apply_fn(restore(params), *args, **kw)

    dec = (apply_restored, make_cache)
    eng = Engine(*dec, tree, EngineConfig(
        max_slots=3, max_len=48, prefill_chunk=8,
        vocab_size=cfg.vocab_size))
    leaves = jax.tree_util.tree_leaves(tree)
    assert eng._packed.layout.groups == []
    assert len(eng._packed.operands) == len(leaves)
    assert all(a is b for a, b in zip(eng._packed.operands, leaves))
    for prompt, n_new, got in _serve(eng, cfg.vocab_size):
        np.testing.assert_array_equal(
            got, _solo(dec, tree, cfg.vocab_size, prompt, n_new))


def test_leaves_that_differ_in_sharding_never_share_a_stack():
    """Shapes alone (an engine built to be lowered): four (64,) vectors
    on one device and two on another make two stacks, each with its
    members' sharding; the vector that is alone of its sharding, the
    matrix and what is no array are handed over as they are."""
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)
    devs = jax.devices()
    here, there = (SingleDeviceSharding(d) for d in devs[:2])
    spread = NamedSharding(Mesh(np.asarray(devs[:2]), ("x",)),
                           PartitionSpec("x"))

    def vec(sharding, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((64,), dtype, sharding=sharding)

    big = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16, sharding=here)
    tree = {"a": [vec(here), vec(there), vec(here), vec(spread)],
            "b": [vec(there), vec(here), vec(here)], "w": big, "n": 3}
    layout = packing.Layout(tree)
    operands = layout.pack(tree)
    assert [(o.shape, o.sharding) for o in operands[:2]] == [
        ((4, 64), here), ((2, 64), there)]
    assert layout.n_operands == len(operands) == 2 + 3
    assert {id(o) for o in operands[2:]} == {
        id(tree["a"][3]), id(big), id(tree["n"])}
    # cut apart again, every leaf is back at its own place
    flat, treedef = jax.tree_util.tree_flatten(tree)
    numbered = treedef.unflatten([
        jnp.full(s.shape, i, s.dtype) if hasattr(s, "shape") else s
        for i, s in enumerate(flat)])
    back = jax.tree_util.tree_leaves(layout.unpack(layout.pack(numbered)))
    assert [int(np.ravel(x)[0]) for x in back] == [
        i if hasattr(s, "shape") else 3 for i, s in enumerate(flat)]
    assert [np.shape(x) for x in back] == [np.shape(x) for x in flat]


def test_params_stay_the_callers_and_no_large_leaf_is_copied(families):
    dec, params, vocab = families["gpt2"]
    eng = Engine(*dec, params, EngineConfig(
        max_slots=2, max_len=32, prefill_chunk=8, vocab_size=vocab))
    assert eng.params is params
    layout, operands = eng._packed.layout, eng._packed.operands
    leaves = jax.tree_util.tree_leaves(params)
    large = [x for x in leaves if x.nbytes > packing.SMALL_BYTES]
    assert len(large) == 9
    handed = {id(x) for x in operands}
    assert all(id(x) in handed for x in large)
    assert {x.unsafe_buffer_pointer() for x in large} <= {
        x.unsafe_buffer_pointer() for x in operands}
    # the stacks hold the small leaves' values, row for row
    for g, members in enumerate(layout.groups):
        for row, i in enumerate(members):
            np.testing.assert_array_equal(operands[g][row], leaves[i])
    # the engine's own tree is launched with the operands made once;
    # another tree of the same structure is packed anew
    assert eng._packed.operands_of(params) is operands
    other = jax.tree_util.tree_map(lambda x: x + 1, params)
    assert eng._packed.operands_of(other) is not operands
    with pytest.raises(ValueError, match="another structure"):
        eng._packed.operands_of({"wte": params["wte"]})


def test_an_engine_of_packed_operands_goes_with_its_last_reference(
        families):
    """As PR 34's test for the hybrid decoder (`test_granite_hybrid.py`):
    no cycle through the executables or what they are launched with."""
    dec, params, vocab = families["gpt2"]
    eng = Engine(*dec, params, EngineConfig(
        max_slots=2, max_len=32, prefill_chunk=8, vocab_size=vocab))
    eng.submit(np.arange(12, dtype=np.int32), 4)
    eng.run()
    gc.collect()
    gc.freeze()
    try:
        refs = [weakref.ref(eng), weakref.ref(eng._packed.operands[0]),
                weakref.ref(jax.tree_util.tree_leaves(eng.kv.cache)[0])]
        del eng
        assert [r() for r in refs] == [None, None, None]
    finally:
        gc.unfreeze()


@pytest.mark.parametrize("program", ["packed", "no-operands", "no-steps"])
def test_the_launch_metric_reads_the_window_s_launches(families, program,
                                                       capsys):
    """`engine.launch_ms_p50.chat`'s own reader: the median of the
    `serving/decode_step` spans under the window's LAST step spans, with
    their `operands` printed; spans without the count (a commit from
    before PR 35) are read all the same, and no step spans is None."""
    from benchmark.harness import manifest
    read = manifest.load_layer_metric(
        "engine.launch_ms_p50.chat")["_module"].read
    if program == "packed":
        dec, params, vocab = families["gpt2"]
        eng = Engine(*dec, params, EngineConfig(
            max_slots=3, max_len=48, prefill_chunk=8, vocab_size=vocab,
            eos_id=vocab - 1))
        t0 = spine.monotonic_ns()
        _serve(eng, vocab)
        said = f"operands a launch [{eng._n_operands['step']}]"
    else:
        t0 = spine.monotonic_ns()
        for _ in range(4):
            with spine.span("serving/step"):
                with spine.span("serving/decode_step"):
                    pass
        said = "operands a launch not counted"
    spans = [r for r in spine.snapshot() if r.start_ns >= t0]
    launches = [r for r in spans if r.name == "serving/decode_step"]
    n_steps = sum(r.name == "serving/step" for r in spans)
    if program == "no-steps":
        assert read({"scalars": {"window.steps": 0}}) is None
        return
    # the last three steps only: the launches under them, no other (the
    # last two of an engine two launches deep only hand out what is owed)
    value = read({"scalars": {"window.steps": 3}})
    last = set([r.id for r in spans if r.name == "serving/step"][-3:])
    want = [(r.end_ns - r.start_ns) * 1e-6 for r in launches
            if r.parent in last]
    assert n_steps > 3 and want
    assert value == pytest.approx(float(np.median(want)))
    assert said in capsys.readouterr().out


@pytest.mark.parametrize("gigabytes,generation,stacked", [
    (0.7, "v5e", True),     # 0.9 ms of stream against a launch of 1.3
    (6.4, "v5e", False),    # 7.8 ms: the launch lies under the step
    (6.4, None, True),      # off an accelerator nothing is known
], ids=["launch-shows", "launch-hidden", "no-accelerator"])
def test_a_tree_whose_stream_hides_the_launch_is_handed_over_as_it_is(
        gigabytes, generation, stacked):
    """Shapes alone: 100 matrices of ``gigabytes`` together and 200
    vectors. On a v5e (819 GB/s) the first is streamed in less than its
    launch takes and is packed; the second outlasts any launch
    (`packing.launch_is_hidden`) and every leaf is an operand of its
    own, as on a parent commit."""
    import contextlib
    from apex1_tpu.core import capability
    rows = int(gigabytes * 1e9 / 100 / 2 / 4096)
    tree = {"w": [jax.ShapeDtypeStruct((rows, 4096), jnp.bfloat16)] * 100,
            "b": [jax.ShapeDtypeStruct((4096,), jnp.bfloat16)] * 200}
    target = (capability.target_generation(generation) if generation
              else contextlib.nullcontext())
    with target:
        layout = packing.Layout(tree, n_other=53)
        hidden = packing.launch_is_hidden(
            jax.tree_util.tree_leaves(tree), 53)
    assert hidden is not stacked and layout.hidden is hidden
    assert layout.n_operands == (101 if stacked else 300)
    operands = layout.pack(tree)
    assert len(operands) == layout.n_operands
    if not stacked:
        assert all(a is b for a, b in zip(
            operands, jax.tree_util.tree_leaves(tree)))
