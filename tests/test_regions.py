"""The program's regions (`apex1_tpu/obs/regions.py`): the segment's form
and what `region_of` reads from it; on the compiled tiny programs of the
three model families (train step, decode, prefill) every instruction the
program wrote lies in a region; the scopes change nothing that is lowered.
Counts and texts only: a CPU run is never a speed."""

import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from apex1_tpu.obs import regions
from apex1_tpu.obs.regions import REGIONS, region, region_of


@pytest.fixture(scope="module", autouse=True)
def metadata_in_the_cache_key():
    """JAX's persistent compile cache leaves an instruction's metadata
    out of its key, so an executable compiled before a scope existed (a
    checkout's `.jax_cache` from an earlier commit) would be loaded in
    place of this tree's, with the old paths. These tests read paths out
    of compiled programs: they key the cache by the metadata too."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    saved = getattr(jax.config, flag)
    jax.config.update(flag, True)
    yield
    jax.config.update(flag, saved)


@pytest.mark.parametrize("path,want", [
    ("jit(train_step)/jvp(GPT2)/h3/~attn/qkv/dot_general", ("attn", "fwd")),
    ("jit(train_step)/transpose(jvp(GPT2))/h3/~ffn/fc_in/dot_general",
     ("ffn", "bwd")),
    # a transform wraps the FIRST scope under it, a region's too
    ("jit(f)/jvp(~attn)/tanh", ("attn", "fwd")),
    ("jit(f)/transpose(jvp(~head))/mul", ("head", "bwd")),
    # the innermost wins: a norm inside the mixer is the norm's
    ("jit(decode)/~engine/M/layer0/~mixer/~norm/rsqrt", ("norm", "fwd")),
    ("jit(decode)/~engine/slice", ("engine", "fwd")),
    ("jit(train_step)/~optim/mul", ("optim", "fwd")),
    ("~amp", ("amp", "fwd")),
    # no region: a module may be CALLED attn, only the mark makes one
    ("jit(step)/jvp(M)/attn/dot_general", None),
    ("jit(step)/jvp(M)/x~attn/dot_general", None),
    ("jit(step)/~attnx/dot_general", None),
    ("jit(step)/~bogus/add", None),
    ("jit(step)/@attn/add", None),
    ("", None), (None, None),
])
def test_region_of(path, want):
    assert region_of(path) == want


def test_region_is_one_closed_list_and_one_form():
    assert len(set(REGIONS)) == len(REGIONS) == 9
    for name in REGIONS:
        jaxpr = jax.make_jaxpr(
            lambda x, name=name: _in(name, x))(jnp.ones(3))
        (eqn,) = jaxpr.eqns
        assert str(eqn.source_info.name_stack) == regions.MARK + name
    with pytest.raises(ValueError, match="no region"):
        region("mlp")


def _in(name, x):
    with region(name):
        return x + 1


def test_the_mark_survives_xlas_export_and_an_at_sign_does_not():
    """Why the mark is `~`: XLA's export of an instruction's location
    cuts the name at its first `@`, so a scope `@attn` takes the whole
    path behind it out of `op_name`, the primitive too."""
    def f(x):
        with jax.named_scope("@attn"):
            y = jnp.tanh(x)
        with region("ffn"):
            return jnp.sin(y)

    text = jax.jit(f).lower(jnp.ones((8,))).compile().as_text()
    paths = re.findall(r'op_name="([^"]*)"', text)
    assert "jit(f)/~ffn/sin" in paths
    assert not any("attn" in p for p in paths)


# ---- the three families' tiny programs -------------------------------------

def _head_loss(logits, tokens):
    with region("head"):
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1))


def _programs():
    """{name: a function that lowers the program}: the Amp train step of
    each family's tiny model, and each family's decode and prefill
    executables out of a tiny engine."""
    from apex1_tpu.amp import Amp
    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.generate import (gpt2_decoder,
                                           granite_hybrid_decoder,
                                           lfm2_moe_decoder)
    from apex1_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn
    from apex1_tpu.models.granite_hybrid import (GraniteHybrid,
                                                 GraniteHybridConfig)
    from apex1_tpu.models.lfm2 import Lfm2Moe, Lfm2MoeConfig
    from apex1_tpu.optim.fused_adam import fused_adam
    from apex1_tpu.serving import Engine, EngineConfig
    pol = get_policy("O2")
    toks = jnp.zeros((2, 32), jnp.int32)
    families = {
        "gpt2": (GPT2(GPT2Config.tiny(policy=pol)), gpt2_decoder, 256, {}),
        "granite": (GraniteHybrid(GraniteHybridConfig.tiny(policy=pol)),
                    granite_hybrid_decoder, 512, {"prefix_cache": False}),
        "lfm2": (Lfm2Moe(Lfm2MoeConfig.tiny(policy=pol)), lfm2_moe_decoder,
                 512, {"prefix_cache": False}),
    }
    out = {}
    for tag, (model, decoder, vocab, kw) in families.items():
        params = model.init(jax.random.key(0), toks)["params"]
        loss_fn = (gpt2_loss_fn(model) if tag == "gpt2" else
                   lambda p, t, model=model: _head_loss(
                       model.apply({"params": p}, t), t))

        def train(params=params, loss_fn=loss_fn):
            amp = Amp(tx=fused_adam(1e-3), opt_level="O2")
            step = jax.jit(amp.make_train_step(loss_fn))
            return step.lower(amp.init(params), toks)

        def engine(which, model=model, decoder=decoder, params=params,
                   vocab=vocab, kw=kw):
            eng = Engine(*decoder(model), params, EngineConfig(
                max_slots=3, max_len=96, prefill_chunk=16,
                eos_id=vocab - 1, vocab_size=vocab, **kw))
            if which == "decode":
                return eng._decode.lower(
                    params, eng.kv.cache, eng._d_toks, eng._d_idxs,
                    eng._d_active, eng._d_seeds, eng._d_pos)
            i32 = jnp.zeros((), jnp.int32)
            return eng._prefill.lower(
                params, eng.kv.cache, i32, eng.kv.zeros_lane,
                jnp.zeros((), jnp.bool_), jnp.zeros((1, 16), jnp.int32),
                i32, i32, i32)

        out[tag + "_train"] = train
        out[tag + "_decode"] = lambda engine=engine: engine("decode")
        out[tag + "_prefill"] = lambda engine=engine: engine("prefill")
    return out


PROGRAMS = ["gpt2_train", "gpt2_decode", "gpt2_prefill", "granite_train",
            "granite_decode", "granite_prefill", "lfm2_train",
            "lfm2_decode", "lfm2_prefill"]
#: what a family's programs must hold (every one holds `norm`)
EXPECTED = {"gpt2": {"embed", "attn", "ffn", "norm", "head"},
            "granite": {"embed", "attn", "mixer", "ffn", "norm", "head"},
            "lfm2": {"embed", "attn", "mixer", "ffn", "norm", "head"}}
#: the opcodes that are a program's plumbing, with or without a path
PLUMBING = {"parameter", "tuple", "get-tuple-element", "constant"}
INSTR_RE = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([\w\-]+)\(")
#: a path with no scope at all: `jit(train_step)/jit(tril)/iota`
HOISTED_RE = re.compile(r"^jit\(\w+\)/(?:jit\(\w+\)/)*[\w\-]+$")


@pytest.fixture(scope="module")
def programs():
    return _programs()


@pytest.mark.parametrize("name", PROGRAMS)
def test_every_instruction_the_program_wrote_lies_in_a_region(programs,
                                                              name):
    """Every instruction of the compiled program whose path is the
    program's (`jit(...)/...`) has a region, the plumbing apart; what the
    COMPILER made carries no path at all (a reader gives it its user's),
    and a reducer's body carries the bare primitive."""
    text = programs[name]().compile().as_text()
    found, bare = set(), []
    for line in text.splitlines():
        m = INSTR_RE.match(line)
        path = re.search(r'op_name="([^"]*)"', line)
        if not m or not path or not path.group(1).startswith("jit("):
            continue
        got = region_of(path.group(1))
        if got is not None:
            found.add(got)
        elif m.group(1) not in PLUMBING:
            bare.append(path.group(1))
    if " while(" in text:
        # a scan's plumbing: the backward pass of a scan lifts what does
        # not change with the step (the chunk's causal mask) out of its
        # body and evaluates it outside every scope, under the step's
        # own `jit(...)` alone
        lifted = [p for p in bare if HOISTED_RE.match(p)]
        assert len(lifted) <= 12, lifted
        bare = [p for p in bare if p not in lifted]
    assert not bare, bare[:5]
    family, kind = name.split("_")
    want = set(EXPECTED[family])
    have = {r for r, _ in found}
    if kind == "train":
        want |= {"amp", "optim"}
        assert {("attn", "bwd"), ("ffn", "bwd"), ("norm", "bwd"),
                ("head", "bwd"), ("optim", "fwd")} <= found
        # the casts and the loss's scale are transposed too; the
        # optimizer runs after the backward pass
        assert ("amp", "bwd") in found and ("optim", "bwd") not in found
    else:
        want |= {"engine"}
        assert all(phase == "fwd" for _, phase in found)
    assert want <= have, want - have


@pytest.mark.parametrize("name", PROGRAMS)
def test_scopes_change_nothing_that_is_lowered(programs, name, monkeypatch):
    """The StableHLO text is the same with the regions' scopes and with
    them taken out (`jax.named_scope` left as it is for every other
    name): sha256 of `.lower(...).as_text()`. The text WITH locations
    holds the scopes, so they were there to make a difference."""
    import contextlib
    with_scopes = programs[name]()
    assert "~norm" in with_scopes.as_text(debug_info=True)
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope",
        lambda n: contextlib.nullcontext() if n.startswith(regions.MARK)
        else real(n))
    jax.clear_caches()
    without = programs[name]()
    monkeypatch.undo()
    jax.clear_caches()
    assert regions.MARK + "norm" not in without.as_text(debug_info=True)
    digest = lambda low: hashlib.sha256(low.as_text().encode()).hexdigest()
    assert digest(with_scopes) == digest(without)
