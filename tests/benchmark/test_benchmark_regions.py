"""The device's time by the program's regions, as the benchmark reads it
(`benchmark/harness/regions.py`) and as the program's own report does
(`apex1_tpu/obs/xspace.py`): the benchmark's copy of `region_of`; a
synthetic trace file (an op cut by the window's edge, one outside the main
module, a loop's body inside its `while`, a prefetch the compiler made); a
real CPU trace of the tiny train step; the recorded chip traces; and each
tiny cell's traced rehearsal held to the manifest's lists. Counts and
shares only: a CPU run is never a speed."""

import os

import jax
import jax.numpy as jnp
import pytest

import benchmark_testlib as lib
from apex1_tpu.obs import regions as program_regions
from apex1_tpu.obs import xspace
from benchmark.harness import regions, trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW_TRAIN = {"model.attn_ms.train", "model.mlp_ms.train",
             "model.norm_ms.train", "model.head_loss_ms.train",
             "step.optim_ms.train", "step.amp_ms.train",
             "step.unattributed_pct.train"}
NEW_CHAT = {"model.attn_ms.chat", "model.ffn_ms.chat", "model.head_ms.chat",
            "engine.body_ms.chat", "step.unattributed_pct.chat"}
MIXER = "model.mixer_ms.chat"


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


@pytest.mark.parametrize("path", [
    "jit(train_step)/jvp(GPT2)/h3/~attn/qkv/dot_general",
    "jit(train_step)/transpose(jvp(GPT2))/h3/~ffn/fc_in/dot_general",
    "jit(f)/transpose(jvp(~head))/mul", "jit(d)/~engine/M/~mixer/~norm/x",
    "jit(step)/jvp(M)/attn/dot_general", "jit(step)/~bogus/add",
    "jit(step)/@attn/add", "", None])
def test_the_benchmarks_copy_reads_a_path_as_the_programs_does(path):
    assert regions.region_of(path) == program_regions.region_of(path)
    assert regions.REGIONS == program_regions.REGIONS


def test_the_manifest_lists_the_new_metrics_by_class_of_cell():
    man = lib.mf.load_manifest(lib.ROOT)
    serving = ["gpt2m_serve_chat", "granite4hm_serve_chat",
               "lfm2moe_serve_rollout"]
    for m in man["per_layer"]:
        if m["name"] in NEW_TRAIN:
            assert m["workloads"] == ["gpt2m_train"], m
        elif m["name"] in NEW_CHAT:
            assert m["workloads"] == serving, m
        elif m["name"] == MIXER:        # GPT-2 has no such mixer
            assert m["workloads"] == serving[1:], m
        else:
            continue
        assert m["source"] == "device_trace" and m["better"] == "lower"
        assert m["unit"] == ("%" if "_pct" in m["name"] else "ms")
    names = {m["name"] for m in man["per_layer"]}
    assert NEW_TRAIN | NEW_CHAT | {MIXER} <= names


# ---- a synthetic trace file ------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(field: int, value) -> bytes:
    """One protobuf field: an int as a varint, bytes or str
    length-delimited."""
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _plane(name, lines=(), metadata=(), stat_names=()):
    """`lines`: [(line name, [(metadata id, start ns, dur ns)])];
    `metadata`: [(id, name, {stat id: bytes})]."""
    out = _f(2, name)
    for ln, events in lines:
        out += _f(3, _f(2, ln) + _f(3, 0) + b"".join(
            _f(4, _f(1, mid) + _f(2, s * 1000) + _f(3, d * 1000))
            for mid, s, d in events))
    for mid, mname, stats in metadata:
        meta = _f(1, mid) + _f(2, mname) + b"".join(
            _f(5, _f(1, sid) + _f(6, blob)) for sid, blob in stats.items())
        out += _f(4, _f(1, mid) + _f(2, meta))
    for sid, sname in stat_names:
        out += _f(5, _f(1, sid) + _f(2, _f(1, sid) + _f(2, sname)))
    return _f(1, out)


def _hlo(module: str, instructions) -> bytes:
    """An `HloProto` of one computation: [(id, name, op_name, operand
    ids)]."""
    comp = _f(1, "main") + b"".join(
        _f(2, _f(1, name) + (_f(7, _f(2, path)) if path else b"")
           + _f(35, uid) + (_f(36, b"".join(map(_varint, operands)))
                            if operands else b""))
        for uid, name, path, operands in instructions)
    return _f(1, _f(1, module) + _f(3, comp))


STEP = "jit_step(7)"
#: (id, instruction, path, operands): two regions' ops, a loop with its
#: body, the compiler's prefetch of a weight that the ffn reads, an op
#: without any path that nothing reads
INSTRUCTIONS = [
    (1, "fusion.1", "jit(step)/jvp(M)/h0/~attn/qkv/dot_general", []),
    (2, "fusion.2", "jit(step)/transpose(jvp(M))/h0/~attn/qkv/dot_general",
     [1]),
    (3, "while.3", "jit(step)/jvp(M)/h0/~mixer/while", [2]),
    (4, "fusion.4", "jit(step)/jvp(M)/h0/~mixer/while/body/mul", []),
    (5, "copy-start.5", "", []),
    (6, "copy-done.6", "", [5]),
    (7, "fusion.7", "jit(step)/jvp(M)/h0/~ffn/fc/dot_general", [6, 3]),
    (8, "copy.8", "", []),
]


def _hlo_text(uid):
    name = {i[0]: i[1] for i in INSTRUCTIONS}[uid]
    return f"%{name} = f32[8,128]{{1,0}} {name.split('.')[0]}(%p.0)"


def _synthetic(tmp_path):
    """Two steps of `jit_step`, 1000 ns each from 1000 and 3000, a prefill
    between them, the window [1500, 4000]: the first step is cut in
    half."""
    ids = {uid: 100 + uid for uid, *_ in INSTRUCTIONS}
    ops = []
    for base in (1000, 3000):
        ops += [(ids[1], base, 100), (ids[2], base + 100, 200),
                (ids[3], base + 300, 300),         # the loop ...
                (ids[4], base + 320, 100),         # ... and its body
                (ids[4], base + 450, 100),
                (ids[5], base + 600, 10), (ids[6], base + 700, 50),
                (ids[7], base + 750, 200), (ids[8], base + 960, 40)]
    ops.append((ids[1], 2200, 300))                # inside the prefill
    device = _plane(
        "/device:TPU:0",
        lines=[("XLA Modules", [(1, 1000, 1000), (2, 2100, 500),
                                (1, 3000, 1000)]),
               ("XLA Ops", sorted(ops, key=lambda e: e[1]))],
        metadata=[(1, STEP, {}), (2, "jit_prefill(9)", {})] + [
            (ids[uid], _hlo_text(uid), {}) for uid, *_ in INSTRUCTIONS])
    host = _plane("/host:CPU", lines=[("main", [(1, 1500, 2500)])],
                  metadata=[(1, "bench/window", {})])
    meta = _plane(regions.METADATA_PLANE,
                  metadata=[(1, STEP, {1: _hlo("jit_step", INSTRUCTIONS)}),
                            (2, "jit_prefill(9)", {1: _hlo("jit_prefill", [
                                (1, "fusion.1", "jit(prefill)/~head/x",
                                 [])])})],
                  stat_names=[(1, "Hlo Proto")])
    path = tmp_path / "synthetic.xplane.pb"
    path.write_bytes(device + host + meta)
    return str(path)


def test_a_synthetic_trace_by_both_readers(tmp_path):
    path = _synthetic(tmp_path)
    red = tr.reduce(path)
    assert red["main_module"] == "jit_step"
    assert red["n_steps"] == pytest.approx(1.5)
    got = regions.by_region(path, "jit_step")
    ns = lambda r, k: round(got["regions"][r][k + "_s"] * 1e9)
    # the second step whole; of the first what lies past 1500: its
    # attention not at all, and the op inside the prefill is the
    # prefill's
    assert ns("attn", "fwd") == 100 and ns("attn", "bwd") == 200
    # a loop counts its own time alone, 300 less two bodies of 100; the
    # first step's loop [1300, 1600] and its second body [1450, 1550] are
    # cut by the window's edge and count by their parts inside
    assert ns("mixer", "fwd") == (100 + 200) + (50 + 50)
    # the compiler's copy-start / copy-done take their reader's region
    assert ns("ffn", "fwd") == (10 + 50 + 200) * 2
    # nothing reads `copy.8`, nothing feeds it: no region reaches it
    assert round(got["unattributed"]["s"] * 1e9) == 80
    assert got["unattributed"]["top"] == [["copy_f32_8_128_",
                                           pytest.approx(80e-9)]]
    assert "head" not in got["regions"]
    total = sum(v["fwd_s"] + v["bwd_s"] for v in got["regions"].values())
    assert total + got["unattributed"]["s"] == pytest.approx(got["busy_s"])
    # ... which is the main module's busy time inside the window
    _, _, (ops,), (lo, hi) = regions._device_ops(tr.load(path), "jit_step")
    union = tr._length(tr._union(tr._clip(
        [(s, s + d) for _, _, s, d in ops], lo, hi))) * 1e-9
    assert got["busy_s"] == pytest.approx(union)
    assert got["n_steps"] == pytest.approx(1.5)
    # the program's own report reads the same file to the same seconds
    mine = xspace.build_report(path, window_span="bench/window",
                               module="jit_step")["by_region"]
    assert mine["module"] == "jit_step"
    assert mine["executions"] == pytest.approx(1.5)
    _same(mine, got)
    # and picks the module of most device time where none is named
    assert xspace.build_report(
        path, window_span="bench/window")["by_region"]["module"] == "jit_step"
    text = xspace.format_report(xspace.build_report(
        path, window_span="bench/window"))
    assert "by region, ms an execution of jit_step" in text
    assert "unattributed" in text and "mixer" in text


def _same(mine, got, rel=1e-3):
    assert set(mine["regions"]) == set(got["regions"])
    for r, row in got["regions"].items():
        for k in ("fwd_s", "bwd_s"):
            assert mine["regions"][r][k] == pytest.approx(row[k], rel=rel)
    assert mine["unattributed"]["s"] == pytest.approx(
        got["unattributed"]["s"], rel=rel, abs=1e-12)
    assert mine["busy_s"] == pytest.approx(got["busy_s"], rel=rel)


def test_the_metrics_readers_on_the_synthetic_trace(tmp_path):
    path = _synthetic(tmp_path)
    ctx = {"xplane": path, "trace": tr.reduce(path)}
    assert regions.region_ms(ctx, "attn") == pytest.approx(300e-6 / 1.5)
    # a region the program has not opened in this step reads 0, not None:
    # the cell's list promises a number wherever the program has regions
    assert regions.region_ms(ctx, "engine") == 0.0
    busy = regions.by_region(path, "jit_step")["busy_s"]
    assert regions.unattributed_pct(ctx) == pytest.approx(
        100 * 80e-9 / busy)
    for name, want in (("model.attn_ms.chat", 300e-6 / 1.5),
                       ("engine.body_ms.chat", 0.0)):
        spec = lib.mf.load_layer_metric(name, lib.ROOT)
        assert spec["_module"].read(ctx) == pytest.approx(want)
    # without a trace, and on a program that opens no scope (a parent
    # commit's), there is nothing to read: None, never a raise
    assert regions.region_ms({}, "attn") is None
    assert regions.unattributed_pct({"xplane": None}) is None


@pytest.mark.parametrize("name", ["gpt2_tiny_train.xplane.pb.gz",
                                  "ddp4_tiny.xplane.pb.gz"])
def test_a_recorded_chip_trace_of_a_program_without_scopes(name):
    """The traces recorded on a v5e before the program had regions: every
    op of the main module is unattributed, the sum is the main module's
    busy time, both readers agree, and a region metric reads nothing."""
    path = os.path.join(DATA, name)
    red = tr.reduce(path)
    got = regions.by_region(path, red["main_module"])
    assert got["regions"] == {} and got["n_steps"] == red["n_steps"]
    assert got["unattributed"]["s"] == pytest.approx(got["busy_s"])
    _, _, (ops,), (lo, hi) = regions._device_ops(tr.load(path),
                                                 red["main_module"])
    union = tr._length(tr._union(tr._clip(
        [(s, s + d) for _, _, s, d in ops], lo, hi))) * 1e-9
    assert got["busy_s"] == pytest.approx(union, rel=1e-9)
    mine = xspace.build_report(path, window_span="bench/window",
                               module=red["main_module"])["by_region"]
    _same(mine, got)
    ctx = {"xplane": path, "trace": red}
    assert regions.region_ms(ctx, "attn") is None
    assert regions.unattributed_pct(ctx) is None


# ---- a real CPU trace of the tiny train step -------------------------------

def test_a_real_cpu_trace_by_both_readers(tmp_path):
    from apex1_tpu.amp import Amp
    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn
    from apex1_tpu.optim.fused_adam import fused_adam
    toks = jnp.zeros((2, 32), jnp.int32)
    model = GPT2(GPT2Config.tiny(policy=get_policy("O2")))
    amp = Amp(tx=fused_adam(1e-3), opt_level="O2")
    state = amp.init(model.init(jax.random.key(0), toks)["params"])
    step = jax.jit(amp.make_train_step(gpt2_loss_fn(model)))
    state, metrics = step(state, toks)
    jax.block_until_ready(metrics)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        state, metrics = step(state, toks)
    jax.block_until_ready(metrics)
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    got = regions.by_region(path)
    assert got["module"] == "jit_train_step" and got["n_steps"] == 3
    assert {"embed", "attn", "ffn", "norm", "head", "amp",
            "optim"} == set(got["regions"])
    for r in ("attn", "ffn", "norm", "head"):
        assert got["regions"][r]["fwd_s"] > 0 < got["regions"][r]["bwd_s"]
    assert got["regions"]["optim"]["bwd_s"] == 0
    total = sum(v["fwd_s"] + v["bwd_s"] for v in got["regions"].values())
    assert total + got["unattributed"]["s"] == pytest.approx(got["busy_s"])
    # the program wrote every op of this step inside a region; what the
    # compiler added takes its reader's: almost nothing is left
    assert got["unattributed"]["s"] < 0.02 * got["busy_s"]
    mine = xspace.build_report(path)["by_region"]
    assert mine["module"] == "jit_train_step" and mine["executions"] == 3
    _same(mine, got)


# ---- each tiny cell's traced rehearsal, held to the manifest ---------------

def _family_root(tmp_path_factory, module):
    root = lib.make_root(str(tmp_path_factory.mktemp(module.CELL)),
                         cells=())
    module._add_cell(root)
    return root


@pytest.fixture(scope="module")
def roots(tmp_path_factory):
    import test_benchmark_granite_hybrid as granite
    import test_benchmark_lfm2_moe as lfm2
    tiny = lib.make_root(str(tmp_path_factory.mktemp("regions_tiny")),
                         cells=("tiny_train", "tiny_chat"))
    return {"tiny_train": tiny, "tiny_chat": tiny,
            granite.CELL: _family_root(tmp_path_factory, granite),
            lfm2.CELL: _family_root(tmp_path_factory, lfm2)}


@pytest.mark.parametrize("cell,want", [
    ("tiny_train", NEW_TRAIN), ("tiny_chat", NEW_CHAT),
    ("granite_tiny_chat", NEW_CHAT | {MIXER}),
    ("lfm2_tiny_rollout", NEW_CHAT | {MIXER})])
def test_a_traced_rehearsal_reports_what_the_manifest_lists(roots, cell,
                                                            want):
    """What the driver checks on the chip, line against list: every new
    metric the manifest lists for the cell is on the traced run's line
    (the CPU trace stores its programs as the chip's does), and none that
    it does not list. Values are shares of a CPU's time: only their
    presence and their sum are held."""
    root = roots[cell]
    man = lib.mf.load_manifest(root)
    listed = {m["name"] for m in lib.mf.cell_metrics(man, cell, "per_layer")}
    new = NEW_TRAIN | NEW_CHAT | {MIXER}
    assert listed & new == want
    code, res = lib.run_tiny(root, cell, trace=1)
    assert code == 4 and res["correct"] is True, res
    got = {n: v["value"] for n, v in res["metrics"].items() if n in new}
    assert set(got) == want
    gauge = next(n for n in want if "unattributed" in n)
    assert 0 <= got[gauge] < 5
    model = [n for n in want if n.startswith("model.")]
    assert all(got[n] > 0 for n in model), got
    if cell != "tiny_train":
        # what the engine's body adds around the decoder stands as ops of
        # its own on the CPU: the cuts, the control vectors
        assert got["engine.body_ms.chat"] >= 0
