"""The trace reducer: interval arithmetic on synthetic intervals, op labels
on the instruction texts a chip trace carries, steps and kernels counted
inside a synthetic window, idle gaps by spans known by their form, and the
whole reduction on two small `.xplane.pb` recorded on the chip
(tests/benchmark/data/)."""

import json
import os

import pytest

from benchmark.harness import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "ddp4_tiny.xplane.pb.gz")
RECORDED_TRAIN = os.path.join(DATA, "gpt2_tiny_train.xplane.pb.gz")


def test_union_merges_overlaps_and_keeps_gaps():
    merged = tr._union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)])
    assert merged == [[0, 3], [5, 8], [10, 11]]
    assert tr._length(merged) == 7


def test_clip_and_subtract():
    assert tr._clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    a = tr._union([(0, 10), (20, 30)])
    b = tr._union([(2, 4), (8, 22), (29, 40)])
    assert tr._subtract(a, b) == [(0, 2), (4, 8), (22, 29)]
    assert tr._subtract(a, []) == [(0, 10), (20, 30)]
    assert tr._subtract(a, [[0, 100]]) == []


def test_exposed_collective_time_is_what_no_other_op_covers():
    ar = tr._union([(100, 200)])
    other = tr._union([(90, 130), (150, 160)])
    exposed = tr._subtract(ar, other)
    assert tr._length(exposed) == (150 - 130) + (200 - 160)


@pytest.mark.parametrize("name,key,code", [
    ("%copy.12 = bf16[48,16,1151,64]{3,2,1,0} copy(%p), metadata={}",
     "copy_bf16_48_16_1151_64_", "copy"),
    ("%custom-call.7 = f32[8,128]{1,0} custom-call(%a, %b), "
     "custom_call_target=\"tpu_custom_call\"", "custom-call_f32_8_128_",
     "custom-call"),
    ("%all-reduce-start.3 = f32[1024,1024]{1,0} all-reduce-start(%g)",
     "all-reduce-start_f32_1024_1024_", "all-reduce-start"),
    ("fusion.123", "fusion", "fusion"),
    ("all-reduce.5", "all-reduce", "all-reduce"),
    # a Pallas kernel carries the name the program gave it (PR 25)
    ("%apex1_flash_dq.7 = bf16[8,16,1024,128]{3,2,1,0} custom-call("
     "bf16[8,16,1024,128]{3,2,1,0} %p.1), "
     'custom_call_target="tpu_custom_call"', "apex1_flash_dq",
     "custom-call"),
    ("%apex1_layer_norm_fwd = (bf16[64,128]{1,0}, f32[64,1]{1,0}) "
     "custom-call(bf16[64,128]{1,0} %x), "
     'custom_call_target="tpu_custom_call"', "apex1_layer_norm_fwd",
     "custom-call"),
    ("%apex1_linear_xent_fwd.12 = (f32[8192,1]{1,0:T(8,128)}, "
     "f32[8192,1]{1,0:T(8,128)}) custom-call(%x, %w, %t, %off)",
     "apex1_linear_xent_fwd", "custom-call"),
    # operands name OTHER instructions: a fusion fed by a kernel is none
    ("%fusion.12 = bf16[8,1024]{1,0:T(8,128)(2,1)} fusion(bf16[8,1024]"
     "{1,0} %apex1_flash_fwd.3, f32[] %all-reduce.1), kind=kLoop",
     "fusion_bf16_8_1024_", "fusion"),
])
def test_op_labels(name, key, code):
    assert tr.op_key(name) == key
    assert tr.opcode(name) == code
    assert tr._is_allreduce(name) == code.startswith("all-reduce")
    named = key.startswith("apex1_")
    assert tr.kernel_name(name) == (key if named else None)


@pytest.mark.parametrize("name,is_span", [
    ("serving/step", True), ("serving/admit.register", True),
    ("engine/step", True), ("train/dispatch", True), ("loadgen", True),
    ("bench/window", True), ("moe/dispatch.all_to_all", True),
    # the profiler's own host events are of other forms
    ("PjitFunction(step)", False), ("$profiler.py:91 start_trace", False),
    ("TfrtCpuExecutable::ExecuteHelper", False), ("ThreadpoolListener::"
     "Region", False), ("jit_step(8120773981235)", False),
    ("XlaModule:#hlo_module=jit_step,program_id=12#", False),
    ("tsl/profiler/lib/traceme.h", False), ("a/b/c", False),
    ("serving/", False), ("/step", False), ("serving step", False)])
def test_a_span_is_known_by_its_form(name, is_span):
    assert (tr.SPAN_RE.match(name) is not None) == is_span


def _synthetic(step_ns=100.0, n_exec=6, lo=50.0):
    """Six executions of one program back to back from t=0, each two ops
    (a named kernel over its first 30 %, a fusion over the rest), and a
    window of exactly five steps that opens in the middle of the first
    execution: six executions TOUCH it, five steps of ops lie inside."""
    ops, mods = [], []
    for i in range(n_exec):
        t = i * step_ns
        mods.append(("jit_step(123)", t, step_ns))
        ops.append((f"%apex1_flash_fwd.{i} = bf16[8,16]{{1,0}} "
                    "custom-call(%q)", t, 0.3 * step_ns))
        ops.append((f"%fusion.{i} = f32[8]{{0}} fusion(%x)",
                    t + 0.3 * step_ns, 0.7 * step_ns))
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                          "XLA Modules": mods}},
            "host": {"bench/window": [(lo, lo + 5 * step_ns)]}}


def test_per_step_numbers_divide_by_the_steps_the_window_holds():
    red = tr.reduce_events(_synthetic())
    assert red["n_executions"] == 6
    assert red["n_steps"] == pytest.approx(5.0)
    assert red["busy_s"] == pytest.approx(500e-9)
    assert red["idle_pct"] == pytest.approx(0.0)
    # the median is of WHOLE executions
    assert red["step_device_ms_p50"] == pytest.approx(100e-6)
    # the kernel: five calls lie inside (the first execution's ended at
    # t=30, before the window opened)
    calls, seconds, ms_per_step = red["kernels"]["apex1_flash_fwd"]
    assert calls == 5 and seconds == pytest.approx(150e-9)
    assert ms_per_step == pytest.approx(30e-6)      # 30 % of a step
    assert red["custom_call_ms_per_step"] == pytest.approx(30e-6)
    assert red["custom_call_ms_per_step"] == pytest.approx(sum(
        row[2] for row in red["kernels"].values()))
    # the old divisor (executions that touch the window) read 25: 5/6
    assert 1e3 * red["custom_call_s"] / red["n_executions"] \
        == pytest.approx(25e-6)
    assert dict(red["device_ops"]) == pytest.approx(
        {"apex1_flash_fwd": 150e-9, "fusion_f32_8_": 350e-9})


def test_a_window_cut_through_a_kernel_counts_the_part_inside():
    red = tr.reduce_events(_synthetic(lo=20.0))     # opens inside call 0
    calls, seconds, _ = red["kernels"]["apex1_flash_fwd"]
    # 10 ns of the first call, four whole ones, and the sixth execution's
    # first 20 ns
    assert calls == 6 and seconds == pytest.approx((10 + 4 * 30 + 20) * 1e-9)
    assert red["n_steps"] == pytest.approx(5.0)


def test_idle_gap_goes_to_a_span_no_list_ever_held():
    raw = _synthetic(step_ns=10e3, n_exec=1, lo=0.0)
    # one execution of 10 us in a 50 us window: idle from 10 to 50
    raw["host"].update({
        "engine/step": [(9e3, 40e3)],
        "serving/step": [(10e3, 39e3)],
        "moe/route.top8": [(15e3, 25e3)],      # a later PR's span
    })
    red = tr.reduce_events(raw)
    assert red["n_steps"] == pytest.approx(1.0)
    # ONE gap, midpoint 30 us: inside serving/step, the innermost there
    assert red["idle_gaps"] == [["serving/step", pytest.approx(40e-6)]]
    raw["host"]["moe/route.top8"] = [(25e3, 35e3)]
    red = tr.reduce_events(raw)
    assert red["idle_gaps"][0][0] == "moe/route.top8"
    # the window's own span never owns a gap: what nothing else covers is
    # the host's
    del raw["host"]["engine/step"], raw["host"]["serving/step"], \
        raw["host"]["moe/route.top8"]
    assert tr.reduce_events(raw)["idle_gaps"][0][0] == "host:other"


def test_idle_gap_goes_to_the_innermost_covering_span():
    spans = {
        "engine/step": ([0.0, 100.0], [(0.0, 90.0), (100.0, 190.0)]),
        "serving/prefill": ([10.0], [(10.0, 30.0)]),
        "loadgen": ([90.0], [(90.0, 100.0)]),
    }
    assert tr._covering(spans, 20.0) == "serving/prefill"
    assert tr._covering(spans, 50.0) == "engine/step"
    assert tr._covering(spans, 95.0) == "loadgen"
    assert tr._covering(spans, 195.0) == "host:other"


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded chip trace in tests/benchmark/data")
def test_reduction_of_a_trace_recorded_on_the_chip():
    """A few DDP steps of a tiny BERT on four v5e chips, recorded by this
    PR's four-chip call (gzipped: under a megabyte)."""
    assert os.path.getsize(RECORDED) < 2 ** 20
    red = tr.reduce(RECORDED)
    assert red["n_devices"] == 4
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 <= red["idle_pct"] < 100
    assert red["n_steps"] >= 2 and red["step_device_ms_p50"] > 0
    assert red["n_steps"] <= red["n_executions"]
    # recorded before PR 25 gave the kernels names: each Pallas call
    # carries its innermost jax scope's (`%layer0.7`), and is summed there
    assert {"layer0", "layer1"} <= set(red["kernels"])
    assert sum(row[1] for row in red["kernels"].values()) \
        == pytest.approx(red["custom_call_s"])
    assert red["custom_call_s"] > 0            # the Pallas kernels ran
    assert red["allreduce_ms_per_step"] > 0
    assert 0 <= red["allreduce_exposed_ms_per_step"] <= red[
        "allreduce_ms_per_step"] + 1e-9
    assert 1 <= len(red["device_ops"]) <= 10
    assert red["device_ops"] == sorted(red["device_ops"],
                                       key=lambda kv: -kv[1])
    assert all(isinstance(k, str) and v > 0 for k, v in red["device_ops"])
    assert len(red["idle_gaps"]) <= 10
    busy_from_ops = sum(v for _, v in red["device_ops"])
    assert busy_from_ops <= red["window_s"] * 1.01


@pytest.mark.skipif(not os.path.exists(RECORDED_TRAIN),
                    reason="no recorded chip trace in tests/benchmark/data")
def test_recorded_training_trace_names_every_kernel_and_no_share_passes_100():
    """A few steps of a tiny GPT-2 through `gpt2m_train`'s own path on one
    v5e, recorded by PR 27's chip call with the sizes beside it: every
    Pallas kernel is there under the program's name, `custom-call` time is
    the named kernels' time, and each roofline metric's own file reads a
    share between 0 and 100 %."""
    from benchmark.harness import device, manifest as mf
    assert os.path.getsize(RECORDED_TRAIN) < 2 ** 20
    with open(os.path.join(DATA, "gpt2_tiny_train.json")) as f:
        sizes = json.load(f)
    red = tr.reduce(RECORDED_TRAIN)
    assert red["n_devices"] == 1
    want = {"apex1_" + k for k in (
        "flash_fwd", "flash_dq", "flash_dkv", "linear_xent_fwd",
        "linear_xent_dx", "linear_xent_dw", "layer_norm_fwd",
        "layer_norm_bwd")}
    assert set(red["kernels"]) == want
    assert red["n_executions"] - 1 <= red["n_steps"] <= red["n_executions"]
    assert abs(red["n_steps"] - round(red["n_steps"])) < 0.05
    assert red["custom_call_ms_per_step"] == pytest.approx(
        sum(row[2] for row in red["kernels"].values()), rel=1e-3)
    labels = [k for k, _ in red["device_ops"]]
    assert any(k.startswith("apex1_") for k in labels)
    assert not any(k.startswith("custom-call") for k in labels)
    layers = sizes["cfg"]["n_layer"]
    steps = round(red["n_steps"])
    assert red["kernels"]["apex1_flash_fwd"][0] in (
        layers * steps, layers * (steps + 1))
    ctx = {"cfg": sizes["cfg"], "traffic": sizes["traffic"], "trace": red,
           "device": {"kind": sizes["device_kind"],
                      "peaks": device.peaks(sizes["device_kind"])}}
    for kernel in sorted(want - {"apex1_layer_norm_fwd",
                                 "apex1_layer_norm_bwd"}):
        name = f"kernel.{kernel[len('apex1_'):]}.roofline_pct.train"
        share = mf.load_layer_metric(name)["_module"].read(ctx)
        assert 0 < share <= 100, (name, share)
