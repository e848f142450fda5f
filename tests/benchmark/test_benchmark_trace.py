"""The trace reducer: interval arithmetic on synthetic intervals, op labels
on the instruction texts a chip trace carries, and the whole reduction on
one small `.xplane.pb` recorded on the chip (tests/benchmark/data/)."""

import os

import pytest

from benchmark.harness import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "ddp4_tiny.xplane.pb.gz")


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
])
def test_op_labels(name, key, code):
    assert tr.op_key(name) == key
    assert tr.opcode(name) == code
    assert tr._is_allreduce(name) == code.startswith("all-reduce")


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
