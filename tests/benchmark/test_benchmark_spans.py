"""The per-layer metrics that read the program's own spans
(`benchmark/harness/spans.py`): a traced tiny chat rehearsal on the CPU
reports all eight, each on the window's steps alone, and the spans' step
median agrees with the benchmark's clock around `Engine.step()`. Counts
and control flow only: a CPU run is never a speed."""

import types

import pytest

import benchmark_testlib as lib
from benchmark.harness import spans

NEW = ("engine.host_ms_mean.chat", "engine.read_wait_ms_p50.chat",
       "engine.admit_host_ms_p50.chat", "engine.admit_wait_ms_p50.chat",
       "engine.retire_ms_p50.chat", "engine.between_steps_ms_mean.chat",
       "engine.control_dispatches.chat", "sched.queue_wait_ms_p50.chat")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced tiny chat run, and what the helper reduced in it."""
    root = lib.make_root(str(tmp_path_factory.mktemp("spans")),
                         cells=("tiny_chat",))
    seen = {}
    real = spans.window

    def keep(ctx):
        seen["ctx"] = ctx
        return real(ctx)

    spans.window = keep
    try:
        code, res = lib.run_tiny(root, "tiny_chat", trace=1)
    finally:
        spans.window = real
    assert code == 4 and res["correct"] is True, res
    return res, seen["ctx"]


@pytest.mark.parametrize("name", NEW)
def test_traced_chat_reports_the_span_metric(traced, name):
    res, _ = traced
    assert name in res["metrics"], sorted(res["metrics"])
    value = res["metrics"][name]["value"]
    assert value == value and value >= 0.0


def test_earlier_metrics_are_reported_as_before(traced):
    res, _ = traced
    assert {"engine.step_ms_p50.chat", "engine.occupancy_pct.chat",
            "engine.ttft_p50_ms.chat", "engine.itl_p95_ms.chat",
            "loadgen.late_p99_ms.chat"} <= set(res["metrics"])


def test_spans_step_median_agrees_with_the_outside_clock(traced):
    res, ctx = traced
    red = ctx["_spans"]
    assert red["n_steps"] == ctx["scalars"]["window.steps"]
    outside = sorted(ctx["series"]["engine_step_ms"])
    inside = sorted(red["step_ms"])
    assert len(inside) == len(outside)
    # the same steps on two clocks: the span lies inside the benchmark's
    # two stamps, a few microseconds shorter
    for a, b in zip(inside, outside):
        assert a <= b + 1e-3 and b - a < 0.2, (a, b)


def test_host_and_wait_make_up_the_step(traced):
    _, ctx = traced
    red = ctx["_spans"]
    for step, host, wait in zip(red["step_ms"], red["host_ms"],
                                red["wait_ms"]):
        assert host >= 0.0 and wait >= 0.0
        assert host + wait == pytest.approx(step, abs=1e-9)
    assert red["steps_with_admission"] >= 1
    assert len(red["admit_host_ms"]) == len(red["admit_wait_ms"]) >= 1


def _span(i, parent, name, a, b, wait=False, **counts):
    return types.SimpleNamespace(id=i, parent=parent, name=name,
                                 start_ns=a, end_ns=b, req=None, wait=wait,
                                 counts=counts)


def test_reduction_on_a_hand_made_buffer():
    """Three steps, of which the window holds the last two: a step before
    the window and its children are left out; host = span - wait."""
    ms = 1_000_000
    rec = [
        _span(2, 1, "serving/read_tokens", 1 * ms, 9 * ms, wait=True),
        _span(1, None, "serving/step", 0, 10 * ms, control_dispatches=9),
        _span(5, 4, "serving/admit.first_read", 22 * ms, 26 * ms,
              wait=True),
        _span(4, 3, "serving/admit", 21 * ms, 28 * ms),
        _span(6, 3, "serving/read_tokens", 30 * ms, 38 * ms, wait=True),
        _span(3, None, "serving/step", 20 * ms, 40 * ms, admitted=1,
              control_dispatches=5),
        _span(8, 7, "serving/read_tokens", 42 * ms, 49 * ms, wait=True),
        _span(9, 7, "serving/retire", 49 * ms, 50 * ms),
        _span(7, None, "serving/step", 41 * ms, 51 * ms,
              control_dispatches=1),
    ]
    red = spans._reduce(rec, 2)
    assert red["step_ms"] == [20.0, 10.0]
    assert red["host_ms"] == [8.0, 3.0]
    assert red["read_wait_ms"] == [8.0, 7.0]
    assert red["admit_host_ms"] == [3.0] and red["admit_wait_ms"] == [4.0]
    assert red["retire_ms"] == [1.0]
    assert red["between_steps_ms"] == [1.0]
    assert red["control_dispatches"] == [5, 1]
    assert red["steps_with_admission"] == 1
    assert spans._reduce(rec, 0) is None and spans._reduce([], 3) is None


def test_a_program_without_spans_reads_nothing(monkeypatch):
    """The parent commit's spine has no `snapshot`: every reader returns
    None and raises nothing, so the result line leaves the metric out."""
    from apex1_tpu.obs import spine
    monkeypatch.delattr(spine, "snapshot")
    ctx = {"scalars": {"window.steps": 5}, "series": {}}
    assert spans.read(ctx, "host_ms", "mean") is None
    assert spans.read(ctx, "retire_ms") is None
