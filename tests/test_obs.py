"""Observability subsystem (`apex1_tpu.obs`) — spine schema round-trip,
XSpace parse → bucket → report against the committed CPU-trace fixture
(incl. the corrupt/truncated typed-error contract), and the calibration
fit: predicted-vs-measured within a STATED band on synthetic corpora
(the repo ships no chip corpus), so the flywheel stays verified with no
hardware attached.
"""

import gzip
import importlib.util
import json
import os
import pathlib
import shutil

import pytest

from apex1_tpu.obs import calibrate, spine, xspace

_REPO = pathlib.Path(__file__).resolve().parents[1]
FIXTURE = _REPO / "tests" / "fixtures" / "cpu_trace"
FIXTURE_PB = (FIXTURE / "plugins" / "profile" / "fixture"
              / "fixture.xplane.pb")


@pytest.fixture()
def no_default_run(monkeypatch):
    """Isolate the process-global default run."""
    monkeypatch.delenv("APEX1_OBS_DIR", raising=False)
    spine.set_default_run(None)
    yield
    spine.set_default_run(None)


# ==========================================================================
# spine
# ==========================================================================

class TestSpine:
    def test_run_roundtrip(self, tmp_path):
        with spine.ObsRun(dir=str(tmp_path), component="t") as run:
            with run.span("work", iters=3):
                pass
            run.counter("steps", 7)
            run.gauge("loss", 1.5)
            run.event("note", detail="x")
            path = run.path
        evs = spine.read_events(path)
        assert [e["kind"] for e in evs] == [
            "run", "span", "counter", "gauge", "event"]
        header = evs[0]
        assert header["schema"] == spine.SCHEMA
        assert header["component"] == "t"
        span = evs[1]
        assert span["name"] == "work" and span["iters"] == 3
        assert span["dur_s"] >= 0 and span["t"] >= 0
        assert evs[2]["value"] == 7
        assert evs[3]["value"] == 1.5
        assert evs[4]["detail"] == "x"

    def test_torn_tail_skipped(self, tmp_path):
        with spine.ObsRun(dir=str(tmp_path)) as run:
            run.event("ok")
            path = run.path
        with open(path, "a") as f:
            f.write('{"kind": "event", "name": "torn half li')
        evs = spine.read_events(path)
        assert [e["kind"] for e in evs] == ["run", "event"]

    def test_kind_filter_and_unknown_kind(self, tmp_path):
        with spine.ObsRun(dir=str(tmp_path)) as run:
            run.counter("a", 1)
            run.event("b")
            with pytest.raises(ValueError):
                run.emit("bogus", "x")
            path = run.path
        assert [e["name"] for e in
                spine.read_events(path, kinds=("counter",))] == ["a"]

    def test_emit_inert_without_env(self, no_default_run, tmp_path):
        assert spine.default_run() is None
        spine.emit("event", "nobody-home")   # must be a silent no-op
        assert list(tmp_path.iterdir()) == []

    def test_emit_activates_on_env(self, no_default_run, monkeypatch,
                                   tmp_path):
        monkeypatch.setenv("APEX1_OBS_DIR", str(tmp_path))
        spine.emit("event", "hello", n=1)
        run = spine.default_run()
        assert run is not None
        files = list(tmp_path.glob("*.jsonl"))
        assert len(files) == 1
        evs = spine.read_events(str(files[0]))
        assert evs[0]["kind"] == "run"
        assert evs[1]["name"] == "hello" and evs[1]["n"] == 1

    def test_stopwatch_cumulative_and_reset(self):
        sw = spine.StopWatch()
        sw.start()
        d1 = sw.stop()
        sw.start()
        sw.stop()
        assert sw.count == 2
        assert sw.elapsed() >= d1
        assert sw.elapsed(reset=True) >= 0
        assert sw.count == 0 and sw.elapsed() == 0.0

    def test_timers_adapter_is_stopwatch(self):
        from apex1_tpu.utils.observability import Timers
        timers = Timers()
        t = timers("fwd")
        assert isinstance(t, spine.StopWatch)   # the ONE primitive
        t.start()
        t.stop()
        out = timers.log(reset=True)
        assert out["fwd"] >= 0
        assert timers("fwd").elapsed() == 0.0

    def test_metrics_logger_mirrors_to_spine(self, no_default_run,
                                             tmp_path):
        from apex1_tpu.utils.observability import MetricsLogger
        run = spine.ObsRun(dir=str(tmp_path))
        spine.set_default_run(run)
        sunk = []
        logger = MetricsLogger(writer=sunk.append, n_chips=1)
        logger.log(3, {"loss": 2.5})
        logger.log(4, {"loss": 2.4}, _obs_name=None)   # suppressed
        run.close()
        assert len(sunk) == 2                      # writer unaffected
        evs = spine.read_events(run.path, kinds=("event",))
        assert len(evs) == 1
        assert evs[0]["name"] == "metrics"
        assert evs[0]["step"] == 3 and evs[0]["loss"] == 2.5

    def test_serving_metrics_mirror(self, no_default_run, tmp_path):
        from apex1_tpu.serving.metrics import ServingMetrics
        run = spine.ObsRun(dir=str(tmp_path))
        spine.set_default_run(run)
        m = ServingMetrics()
        m.event(11, "queued", n_prompt=4)
        m.event(11, "token")              # never mirrored (volume)
        m.transition("replica_death", replica=0)
        run.close()
        evs = spine.read_events(run.path, kinds=("event",))
        names = [(e["name"], e.get("event")) for e in evs]
        assert ("serving.request", "queued") in names
        assert ("serving.transition", "replica_death") in names
        assert not any(e.get("event") == "token" for e in evs)

    def test_sentinel_diagnostic_mirror(self, no_default_run, tmp_path):
        from apex1_tpu.resilience.sentinel import Sentinel
        run = spine.ObsRun(dir=str(tmp_path))
        spine.set_default_run(run)
        s = Sentinel(None, check_every=1, rollback_after=1,
                     abort_after=1)
        rec = s._bank({"action": "skip", "steps_seen": 5})
        run.close()
        evs = spine.read_events(run.path, kinds=("event",))
        assert evs and evs[0]["name"] == "sentinel.diagnostic"
        assert evs[0]["action"] == "skip" and rec["action"] == "skip"


# ==========================================================================
# xspace — parse -> bucket -> report against the committed fixture
# ==========================================================================

class TestXSpace:
    def test_fixture_parses(self):
        planes = xspace.parse_xspace(FIXTURE_PB)
        names = [p.name for p in planes]
        assert "/host:CPU" in names
        cpu = planes[names.index("/host:CPU")]
        assert cpu.lines and cpu.event_names

    def test_report_attributes_ops(self):
        report = xspace.build_report(FIXTURE)
        assert report["schema"] == xspace.REPORT_SCHEMA
        # the fixture traced tanh(x @ w) @ w.T: both dots must appear
        assert report["plane_class"] == "host-xla-proxy"
        op_names = [o["name"] for o in report["ops"]]
        assert any(n.startswith("dot") for n in op_names)
        assert any(n.startswith("tanh") for n in op_names)
        assert report["total_op_ms"] > 0
        # ops sorted by time desc; shares consistent
        ms = [o["ms"] for o in report["ops"]]
        assert ms == sorted(ms, reverse=True)
        share_sum = sum(o["share"] for o in report["ops"])
        assert 0.98 < share_sum < 1.02
        assert set(report["buckets"]) == set(xspace.BUCKETS)
        bucket_ms = sum(b["ms"] for b in report["buckets"].values())
        assert bucket_ms == pytest.approx(report["total_op_ms"],
                                          rel=1e-6)

    def test_per_step_division(self):
        report = xspace.build_report(FIXTURE, steps=4)
        assert report["per_step_ms"] == pytest.approx(
            report["total_op_ms"] / 4, abs=1e-5)

    def test_gz_variant_parses_identically(self, tmp_path):
        raw = FIXTURE_PB.read_bytes()
        gz = tmp_path / "fixture.xplane.pb.gz"
        gz.write_bytes(gzip.compress(raw))
        a = xspace.op_totals(xspace.parse_xspace(FIXTURE_PB))
        b = xspace.op_totals(xspace.parse_xspace(gz))
        assert a == b

    def test_report_persisted_roundtrip(self, tmp_path):
        tdir = tmp_path / "trace"
        shutil.copytree(FIXTURE, tdir)
        path = xspace.write_report(tdir, steps=2)
        assert os.path.basename(path) == xspace.REPORT_NAME
        banked = json.loads(pathlib.Path(path).read_text())
        assert banked["schema"] == xspace.REPORT_SCHEMA
        assert banked["steps"] == 2 and banked["n_ops"] > 0

    def test_truncated_trace_typed_error(self, tmp_path):
        raw = FIXTURE_PB.read_bytes()
        for cut in (100, len(raw) // 2, len(raw) - 5):
            p = tmp_path / f"cut{cut}.xplane.pb"
            p.write_bytes(raw[:cut])
            with pytest.raises(xspace.TraceError) as ei:
                xspace.parse_xspace(p)
            assert "corrupt protobuf" in ei.value.reason

    def test_garbage_and_missing_typed_error(self, tmp_path):
        p = tmp_path / "junk.xplane.pb"
        p.write_bytes(b"\xff" * 64)
        with pytest.raises(xspace.TraceError):
            xspace.parse_xspace(p)
        with pytest.raises(xspace.TraceError):
            xspace.parse_xspace(tmp_path / "nope.xplane.pb")
        bad_gz = tmp_path / "bad.xplane.pb.gz"
        bad_gz.write_bytes(b"not gzip at all")
        with pytest.raises(xspace.TraceError):
            xspace.parse_xspace(bad_gz)
        # valid gzip HEADER over a corrupt deflate body: raises
        # zlib.error, not BadGzipFile — must still be typed
        blob = bytearray(gzip.compress(FIXTURE_PB.read_bytes()))
        mid = len(blob) // 2
        blob[mid:mid + 16] = b"\x00" * 16
        corrupt_body = tmp_path / "body.xplane.pb.gz"
        corrupt_body.write_bytes(bytes(blob))
        with pytest.raises(xspace.TraceError):
            xspace.parse_xspace(corrupt_body)

    def test_empty_dir_typed_error(self, tmp_path):
        with pytest.raises(xspace.TraceError) as ei:
            xspace.build_report(tmp_path)
        assert "no *.xplane.pb" in ei.value.reason

    def test_corrupt_trace_in_report_path(self, tmp_path):
        d = tmp_path / "plugins" / "profile" / "x"
        d.mkdir(parents=True)
        (d / "t.xplane.pb").write_bytes(b"\x07" * 32)
        with pytest.raises(xspace.TraceError):
            xspace.build_report(tmp_path)

    def test_bucket_rules(self):
        assert xspace.bucket_of("all-reduce-start.1") == "collective"
        assert xspace.bucket_of("collective-permute-done") == "collective"
        assert xspace.bucket_of("reduce-scatter.3") == "collective"
        assert xspace.bucket_of("custom-call.7") == "pallas"
        assert xspace.bucket_of("tpu_custom_call") == "pallas"
        assert xspace.bucket_of("fusion.12") == "xla"
        assert xspace.bucket_of("dot.4") == "xla"
        # near-misses must NOT land in collective
        assert xspace.bucket_of("reduce-window") == "xla"
        assert xspace.bucket_of("reduce.8") == "xla"

    def test_live_capture_roundtrip(self, tmp_path):
        """The CPU-rehearsable leg: a real jax.profiler.trace of one
        tiny jitted step parses and attributes through the same path
        the banked profile_artifacts will use on silicon."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return jnp.sum(x @ x)

        x = jnp.ones((64, 64), jnp.float32)
        f(x).block_until_ready()
        tdir = str(tmp_path / "live")
        with jax.profiler.trace(tdir):
            f(x).block_until_ready()
        report = xspace.build_report(tdir, steps=1)
        assert report["n_ops"] > 0 and report["total_op_ms"] > 0


# ==========================================================================
# calibrate
# ==========================================================================

def _write(path, doc):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(doc) if isinstance(doc, dict)
                    else doc)


def _synthetic_results(tmp_path):
    """A results dir with one priceable tpu record, one excluded decode
    record, one cpu record, and a tuning table with a measured + an
    interpret entry."""
    res = tmp_path / "perf_results"
    _write(res / "predicted_r9.json", {
        "steps": [
            # flops/bytes chosen so v5e roofline rate = units/t is easy
            {"name": "gpt2", "units_per_step": 16384,
             "flops": 1e12, "bytes": 1e9},
            {"name": "decode", "units_per_step": 1024,
             "flops": 1e10, "bytes": 1e8},
        ]})
    _write(res / "bench_gpt2.log",
           json.dumps({"metric": "tok/s gpt2 [tpu]", "value": 50_000.0,
                       "unit": "u"}) + "\n")
    _write(res / "bench_decode.log",
           json.dumps({"metric": "tok/s decode [tpu]", "value": 9_000.0,
                       "unit": "u"}) + "\n")
    _write(res / "bench_bert.log",
           json.dumps({"metric": "tok/s bert [cpu]", "value": 123.0,
                       "unit": "u"}) + "\n")
    _write(res / "tuning" / "layer_norm.json", {
        "schema": 1, "kernel": "layer_norm", "entries": {
            "v5e|bfloat16|lanes=768": {
                "blocks": {"block_rows": 128}, "time_ms": 2.0,
                "timing": "measured", "backend": "tpu",
                "predicted": {"ms": 1.0, "flops": 1.0, "bytes": 1.0,
                              "generation": "v5e"}},
            "v5e|bfloat16|lanes=128": {
                "blocks": {"block_rows": 64}, "time_ms": 500.0,
                "timing": "interpret", "backend": "cpu",
                "predicted": {"ms": 0.5, "flops": 1.0, "bytes": 1.0,
                              "generation": "v5e"}},
            "v5e|bfloat16|lanes=256": {   # no predicted -> no pair
                "blocks": {"block_rows": 64}, "time_ms": 1.0,
                "timing": "measured", "backend": "tpu"},
        }})
    return res


class TestCalibrate:
    def test_newest_prediction_by_mtime(self, tmp_path):
        a = tmp_path / "predicted_r9.json"
        b = tmp_path / "predicted_r10.json"
        _write(a, {"steps": []})
        _write(b, {"steps": []})
        os.utime(a, (1_000_000_000, 1_000_000_000))
        os.utime(b, (2_000_000_000, 2_000_000_000))
        assert calibrate.newest_prediction_path(
            str(tmp_path)).endswith("predicted_r10.json")
        # mtime, not lexicographic: flip the clock and r9 wins
        os.utime(a, (3_000_000_000, 3_000_000_000))
        assert calibrate.newest_prediction_path(
            str(tmp_path)).endswith("predicted_r9.json")

    def test_roofline_ms_arithmetic(self):
        from apex1_tpu.core.capability import get_capability
        cap = get_capability("v5e")
        # compute-bound case
        ms = calibrate.roofline_ms(cap.bf16_tflops * 1e12, 0.0, "v5e")
        assert ms == pytest.approx(1e3)
        # bandwidth-bound case
        ms = calibrate.roofline_ms(0.0, cap.hbm_gbps * 1e9, "v5e")
        assert ms == pytest.approx(1e3)

    def test_collect_fit_and_exclusions(self, tmp_path):
        res = _synthetic_results(tmp_path)
        pairs, excluded = calibrate.collect_pairs(
            str(res), "v5e", str(res / "tuning"))
        by_key = {}
        for p in pairs:
            by_key.setdefault(p.key, []).append(p)
        # the tpu step pair: slowdown = predicted_rate / measured
        assert len(by_key["step:gpt2"]) == 1
        sp = by_key["step:gpt2"][0]
        pred = calibrate.predicted_step_rate(
            {"name": "gpt2", "units_per_step": 16384,
             "flops": 1e12, "bytes": 1e9}, "v5e")
        assert sp.predicted == pytest.approx(pred, rel=1e-3)
        assert sp.slowdown == pytest.approx(pred / 50_000.0, rel=1e-3)
        assert sp.backend == "tpu"
        # kernel pairs: measured->tpu, interpret->cpu-proxy, the
        # predicted-less entry contributes nothing
        kps = by_key["kernel:layer_norm"]
        assert sorted((p.backend, p.slowdown) for p in kps) == [
            ("cpu-proxy", pytest.approx(1000.0)),
            ("tpu", pytest.approx(2.0))]
        # decode excluded WITH its stated reason; cpu bench rec skipped
        assert any(e["key"] == "step:decode"
                   and "scanned-loop" in e["reason"] for e in excluded)
        assert "step:bert" not in by_key
        factors, proxy = calibrate.fit(pairs)
        assert factors["step:gpt2"]["n"] == 1
        assert factors["kernel:layer_norm"]["slowdown"] == \
            pytest.approx(2.0)
        assert proxy["kernel:layer_norm"]["slowdown"] == \
            pytest.approx(1000.0)
        assert proxy["kernel:layer_norm"]["backend"] == "cpu-proxy"

    def test_save_load_roundtrip_and_failsafe(self, tmp_path):
        res = _synthetic_results(tmp_path)
        doc = calibrate.build_calibration(str(res), "v5e",
                                          str(res / "tuning"))
        path = calibrate.save_calibration(doc, results_dir=str(res))
        loaded = calibrate.load_calibration(str(res))
        assert loaded["factors"] == doc["factors"]
        # the lookup API serves tpu-backed factors only
        assert calibrate.step_slowdown("gpt2", str(res))
        assert calibrate.kernel_slowdown("layer_norm", str(res))[
            "backend"] == "tpu"
        assert calibrate.step_slowdown("nope", str(res)) is None
        # corrupt / foreign-schema files are a miss, never a raise
        pathlib.Path(path).write_text("{ not json")
        assert calibrate.load_calibration(str(res)) is None
        pathlib.Path(path).write_text(json.dumps({"schema": "other"}))
        assert calibrate.load_calibration(str(res)) is None
        assert calibrate.step_slowdown("gpt2", str(res)) is None

    #: stated band for raw tpu step slowdowns: between 2x FASTER than
    #: the roofline (a cost model that overcounts bytes) and 4x slower.
    #: Outside this band = either a broken join or a real regression.
    RAW_BAND = (0.5, 4.0)
    #: post-fit residual band: each pair within 1.35x of its key's
    #: fitted factor (multi-record keys like gpt2 must agree with
    #: themselves this tightly)
    RESIDUAL = 1.35

    def test_corpus_within_stated_band(self, tmp_path):
        """Every tpu step pair of a corpus sits inside the stated raw
        band and within the residual of its key's fitted factor; the
        decode blind spot stays excluded with its reason; interpret-
        timed entries only ever feed proxy factors."""
        res = _synthetic_results(tmp_path)
        # v5e roofline of this row = 0.1 s -> 163,840 units/s; two gpt2
        # logs at 2.0x and 1.82x slowdown: a multi-record key must agree
        # with itself inside RESIDUAL
        _write(res / "predicted_r9.json", {"steps": [
            {"name": "gpt2", "units_per_step": 16384,
             "flops": 19.7e12, "bytes": 1e9},
            {"name": "decode", "units_per_step": 1024,
             "flops": 1e10, "bytes": 1e8}]})
        for log, val in (("bench_gpt2.log", 81_920.0),
                         ("bench_gpt2_b24.log", 90_000.0)):
            _write(res / log, json.dumps(
                {"metric": "tok/s gpt2 [tpu]", "value": val,
                 "unit": "u"}) + "\n")
        pairs, excluded = calibrate.collect_pairs(str(res), "v5e")
        tpu_steps = [p for p in pairs
                     if p.backend == "tpu" and p.key.startswith("step:")]
        assert len(tpu_steps) == 2
        for p in tpu_steps:
            assert self.RAW_BAND[0] <= p.slowdown <= self.RAW_BAND[1], (
                f"{p.key} slowdown {p.slowdown:.2f} outside stated "
                f"band {self.RAW_BAND} (source {p.source})")
        factors, proxy = calibrate.fit(pairs)
        assert set(factors) == {"step:gpt2", "kernel:layer_norm"}
        for p in pairs:
            f = (factors if p.backend == "tpu" else proxy)[p.key]
            resid = p.slowdown / f["slowdown"]
            assert 1 / self.RESIDUAL <= resid <= self.RESIDUAL, (
                f"{p.key} residual x{resid:.2f} outside "
                f"x{self.RESIDUAL} of fitted factor")
        assert any(e["key"] == "step:decode" for e in excluded)
        assert proxy and all(f["backend"] == "cpu-proxy"
                             for f in proxy.values())

    def test_calibration_table_is_a_build_product(self, tmp_path):
        """A saved calibration.json must parse and agree with a re-fit
        of the corpus it was built from (the table is a build product of
        the corpus, not hand-maintained state)."""
        res = _synthetic_results(tmp_path)
        calibrate.save_calibration(
            calibrate.build_calibration(str(res), "v5e"),
            results_dir=str(res))
        doc = calibrate.load_calibration(str(res))
        assert doc is not None
        refit, _proxy = calibrate.fit(
            calibrate.collect_pairs(str(res), "v5e")[0])
        assert set(doc["factors"]) == set(refit)
        for key, f in refit.items():
            assert doc["factors"][key]["slowdown"] == pytest.approx(
                f["slowdown"], rel=0.05)

    def test_no_committed_corpus_prices_uncalibrated(self):
        """The repo ships no calibration table (its corpus predated the
        code it described): every lookup degrades to 'uncalibrated'."""
        assert calibrate.load_calibration() is None
        assert calibrate.step_slowdown("gpt2") is None


# ==========================================================================
# feedback into bench records
# ==========================================================================

@pytest.fixture(scope="module")
def bench_mod():
    spec = importlib.util.spec_from_file_location("_bench_for_obs2",
                                                  _REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestBenchCalibrationFeedback:
    def _results_with_calibration(self, tmp_path, slowdown=2.0):
        res = tmp_path / "perf_results"
        _write(res / "predicted_r9.json", {
            "steps": [{"name": "gpt2", "units_per_step": 1000,
                       "flops": 1e12, "bytes": 1e9}]})
        _write(res / "calibration.json", {
            "schema": calibrate.SCHEMA,
            "factors": {"step:gpt2": {"slowdown": slowdown, "n": 3,
                                      "backend": "tpu"}},
            "proxy_factors": {}, "excluded": [], "pairs": []})
        return str(res)

    def test_calibrated_fields_attached(self, bench_mod, tmp_path):
        res = self._results_with_calibration(tmp_path, slowdown=2.0)
        rec = {"metric": "m [tpu]", "value": 4000.0}
        out = bench_mod._attach_roofline(dict(rec), "gpt2", res, "v5e")
        assert out["predicted"] > 0
        assert out["calibrated_predicted"] == pytest.approx(
            out["predicted"] / 2.0, rel=1e-3)
        assert out["calibrated_ratio"] == pytest.approx(
            out["value"] / out["calibrated_predicted"], rel=1e-3)
        assert out["calibration"] == {"slowdown": 2.0, "n": 3}
        # raw localizer untouched
        assert out["roofline_ratio"] == pytest.approx(
            out["value"] / out["predicted"], rel=1e-3)

    def test_no_calibration_no_fields(self, bench_mod, tmp_path):
        res = self._results_with_calibration(tmp_path)
        os.remove(os.path.join(res, "calibration.json"))
        out = bench_mod._attach_roofline(
            {"metric": "m [tpu]", "value": 4000.0}, "gpt2", res, "v5e")
        assert "predicted" in out
        assert "calibrated_predicted" not in out

    def test_corrupt_calibration_never_breaks_record(self, bench_mod,
                                                     tmp_path):
        res = self._results_with_calibration(tmp_path)
        with open(os.path.join(res, "calibration.json"), "w") as f:
            f.write("!! not json")
        out = bench_mod._attach_roofline(
            {"metric": "m [tpu]", "value": 4000.0}, "gpt2", res, "v5e")
        assert out["value"] == 4000.0 and "predicted" in out
        assert "calibrated_predicted" not in out

    def test_cpu_records_never_calibrated(self, bench_mod, tmp_path):
        res = self._results_with_calibration(tmp_path)
        out = bench_mod._attach_roofline(
            {"metric": "m [cpu]", "value": 10.0}, "gpt2", res)
        assert "predicted" not in out
        assert "calibrated_predicted" not in out


# ==========================================================================
# measured_vs_predicted: newest-table resolution (satellite fix)
# ==========================================================================

class TestMeasuredVsPredicted:
    @pytest.fixture()
    def mvp_main(self, monkeypatch):
        spec = importlib.util.spec_from_file_location(
            "_mvp_for_obs", _REPO / "tools" / "measured_vs_predicted.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_resolves_newest_and_derives_out_name(self, mvp_main,
                                                  tmp_path,
                                                  monkeypatch, capsys):
        res = tmp_path / "perf_results"
        _write(res / "predicted_r5.json", {"steps": []})
        _write(res / "predicted_r12.json", {"steps": [
            {"name": "gpt2", "units_per_step": 1000, "flops": 1e12,
             "bytes": 1e9}]})
        os.utime(res / "predicted_r5.json", (1e9, 1e9))
        os.utime(res / "predicted_r12.json", (2e9, 2e9))
        monkeypatch.setattr(
            "sys.argv", ["measured_vs_predicted.py",
                         "--results", str(res)])
        mvp_main.main()
        out = res / "measured_r12.md"
        assert out.exists(), "out name must follow the resolved table"
        text = out.read_text()
        assert "predicted_r12.json" in text
        assert "predicted_r5.json" not in text

    def test_exits_loud_when_no_table(self, mvp_main, tmp_path,
                                      monkeypatch):
        monkeypatch.setattr(
            "sys.argv", ["measured_vs_predicted.py",
                         "--results", str(tmp_path)])
        with pytest.raises(SystemExit):
            mvp_main.main()
