"""Unit tests for the driver-facing perf tooling: bench.py's record
banking and roofline annotation (pinned against synthetic results
dirs), and tools/measured_vs_predicted.py's roofline-scoring join.
"""

import importlib.util
import json
import os
import pathlib

import pytest

_REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def bench_mod():
    spec = importlib.util.spec_from_file_location("_bench_for_test",
                                                  _REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _results(tmp_path, logs):
    """Write a synthetic perf_results dir."""
    res = tmp_path / "perf_results"
    res.mkdir()
    for name, lines in logs.items():
        (res / name).write_text("\n".join(
            json.dumps(x) if isinstance(x, dict) else x for x in lines)
            + "\n")
    return str(res)


@pytest.fixture(scope="module")
def mvp_mod():
    spec = importlib.util.spec_from_file_location(
        "_mvp_for_test", _REPO / "tools" / "measured_vs_predicted.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestCrashSafeBanking:
    def test_emit_banks_record_atomically(self, bench_mod, tmp_path,
                                          capsys):
        """--out satellite: the record lands at out_path via temp-file
        + atomic rename (no .tmp debris), nested dirs are created, and
        stdout still carries the driver's JSON line."""
        rec = {"metric": "m [tpu]", "value": 1.5}
        out = tmp_path / "sweep" / "gpt2.json"
        bench_mod._emit(rec, str(out))
        assert json.loads(capsys.readouterr().out) == rec
        assert json.loads(out.read_text()) == rec
        assert os.listdir(out.parent) == ["gpt2.json"]

    def test_emit_overwrites_previous_record(self, bench_mod, tmp_path):
        out = tmp_path / "r.json"
        bench_mod._emit({"value": 1}, str(out))
        bench_mod._emit({"value": 2}, str(out))
        assert json.loads(out.read_text()) == {"value": 2}

    def test_emit_banking_failure_never_eats_the_record(self, bench_mod,
                                                        tmp_path,
                                                        capsys):
        """Banking is best-effort: an unwritable out_path warns on
        stderr but the stdout line (the driver contract) still prints."""
        target = tmp_path / "f"
        target.write_text("not a dir")
        rec = {"value": 3}
        bench_mod._emit(rec, str(target / "x.json"))
        captured = capsys.readouterr()
        assert json.loads(captured.out) == rec
        assert "could not bank" in captured.err

    def test_try_resume_falls_back_to_fresh_on_junk_dir(self, bench_mod,
                                                        tmp_path,
                                                        capsys):
        """--resume auto must measure, not die, on a stale/foreign
        checkpoint dir."""
        template = {"w": [1, 2, 3]}
        (tmp_path / "step_00000001").mkdir()   # uncommitted debris
        state, resumed = bench_mod._try_resume(str(tmp_path), template)
        assert state is template and resumed is None
        assert "starting fresh" in capsys.readouterr().err


class TestMeasuredVsPredicted:
    """The roofline-scoring artifact generator: its rows feed BASELINE.md
    and the judge's perf assessment, so pin the join arithmetic."""

    def _run(self, mvp_mod, tmp_path, logs, monkeypatch):
        res = pathlib.Path(_results(tmp_path, logs))
        pred = {"topology": "v5e:2x2", "kernels": [], "steps": [
            {"name": "gpt2", "metric": "m", "unit": "tokens/sec/chip",
             "proxy": 145000.0, "units_per_step": 16384,
             # 19.7 TF, 81.9 GB -> v5e roofline: max(0.1s, 0.1s) = 100ms
             "flops": 19.7e12, "bytes": 81.9e9,
             "flops_pallas_visible": 1e12, "mfu_correction": 2.0,
             "temp_gib": 1.0, "args_gib": 1.0}]}
        (res / "predicted_r5.json").write_text(json.dumps(pred))
        out = tmp_path / "out.md"
        monkeypatch.setattr(
            "sys.argv",
            ["mvp", "--results", str(res), "--out", str(out)])
        mvp_mod.main()
        return out.read_text()

    def test_join_arithmetic(self, mvp_mod, tmp_path, monkeypatch):
        text = self._run(mvp_mod, tmp_path, {
            "bench_gpt2.log": [{
                "metric": "m [tpu]", "value": 81920.0,
                "unit": "tokens/sec/chip", "vs_baseline": 0.565,
                "step_ms": 200.0}],
        }, monkeypatch)
        row = [l for l in text.splitlines() if l.startswith("| gpt2")][0]
        cells = [c.strip() for c in row.split("|")]
        # pred ms: max(19.7e12/197e12, 81.9e9/819e9) = 0.1 s
        assert cells[6] == "100.0"
        # roofline frac: 100 / 200 = 0.50
        assert cells[7] == "0.50"
        # true MFU: 19.7e12 / 0.2 / 197e12 = 0.5
        assert cells[8] == "0.500"
        # HBM GB/s: 81.9e9 / 0.2 / 1e9 = 410
        assert cells[9] == "410"

    def test_missing_and_failed_rows_render(self, mvp_mod, tmp_path,
                                            monkeypatch):
        text = self._run(mvp_mod, tmp_path, {
            "bench_gpt2.log": [{"metric": "m [unreachable]",
                                "value": 0.0, "unit": "u"}],
        }, monkeypatch)
        # a 0.0 (failed) record and absent logs both render as no-result
        gpt2 = [l for l in text.splitlines() if l.startswith("| gpt2")]
        assert gpt2 and "(no result)" in gpt2[0]
        bert = [l for l in text.splitlines() if l.startswith("| bert ")]
        assert bert and "(no result)" in bert[0]


class TestRooflineRatio:
    """bench.py's roofline surface: `predicted` + `roofline_ratio` ride
    every record with a real value, from the newest banked
    predicted_*.json priced at the named (on a chip: the attached)
    generation's capability row."""

    def _predictions(self, tmp_path, flops=197e12, nbytes=819e9,
                     units=16384):
        res = tmp_path / "perf_results"
        res.mkdir(exist_ok=True)
        (res / "predicted_r5.json").write_text(json.dumps({
            "steps": [{"name": "gpt2", "units_per_step": units,
                       "flops": flops, "bytes": nbytes}]}))
        return str(res)

    def test_predicted_rate_roofline_math(self, bench_mod, tmp_path):
        res = self._predictions(tmp_path)
        # the v5e row (197 TF, 819 GB/s), named explicitly off-TPU:
        # t_pred = max(1.0, 1.0) = 1 s -> units/sec == units_per_step
        assert bench_mod._predicted_rate("gpt2", res, "v5e") == \
            pytest.approx(16384.0)

    def test_attach_ratio(self, bench_mod, tmp_path):
        res = self._predictions(tmp_path)
        rec = bench_mod._attach_roofline(
            {"metric": "m [tpu]", "value": 8192.0}, "gpt2", res, "v5e")
        assert rec["predicted"] == pytest.approx(16384.0)
        assert rec["roofline_ratio"] == pytest.approx(0.5)

    def test_no_ratio_on_zero_value_or_missing_table(self, bench_mod,
                                                     tmp_path):
        res = self._predictions(tmp_path)
        rec = bench_mod._attach_roofline({"value": 0.0}, "gpt2", res)
        assert "roofline_ratio" not in rec and "predicted" not in rec
        # unknown config / empty results dir: record passes through
        assert bench_mod._attach_roofline(
            {"value": 5.0}, "nope", res) == {"value": 5.0}
        empty = tmp_path / "empty"
        empty.mkdir()
        assert bench_mod._predicted_rate("gpt2", str(empty), "v5e") is None


    def test_no_ratio_on_cpu_smoke_records(self, bench_mod, tmp_path):
        # cpu smoke runs measure tiny auto-shrunk shapes — a ratio vs
        # the accelerator-shape prediction would be noise
        res = self._predictions(tmp_path)
        rec = bench_mod._attach_roofline(
            {"metric": "m [cpu]", "value": 9.0}, "gpt2", res)
        assert "roofline_ratio" not in rec


    def test_newest_prediction_table_by_mtime(self, bench_mod,
                                              tmp_path):
        # lexicographic order would pick r9 over r10; mtime must win
        res = tmp_path / "perf_results"
        res.mkdir()
        old = res / "predicted_r9.json"
        new = res / "predicted_r10.json"
        old.write_text(json.dumps({"steps": [
            {"name": "gpt2", "units_per_step": 1,
             "flops": 197e12, "bytes": 1.0}]}))
        new.write_text(json.dumps({"steps": [
            {"name": "gpt2", "units_per_step": 2,
             "flops": 197e12, "bytes": 1.0}]}))
        os.utime(old, (1_000_000, 1_000_000))
        os.utime(new, (2_000_000, 2_000_000))
        assert bench_mod._predicted_rate("gpt2", str(res), "v5e") == \
            pytest.approx(2.0)

    def test_garbage_prediction_file_never_raises(self, bench_mod,
                                                  tmp_path):
        res = tmp_path / "perf_results"
        res.mkdir()
        (res / "predicted_r9.json").write_text("{broken")
        rec = bench_mod._attach_roofline({"value": 7.0}, "gpt2",
                                         str(res))
        assert rec == {"value": 7.0}


class TestCommsTerm:
    """The roofline ICI comms term: exposed (non-overlapped) bytes ADD
    transfer time to the prediction, so `roofline_ratio` prices the
    overlap layer's win instead of crediting serialized collectives as
    free; and the analytic comms table itself is well-formed."""

    def test_predicted_rate_prices_exposed_ici_bytes(self, bench_mod,
                                                     tmp_path):
        res = tmp_path / "perf_results"
        res.mkdir()
        # off-TPU capability = v5e row: 197 TF, 819 GB/s, ici link
        # 200/(2*2) = 50 GB/s. base t = 1 s; exposed 50 GB -> +1 s.
        (res / "predicted_r9.json").write_text(json.dumps({"steps": [
            {"name": "gpt2", "units_per_step": 16384,
             "flops": 197e12, "bytes": 819e9,
             "ici_exposed_bytes": 50e9}]}))
        assert bench_mod._predicted_rate("gpt2", str(res), "v5e") == \
            pytest.approx(16384.0 / 2.0)

    def test_zero_ici_field_changes_nothing(self, bench_mod, tmp_path):
        res = tmp_path / "perf_results"
        res.mkdir()
        (res / "predicted_r9.json").write_text(json.dumps({"steps": [
            {"name": "gpt2", "units_per_step": 16384,
             "flops": 197e12, "bytes": 819e9,
             "ici_bytes": 0.0, "ici_exposed_bytes": 0.0}]}))
        assert bench_mod._predicted_rate("gpt2", str(res), "v5e") == \
            pytest.approx(16384.0)

    def test_ici_link_rate(self):
        from apex1_tpu.core.capability import ici_link_gbps
        # v5e: 200 GB/s aggregate over a 2D torus's 4 links
        assert ici_link_gbps("v5e") == pytest.approx(50.0)
        assert ici_link_gbps("v5p") == pytest.approx(600.0 / 6.0)

    def test_predict_comms_rows(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "_pp_for_test", _REPO / "tools" / "predict_perf.py")
        pp = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pp)
        rows = pp.predict_comms()
        assert len(rows) == 8  # {v5e,v5p} x {cp4,cp8} x {fwd,bwd}
        for r in rows:
            assert r["exposed_bytes_serial"] == r["ici_bytes"]
            assert 0.0 <= r["exposed_bytes_overlap"] <= r["ici_bytes"]
        # at the 16k shape the attend covers the hop: overlap hides all
        v5e_fwd4 = next(r for r in rows if r["generation"] == "v5e"
                        and r["cp"] == 4 and r["phase"] == "fwd")
        assert v5e_fwd4["exposed_bytes_overlap"] == 0.0
        assert v5e_fwd4["t_serial_ms"] > 0.1
