"""Observability subsystem (`apex1_tpu.obs`) — spine schema round-trip,
XSpace parse → bucket → report against the committed CPU-trace fixture
(incl. the corrupt/truncated typed-error contract), and the calibration
fit: predicted-vs-measured within a STATED band on synthetic corpora
(the repo ships no chip corpus), so the flywheel stays verified with no
hardware attached.
"""

import gzip
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
            with spine.span("work", iters=3):
                pass
            run.counter("steps", 7)
            run.event("note", detail="x")
            path = run.path
        evs = spine.read_events(path)
        # the span was in memory until the run closed: it is written last
        assert [e["kind"] for e in evs] == [
            "run", "counter", "event", "span"]
        header = evs[0]
        assert header["schema"] == spine.SCHEMA
        assert header["component"] == "t"
        assert evs[1]["value"] == 7
        assert evs[2]["detail"] == "x"
        span = evs[3]
        assert span["name"] == "work" and span["iters"] == 3
        assert span["dur_s"] >= 0 and span["t"] >= 0

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
# spans — the one recorder (spine.span / record_span / snapshot)
# ==========================================================================

@pytest.fixture(scope="module")
def tiny_engine_factory():
    """A tiny fp32 GPT-2 behind `serving.Engine` (eos set, so every
    token is read back each step, as in the benchmark's chat cell)."""
    import jax
    import jax.numpy as jnp
    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.generate import gpt2_decoder
    from apex1_tpu.models.gpt2 import GPT2, GPT2Config
    from apex1_tpu.serving import Engine, EngineConfig

    cfg = GPT2Config.tiny(policy=get_policy("O0"), max_seq_len=64)
    model = GPT2(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 7), jnp.int32))["params"]
    apply_fn, make_cache = gpt2_decoder(model)

    def make(**kw):
        ekw = dict(max_slots=3, max_len=48, prefill_chunk=4,
                   vocab_size=cfg.vocab_size, eos_id=cfg.vocab_size - 1)
        ekw.update(kw)
        return Engine(apply_fn, make_cache, params, EngineConfig(**ekw))

    return make


def _run_requests(engine, lens_news=((3, 4), (7, 3), (5, 5), (9, 2))):
    import numpy as np
    rng = np.random.default_rng(5)
    ids = [engine.submit(rng.integers(0, 100, (n,)).tolist(),
                         max_new_tokens=k) for n, k in lens_news]
    engine.run(max_steps=100)
    return ids


def _since(mark):
    return [sp for sp in spine.snapshot() if sp.id > mark]


def _mark():
    return spine.record_span("test/mark", 0, 0).id


class TestSpans:
    def test_nesting_gives_parents_and_counts_close_late(self):
        mark = _mark()
        with spine.span("outer", req=7) as outer:
            with spine.span("inner", wait=True, k=1) as inner:
                pass
            with spine.span("inner2"):
                pass
            outer.counts["n"] = 2          # known only at the end
        got = {sp.name: sp for sp in _since(mark)}
        assert got["outer"].parent is None and got["outer"].req == 7
        assert got["inner"].parent == got["outer"].id == outer.id
        assert got["inner2"].parent == outer.id
        assert got["inner"].wait and not got["outer"].wait
        assert got["inner"].counts == {"k": 1}
        assert got["outer"].counts == {"n": 2}
        assert inner.id != outer.id
        assert (got["outer"].start_ns <= got["inner"].start_ns
                <= got["inner"].end_ns <= got["inner2"].start_ns
                <= got["inner2"].end_ns <= got["outer"].end_ns)

    def test_record_span_has_no_parent(self):
        mark = _mark()
        with spine.span("around"):
            sp = spine.record_span("queued", 10, 30, req=4, depth=2)
        assert sp.parent is None and (sp.start_ns, sp.end_ns) == (10, 30)
        assert sp.req == 4 and sp.counts == {"depth": 2}
        assert [x.name for x in _since(mark)] == ["queued", "around"]

    def test_a_span_that_raises_is_recorded_and_popped(self):
        mark = _mark()
        with pytest.raises(KeyError):
            with spine.span("outer"):
                with spine.span("boom"):
                    raise KeyError("x")
        with spine.span("after"):
            pass
        got = {sp.name: sp for sp in _since(mark)}
        assert got["boom"].parent == got["outer"].id
        assert got["after"].parent is None      # the stack unwound

    def test_two_threads_do_not_adopt_each_others_spans(self):
        import threading
        mark = _mark()
        inside = threading.Event()
        done = threading.Event()

        def other():
            with spine.span("thread/outer"):
                inside.set()
                done.wait(5)
                with spine.span("thread/inner"):
                    pass

        t = threading.Thread(target=other)
        with spine.span("main/outer"):
            t.start()
            assert inside.wait(5)
            with spine.span("main/inner"):       # while the other is open
                pass
            done.set()
            t.join(5)
        got = {sp.name: sp for sp in _since(mark)}
        assert got["main/outer"].parent is None
        assert got["thread/outer"].parent is None
        assert got["main/inner"].parent == got["main/outer"].id
        assert got["thread/inner"].parent == got["thread/outer"].id

    def test_buffer_drops_the_oldest_and_never_grows(self):
        first = _mark()
        for _ in range(spine.SPAN_CAPACITY + 10):
            with spine.span("fill"):
                pass
        snap = spine.snapshot()
        assert len(snap) == spine.SPAN_CAPACITY
        assert snap[0].id > first               # the oldest went
        assert snap[-1].id == snap[0].id + spine.SPAN_CAPACITY - 1

    def test_a_region_is_a_named_scope_and_no_span(self):
        """`obs.regions.region` names DEVICE work: a scope on the traced
        ops' name stack, nothing in the span buffer (a host span around
        traced code would time the tracing, once); a name outside the
        closed list raises where it is opened."""
        import jax
        import jax.numpy as jnp
        from apex1_tpu.obs.regions import REGIONS, region

        def f(x):
            with region("attn"):
                return jnp.tanh(x)

        mark = _mark()
        jaxpr = jax.make_jaxpr(f)(jnp.ones((4,)))
        assert not _since(mark)
        assert [str(e.source_info.name_stack) for e in jaxpr.eqns] == [
            "~attn"]
        assert "attn" in REGIONS and "train/fwd" not in REGIONS
        with pytest.raises(ValueError, match="no region"):
            region("train/fwd")

    def test_req_is_shared_along_a_requests_life(self,
                                                 tiny_engine_factory):
        mark = _mark()
        engine = tiny_engine_factory()
        ids = _run_requests(engine)
        by_req = {}
        for sp in _since(mark):
            if sp.req is not None:
                by_req.setdefault(sp.req, set()).add(sp.name)
        assert set(by_req) == set(ids)
        for rid in ids:
            assert {"serving/queued", "serving/admit",
                    "serving/admit.alloc", "serving/prefill",
                    "serving/admit.first_read",
                    "serving/retire"} <= by_req[rid], by_req[rid]

    def test_profiler_trace_holds_the_same_spans_nested_the_same_way(
            self, tiny_engine_factory, tmp_path):
        """`span` enters `TraceAnnotation`, so a trace taken around a
        run holds host events of the same names, nested as the buffer's
        parents say."""
        import jax
        engine = tiny_engine_factory()
        _run_requests(engine, ((5, 2),))                  # compile
        mark = _mark()
        with jax.profiler.trace(str(tmp_path)):
            _run_requests(engine)
        mine = [sp for sp in _since(mark) if sp.name != "serving/queued"]
        (pb,) = xspace.find_xplane_files(tmp_path)
        events = []                                       # (name, a, b)
        for plane in xspace.parse_xspace(pb):
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    name = plane.event_names.get(ev.metadata_id, "")
                    if name.startswith("serving/"):
                        a = line.timestamp_ns * 1000 + ev.offset_ps
                        events.append((name, a, a + ev.duration_ps))
        import collections
        assert (collections.Counter(n for n, _, _ in events)
                == collections.Counter(sp.name for sp in mine))
        # each kind in time order pairs the trace's events with the
        # buffer's records; a record's parent must enclose it there too
        by_name = collections.defaultdict(list)
        for ev in sorted(events, key=lambda e: e[1]):
            by_name[ev[0]].append(ev)
        in_trace = {}
        seen = collections.Counter()
        for sp in sorted(mine, key=lambda sp: sp.start_ns):
            in_trace[sp.id] = by_name[sp.name][seen[sp.name]]
            seen[sp.name] += 1
        checked = 0
        for sp in mine:
            if sp.parent in in_trace:
                _, a, b = in_trace[sp.id]
                _, pa, pb_ = in_trace[sp.parent]
                assert pa <= a and b <= pb_, (sp.name, a, b, pa, pb_)
                checked += 1
        assert checked >= 20

    def test_spans_written_at_close_read_back(self, no_default_run,
                                              monkeypatch, tmp_path,
                                              tiny_engine_factory):
        monkeypatch.setenv("APEX1_OBS_DIR", str(tmp_path))
        with spine.span("before/the-run"):
            pass
        run = spine.default_run()
        ids = _run_requests(tiny_engine_factory())
        assert spine.read_events(run.path, kinds=("span",)) == []
        run.close()                       # in memory first, written now
        rows = spine.read_events(run.path, kinds=("span",))
        assert rows and all(r["t"] >= 0 and r["dur_s"] >= 0
                            for r in rows)
        assert "before/the-run" not in {r["name"] for r in rows}
        by_id = {r["id"]: r for r in rows}
        steps = [r for r in rows if r["name"] == "serving/step"]
        assert steps and all(r["parent"] is None for r in steps)
        assert "control_dispatches" in steps[0]
        admits = [r for r in rows if r["name"] == "serving/admit"]
        assert sorted(r["req"] for r in admits) == sorted(ids)
        for r in admits:
            assert by_id[r["parent"]]["name"] == "serving/step"
        reads = [r for r in rows if r["name"] == "serving/read_tokens"]
        assert reads and all(r["wait"] is True for r in reads)
        assert all("wait" not in r for r in steps)


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

    def test_custom_call_is_keyed_by_its_kernel(self):
        hlo = ("%apex1_flash_dq.7 = bf16[8,16,1024,128]{3,2,1,0} "
               "custom-call(bf16[8,16,1024,128]{3,2,1,0} %p.1), "
               'custom_call_target="tpu_custom_call"')
        assert xspace.op_key(hlo) == "apex1_flash_dq"
        assert xspace.bucket_of(hlo) == "pallas"
        tup = ("%apex1_layer_norm_fwd = (bf16[64,128]{1,0}, f32[64,1]"
               "{1,0}) custom-call(bf16[64,128]{1,0} %x), "
               'custom_call_target="tpu_custom_call"')
        assert xspace.op_key(tup) == "apex1_layer_norm_fwd"
        fusion = ("%fusion.12 = bf16[8,1024]{1,0:T(8,128)(2,1)} fusion("
                  "bf16[8,1024]{1,0} %custom-call.3, f32[] %all-reduce.1"
                  "), kind=kLoop")
        assert xspace.op_key(fusion) == fusion
        # operands name other instructions: the fusion is neither
        assert xspace.bucket_of(fusion) == "xla"
        assert xspace.bucket_of(
            "%ar.1 = (f32[64]{0}, f32[64]{0}) all-reduce(f32[64]{0} %a, "
            "f32[64]{0} %b), channel_id=1") == "collective"
        assert xspace.op_key("dot.4") == "dot.4"

    def test_chip_trace_busy_and_idle_agree_with_the_benchmarks_reader(
            self):
        """Two readers written apart — this walker and the benchmark's
        `harness/trace.py` on `jax.profiler.ProfileData` — agree on a
        recorded four-chip trace: only "XLA Ops" counts, busy is a union
        of intervals, idle is the window less busy."""
        import sys
        sys.path.insert(0, str(_REPO))
        from benchmark.harness import trace as bench_trace
        pb = _REPO / "tests" / "benchmark" / "data" / \
            "ddp4_tiny.xplane.pb.gz"
        mine = xspace.build_report(pb, window_span="bench/window")
        theirs = bench_trace.reduce(str(pb))
        assert mine["plane_class"] == "device"
        assert mine["n_devices"] == theirs["n_devices"] == 4
        assert mine["window_s"] == pytest.approx(theirs["window_s"],
                                                 abs=1e-6)
        assert mine["busy_s"] == pytest.approx(theirs["busy_s"], abs=1e-6)
        assert mine["idle_s"] == pytest.approx(
            theirs["window_s"] - theirs["busy_s"], abs=1e-6)
        assert mine["idle_gaps"][0][0] == theirs["idle_gaps"][0][0] \
            == "train/dispatch"
        assert mine["idle_gaps"][0][1] == pytest.approx(
            theirs["idle_gaps"][0][1], abs=1e-6)
        # the Pallas calls of that (older) program carry their jax
        # scope's name; each is one line, not one per instruction
        pallas = [o for o in mine["ops"] if o["bucket"] == "pallas"]
        assert {"layer0", "layer1"} <= {o["name"] for o in pallas}
        # without the window span the window is the ops' own extent
        own = xspace.build_report(pb)
        assert own["window_s"] < mine["window_s"]
        assert own["busy_s"] == pytest.approx(mine["busy_s"], rel=0.02)

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
