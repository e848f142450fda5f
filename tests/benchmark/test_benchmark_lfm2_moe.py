"""The LFM2-with-experts family in the benchmark: the real cell in the
manifest at its published widths; a tiny cell of it added to a throw-away
copy of the benchmark's data by files and entries alone and run end to end
on the CPU, closed loop and all, with the control; a timed path whose
router ignores its bias coming out as not correct; and the four new
per-layer metrics on a made-up context. Counts and control flow only: a CPU run is never a speed."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

import benchmark_testlib as lib

ROOT = lib.ROOT
CELL = "lfm2_tiny_rollout"
REAL = "lfm2moe_serve_rollout"


def _tiny_config() -> dict:
    """The published file with every width cut by 4 (ratios kept), one
    dense layer and a period of the pattern twice over, 8 experts of
    which this chip holds 4 and a token takes 2. No narrower: the
    harness draws matrices 0.02 normal, a product through one gains 0.02
    sqrt(hidden), and at hidden 128 the six layers together add less to
    the stream than the token's own embedding: every served token would
    repeat the last, whatever the router did."""
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "lfm2-8b-a1b.json")))
    cfg.update(
        vocab_size=512, hidden_size=512, intermediate_size=1792,
        moe_intermediate_size=448, num_hidden_layers=6,
        layer_types=["conv", "conv", "full_attention"] * 2,
        num_attention_heads=8, num_key_value_heads=2, num_dense_layers=1,
        num_experts=4, num_experts_per_tok=2,
        published={"num_experts": 8},
        expert_parallel={"chips": 2, "rank": 1, "experts_held": [4, 4]})
    return cfg


def _add_cell(root: str) -> None:
    """The tiny cell as NEW files and NEW entries; the cell's name appended
    where the real cell's is."""
    real = lib.mf.load_traffic("rollout_closed", ROOT)
    real.pop("_name")
    files = {
        "benchmark/configs/lfm2-tiny.json": _tiny_config(),
        "benchmark/traffic/lfm2_tiny_rollout.json": dict(
            real, callers=6,
            engine=dict(lib.TINY_ENGINE, eos_id=511, prefix_cache=False),
            prompt_len={"dist": "lognormal", "median": 20, "sigma": 0.5,
                        "min": 4, "max": 48},
            output_len={"dist": "uniform", "min": 4, "max": 12},
            ramp={"seconds": 0.5}, check_requests=3, trace={"seconds": 1}),
        # the bfloat16 program reads 0.001 to 0.002 here at two seeds of
        # three (logit std 0.5; 0.12 at the third: a near-tie between two
        # experts that the bfloat16 rows decide the other way), the float8
        # control 0.12 to 0.22; the two broken routers are read in their
        # tests
        f"benchmark/limits/{CELL}.json": {"logit_gap": 0.08},
    }
    for rel, content in files.items():
        path = os.path.join(root, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        json.dump(content, open(path, "w"))
    man = lib.mf.load_manifest(root)
    man["configs"].append({
        "name": "lfm2-tiny", "source": "test", "reduced": ["num_experts"],
        "file": "benchmark/configs/lfm2-tiny.json", "why": "tiny"})
    man["workloads"].append({
        "name": CELL, "config": "lfm2-tiny",
        "traffic": "lfm2_tiny_rollout", "chips": 1, "why": "tiny"})
    for m in man["end_to_end"] + man["per_layer"]:
        if REAL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    json.dump(man, open(os.path.join(root, "BENCHMARK.json"), "w"))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = lib.make_root(str(tmp_path_factory.mktemp("lfm2")), cells=())
    before = {p: open(os.path.join(root, p), "rb").read()
              for p in lib._data_files(root)}
    _add_cell(root)
    for p, content in before.items():       # nothing that was there moved
        assert open(os.path.join(root, p), "rb").read() == content, p
    return root


def test_the_real_cell_is_in_the_manifest_at_its_published_widths():
    man = lib.mf.load_manifest(ROOT)
    lib.mf.validate(man, ROOT)
    entry = lib.mf.find(man, "configs", "lfm2-8b-a1b")
    assert entry["reduced"] == ["num_experts"]
    cfg = lib.mf.load_config(man, "lfm2-8b-a1b", ROOT)
    catalog = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts_per_tok": 4, "num_hidden_layers": 24,
        "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    assert {k: cfg[k] for k in catalog} == catalog
    assert cfg["layer_types"].count("full_attention") == 6
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    # the one key cut, with the published count and the deployment beside
    assert cfg["num_experts"] == 8
    assert cfg["published"] == {"num_experts": 32}
    assert cfg["expert_parallel"] == {"chips": 4, "rank": 0,
                                      "experts_held": [0, 8]}
    assert {"tie_word_embeddings", "eos_token_id", "cache_dtypes",
            "weights"} <= set(cfg["assumed"])
    cell = lib.mf.find(man, "workloads", REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-8b-a1b", "rollout_closed", 1)
    traffic = lib.mf.load_traffic("rollout_closed", ROOT)
    assert traffic["kind"] == "serve_closed" and traffic["callers"] == 128
    assert traffic["engine"] == {
        "max_slots": 96, "max_len": 2560, "prefill_chunk": 256,
        "eos_id": 7, "max_queue": 128, "prefix_cache": False}
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 256,
                                     "sigma": 0.5, "min": 64, "max": 512}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 1024,
                                     "sigma": 0.4, "min": 512, "max": 2048}
    assert traffic["ramp"] == {"seconds": 8.0} and traffic["gc_freeze"]
    assert traffic["check_requests"] == 6
    assert traffic["trace"] == {"seconds": 5}
    assert "arrivals" not in traffic and "prefix" not in traffic
    # the longest request fits a lane
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        <= traffic["engine"]["max_len"]
    mine = {m["name"] for m in lib.mf.cell_metrics(man, REAL, "per_layer")}
    assert {"kernel.moe_experts.ms_step.chat",
            "kernel.moe_experts.roofline_pct.chat",
            "moe.tokens_per_expert.chat", "moe.experts_touched_pct.chat",
            "engine.state_mb_step.chat", "device.idle_pct.chat"} <= mine
    assert not any(n.startswith("kernel.ssm_step") for n in mine)
    assert [m["name"] for m in lib.mf.cell_metrics(
        man, REAL, "end_to_end")] == ["tpot_p50_ms", "setup_s"]


def test_builder_hands_model_and_reference_the_published_count_and_share():
    from benchmark.harness import builders
    man = lib.mf.load_manifest(ROOT)
    b = builders.get(lib.mf.load_config(man, "lfm2-8b-a1b", ROOT))
    assert b.family == "lfm2_moe" and b.vocab_size == 65536
    assert b.ref_cfg["num_experts"] == 32 and b.ref_cfg["held"] == [0, 8]
    model = b.model("O2")
    assert model.cfg.num_experts == 32 and model.cfg.held == range(0, 8)
    shapes = b.param_shapes(model)
    assert shapes["layer2"]["router"].shape == (2048, 32)
    assert shapes["layer2"]["experts_w1"].shape == (8, 2048, 1792)
    assert shapes["layer0"]["w1"].shape == (2048, 7168)
    n = sum(int(jnp.prod(jnp.asarray(s.shape)))
            for s in jax.tree_util.tree_leaves(shapes))
    assert 2.52e9 < n < 2.54e9                     # 2.527 B held
    cfg = dict(_tiny_config(), _root=ROOT)
    for key, value in (("conv_bias", True), ("tie_word_embeddings", False)):
        with pytest.raises(ValueError, match=key):
            builders.get(dict(cfg, **{key: value}))
    with pytest.raises(ValueError, match="experts held"):
        builders.get(dict(cfg, num_experts=8))


def test_reference_loss_is_the_builders_and_the_count_is_the_models():
    from benchmark.harness import builders
    b = builders.get(dict(_tiny_config(), _root=ROOT))
    model = b.model("O0")
    shapes = b.param_shapes(model)
    params = builders.make_params(shapes, 3, jnp.float32)
    batch = b.make_batch(jax.random.key(1), 3, 40, {})
    ref = lib.mf.load_reference("lfm2_moe", ROOT)
    assert abs(float(b.loss_fn(model)(params, batch))
               - float(ref.loss(params, batch, b.ref_cfg))) < 2e-5
    h = 512
    matmul = (4 * 4 * h * h + 2 * (2 * h * h + 2 * h * 128) + 3 * h * 1792
              + 5 * (h * 8 + 2 * 3 * h * 448) + 512 * h)
    assert b.train_flops_per_token(40) == 6.0 * matmul + 12 * 40 * h * 2 / 2


def test_tiny_cell_runs_end_to_end_with_its_control_and_counts(root):
    code, res = lib.run_tiny(root, CELL, "--control", "1", trace=1)
    assert code == 4 and res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] > 6
    assert res["compared"]["served_token_widest_logit_gap"]["ok"]
    got = res["metrics"]
    # 4 experts held of 8, top-2: a row meets a held expert once a layer
    # on average, and a step of up to 4 lanes touches some of the 20
    assert 1.0 <= got["moe.tokens_per_expert.chat"]["value"] <= 4.0
    assert 5 < got["moe.experts_touched_pct.chat"]["value"] <= 100
    assert got["engine.state_mb_step.chat"]["value"] > 0
    assert got["engine.occupancy_pct.chat"]["value"] > 50     # closed loop
    # nothing to read from a CPU trace: the kernel's metrics are left out
    assert not any(n.startswith("kernel.moe_experts") for n in got)
    # every metric that lists the cell finds something to read in a traced
    # run, but for what only a device's trace holds: a closed loop has no
    # arrivals to be late, so `loadgen.late_p99_ms.chat` does not list it
    listed = {m["name"]: m["source"] for m in lib.mf.cell_metrics(
        lib.mf.load_manifest(root), CELL, "per_layer")}
    assert "loadgen.late_p99_ms.chat" not in listed
    absent = {n: s for n, s in listed.items() if n not in got}
    assert set(absent.values()) <= {"device_trace"}, absent
    code, res = lib.run_tiny(root, CELL)
    assert code == 4 and res["correct"] is True, res
    assert res["metrics"]["tpot_p50_ms"]["value"] > 0


def test_the_control_fails_the_check(root, capsys):
    """The reference in float8 puts first, at the compared positions, a
    token that the float32 reference holds far below its best: past the
    limit that the bfloat16 program stays inside."""
    from benchmark.harness import builders, check
    cfg = dict(_tiny_config(), _root=ROOT)
    b = builders.get(cfg)
    model = b.model("O2")
    params = builders.make_params(b.param_shapes(model), 11, jnp.bfloat16)
    apply_fn, make_cache = b.decoder(model)
    from apex1_tpu.models.generate import generate
    prompts = jax.random.randint(jax.random.key(2), (3, 20), 0, 512)
    toks = generate(apply_fn, params, prompts, max_new_tokens=12,
                    cache=make_cache(3, 64), vocab_size=512)
    import numpy as np
    sample = [{"prompt": np.asarray(p), "tokens": np.asarray(t)}
              for p, t in zip(prompts, toks)]
    ref = lib.mf.load_reference("lfm2_moe", ROOT)
    out = check.serve_gaps(ref, b.ref_cfg, params, sample, 96, 12,
                           check.control_quant(1))
    limit = check.load_limits(CELL, root)["logit_gap"]
    assert out["widest_gap"] <= limit < out["control_widest_gap"]


def _break_router(monkeypatch, broken):
    from apex1_tpu.models import lfm2
    real = lfm2.dropless_route
    monkeypatch.setattr(lfm2, "dropless_route",
                        lambda *a: broken(real, *a))


def test_a_router_that_ignores_its_bias_is_not_correct(root, monkeypatch):
    """The broken timed path: the experts chosen by the unbiased scores.
    It reads 0.21 to 0.30 over three seeds. (A router that WEIGHS by the
    biased score moves the logits by less, inside what three requests can
    show: 0.01 to 0.05 over three seeds, since four weights that sum to 1
    hardly move under a bias of 0.02; `tests/test_lfm2.py` holds it
    against the float32 reference, where it is 2000 times the
    tolerance.)"""
    import dataclasses
    _break_router(monkeypatch, lambda real, x2, wg, bias, cfg: real(
        x2, wg, None, dataclasses.replace(cfg, select_bias=False)))
    code, res = lib.run_tiny(root, CELL)
    assert code == 4 and res["correct"] is False
    assert not res["compared"]["served_token_widest_logit_gap"]["ok"]


def _synthetic_ctx(rows: int, touched: int, kernel_ms: float,
                   xplane=None) -> dict:
    """A traced run's context as `run_cell` hands it to a metric's file,
    with ten step spans on the program's spine."""
    from apex1_tpu.obs import spine
    from benchmark.harness import device
    for _ in range(10):
        with spine.span("serving/step") as sp:
            sp.counts = {"moe_rows": rows, "moe_experts_touched": touched,
                         "moe_expert_slots": 176}
    man = lib.mf.load_manifest(ROOT)
    return {"scalars": {"window.steps": 10}, "xplane": xplane,
            "cfg": lib.mf.load_config(man, "lfm2-8b-a1b", ROOT),
            "device": {"peaks": device.peaks("TPU v5 lite")},
            "trace": {"n_steps": 10.0, "main_module": "jit_decode",
                      "kernels": {"apex1_moe_experts": [
                          220, 1e-2 * kernel_ms, kernel_ms]}}}


def test_roofline_metric_counts_what_the_steps_routed(monkeypatch):
    """2112 pairs over all 176 held experts a step (12 a touched expert):
    176 x 22 MB of matrices and the rows in and out, 3.89 GB a step, 4.75
    ms at 819 GB/s; over 5.2 ms in the steps' own calls that is 91 %, bound
    by bytes. With half the experts untouched the bytes halve. The time is
    the kernel's inside the main program, not the window's whole."""
    from benchmark.harness import step_kernels
    read = lib.mf.load_layer_metric
    mod = read("kernel.moe_experts.roofline_pct.chat", ROOT)["_module"]
    ctx = _synthetic_ctx(2112, 176, 5.2)
    ops, bytes_ = mod.count(ctx["cfg"], 2112, 176)
    assert bytes_ == 176 * 3 * 2048 * 1792 * 2 + 2112 * 2 * 2048 * 2
    assert ops == 2112 * 6 * 2048 * 1792
    assert mod.count(ctx["cfg"], 2112, 88)[1] < 0.51 * bytes_
    # the steps' own calls take 5.2 ms of the 6.0 the window's all take
    monkeypatch.setattr(step_kernels, "in_main_module",
                        lambda c, k: [220, 0.052, 5.2])
    ctx["trace"]["kernels"]["apex1_moe_experts"] = [260, 0.06, 6.0]
    share = mod.read(ctx)
    assert share == pytest.approx(100 * bytes_ / 819e9 / 5.2e-3)
    assert 85 < share <= 100
    assert read("kernel.moe_experts.ms_step.chat", ROOT)["_module"].read(
        ctx) == 5.2
    assert read("moe.tokens_per_expert.chat", ROOT)["_module"].read(
        ctx) == pytest.approx(12.0)
    assert read("moe.experts_touched_pct.chat", ROOT)["_module"].read(
        ctx) == pytest.approx(100.0)
    monkeypatch.undo()
    # nothing to read: no trace file, a trace without the kernel, a
    # program whose steps carry no such count (the parent commit's)
    assert step_kernels.in_main_module(ctx, "apex1_moe_experts") is None
    assert mod.read(ctx) is None
    assert mod.read(dict(ctx, trace={"n_steps": 10.0, "kernels": {}})) \
        is None
    from apex1_tpu.obs import spine
    for _ in range(10):
        with spine.span("serving/step") as sp:
            sp.counts = {"n_active": 3}
    for name in ("kernel.moe_experts.roofline_pct.chat",
                 "moe.tokens_per_expert.chat",
                 "moe.experts_touched_pct.chat"):
        assert read(name, ROOT)["_module"].read(ctx) is None


def test_a_kernels_time_inside_the_main_program_alone(tmp_path,
                                                      monkeypatch):
    """Three executions of the step program with two calls of the kernel
    each, and one of the prefill program with two longer ones between:
    the step's own calls are kept, the prefill's left out, and a call cut
    by the window's edge counts by its part inside."""
    from benchmark.harness import step_kernels, trace
    k = "%apex1_moe_experts.3 = bf16[624,2048]{1,0} custom-call(%a), " \
        "custom_call_target=\"tpu_custom_call\""
    other = "%fusion.1 = bf16[96,2048]{1,0} fusion(%p)"
    ops, mods = [], []
    t = 0.0
    for i in range(3):
        mods.append(("jit_decode(123)", t, 1000.0))
        ops += [(other, t, 100.0), (k, t + 100, 200.0), (k, t + 500, 200.0)]
        t += 1000.0
        if i == 0:
            mods.append(("jit_prefill(9)", t, 2000.0))
            ops += [(k, t + 100, 700.0), (k, t + 1000, 700.0)]
            t += 2000.0
    raw = {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                        "XLA Modules": mods}},
           "host": {"bench/window": [(200.0, t)]}}
    monkeypatch.setattr(trace, "load", lambda path: raw)
    step_kernels._inside_main.cache_clear()
    red = trace.reduce_events(raw)
    assert red["main_module"] == "jit_decode"
    whole = red["kernels"]["apex1_moe_experts"]
    assert whole[0] == 8 and whole[1] == pytest.approx(2.5e-6)
    ctx = {"xplane": "made-up", "trace": red}
    calls, secs, ms = step_kernels.in_main_module(ctx, "apex1_moe_experts")
    # the first call starts at 100 and the window at 200: half of it
    assert calls == 6 and secs == pytest.approx(1.1e-6)
    assert ms == pytest.approx(1e3 * secs / red["n_steps"])
    assert step_kernels.in_main_module(ctx, "apex1_ssm_step") is None
    step_kernels._inside_main.cache_clear()
