"""The AFMoE family (Trinity-Mini) in the benchmark: the real cell in the
manifest at its published widths; a tiny cell of it added to a throw-away
copy of the benchmark's data by files and entries alone and run end to end
on the CPU, closed loop and all, through rings that wrap, with the control;
timed paths whose window is one position off, or whose ring is read as a
line, coming out as not correct; and the three new per-layer metrics on a
made-up context. Counts and control flow only: a CPU run is never a
speed."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_testlib as lib

ROOT = lib.ROOT
CELL = "afmoe_tiny_longctx"
REAL = "trinitymini_serve_longctx"
#: the metrics this PR brings list the real cell alone
NEW = ("kernel.decode_attend.ms_step.chat",
       "kernel.decode_attend.roofline_pct.chat", "attn.window_read_pct.chat")


def _tiny_config() -> dict:
    """The published file with every width cut by 8 (ratios kept), one
    dense layer and the period of the pattern twice over less one sliding
    layer, a window of 64, 16 experts of which this chip holds 4 and a
    token takes 4. The four norms a layer keep every layer's part of the
    stream's own size, so hidden 256 hides nothing."""
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "trinity-mini.json")))
    cfg.update(
        vocab_size=512, hidden_size=256, intermediate_size=768,
        moe_intermediate_size=128, num_hidden_layers=6,
        layer_types=["sliding_attention", "sliding_attention",
                     "full_attention"] * 2,
        global_attn_every_n_layers=3, head_dim=32,
        num_attention_heads=8, num_key_value_heads=2, sliding_window=64,
        num_dense_layers=1, num_experts=4, num_experts_per_tok=4,
        published={"num_experts": 16},
        expert_parallel={"chips": 4, "rank": 1, "experts_held": [4, 4]})
    return cfg


#: a pool of 768 positions: the sliding layers keep rings of 384 rows (a
#: window of 64 and the decoder's 256 rows of slack, in whole blocks), so
#: a prompt of 450 has been once round its rings and one of 700 nearly
#: twice; chunks of 64 reach the window's end inside a prompt
_ENGINE = {"max_slots": 4, "max_len": 768, "prefill_chunk": 64,
           "eos_id": 511, "max_queue": 64, "prefix_cache": False}


def _add_cell(root: str) -> None:
    """The tiny cell as NEW files and NEW entries; the cell's name appended
    where the real cell's is."""
    real = lib.mf.load_traffic("longctx_rollout_closed", ROOT)
    real.pop("_name")
    files = {
        "benchmark/configs/afmoe-tiny.json": _tiny_config(),
        "benchmark/traffic/afmoe_tiny_longctx.json": dict(
            real, callers=6, engine=_ENGINE,
            prompt_len={"dist": "uniform", "min": 300, "max": 740},
            output_len={"dist": "uniform", "min": 4, "max": 12},
            ramp={"seconds": 0.5}, check_requests=3, trace={"seconds": 1}),
        # the bfloat16 program reads 0 to 0.027 here at four seeds (logit
        # std 0.32), the float8 control 0.097 to 0.59; the two broken
        # paths are read in their test
        f"benchmark/limits/{CELL}.json": {"logit_gap": 0.05},
    }
    for rel, content in files.items():
        path = os.path.join(root, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        json.dump(content, open(path, "w"))
    man = lib.mf.load_manifest(root)
    man["configs"].append({
        "name": "afmoe-tiny", "source": "test", "reduced": ["num_experts"],
        "file": "benchmark/configs/afmoe-tiny.json", "why": "tiny"})
    man["workloads"].append({
        "name": CELL, "config": "afmoe-tiny",
        "traffic": "afmoe_tiny_longctx", "chips": 1, "why": "tiny"})
    for m in man["end_to_end"] + man["per_layer"]:
        if REAL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    json.dump(man, open(os.path.join(root, "BENCHMARK.json"), "w"))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = lib.make_root(str(tmp_path_factory.mktemp("afmoe")), cells=())
    before = {p: open(os.path.join(root, p), "rb").read()
              for p in lib._data_files(root)}
    _add_cell(root)
    for p, content in before.items():       # nothing that was there moved
        assert open(os.path.join(root, p), "rb").read() == content, p
    return root


def test_the_real_cell_is_in_the_manifest_at_its_published_widths():
    man = lib.mf.load_manifest(ROOT)
    lib.mf.validate(man, ROOT)
    entry = lib.mf.find(man, "configs", "trinity-mini")
    assert entry["reduced"] == ["num_experts"]
    assert entry["source"] == ("https://huggingface.co/arcee-ai/"
                               "Trinity-Mini/blob/main/config.json")
    cfg = lib.mf.load_config(man, "trinity-mini", ROOT)
    catalog = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_expert_groups": 1, "num_experts_per_tok": 8,
        "num_hidden_layers": 32, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    assert {k: cfg[k] for k in catalog} == catalog
    assert len(cfg["layer_types"]) == 32
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t == "full_attention"] == list(range(3, 32, 4))
    assert set(cfg["layer_types"]) == {"sliding_attention",
                                       "full_attention"}
    # the one key cut, with the published count and the deployment beside
    assert cfg["num_experts"] == 16
    assert cfg["published"] == {"num_experts": 128}
    assert cfg["expert_parallel"] == {"chips": 8, "rank": 0,
                                      "experts_held": [0, 16]}
    assert cfg["reduced"] == ["num_experts"] and cfg["deployment"]
    assert {"attention_gate", "qk_norm", "nope_on_global_layers",
            "four_norms", "mup_embedding", "route_norm_epsilon",
            "eos_token_id", "cache_dtypes", "mask_convention",
            "weights"} <= set(cfg["assumed"])
    cell = lib.mf.find(man, "workloads", REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-mini", "longctx_rollout_closed", 1)
    traffic = lib.mf.load_traffic("longctx_rollout_closed", ROOT)
    assert traffic["kind"] == "serve_closed" and traffic["callers"] == 24
    assert traffic["engine"] == {
        "max_slots": 16, "max_len": 8704, "prefill_chunk": 256,
        "eos_id": cfg["eos_token_id"], "max_queue": 24,
        "prefix_cache": False}
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 3072,
                                     "sigma": 0.5, "min": 1024, "max": 7168}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 768,
                                     "sigma": 0.4, "min": 384, "max": 1536}
    # 16 s, not the other cells' 8: sixteen prompts are prefilled first and
    # no answer is under 384 tokens, so nothing finishes before ~13 s and a
    # traced 5 s window behind 8 s would have nothing to compare
    assert traffic["ramp"] == {"seconds": 16.0} and traffic["gc_freeze"]
    assert traffic["check_requests"] == 6
    assert traffic["trace"] == {"seconds": 5}
    assert "arrivals" not in traffic and "prefix" not in traffic
    shape_seeds = {lib.mf.load_traffic(w["traffic"], ROOT).get("shape_seed")
                   for w in man["workloads"] if w["name"] != REAL}
    assert traffic["shape_seed"] not in shape_seeds
    # the longest request fits a lane, and prompts reach past two windows
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        <= traffic["engine"]["max_len"]
    assert traffic["prompt_len"]["median"] > cfg["sliding_window"]
    mine = {m["name"] for m in lib.mf.cell_metrics(man, REAL, "per_layer")}
    assert set(NEW) | {"kernel.moe_experts.ms_step.chat",
                       "kernel.moe_experts.roofline_pct.chat",
                       "moe.tokens_per_expert.chat",
                       "moe.experts_touched_pct.chat",
                       "engine.kv_read_pct.chat", "device.idle_pct.chat",
                       "device.peak_hbm_gib.chat"} <= mine
    # no region of a mixer, no leaf without positions, no arrivals
    assert not {"model.mixer_ms.chat", "engine.state_mb_step.chat",
                "loadgen.late_p99_ms.chat"} & mine
    assert not any(n.startswith("kernel.ssm_step") for n in mine)
    for m in man["per_layer"]:
        if m["name"] in NEW:        # the parent cannot hand the others these
            assert m["workloads"] == [REAL] and m["moves"] == "tpot_p50_ms"
    assert [m["name"] for m in lib.mf.cell_metrics(
        man, REAL, "end_to_end")] == ["tpot_p50_ms", "setup_s"]
    limits = json.load(open(os.path.join(
        ROOT, "benchmark", "limits", REAL + ".json")))
    assert set(limits) == {"logit_gap", "set_from"}


def test_builder_hands_model_and_reference_the_published_count_and_share():
    from benchmark.harness import builders
    man = lib.mf.load_manifest(ROOT)
    b = builders.get(lib.mf.load_config(man, "trinity-mini", ROOT))
    assert b.family == "afmoe" and b.vocab_size == 200192
    assert b.ref_cfg["num_experts"] == 128 and b.ref_cfg["held"] == [0, 16]
    model = b.model("O2")
    assert model.cfg.num_experts == 128 and model.cfg.held == range(0, 16)
    assert model.cfg.sliding_window == 2048 and model.cfg.mup_enabled
    assert model.cfg.route.scale == 2.826 and model.cfg.route.top_k == 8
    shapes = b.param_shapes(model)
    assert shapes["layer2"]["router"].shape == (2048, 128)
    assert shapes["layer2"]["experts_w1"].shape == (16, 2048, 1024)
    assert shapes["layer2"]["shared_w2"].shape == (1024, 2048)
    assert shapes["layer0"]["w1"].shape == (2048, 6144)
    assert shapes["layer0"]["wgate"].shape == (2048, 4096)
    assert shapes["lm_head"].shape == shapes["embed"].shape == (200192, 2048)
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert 4.98e9 < n < 4.99e9                     # 4.985 B held
    cfg = dict(_tiny_config(), _root=ROOT)
    for key, value in (("hidden_act", "gelu"), ("num_expert_groups", 4),
                       ("global_attn_every_n_layers", 4)):
        with pytest.raises(ValueError, match=key):
            builders.get(dict(cfg, **{key: value}))
    for key, value in (("n_group", 4), ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            builders.get(dict(cfg, **{key: value})).model("O2")
    with pytest.raises(ValueError, match="experts held"):
        builders.get(dict(cfg, num_experts=16))


def test_reference_loss_is_the_builders_and_the_count_is_the_models():
    from benchmark.harness import builders
    b = builders.get(dict(_tiny_config(), _root=ROOT))
    model = b.model("O0")
    shapes = b.param_shapes(model)
    params = builders.make_params(shapes, 3, jnp.float32)
    batch = b.make_batch(jax.random.key(1), 3, 100, {})
    ref = lib.mf.load_reference("afmoe", ROOT)
    assert abs(float(b.loss_fn(model)(params, batch))
               - float(ref.loss(params, batch, b.ref_cfg))) < 2e-5
    h, q, kv = 256, 8 * 32, 2 * 32
    matmul = (6 * (3 * h * q + 2 * h * kv) + 3 * h * 768
              + 5 * (h * 16 + 3 * h * 128 * (4 + 1)) + 512 * h)
    assert b.train_flops_per_token(100) == 6.0 * matmul + 12 * q * (
        4 * 100 + 2 * 100) / 2
    # past two windows a sliding layer attends its window, not the row
    assert b.train_flops_per_token(1000) == 6.0 * matmul + 12 * q * (
        4 * 128 + 2 * 1000) / 2


def test_tiny_cell_runs_end_to_end_with_its_control_and_counts(root):
    """Prompts of 300 to 740 through rings of 384 rows: the longest sampled
    request has been round its rings, so the comparison (the reference's
    full forward, a mask over the full row) holds the ring and the window
    to account. It reads 0 to 0.027 here at four seeds (bfloat16, logit
    std 0.32); the control, the reference in float8, 0.097 to 0.59."""
    code, res = lib.run_tiny(root, CELL, "--control", "1", trace=1)
    assert code == 4 and res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] > 6
    assert res["compared"]["served_token_widest_logit_gap"]["ok"]
    got = res["metrics"]
    assert 0 < got["attn.window_read_pct.chat"]["value"] < 100
    assert 0 < got["engine.kv_read_pct.chat"]["value"] <= 100
    assert 1.0 <= got["moe.tokens_per_expert.chat"]["value"] <= 4.0
    assert 5 < got["moe.experts_touched_pct.chat"]["value"] <= 100
    assert got["engine.occupancy_pct.chat"]["value"] > 50     # closed loop
    # nothing to read from a CPU trace: the kernels' metrics are left out
    assert not any(n.startswith("kernel.") for n in got)
    # every metric that lists the cell finds something to read in a traced
    # run, but for what only a device's trace holds
    listed = {m["name"]: m["source"] for m in lib.mf.cell_metrics(
        lib.mf.load_manifest(root), CELL, "per_layer")}
    assert not {"loadgen.late_p99_ms.chat", "model.mixer_ms.chat",
                "engine.state_mb_step.chat"} & set(listed)
    absent = {n: s for n, s in listed.items() if n not in got}
    assert set(absent.values()) <= {"device_trace"}, absent
    code, res = lib.run_tiny(root, CELL)
    assert code == 4 and res["correct"] is True, res
    assert res["metrics"]["tpot_p50_ms"]["value"] > 0


def test_the_control_fails_the_check(root):
    """The reference in float8 puts first, at the compared positions, a
    token that the float32 reference holds far below its best: past the
    limit that the bfloat16 program stays inside. Read here over six seeds
    of weights: the program 0.0001 to 0.014 and the control 0.15 to 0.48
    at five; at the sixth (seed 11) BOTH put first the same token 0.115
    below the float32 reference's best: a choice between two experts that
    lies inside bfloat16's rounding, which a limit cannot tell from a
    fault at this size (36 tokens, a vocabulary of 512)."""
    from benchmark.harness import builders, check
    from apex1_tpu.models.generate import generate
    b = builders.get(dict(_tiny_config(), _root=ROOT))
    model = b.model("O2")
    params = builders.make_params(b.param_shapes(model), 12, jnp.bfloat16)
    apply_fn, make_cache = b.decoder(model)
    prompts = jax.random.randint(jax.random.key(2), (3, 150), 0, 512)
    toks = generate(apply_fn, params, prompts, max_new_tokens=12,
                    cache=make_cache(3, 256), vocab_size=512)
    sample = [{"prompt": np.asarray(p), "tokens": np.asarray(t)}
              for p, t in zip(prompts, toks)]
    ref = lib.mf.load_reference("afmoe", ROOT)
    out = check.serve_gaps(ref, b.ref_cfg, params, sample, 192, 12,
                           check.control_quant(1))
    limit = check.load_limits(CELL, root)["logit_gap"]
    assert out["widest_gap"] <= limit < out["control_widest_gap"]


@pytest.mark.parametrize("broken", ["window_one_off", "ring_as_a_line"])
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, broken):
    """The timed path alone is broken (the composite behind both
    executables on the CPU), the reference is whole: a window that sees
    one position more reads 0.12 to 0.16 here at two seeds, a ring whose
    rows are taken for the positions they had before it wrapped 0.83 to
    1.0, against a limit of 0.05."""
    import importlib
    generate = importlib.import_module("apex1_tpu.models.generate")
    real = generate.cache_attend

    def attend(q, k_all, v_all, idx, *, window=None, **kw):
        if window is None:
            return real(q, k_all, v_all, idx, **kw)
        if broken == "window_one_off":
            return real(q, k_all, v_all, idx, window=window + 1, **kw)
        return real(q, k_all, v_all, idx % k_all.shape[1], **kw)

    monkeypatch.setattr(generate, "cache_attend", attend)
    code, res = lib.run_tiny(root, CELL)
    assert code == 4 and res["correct"] is False
    assert not res["compared"]["served_token_widest_logit_gap"]["ok"]


def _synthetic_ctx(blocks: int, in_window: int, kernel_ms: float) -> dict:
    """A traced run's context as `run_cell` hands it to a metric's file,
    with ten step spans on the program's spine."""
    from apex1_tpu.obs import spine
    from benchmark.harness import device
    for _ in range(10):
        with spine.span("serving/step") as sp:
            sp.counts = {"kv_blocks_read": blocks, "kv_layers": 32,
                         "kv_blocks_read_window": in_window,
                         "kv_blocks_pool": 16 * (8 * 70 + 24 * 18)}
    man = lib.mf.load_manifest(ROOT)
    return {"scalars": {"window.steps": 10}, "xplane": None,
            "cfg": lib.mf.load_config(man, "trinity-mini", ROOT),
            "device": {"peaks": device.peaks("TPU v5 lite")},
            "trace": {"n_steps": 10.0, "main_module": "jit_decode",
                      "kernels": {"apex1_decode_attend": [
                          320, 1e-2 * kernel_ms, kernel_ms]}}}


def test_the_new_metrics_count_what_the_steps_read(monkeypatch):
    """16 lanes at depth 3800: a global layer reads 30 blocks a lane, a
    sliding one 17: 16 x (8 x 30 + 24 x 17) = 10 368 blocks of 128
    positions of 2 x 512 numbers of 2 B = 2.72 GB a step, 3.3 ms at 819
    GB/s; over 6.0 ms in the steps' own calls that is 55 %, bound by
    bytes. 63 % of the blocks are the sliding layers'."""
    from benchmark.harness import step_kernels
    read = lib.mf.load_layer_metric
    mod = read(NEW[1], ROOT)["_module"]
    blocks, in_window = 16 * (8 * 30 + 24 * 17), 16 * 24 * 17
    ctx = _synthetic_ctx(blocks, in_window, 6.0)
    ops, bytes_ = mod.count(ctx["cfg"], blocks)
    assert bytes_ == blocks * 128 * 2 * 4 * 128 * 2 == 2_717_908_992
    assert ops == blocks * 128 * 4 * 32 * 128
    monkeypatch.setattr(step_kernels, "in_main_module",
                        lambda c, k: [320, 0.06, 6.0])
    # the window's whole (the prefill program has no such kernel: the same)
    share = mod.read(ctx)
    assert share == pytest.approx(100 * bytes_ / 819e9 / 6.0e-3)
    assert 50 < share < 60
    assert read(NEW[0], ROOT)["_module"].read(ctx) == 6.0
    assert read(NEW[2], ROOT)["_module"].read(ctx) == pytest.approx(
        100 * 24 * 17 / (8 * 30 + 24 * 17))
    assert read("engine.kv_read_pct.chat", ROOT)["_module"].read(ctx) \
        == pytest.approx(100 * blocks / (16 * (8 * 70 + 24 * 18)))
    monkeypatch.undo()
    # nothing to read: no trace file; a trace without the kernel; a
    # program whose steps carry no such counts (the parent commit's, or a
    # decoder whose leaves have one length: its kv_blocks_read is a
    # lane's, not a layer's)
    assert step_kernels.in_main_module(ctx, "apex1_decode_attend") is None
    for name in NEW[:2]:
        assert read(name, ROOT)["_module"].read(ctx) is None
        assert read(name, ROOT)["_module"].read(
            dict(ctx, trace={"n_steps": 10.0, "kernels": {}})) is None
    from apex1_tpu.obs import spine
    for _ in range(10):
        with spine.span("serving/step") as sp:
            sp.counts = {"n_active": 3, "kv_blocks_read": 40,
                         "kv_blocks_pool": 96}
    monkeypatch.setattr(step_kernels, "in_main_module",
                        lambda c, k: [320, 0.06, 6.0])
    for name in NEW[1:]:
        assert read(name, ROOT)["_module"].read(ctx) is None
    # the kernel's time needs no count: any program that runs it has it
    assert read(NEW[0], ROOT)["_module"].read(ctx) == 6.0
