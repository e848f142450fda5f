"""The Granite 4.0-H family in the benchmark: added to a throw-away copy of
the benchmark's data by files and entries alone (its builder, reference
and metric files are the repo's own, copied with the rest), a tiny cell of
it run end to end on the CPU, the two faults a recurrent state invites
each coming out as not correct, and the new kernel's roofline metric on a
made-up context. Counts and control flow only: a CPU run is never a
speed."""

import json
import os

import jax.numpy as jnp
import pytest

import benchmark_testlib as lib

ROOT = lib.ROOT
CELL = "granite_tiny_chat"
REAL = "granite4hm_serve_chat"

#: the published file with every width cut by 16 (ratios kept) and two
#: periods of a 3-layer pattern. The three multipliers are not the
#: published ones: with the harness's 0.02-normal weights at hidden 128 the
#: published 12 x E[tok] would drown the layers, and every served token
#: would repeat the last one whatever the state held
def _tiny_config() -> dict:
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "granite-4.0-h-micro.json")))
    cfg.update(
        reference="granite-4.0-h-micro", vocab_size=512, hidden_size=128,
        intermediate_size=512, shared_intermediate_size=512,
        num_hidden_layers=6, layer_types=["mamba", "attention", "mamba"] * 2,
        num_attention_heads=8, num_key_value_heads=2,
        attention_multiplier=0.0625, mamba_d_head=16, mamba_d_state=32,
        mamba_n_heads=16, mamba_chunk_size=16, embedding_multiplier=0.05,
        residual_multiplier=1, logits_scaling=1)
    return cfg


def _add_cell(root: str) -> None:
    """The tiny cell as NEW files and NEW entries: a configuration, a
    traffic file, a limits file; the cell's name appended where the real
    cell's is."""
    tiny = lib.tiny_files()["benchmark/traffic/tiny_chat.json"]
    files = {
        "benchmark/configs/granite-tiny.json": _tiny_config(),
        "benchmark/traffic/granite_tiny_chat.json": dict(
            tiny, engine=dict(lib.TINY_ENGINE, eos_id=511,
                              prefix_cache=False)),
        # the bfloat16 program reads 0.03-0.10 here (logit std 0.23), each of
        # the two state faults 0.7-1.4
        f"benchmark/limits/{CELL}.json": {"logit_gap": 0.3},
    }
    for rel, content in files.items():
        path = os.path.join(root, rel)
        assert not os.path.exists(path), f"{rel} would be an edit"
        json.dump(content, open(path, "w"))
    man = lib.mf.load_manifest(root)
    man["configs"].append({
        "name": "granite-tiny", "source": "test", "reduced": [],
        "file": "benchmark/configs/granite-tiny.json", "why": "tiny"})
    man["workloads"].append({
        "name": CELL, "config": "granite-tiny",
        "traffic": "granite_tiny_chat", "chips": 1, "why": "tiny"})
    for m in man["end_to_end"] + man["per_layer"]:
        if REAL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    json.dump(man, open(os.path.join(root, "BENCHMARK.json"), "w"))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = lib.make_root(str(tmp_path_factory.mktemp("granite")), cells=())
    before = {p: open(os.path.join(root, p), "rb").read()
              for p in lib._data_files(root)}
    _add_cell(root)
    for p, content in before.items():       # nothing that was there moved
        assert open(os.path.join(root, p), "rb").read() == content, p
    return root


def test_the_real_cell_is_in_the_manifest_uncut():
    man = lib.mf.load_manifest(ROOT)
    lib.mf.validate(man, ROOT)
    entry = lib.mf.find(man, "configs", "granite-4.0-h-micro")
    assert entry["reduced"] == []
    cell = lib.mf.find(man, "workloads", REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-micro", "hybrid_chat_steady", 1)
    cfg = lib.mf.load_config(man, "granite-4.0-h-micro", ROOT)
    assert (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["vocab_size"]) == (40, 2048, 100352)
    assert cfg["layer_types"].count("attention") == 4
    traffic = lib.mf.load_traffic("hybrid_chat_steady", ROOT)
    assert traffic["engine"] == {
        "max_slots": 48, "max_len": 1024, "prefill_chunk": 256,
        "eos_id": 100257, "max_queue": 256, "prefix_cache": False}
    assert isinstance(traffic["arrivals"]["rate_rps"], float)
    chat = lib.mf.load_traffic("chat_steady", ROOT)
    for key in ("prompt_len", "output_len", "ramp", "gc_freeze",
                "check_requests", "trace"):
        assert traffic[key] == chat[key], key
    assert traffic["shape_seed"] != chat["shape_seed"]


def test_builder_refuses_what_the_model_does_not_compute():
    from benchmark.harness import builders
    cfg = dict(_tiny_config(), _root=ROOT)
    assert builders.get(cfg).family == "granite_hybrid"
    for key, value in (("num_local_experts", 8), ("attention_bias", True),
                       ("position_embedding_type", "rope"),
                       ("tie_word_embeddings", False)):
        with pytest.raises(ValueError, match=key):
            builders.get(dict(cfg, **{key: value}))


def test_reference_loss_is_the_builders_and_the_count_is_the_models():
    import jax
    from benchmark.harness import builders
    b = builders.get(dict(_tiny_config(), _root=ROOT))
    model = b.model("O0")
    shapes = b.param_shapes(model)
    params = builders.make_params(shapes, 3, jnp.float32)
    batch = b.make_batch(jax.random.key(1), 3, 40, {})
    ref = lib.mf.load_reference("granite-4.0-h-micro", ROOT)
    assert abs(float(b.loss_fn(model)(params, batch))
               - float(ref.loss(params, batch, b.ref_cfg))) < 2e-5
    # 6 x every parameter that sits in a matmul (all but norms, biases,
    # the convolution and the three per-head vectors), and the two kinds
    # of product over positions
    n = sum(int(jnp.prod(jnp.asarray(s.shape))) for s in
            jax.tree_util.tree_leaves(shapes) if len(s.shape) == 2
            and s.shape[0] > 4)
    flops = b.train_flops_per_token(40)
    assert flops == 6.0 * n + 12 * 256 * 32 * 4 + 12 * 40 * 128 * 2 / 2


def test_tiny_cell_runs_end_to_end_and_counts_its_state(root):
    code, res = lib.run_tiny(root, CELL, trace=1)
    assert code == 4 and res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["compared"]["served_token_widest_logit_gap"]["ok"]
    mb = res["metrics"]["engine.state_mb_step.chat"]
    # a lane's recurrent leaves: 4 layers x (16 x 16 x 32 float32 state +
    # 3 x 320 bfloat16 inputs), read once and written once
    lane = 2 * 4 * (16 * 16 * 32 * 4 + 3 * 320 * 2)
    assert mb["unit"] == "MB" and 0 < mb["value"] <= 4 * lane / 1e6
    # nothing to read from a CPU trace: the kernel's metrics are left out
    assert not any(n.startswith("kernel.ssm_step") for n in res["metrics"])
    code, res = lib.run_tiny(root, CELL)
    assert code == 4 and res["correct"] is True, res
    assert res["metrics"]["tpot_p50_ms"]["value"] > 0


def _break(monkeypatch, name, broken):
    from apex1_tpu.models import granite_hybrid
    real = getattr(granite_hybrid, name)
    monkeypatch.setattr(granite_hybrid, name,
                        lambda *a, **kw: broken(real, *a, **kw))


def test_state_zeroed_at_a_chunk_boundary_is_not_correct(root, monkeypatch):
    """Every prefill chunk starts from an empty state, as if a lane did
    not carry it from one chunk of a prompt to the next."""
    _break(monkeypatch, "ssd_chunk",
           lambda real, x, dt, A, B, C, D, state, *a, **kw: real(
               x, dt, A, B, C, D, jnp.zeros_like(state), *a, **kw))
    code, res = lib.run_tiny(root, CELL)
    assert code == 4 and res["correct"] is False
    assert not res["compared"]["served_token_widest_logit_gap"]["ok"]


def test_pad_tokens_that_advance_the_state_are_not_correct(root,
                                                           monkeypatch):
    """`n_real` dropped on its way to the state: the padding of a prompt's
    last chunk decays and feeds the state like real tokens."""
    _break(monkeypatch, "ssd_chunk",
           lambda real, x, dt, A, B, C, D, state, n_real=None, **kw: real(
               x, dt, A, B, C, D, state, None, **kw))
    _break(monkeypatch, "causal_conv",
           lambda real, x, w, b, conv_state, n_real=None: real(
               x, w, b, conv_state, None))
    code, res = lib.run_tiny(root, CELL)
    assert code == 4 and res["correct"] is False
    assert not res["compared"]["served_token_widest_logit_gap"]["ok"]


def _synthetic_ctx(lanes_a_step: float, kernel_ms: float) -> dict:
    """A traced run's context as `run_cell` hands it to a metric's file,
    with ten step spans on the program's spine."""
    from apex1_tpu.obs import spine
    from benchmark.harness import device
    lanes = int(10 * lanes_a_step)
    for _ in range(10):
        with spine.span("serving/step") as sp:
            sp.counts = {"state_lanes": lanes // 10,
                         "state_bytes": 2 * (lanes // 10) * 76437504}
    man = lib.mf.load_manifest(ROOT)
    return {"scalars": {"window.steps": 10},
            "cfg": lib.mf.load_config(man, "granite-4.0-h-micro", ROOT),
            "device": {"peaks": device.peaks("TPU v5 lite")},
            "trace": {"n_steps": 10.0, "kernels": {
                "apex1_ssm_step": [360, 1e-2 * kernel_ms, kernel_ms]}}}


def test_roofline_metric_counts_what_the_kernel_moves():
    """30 live lanes a step, 36 layers: 2 x 2.1 MB of state a lane a layer
    and its small operands, 4.59 GB a step, 5.60 ms at 819 GB/s. Over a
    kernel time of 7 ms that is 80 %, bound by bytes; a share cannot pass
    100, and the reader never clips one."""
    read = lib.mf.load_layer_metric
    ctx = _synthetic_ctx(30, 7.0)
    mod = read("kernel.ssm_step.roofline_pct.chat", ROOT)["_module"]
    ops, bytes_ = mod.count(ctx["cfg"], 30)
    assert bytes_ == 36 * 30 * 4 * (2 * 64 * 64 * 128 + 3 * 64 * 64 + 256)
    assert ops == 36 * 30 * 4 * 64 * 64 * 128
    share = mod.read(ctx)
    assert share == pytest.approx(100 * bytes_ / 819e9 / 7e-3)
    assert 75 < share <= 100
    assert read("kernel.ssm_step.ms_step.chat", ROOT)["_module"].read(
        ctx) == 7.0
    assert read("engine.state_mb_step.chat", ROOT)["_module"].read(
        ctx) == pytest.approx(2 * 30 * 76437504 / 1e6)
    # nothing to read: an untraced run, a trace without the kernel, a
    # device without peaks (a CPU rehearsal)
    for broken in ({"trace": None}, {"trace": {"n_steps": 10.0,
                                                "kernels": {}}},
                   {"device": {"peaks": None}}):
        assert mod.read(dict(ctx, **broken)) is None
