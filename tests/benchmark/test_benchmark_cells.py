"""Each kind of cell end to end at a tiny preset on the CPU (DDP on four
virtual devices), added to a throw-away copy of the benchmark as new
files plus new entries only; the real command refuses to measure without
a TPU; a broken timed path comes out as not correct; the lower-precision
control fails the comparison that the program passes."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import benchmark_testlib as lib

ROOT = lib.ROOT


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return lib.make_root(str(tmp_path_factory.mktemp("bench_root")))


def _assert_result_shape(res, names):
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    for n in names:
        assert n in res["metrics"], (n, sorted(res["metrics"]))
        assert res["metrics"][n]["value"] > 0
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell,metric", [
    ("tiny_train", "train_tok_s_chip"), ("tiny_ddp", "train_tok_s_chip"),
    ("tiny_chat", "tpot_p50_ms"), ("tiny_docs", "serve_tok_s")])
def test_cell_kind_runs_end_to_end_on_cpu(tiny_root, cell, metric):
    code, res = lib.run_tiny(tiny_root, cell)
    # a rehearsal never yields exit 0 (no result line is printed for it)
    assert code == 4
    _assert_result_shape(res, [metric, "setup_s"])
    assert res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] > 0
    if cell == "tiny_ddp":
        assert res["device"]["count"] == 4


@pytest.mark.parametrize("cell", ["tiny_train", "tiny_chat"])
def test_traced_run_reports_per_layer_metrics(tiny_root, cell):
    code, res = lib.run_tiny(tiny_root, cell, trace=1)
    assert code == 4
    assert {"setup.import_s", "setup.compile_s", "setup.warmup_s",
            "setup.ramp_s"} <= set(res["metrics"])
    assert "busy_s" in res["device"] and "window_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # nothing to read from a CPU trace: device metrics are left OUT, not
    # reported as zero
    assert not any(n.startswith(("device.idle_pct", "ops.custom_call_ms",
                                 "step.device_ms", "step.mfu_pct"))
                   for n in res["metrics"])


def test_throwaway_cell_is_three_new_files_and_entries(tmp_path):
    """A cell of an existing kind = a config file, a traffic file, a
    limits file, and entries in BENCHMARK.json. `make_root` asserts that
    no file that was there changed."""
    root = lib.make_root(str(tmp_path / "r"), cells=("tiny_docs",))
    added = sorted(set(lib._data_files(root)) - set(lib._data_files(ROOT)))
    assert added == ["benchmark/configs/gpt2-tiny.json",
                     "benchmark/limits/tiny_docs.json",
                     "benchmark/traffic/tiny_docs.json"]
    code, res = lib.run_tiny(root, "tiny_docs")
    assert code == 4 and res["correct"] is True
    assert "serve_tok_s" in res["metrics"]


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The benchmark's data plus a model FAMILY it does not have."""
    root = lib.make_root(str(tmp_path_factory.mktemp("toy_root")), cells=())
    lib.add_toy_family(root)
    return root


def test_a_family_is_added_by_files_and_entries_alone(toy_root):
    """What the next `model_config` PR does: a configuration whose
    `builder` no table knows, its builder, its plain reference, traffic,
    limits, and a per-layer metric whose own file reads the trace, the
    configuration and the device — new files and new entries only
    (`add_toy_family` asserts that no file that was there differs, and
    the harness's code is the repo's). A training rehearsal (traced, so
    the metric is read) and a serving rehearsal both return a result."""
    added = sorted(set(lib._data_files(toy_root))
                   - set(lib._data_files(ROOT)))
    assert all(f.rsplit("/", 1)[1].startswith("toy") for f in added), added
    assert not set(os.listdir(os.path.join(toy_root, "benchmark",
                                           "harness"))) - set(os.listdir(
        os.path.join(ROOT, "benchmark", "harness")))
    code, res = lib.run_tiny(toy_root, "toy_train", trace=1)
    assert code == 4 and res["correct"] is True, res
    assert res["metrics"]["toy.layers_traced.train"] == {
        "value": 2.0, "unit": "layers"}
    assert "setup.compile_s" in res["metrics"]
    # every number compared, beside its limit, is the line's last key
    assert list(res)[-1] == "compared"
    assert all(set(row) == {"value", "limit", "ok"} and row["ok"]
               for row in res["compared"].values())
    assert "first_grad_rel_diff" in res["compared"]
    code, res = lib.run_tiny(toy_root, "toy_train")
    assert code == 4 and res["correct"] is True
    assert res["metrics"]["train_tok_s_chip"]["value"] > 0
    code, res = lib.run_tiny(toy_root, "toy_chat")
    assert code == 4 and res["correct"] is True, res
    assert res["metrics"]["tpot_p50_ms"]["value"] > 0
    assert "served_token_widest_logit_gap" in res["compared"]


def test_a_builders_own_layout_over_four_chips_is_used(toy_root, capsys):
    """A builder with `shard_step` lays its step over the chips itself:
    `train.make_step` calls it in place of its own `ddp` wrapping, and
    the cell runs end to end and is correct."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    code, res = lib.run_tiny(toy_root, "toy_dp4")
    assert "toy_sharded.shard_step: 4 devices" in capsys.readouterr().out
    assert code == 4 and res["correct"] is True, res
    assert res["device"]["count"] == 4


def test_a_builder_that_has_no_file_is_refused(toy_root):
    man = lib.mf.load_manifest(toy_root)
    cfg = lib.mf.load_config(man, "toy-tiny", toy_root)
    from benchmark.harness import builders
    assert builders.get(cfg).family == "toy_decoder"
    # the same name under the repo's own root has no file: refused by
    # name, with the path that was looked for
    with pytest.raises(lib.mf.ManifestError, match="toy_decoder.py"):
        builders.get(dict(lib.TOY_CONFIG))
    with pytest.raises(lib.mf.ManifestError, match="no builder"):
        builders.get({"builder": "nope", "_root": toy_root})


def test_real_command_exits_nonzero_without_a_tpu():
    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = man["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable if w == "python3" else w for w in man["command"]]
    proc = subprocess.run(
        cmd + ["--workload", cell, "--seed", "1", "--seconds", "1",
               "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    assert not last.startswith("{"), "a result line without a chip"


def test_broken_training_step_is_not_correct(tiny_root, monkeypatch):
    """The timed path broken underneath: a step that returns its state
    unchanged. The harness's look for a chip is skipped, the rest of the
    run is driven, and `correct` comes out false."""
    import types

    import jax
    from benchmark.harness import train
    real = train.make_step

    def broken(cfg, traffic, devices):
        pieces = real(cfg, traffic, devices)
        step = pieces["step"]

        class Unchanged:
            def lower(self, state, batch):
                compiled = step.lower(state, batch).compile()

                def call(state, batch):
                    keep = jax.tree_util.tree_map(lambda x: x.copy(), state)
                    _, metrics = compiled(state, batch)
                    return keep, metrics
                return types.SimpleNamespace(compile=lambda: call)

        pieces["step"] = Unchanged()
        return pieces

    monkeypatch.setattr(train, "make_step", broken)
    code, res = lib.run_tiny(tiny_root, "tiny_train")
    assert code == 4 and res["correct"] is False


def test_altered_served_token_is_not_correct(tiny_root, monkeypatch):
    """A token altered where it is produced: the engine's sampler is made
    to emit its choice + 1."""
    from apex1_tpu.serving import engine as eng
    real = eng.sample_token

    def off_by_one(logits, key, **kw):
        tok = real(logits, key, **kw)
        return (tok + 1) % 250

    monkeypatch.setattr(eng, "sample_token", off_by_one)
    code, res = lib.run_tiny(tiny_root, "tiny_chat")
    assert code == 4 and res["correct"] is False


def _tiny_reference_inputs(seed=5):
    import jax
    import jax.numpy as jnp
    from benchmark.harness import builders
    ref = lib.mf.load_reference("gpt2-medium", ROOT)
    b = builders.get(dict(lib.TINY_GPT2))
    shapes = b.param_shapes(b.model("O2"))
    p0 = builders.make_params(shapes, seed, jnp.float32)
    batches = [jax.tree_util.tree_map(np.asarray, b.make_batch(
        jax.random.key(i), 4, 64, {})) for i in range(3)]
    return ref, b, p0, batches


def _follow_pair(ref, b, p0, batches, opt, quant, opt_other=None):
    """(other, sound): a second run of the reference (in `quant`, or with
    another optimizer setting) in the program's place, compared with the
    sound float32 run as `train.follow` compares."""
    from benchmark.harness import check
    other = check.train_reference(ref, b.ref_cfg, p0, batches, 1,
                                  opt_other or opt, 2, quant,
                                  keep_first=True)
    sound = check.train_reference(
        ref, b.ref_cfg, p0, batches, 1, opt, 2,
        compare_first={"other": other.pop("first_grad")})
    other["first_grad_diff_norms"] = sound.pop(
        "first_grad_diff_norms")["other"]
    return other, sound


ADAM = {"name": "adam", "lr": 1e-4, "weight_decay": 0.01}


def test_training_control_fails_where_the_program_passes():
    """The control (the reference with matmul operands rounded through
    float8_e4m3fn, gradients through float8_e5m2) against the float32
    reference, at a tiny size: the difference of its first gradient is
    several times what bfloat16 gives, so a limit between the two exists;
    the gap between NORMS, second order in rounding noise, separates them
    less."""
    import jax.numpy as jnp
    from benchmark.harness import check
    ref, b, p0, batches = _tiny_reference_inputs()
    diffs = {}
    for name, quant in (("bfloat16", jnp.dtype("bfloat16")),
                        ("control", check.control_quant(True))):
        low, sound = _follow_pair(ref, b, p0, batches, ADAM, quant)
        diffs[name] = check.rel_diff(low["first_grad_diff_norms"],
                                     sound["first_grad_norms"])
    assert str(check.control_quant(True)) == "float8_e4m3fn"
    assert diffs["control"] > 5 * diffs["bfloat16"], diffs
    limits = dict(lib.TRAIN_LIMITS,
                  first_grad_rel_diff=3 * diffs["bfloat16"])
    rows = check.compare_training(low, sound, limits)
    failed = [name for name, *_, ok in rows if not ok]
    assert "first_grad_rel_diff" in failed, rows


def test_a_learning_rate_wrong_by_a_third_is_not_correct():
    """An optimizer fault that is not gross: the same float32 run with its
    learning rate scaled by 1.3 in the program's place. Every leaf's change
    is 1.3 times the reference's, and the parameter-change number (over
    live leaves, key biases apart) fails a limit set at three times what
    sound runs read."""
    from benchmark.harness import check
    ref, b, p0, batches = _tiny_reference_inputs()
    wrong, sound = _follow_pair(ref, b, p0, batches, ADAM, None,
                                dict(ADAM, lr=1.3e-4))
    rows = {name: (v, ok) for name, v, _, ok in check.compare_training(
        wrong, sound, lib.TRAIN_LIMITS)}
    v, ok = rows["param_change_worst_live_leaf_gap"]
    assert not ok and 0.25 < v < 0.35, rows
    # the key-bias part of the fused q|k|v bias is dead and left out
    live = check.live_leaves(sound["first_grad_norms"])
    assert 0 < (~live).sum() <= 2 * lib.TINY_GPT2["n_layer"]


def test_reference_over_four_chips_gives_the_sums_of_one():
    """The reference's blocks of a step divided among four (virtual)
    chips: the same losses, gradient norms and change as on one."""
    import jax
    from benchmark.harness import check
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    ref, b, p0, batches = _tiny_reference_inputs()
    one = check.train_reference(ref, b.ref_cfg, p0, batches, 1, ADAM, 1,
                                devices=jax.devices()[:1])
    four = check.train_reference(ref, b.ref_cfg, p0, batches, 1, ADAM, 1,
                                 devices=jax.devices()[:4])
    np.testing.assert_allclose(one["losses"], four["losses"], rtol=1e-6)
    for k in ("first_grad_norms", "change_norms"):
        np.testing.assert_allclose(one[k], four[k], rtol=1e-4, atol=1e-9)


def test_serving_control_picks_tokens_below_the_reference_best():
    import jax.numpy as jnp
    from benchmark.harness import builders, check
    ref = lib.mf.load_reference("gpt2-medium", ROOT)
    b = builders.get(dict(lib.TINY_GPT2))
    shapes = b.param_shapes(b.model("O2"))
    params = builders.make_params(shapes, 9, jnp.bfloat16)
    toks = np.random.default_rng(0).integers(0, 256, 96).astype(np.int32)
    # "served" tokens that are arbitrary ids, not a decode
    sample = [{"prompt": toks[:n], "tokens": toks[n:n + 8]}
              for n in (20, 33, 50)]
    out = check.serve_gaps(ref, b.ref_cfg, params, sample, 96, 8,
                           check.control_quant(True))
    assert out["n_tokens"] == 24
    # arbitrary served tokens lie far below the best; the control's first
    # choice lies below it too, but by less than a random token does
    assert out["widest_gap"] > out["control_widest_gap"] > 0
