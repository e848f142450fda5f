"""Checkpoint/resume + observability tests — SURVEY.md §5.4/§5.1.

Key reference behaviors: amp loss-scaler state round-trips; sharded opt
state saves/restores; resume onto a different mesh layout; exact training
continuation after restore."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from apex1_tpu.amp import Amp
from apex1_tpu.checkpoint import (CheckpointManager, restore_checkpoint,
                                  save_checkpoint)
from apex1_tpu.core.mesh import make_mesh
from apex1_tpu.optim.fused_adam import fused_adam
from apex1_tpu.utils.observability import (MetricsLogger, Timers,
                                           cost_analysis)


def _state_and_step():
    amp = Amp(tx=fused_adam(1e-2), opt_level="O1_fp16",
              loss_scale="dynamic")
    params = {"w": jnp.ones((8,), jnp.float32),
              "b": jnp.zeros((4,), jnp.float32)}
    state = amp.init(params)
    step = jax.jit(amp.make_train_step(
        lambda p, x: jnp.sum(jnp.square(p["w"])) * x + jnp.sum(p["b"])))
    return amp, state, step


def test_roundtrip_amp_state(tmp_path):
    amp, state, step = _state_and_step()
    for _ in range(3):
        state, _ = step(state, jnp.float32(1.0))
    save_checkpoint(tmp_path / "ckpt", state)
    restored = restore_checkpoint(tmp_path / "ckpt", template=state)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # training continues identically from the restore
    s1, m1 = step(state, jnp.float32(1.0))
    s2, m2 = step(restored, jnp.float32(1.0))
    np.testing.assert_allclose(np.asarray(m1["loss"]),
                               np.asarray(m2["loss"]))
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow  # 870s-cap headroom: quant x checkpoint COMPOSITION
# (26s: two generate compiles); each layer stays pinned in tier-1 —
# int8 generate parity in test_quantized, orbax round-trip fidelity in
# test_roundtrip_amp_state/test_loss_scale_state_round_trips
def test_quantized_decode_params_round_trip(tmp_path):
    """int8 serving trees (models.quant_decode) checkpoint bit-exactly —
    int8 weights, fp32 scales, bf16 embedding table all survive orbax,
    and a restored tree generates identical tokens."""
    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.generate import generate
    from apex1_tpu.models.llama import Llama, LlamaConfig
    from apex1_tpu.models.quant_decode import llama_quant_decoder

    # O2 so the embedding table really is bf16 (O0 would make every
    # non-int8 leaf fp32 and silently drop the mixed-dtype coverage)
    cfg = LlamaConfig.tiny(policy=get_policy("O2"), max_seq_len=32)
    model = Llama(cfg)
    rng = np.random.default_rng(3)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 4)),
                         jnp.int32)
    params = model.init(jax.random.key(0), prompt)["params"]
    apply_q, make_cache, qparams = llama_quant_decoder(model, params)
    assert any(l.dtype == jnp.bfloat16
               for l in jax.tree.leaves(qparams))  # coverage is real
    save_checkpoint(tmp_path / "q", qparams)
    restored = restore_checkpoint(tmp_path / "q", template=qparams)
    for a, b in zip(jax.tree.leaves(qparams), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype  # int8 stays int8
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    t1 = generate(apply_q, qparams, prompt, max_new_tokens=4,
                  cache=make_cache(2, 8), vocab_size=cfg.vocab_size)
    t2 = generate(apply_q, restored, prompt, max_new_tokens=4,
                  cache=make_cache(2, 8), vocab_size=cfg.vocab_size)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))


def test_loss_scale_state_round_trips(tmp_path):
    amp, state, step = _state_and_step()
    state, _ = step(state, jnp.float32(1e30))   # overflow: scale halves
    state, _ = step(state, jnp.float32(1.0))
    save_checkpoint(tmp_path / "c2", state)
    restored = restore_checkpoint(tmp_path / "c2", template=state)
    assert float(restored.loss_scale.scale) == float(state.loss_scale.scale)
    # fp16 calibration may overflow more than once while the scale walks
    # down from 2^16 (reference-faithful); the COUNT must round-trip exactly
    assert (int(restored.loss_scale.overflow_count)
            == int(state.loss_scale.overflow_count) >= 1)


def test_restore_onto_mesh(tmp_path, devices):
    """Save unsharded, restore sharded over fsdp=4 — topology-change
    resume the reference cannot do."""
    x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
    state = {"w": x, "step": jnp.int32(7)}
    save_checkpoint(tmp_path / "c3", state)
    mesh = make_mesh(fsdp=4, dp=1, devices=devices[:4])
    specs = {"w": P("fsdp", None), "step": P()}
    restored = restore_checkpoint(tmp_path / "c3", template=state,
                                  mesh=mesh, spec_tree=specs)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(x))
    assert restored["w"].sharding.spec == P("fsdp", None)


def test_manager_rotation_and_resume(tmp_path):
    amp, state, step = _state_and_step()
    with CheckpointManager(tmp_path / "mgr", max_to_keep=2) as mgr:
        for i in range(4):
            state, _ = step(state, jnp.float32(1.0))
            mgr.save(i, state, force=True)
        mgr.wait_until_finished()
        assert mgr.latest() == 3
        restored = mgr.restore(jax.eval_shape(lambda: state))
        np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                      np.asarray(state.params["w"]))
        kept = {os.path.basename(p) for p in
                glob.glob(str(tmp_path / "mgr" / "*")) if
                os.path.basename(p).isdigit()}
        assert kept == {"2", "3"}


def test_cost_analysis_flops():
    a = jnp.ones((128, 128), jnp.float32)
    ca = cost_analysis(lambda a: a @ a, a)
    assert ca.get("flops", 0) >= 2 * 128 ** 3 * 0.9


def test_timers_and_region():
    """A timer around work that a region names: the region is a scope of
    the traced program (it lands in the jaxpr's name stack and changes
    nothing that runs), the timer host time."""
    from apex1_tpu.obs.regions import region

    def f(a):
        with region("ffn"):
            return a @ a

    t = Timers()
    t("fwd").start()
    x = jax.jit(f)(jnp.ones((64, 64)))
    t("fwd").stop(sync=x)
    out = t.log()
    assert out["fwd"] > 0
    (eqn,) = [e for e in jax.make_jaxpr(f)(jnp.ones((64, 64))).eqns
              if e.primitive.name == "dot_general"]
    assert str(eqn.source_info.name_stack) == "~ffn"


def test_metrics_logger():
    lines = []
    ml = MetricsLogger(writer=lines.append, n_chips=1)
    ml.log(0, {"loss": jnp.float32(2.5)}, tokens=100)
    ml.log(1, {"loss": jnp.float32(2.0)}, tokens=100)
    import json
    recs = [json.loads(l) for l in lines]
    assert recs[0]["loss"] == 2.5
    assert "tokens_per_sec_per_chip" in recs[1]
