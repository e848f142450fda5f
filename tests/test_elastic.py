"""ISSUE 14 acceptance tests — elastic resume: planner-driven re-plan
plus manifest-verified checkpoint resharding.

Covers: `resilience.reshard` determinism and A→B→A bit-exact round
trips for every dtype the repo trains (fp32 / bf16 / fp16-master /
int8 + scales), the ZeRO flat-shard repack, checkpoint-level reshard
(byte-identical leaf digests across independent reshards, corrupted
reshard output REFUSED at restore — never trusted), the typed
`LayoutMismatch` contract (no plan meta, layout change, structure
change), the chaos `shrink_schedule` / fleetsim `kill_k_of_n`
helpers, and THE acceptance drill: 8→4-device mid-run shrink through
a planner re-plan with a bit-exact loss trajectory vs the 4-device
from-checkpoint control, reconstructable from banked obs-spine
events alone."""

import dataclasses
import os
import subprocess
import sys

import jax
import ml_dtypes
import numpy as np
import pytest

from apex1_tpu import planner
from apex1_tpu.parallel.distributed_optimizer import (flat_param_len,
                                                      repack_flat_shard,
                                                      shard_padded_len)
from apex1_tpu.resilience import (IntegrityError, LayoutMismatch,
                                  ResilientCheckpointer, elastic_resume,
                                  read_manifest, read_plan,
                                  reshard_checkpoint, reshard_state)
from apex1_tpu.testing import chaos


def _shape_with(**over):
    return planner.ModelShape(**{**dataclasses.asdict(SHAPE), **over})

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHAPE = planner.ModelShape(
    name="tiny-elastic", num_layers=4, hidden_size=32, ffn_size=64,
    num_heads=4, num_kv_heads=2, head_dim=8, vocab_size=64,
    seq_len=16, global_batch=8)

#: stated interleaved 8-dev plan (stack (2, 2, 1)) and a 4-dev plan
#: (stack (1, 2, 2)) — a genuine restack between them
PLAN_A = planner.plan_for_layout(
    SHAPE, planner.Layout(dp=2, pp=2, tp=2, num_microbatches=4,
                          num_chunks=2))
PLAN_B = planner.plan_for_layout(
    SHAPE, planner.Layout(dp=2, pp=2, tp=1, num_microbatches=4))


def _synth_state(stack=(2, 2, 1)):
    """Chunk-stacked state with every dtype the repo trains: fp32
    weights, bf16 activside weights, fp16 master-style copies, int8
    quantized weights + their fp16 scales."""
    rng = np.random.default_rng(7)
    V, PP, L = stack

    def w(dt):
        return rng.normal(size=(V, PP, L, 3, 5)).astype(dt)

    chunk = {
        "w_fp32": w(np.float32),
        "w_bf16": w(ml_dtypes.bfloat16),
        "w_fp16": w(np.float16),
        "q_int8": rng.integers(-127, 127,
                               (V, PP, L, 3, 5)).astype(np.int8),
        "q_scale": w(np.float16),
    }
    return {"step": np.int32(5),
            "params": {"chunk": chunk,
                       "shared": {"emb": w(np.float32)[0, 0, 0]}}}


def test_reshard_plan_schema_matches_planner():
    """reshard.py spells the schema string locally (reading plan meta
    must stay planner-free); the two constants must never drift."""
    from apex1_tpu.resilience import reshard

    assert reshard.PLAN_SCHEMA == planner.PLAN_SCHEMA


class TestReshardState:
    def test_restack_changes_stack_and_round_trips_bit_exact(self):
        state = _synth_state((2, 2, 1))
        mid, rep = reshard_state(state, PLAN_A, PLAN_B)
        assert rep["n_restacked"] == 5 and rep["conserved"]
        assert mid["params"]["chunk"]["w_fp32"].shape[:3] == (1, 2, 2)
        back, rep2 = reshard_state(mid, PLAN_B, PLAN_A)
        for k, v in state["params"]["chunk"].items():
            got = back["params"]["chunk"][k]
            assert got.dtype == v.dtype, k
            assert got.tobytes() == v.tobytes(), \
                f"A->B->A not bit-exact for dtype {v.dtype} ({k})"

    def test_same_inputs_byte_identical(self):
        state = _synth_state((2, 2, 1))
        a, _ = reshard_state(state, PLAN_A, PLAN_B)
        b, _ = reshard_state(state, PLAN_A, PLAN_B)
        la = jax.tree_util.tree_leaves(a)
        lb = jax.tree_util.tree_leaves(b)
        assert all(x.tobytes() == y.tobytes() for x, y in zip(la, lb))

    def test_zero_shard_repack_strips_and_repads(self):
        gb6 = _shape_with(name="tiny-z", global_batch=6)
        pa = planner.plan_for_layout(
            gb6, planner.Layout(dp=3, num_microbatches=2, zero=True))
        pb = planner.plan_for_layout(
            gb6, planner.Layout(dp=2, num_microbatches=3, zero=True))
        params = {"w": np.arange(34.0, dtype=np.float32).reshape(17, 2)}
        n = flat_param_len(params)
        assert n == 34
        # dp=3 pads 34 -> 36; the REAL padding is zero (see
        # repack_flat_shard's exactness contract), which is what makes
        # the round trip an identity
        shard = np.concatenate([np.arange(36.0, dtype=np.float32)[:34],
                                np.zeros(2, np.float32)])
        state = {"params": params,
                 "opt": {"exp_avg_shard": shard,
                         "exp_avg_sq_shard": shard * 2.0}}
        out, rep = reshard_state(state, pa, pb)
        assert rep["n_repacked"] == 2 and rep["conserved"]
        assert out["opt"]["exp_avg_shard"].shape == (34,)  # dp=2: no pad
        np.testing.assert_array_equal(out["opt"]["exp_avg_shard"],
                                      shard[:34])
        back, _ = reshard_state(out, pb, pa)
        np.testing.assert_array_equal(back["opt"]["exp_avg_sq_shard"],
                                      shard * 2.0)

    def test_nonzero_source_tail_refused(self):
        """A nonzero padded tail means the zero-padding invariant
        broke upstream; the repack must refuse loudly rather than
        silently truncate data."""
        gb6 = _shape_with(name="tiny-z4", global_batch=6)
        pa = planner.plan_for_layout(
            gb6, planner.Layout(dp=3, num_microbatches=2, zero=True))
        pb = planner.plan_for_layout(
            gb6, planner.Layout(dp=2, num_microbatches=3, zero=True))
        params = {"w": np.ones((17, 2), np.float32)}
        bad = np.arange(36.0, dtype=np.float32)   # tail 34,35 nonzero
        state = {"params": params,
                 "opt": {"exp_avg_shard": bad}}
        with pytest.raises(LayoutMismatch, match="conservation"):
            reshard_state(state, pa, pb)

    def test_repack_helper_contract(self):
        assert shard_padded_len(34, 3) == 36
        assert shard_padded_len(34, 2) == 34
        with pytest.raises(ValueError, match="expected 36"):
            repack_flat_shard(np.zeros(35, np.float32), flat_len=34,
                              world_from=3, world_to=2)

    def test_zero_flip_is_structure_change_refused(self):
        gb6 = _shape_with(name="tiny-z2", global_batch=6)
        pa = planner.plan_for_layout(
            gb6, planner.Layout(dp=3, num_microbatches=2, zero=True))
        pb = planner.plan_for_layout(
            gb6, planner.Layout(dp=2, num_microbatches=3))
        with pytest.raises(LayoutMismatch, match="zero"):
            reshard_state({"params": {"w": np.zeros(4, np.float32)}},
                          pa, pb)

    def test_model_change_refused(self):
        other = _shape_with(num_layers=8)
        pb = planner.plan_for_layout(
            other, planner.Layout(dp=2, pp=2, tp=1,
                                  num_microbatches=4))
        with pytest.raises(LayoutMismatch, match="never the model"):
            reshard_state(_synth_state(), PLAN_A, pb)

    def test_leaf_disagreeing_with_plan_stack_refused(self):
        state = _synth_state((1, 2, 2))   # plan says (2, 2, 1)
        with pytest.raises(LayoutMismatch, match="own plan meta"):
            reshard_state(state, PLAN_A, PLAN_B)


# ---------------------------------------------------------------------------
# checkpoint-level reshard


def _l3d_state(plan):
    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.llama import LlamaConfig
    from apex1_tpu.models.llama_3d import state_template

    mcfg = LlamaConfig.tiny(
        num_layers=4, max_seq_len=16, vocab_size=64, num_heads=4,
        num_kv_heads=2, hidden_size=32, ffn_size=64,
        policy=get_policy("O2"))
    return state_template(planner.llama3d_config_from_plan(
        plan, mcfg, ignore_zero=True))


class TestReshardCheckpoint:
    def _save(self, directory, plan, state):
        with ResilientCheckpointer(directory, plan=plan) as ck:
            return ck.save_sync(3, state, meta={"data_step": 4})

    def test_reshard_deterministic_and_round_trip(self, tmp_path):
        state = _l3d_state(PLAN_A)
        src = self._save(tmp_path / "ck", PLAN_A, state)
        src_tree = [(e["path"], e["sha256"])
                    for e in read_manifest(src).tree]
        _o1, m1, r1 = reshard_checkpoint(src, _l3d_state(PLAN_A),
                                         PLAN_B, tmp_path / "o1")
        _o2, m2, _r2 = reshard_checkpoint(src, _l3d_state(PLAN_A),
                                          PLAN_B, tmp_path / "o2")
        dig = [(e["path"], e["sha256"]) for e in m1.tree]
        assert dig == [(e["path"], e["sha256"]) for e in m2.tree], \
            "same (checkpoint, target plan) must be byte-identical"
        assert r1["n_restacked"] > 0 and r1["conserved"]
        # B -> A restores the ORIGINAL leaf digests (identity)
        _o3, m3, _r3 = reshard_checkpoint(_o1, _l3d_state(PLAN_B),
                                          PLAN_A, tmp_path / "o3")
        assert [(e["path"], e["sha256"]) for e in m3.tree] == src_tree
        assert read_plan(_o1)["mesh"] == PLAN_B["mesh"]
        assert m1.meta["resharded_from"]["step"] == 3
        assert m1.meta["data_step"] == 4       # resume scalars survive

    def test_resharded_checkpoint_is_verified_not_trusted(self,
                                                          tmp_path):
        state = _l3d_state(PLAN_A)
        src = self._save(tmp_path / "ck", PLAN_A, state)
        out, _m, _r = reshard_checkpoint(src, _l3d_state(PLAN_A),
                                         PLAN_B, tmp_path / "out")
        with ResilientCheckpointer(tmp_path / "ck2",
                                   plan=PLAN_B) as ck2:
            restored, man = ck2.restore(template=_l3d_state(PLAN_B),
                                        path=out)
            assert man.meta["data_step"] == 4
            # now damage ONE payload byte: the restore path must
            # refuse — a resharded checkpoint gets zero trust credit
            chaos.bitflip_checkpoint(out)
            with pytest.raises(IntegrityError):
                ck2.restore(template=_l3d_state(PLAN_B), path=out)

    def test_no_plan_meta_is_clear_layout_mismatch(self, tmp_path):
        state = _l3d_state(PLAN_A)
        with ResilientCheckpointer(tmp_path / "ck") as ck:  # no plan=
            src = ck.save_sync(1, state)
        with pytest.raises(LayoutMismatch, match="no plan meta"):
            reshard_checkpoint(src, state, PLAN_B, tmp_path / "out")
        with pytest.raises(LayoutMismatch, match="no plan meta"):
            elastic_resume(tmp_path / "ck", n_devices=4,
                           make_template=lambda p: state)
        with ResilientCheckpointer(tmp_path / "ck",
                                   plan=PLAN_A) as ck2:
            with pytest.raises(LayoutMismatch, match="no plan meta"):
                ck2.restore(template=state)

    def test_layout_change_restore_is_typed_not_shape_error(self,
                                                            tmp_path):
        """The satellite contract: relaunching with changed axis flags
        gets a LayoutMismatch POINTING AT elastic resume, replacing
        the blanket fingerprint refusal / deep shape error."""
        state = _l3d_state(PLAN_A)
        self._save(tmp_path / "ck", PLAN_A, state)
        with ResilientCheckpointer(tmp_path / "ck",
                                   plan=PLAN_B) as ck2:
            with pytest.raises(LayoutMismatch,
                               match="elastic_resume"):
                ck2.restore(template=state)

    def test_same_device_count_is_plain_resume(self, tmp_path):
        state = _l3d_state(PLAN_A)
        src = self._save(tmp_path / "ck", PLAN_A, state)
        d = elastic_resume(tmp_path / "ck",
                           n_devices=PLAN_A["n_devices"],
                           make_template=lambda p: _l3d_state(p))
        assert not d.resharded and d.path == src
        assert d.plan["mesh"] == PLAN_A["mesh"]


class TestReplanConstraints:
    def test_require_zero_filters_the_search(self):
        """The elastic constraint: a zero-source checkpoint's re-plan
        must search ONLY zero layouts (allow_zero merely permits
        them), because the optimizer-state tree structure is fixed."""
        gb6 = _shape_with(name="tiny-z3", global_batch=6)
        lays = list(planner.enumerate_layouts(gb6, 2,
                                              require_zero=True))
        assert lays and all(l.zero for l in lays)
        assert planner.make_plan(gb6, 2, require_zero=True)[
            "zero"]["enabled"] is True
        assert planner.make_plan(gb6, 2, require_zero=False)[
            "zero"]["enabled"] is False

    def test_drill_batches_are_layout_canonical(self):
        """Step i's GLOBAL batch of sequences must be identical under
        any (M, B) factorization — the 'same data order' half of the
        elastic claim (a layout-shaped RNG draw would regroup the
        flat stream into different sequences)."""
        import types

        from apex1_tpu.resilience.elastic import _drill_fixture

        _s, _c, _m, batch_at = _drill_fixture(7)
        la = types.SimpleNamespace(num_microbatches=4,
                                   microbatch_size=1, dp=2, ep=1)
        lb = types.SimpleNamespace(num_microbatches=2,
                                   microbatch_size=1, dp=4, ep=1)
        ta, _ = batch_at(3, la)     # (4, S, 2)
        tb, _ = batch_at(3, lb)     # (2, S, 4)
        seq_a = np.asarray(ta).transpose(0, 2, 1).reshape(8, -1)
        seq_b = np.asarray(tb).transpose(0, 2, 1).reshape(8, -1)
        np.testing.assert_array_equal(seq_a, seq_b)


# ---------------------------------------------------------------------------
# shrink/kill schedules


class TestShrinkSchedules:
    def test_shrink_schedule_deterministic_and_bounded(self):
        a = chaos.shrink_schedule(11, n_devices=8, lo=2, hi=6)
        b = chaos.shrink_schedule(11, n_devices=8, lo=2, hi=6)
        assert a == b
        step, survivors = a
        assert 2 <= step < 6 and survivors == 4     # kill half of 8
        with pytest.raises(ValueError, match="proper divisor"):
            chaos.shrink_schedule(1, n_devices=1, lo=0, hi=2)

    def test_fleetsim_kill_k_of_n_serves_on_survivors(self):
        from apex1_tpu.serving import FrontendConfig, ReplicaConfig
        from apex1_tpu.testing import fleetsim

        sched = fleetsim.kill_k_of_n(7, n_replicas=3, k=1, lo=2,
                                     hi=10)
        again = fleetsim.kill_k_of_n(7, n_replicas=3, k=1, lo=2,
                                     hi=10)
        assert [(f.replica, f.at_step) for f in sched.faults] \
            == [(f.replica, f.at_step) for f in again.faults]
        trace = fleetsim.synthetic_trace("steady", seed=5,
                                         horizon_s=2.0,
                                         base_rate=10.0)
        rep = fleetsim.run_fleet(
            trace,
            FrontendConfig(n_replicas=3, capacity_per_replica=8,
                           hedge_after_s=None,
                           replica=ReplicaConfig(watchdog_s=30.0,
                                                 max_restarts=1)),
            chaos=sched)
        # the victim crash-loops to failed; every submitted request
        # still completes on the n-k survivors
        assert rep.outcomes and all(o["status"] == "done"
                                    for o in rep.outcomes)
        states = [r["state"] for r in rep.summary["replicas"].values()]
        assert states.count("failed") == 1
        assert rep.summary["n_alive"] == 2


# ---------------------------------------------------------------------------
# THE acceptance drill (ISSUE 14): 8 -> 4 mid-run shrink, planner
# re-plan, manifest-verified reshard, bit-exact vs the 4-device
# control, episode reconstructable from banked obs-spine events alone


class TestElasticDrill:
    def test_drill_8_to_4_bit_exact_and_reconstructable(self):
        from apex1_tpu.resilience import elastic

        res = elastic.drill(8, 4, verbose=False)
        assert res["n_to"] == 4 and res["old_mesh"] != res["new_mesh"]
        assert res["n_restacked"] > 0       # a REAL remap, not copies
        assert len(res["losses"]) >= 1      # resumed steps ran
        assert set(res["events"]) == {
            "elastic.detect", "elastic.replan", "elastic.reshard",
            "elastic.verify", "elastic.resume"}


@pytest.mark.slow
def test_example_kill_then_elastic_relaunch(tmp_path):
    """The examples/llama_3d.py --elastic integration across a REAL
    process boundary: chaos SIGTERM -> exit 75 with a plan-banking
    checkpoint -> relaunch on 4 devices re-plans, reshards, resumes.
    (@slow: two full jax boots + 3D compiles; the in-process drill
    above is the tier-1 pin. Runs via check_all --all.)"""
    from apex1_tpu.resilience import EXIT_RESUMABLE

    # JAX_COMPILATION_CACHE_DIR exported EMPTY = the operator-disable
    # form child_cache_env documents: on XLA:CPU, a 4-device shard_map executable RELOADED from a warm
    # persistent cache aborts (8-device reloads are fine; reproduced
    # cold-pass/warm-crash with a fresh cache dir), so the relaunch
    # children must compile cold. CPU-only; a TPU relaunch caches
    # normally.
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "APEX1_CHAOS_SIGTERM_STEP": "3",
           "JAX_COMPILATION_CACHE_DIR": ""}
    script = os.path.join(REPO, "examples", "llama_3d.py")
    common = [sys.executable, script, "--ckpt-dir",
              str(tmp_path / "ck"), "--steps", "6", "--layers", "4",
              "--chunks", "2", "--ckpt-every", "1", "--elastic"]
    r1 = subprocess.run(common, env=env, cwd=REPO,
                        capture_output=True, text=True, timeout=600)
    assert r1.returncode == EXIT_RESUMABLE, (r1.returncode,
                                             r1.stderr[-2000:])
    env.pop("APEX1_CHAOS_SIGTERM_STEP")
    r2 = subprocess.run(common + ["--devices", "4"], env=env,
                        cwd=REPO, capture_output=True, text=True,
                        timeout=600)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "re-planned and resharded" in r2.stdout
    assert "elastic resume at data step 3" in r2.stdout
    assert "step counter = 6" in r2.stdout
