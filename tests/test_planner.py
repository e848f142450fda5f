"""apex1_tpu.planner — legality, memory pre-filter, calibrated pricing,
plan determinism, and the ISSUE-12 acceptance contract (planner pick
within ~10% of the hand-tuned layouts on the banked bench shapes,
against the COMMITTED perf_results/calibration.json)."""

import json
import os
import subprocess
import sys

import pytest

from apex1_tpu import perf_model, planner
from apex1_tpu.planner.__main__ import TINY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _layout(**kw):
    return planner.Layout(**kw)


# ---------------------------------------------------------------------------
# legality
# ---------------------------------------------------------------------------

class TestLegality:
    def test_enumerated_layouts_all_legal(self):
        for shape, n in ((TINY, 8), (TINY, 4),
                         (planner.BANKED_SHAPES["llama8b"], 16)):
            lays = list(planner.enumerate_layouts(shape, n))
            assert lays, f"{shape.name}/{n}: nothing enumerated"
            for lay in lays:
                assert lay.n_devices == n
                vs = planner.check_layout(shape, lay, n)
                assert not vs, f"{lay} enumerated but illegal: {vs}"

    def test_enumeration_deterministic(self):
        a = list(planner.enumerate_layouts(TINY, 8))
        b = list(planner.enumerate_layouts(TINY, 8))
        assert a == b

    @pytest.mark.parametrize("kw,rule", [
        (dict(tp=3), "tp-heads"),
        (dict(tp=3), "tp-vocab"),
        (dict(tp=3), "sp-seq"),
        (dict(pp=3, num_microbatches=8), "pp-stages"),
        (dict(pp=2, num_microbatches=3, num_chunks=2,
              schedule="1f1b"), "pp-microbatches"),
        (dict(dp=3), "dp-batch"),
        (dict(ep=2, dp=1), "ep-moe"),
        (dict(zero=True), "zero-dp"),
        (dict(sp_mode="bogus"), "sp-mode"),
    ])
    def test_rule_names(self, kw, rule):
        # TINY: 2 layers, 4/2 heads, vocab 256, seq 64, batch 8 — each
        # kw breaks exactly the named rule (others may fire too)
        kw.setdefault("num_microbatches", 8)
        lay = _layout(**kw)
        rules = {v.rule for v in planner.check_layout(TINY, lay)}
        assert rule in rules, rules

    def test_device_product_rule(self):
        lay = _layout(dp=2, num_microbatches=4)
        rules = {v.rule for v in planner.check_layout(TINY, lay, 8)}
        assert "device-product" in rules

    def test_legal_layout_clean(self):
        lay = _layout(dp=2, pp=2, tp=2, num_microbatches=4)
        assert planner.check_layout(TINY, lay, 8) == []

    def test_zero_axis_is_a_violation_not_a_crash(self):
        # review fix: --tp 0 must come back as [axis-positive], not a
        # ZeroDivisionError from the divisibility rules downstream
        vs = planner.check_layout(TINY, _layout(tp=0,
                                                num_microbatches=8))
        assert {v.rule for v in vs} == {"axis-positive"}
        vs = planner.check_layout(TINY, _layout(dp=0, pp=0,
                                                num_microbatches=8))
        assert all(v.rule == "axis-positive" for v in vs)
        assert len(vs) == 2

    def test_check_plan_model(self):
        # the ONE replay-validation helper both --plan consumers use
        import dataclasses
        plan = planner.make_plan(TINY, 8)
        assert planner.check_plan_model(plan, TINY) == []
        other = dataclasses.replace(TINY, num_layers=4,
                                    num_experts=4)
        bad = planner.check_plan_model(plan, other)
        assert any("num_layers" in m for m in bad)
        assert any("num_experts" in m for m in bad)
        # global_batch deliberately unchecked: the plan's schedule is
        # the batch authority on replay
        gb = dataclasses.replace(TINY, global_batch=99)
        assert planner.check_plan_model(plan, gb) == []

    def test_bubbly_scan_schedule_legal_but_pruned(self):
        # review fix: M < pp RUNS under the scan schedule
        # (Llama3DConfig accepts it — a hand --pp 2 --microbatches 1
        # must not be refused), but the enumerator prunes it as
        # dominated (bubble >= 2x)
        import dataclasses
        s = dataclasses.replace(TINY, global_batch=1)
        lay = _layout(pp=2, num_microbatches=1)
        assert planner.check_layout(s, lay, 2) == []
        assert all(l.num_microbatches >= l.pp
                   for l in planner.enumerate_layouts(TINY, 8))

    def test_example_rejects_illegal_layout_loudly(self):
        # the satellite fix: examples/llama_3d.py exits 2 NAMING the
        # rule, before any jax compilation
        proc = subprocess.run(
            [sys.executable, os.path.join("examples", "llama_3d.py"),
             "--tp", "3", "--steps", "1"],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=180)
        assert proc.returncode == 2
        assert "ILLEGAL LAYOUT" in proc.stderr
        assert "tp-heads" in proc.stderr


# ---------------------------------------------------------------------------
# memory pre-filter
# ---------------------------------------------------------------------------

class TestMemory:
    def test_prefilter_reproduces_banked_aot_verdicts(self):
        # the llama_longctx sizing episode (tools/aot_steps.py's comment
        # on `bench_llama_longctx`): 16-layer 0.8B fits a v5e (~14.4 GiB
        # measured), the 22-layer variant does not (18.7 GiB > 15.75)
        import dataclasses
        s16 = planner.BANKED_SHAPES["llama_longctx"]
        s22 = dataclasses.replace(s16, num_layers=22)
        lay = _layout(num_microbatches=1)
        assert planner.fit_check(s16, lay, "v5e") is None
        msg = planner.fit_check(s22, lay, "v5e")
        assert msg is not None
        assert "hbm-fit" in msg and "GiB" in msg
        # the sizing is STATED: needs-X > budget-Y with the breakdown
        assert "15.75" in msg and "opt" in msg and "weights" in msg

    def test_over_budget_plan_raises_with_sizing(self):
        import dataclasses
        s22 = dataclasses.replace(planner.BANKED_SHAPES["llama_longctx"],
                                  num_layers=22)
        with pytest.raises(planner.PlanError) as ei:
            planner.make_plan(s22, 1, generation="v5e")
        assert "hbm-fit" in str(ei.value) and "GiB" in str(ei.value)

    def test_zero_shards_optimizer_memory(self):
        s = planner.BANKED_SHAPES["llama8b"]
        base = _layout(dp=4, tp=4, num_microbatches=2)
        zero = _layout(dp=4, tp=4, num_microbatches=2, zero=True)
        b0 = planner.hbm_breakdown(s, base, "v5p")
        b1 = planner.hbm_breakdown(s, zero, "v5p")
        assert b1["opt"] == pytest.approx(b0["opt"] / 4)
        assert b1["total"] < b0["total"]

    def test_8b_fits_v5p_not_v5e_unsharded(self):
        s = planner.BANKED_SHAPES["llama8b"]
        lay = _layout(dp=2, pp=2, tp=4, num_microbatches=4)
        assert planner.fit_check(s, lay, "v5p") is None
        assert planner.fit_check(s, lay, "v5e") is not None


# ---------------------------------------------------------------------------
# pricing + calibration
# ---------------------------------------------------------------------------

class TestPricing:
    @staticmethod
    def _table(tmp_path):
        """A results dir whose calibration.json carries two tpu step
        factors (no committed table exists: the planner's feedback is
        pinned against a synthetic one)."""
        doc = {"schema": "apex1-calibration-v1",
               "factors": {"step:gpt2": {"slowdown": 1.9, "n": 2,
                                         "backend": "tpu"},
                           "step:bert": {"slowdown": 1.2, "n": 1,
                                         "backend": "tpu"}},
               "proxy_factors": {}, "excluded": [], "pairs": []}
        (tmp_path / "calibration.json").write_text(json.dumps(doc))
        return str(tmp_path)

    def test_calibration_factor_from_table(self, tmp_path):
        # a banked calibration.json must drive the price: the
        # calibrated/analytic ratio IS its step:gpt2 slowdown
        res = self._table(tmp_path)
        shape = planner.BANKED_SHAPES["gpt2"]
        lay = _layout(num_microbatches=16)
        cal = planner.price_layout(shape, lay, generation="v5e",
                                   results_dir=res)
        raw = planner.price_layout(shape, lay, generation="v5e",
                                   results_dir=res,
                                   use_calibration=False)
        assert cal["calibrated_step_ms"] / cal["step_ms"] == \
            pytest.approx(1.9)
        assert raw["calibrated_step_ms"] == raw["step_ms"]
        assert "step:gpt2" in cal["calibration"]["source"]

    def test_uncalibrated_shape_gets_fleet_geomean(self, tmp_path):
        s = planner.BANKED_SHAPES["llama8b"]
        lay = _layout(dp=2, pp=2, tp=4, num_microbatches=4)
        p = planner.price_layout(s, lay, generation="v5p",
                                 results_dir=self._table(tmp_path))
        assert "fleet-geomean" in p["calibration"]["source"]
        assert p["calibrated_step_ms"] / p["step_ms"] == \
            pytest.approx((1.9 * 1.2) ** 0.5)

    def test_no_table_is_labelled_uncalibrated(self, tmp_path):
        p = planner.price_layout(
            planner.BANKED_SHAPES["gpt2"], _layout(num_microbatches=16),
            generation="v5e", results_dir=str(tmp_path))
        assert p["calibration"]["slowdown"] == 1.0
        assert "uncalibrated" in p["calibration"]["source"]

    def test_sp_mode_prices_differently(self):
        # the kernel-selection dimension: serial exposes every SP
        # boundary byte, overlap only the residual — serial must never
        # price cheaper
        s = planner.BANKED_SHAPES["llama8b"]
        t = {}
        for mode in ("serial", "overlap", "fused"):
            lay = _layout(dp=2, pp=2, tp=4, num_microbatches=4,
                          sp_mode=mode)
            p = planner.price_layout(s, lay, generation="v5p")
            t[mode] = p["step_ms"]
            assert p["ici_exposed_bytes"]["sp_boundary"] >= 0.0
        assert t["serial"] >= t["overlap"]
        assert t["fused"] >= t["overlap"]   # fused pays the prologue
        #   hop on compute-rich shapes; overlap's BEST-case residual
        #   can be 0 (perf_model.sp_boundary_comms docstring)

    def test_bubble_factor(self):
        s = planner.BANKED_SHAPES["llama8b"]
        p1 = planner.price_layout(
            s, _layout(dp=2, pp=2, tp=4, num_microbatches=4),
            generation="v5p")
        assert p1["bubble_factor"] == pytest.approx((4 + 2 - 1) / 4)

    def test_acceptance_planner_within_10pct_of_hand_tuned(self):
        # ISSUE 12 acceptance: on the banked bench shapes the
        # planner's pick prices within ~10% of the best hand-tuned
        # config, against the COMMITTED calibration.json. The hand
        # layouts: the single-chip bench configs and aot_check
        # --flagship's dp2 x pp2 x tp4 8B recipe.
        cases = [
            ("gpt2", 1, "v5e", _layout(num_microbatches=16)),
            ("llama_longctx", 1, "v5e", _layout(num_microbatches=1)),
            ("llama8b", 16, "v5p",
             _layout(dp=2, pp=2, tp=4, num_microbatches=4)),
        ]
        for name, n, gen, hand in cases:
            shape = planner.BANKED_SHAPES[name]
            # the hand layout must be IN the search space (legal)…
            assert planner.check_layout(shape, hand, n) == []
            hand_ms = planner.price_layout(
                shape, hand, generation=gen)["calibrated_step_ms"]
            plan = planner.make_plan(shape, n, generation=gen)
            pick_ms = plan["predicted"]["calibrated_step_ms"]
            # …so the pick is at worst 10% over it (and usually at or
            # below: the argmin saw the hand layout too)
            assert pick_ms <= 1.10 * hand_ms, (name, pick_ms, hand_ms)


# ---------------------------------------------------------------------------
# plan emission
# ---------------------------------------------------------------------------

class TestPlan:
    def test_plan_byte_determinism(self):
        a = planner.plan_json(planner.make_plan(TINY, 8))
        b = planner.plan_json(planner.make_plan(TINY, 8))
        assert a == b
        assert a.endswith("\n")

    def test_save_load_roundtrip(self, tmp_path):
        plan = planner.make_plan(TINY, 8)
        path = str(tmp_path / "plan.json")
        planner.save_plan(plan, path)
        assert planner.load_plan(path) == plan

    def test_load_plan_rejects_foreign_files(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text("{\"schema\": \"something-else\"}")
        with pytest.raises(ValueError):
            planner.load_plan(str(bad))
        notjson = tmp_path / "x.json"
        notjson.write_text("not json at all")
        with pytest.raises(ValueError):
            planner.load_plan(str(notjson))
        with pytest.raises(ValueError):
            planner.load_plan(str(tmp_path / "missing.json"))

    def test_plan_carries_calibration_provenance(self, tmp_path):
        res = TestPricing._table(tmp_path)
        plan = planner.make_plan(planner.BANKED_SHAPES["gpt2"], 1,
                                 results_dir=res)
        assert plan["provenance"]["calibration_table"] == \
            "calibration.json"
        assert plan["schema"] == planner.PLAN_SCHEMA
        # the repo ships no table: the default plan says so
        bare = planner.make_plan(planner.BANKED_SHAPES["gpt2"], 1)
        assert bare["provenance"]["calibration_table"] is None
        assert "uncalibrated" in \
            bare["predicted"]["calibration"]["source"]

    def test_llama3d_config_from_plan(self):
        from apex1_tpu.core.policy import get_policy
        from apex1_tpu.models.llama import LlamaConfig

        plan = planner.make_plan(TINY, 8, allow_zero=False)
        mcfg = LlamaConfig.tiny(
            num_layers=TINY.num_layers, max_seq_len=TINY.seq_len,
            vocab_size=TINY.vocab_size, num_heads=TINY.num_heads,
            num_kv_heads=TINY.num_kv_heads,
            hidden_size=TINY.hidden_size, ffn_size=TINY.ffn_size,
            policy=get_policy("O2"))
        cfg = planner.llama3d_config_from_plan(plan, mcfg)
        m = plan["mesh"]
        assert (cfg.dp, cfg.pp, cfg.cp, cfg.ep, cfg.tp) == \
            (m["dp"], m["pp"], m["cp"], m["ep"], m["tp"])
        assert cfg.num_microbatches == \
            plan["schedule"]["num_microbatches"]

    def test_partition_rules_reproduce_llama3d_specs(self):
        # the emitted regex rules, pushed through the generic
        # parallel.specs engine, must equal the model's hand-written
        # spec tables leaf-for-leaf — dense AND MoE
        from apex1_tpu.core.policy import get_policy
        from apex1_tpu.models.llama import LlamaConfig
        from apex1_tpu.models.llama_3d import (Llama3DConfig,
                                               chunk_param_specs,
                                               init_params,
                                               shared_param_specs)

        for moe in (False, True):
            moe_kw = (dict(moe_every=1, num_experts=4, moe_top_k=2)
                      if moe else {})
            mcfg = LlamaConfig.tiny(num_layers=2, max_seq_len=64,
                                    policy=get_policy("O2"), **moe_kw)
            cfg = Llama3DConfig(model=mcfg, dp=2, pp=2, tp=1, moe=moe,
                                ep=2 if moe else 1,
                                num_microbatches=4)
            chunk, shared = init_params(cfg)
            params = {"chunk": chunk, "shared": shared}
            shape = planner.ModelShape.from_llama(
                mcfg, global_batch=8, name="t")
            lay = planner.Layout(dp=2, pp=2, ep=2 if moe else 1,
                                 num_microbatches=4 if moe else 4)
            plan = planner.build_plan(
                shape, lay,
                planner.price_layout(shape, lay),
                planner.hbm_breakdown(shape, lay),
                generation="v5e", search={})
            got = planner.plan_param_specs(plan, params)
            cspecs = chunk_param_specs(cfg)
            want = {"chunk": {k: cspecs[k] for k in chunk},
                    "shared": shared_param_specs()}
            assert got == want, f"moe={moe}"

    def test_zero_plan_refused_by_config_bridge(self):
        # review fix: a zero=True plan's HBM verdict divided opt
        # state by dp; Llama3DConfig has no ZeRO wiring, so the
        # bridge must refuse rather than silently run unsharded
        from apex1_tpu.core.policy import get_policy
        from apex1_tpu.models.llama import LlamaConfig

        lay = planner.Layout(dp=2, pp=2, tp=2, num_microbatches=4,
                             zero=True)
        plan = planner.build_plan(
            TINY, lay, planner.price_layout(TINY, lay),
            planner.hbm_breakdown(TINY, lay), generation="v5e",
            search={})
        mcfg = LlamaConfig.tiny(num_layers=2, max_seq_len=64,
                                policy=get_policy("O2"))
        with pytest.raises(ValueError, match="zero"):
            planner.llama3d_config_from_plan(plan, mcfg)
        cfg = planner.llama3d_config_from_plan(plan, mcfg,
                                               ignore_zero=True)
        assert cfg.dp == 2

    def test_rules_roundtrip_spec_json(self):
        from jax.sharding import PartitionSpec as P

        from apex1_tpu.planner import emit
        assert emit.spec_from_json([None, "pp", ["dp", "ep"]]) == \
            P(None, "pp", ("dp", "ep"))
        assert emit.spec_to_json((None, "pp", ("dp", "ep"))) == \
            [None, "pp", ["dp", "ep"]]


# ---------------------------------------------------------------------------
# perf_model (the pricing library the planner's cost engine rides)
# ---------------------------------------------------------------------------

class TestPerfModel:
    def test_roofline_arithmetic(self):
        from apex1_tpu.core.capability import get_capability
        cap = get_capability("v5e")
        # compute-bound: flops term dominates
        t, bound, mfu = perf_model.roofline(cap.bf16_tflops * 1e12,
                                            1.0, cap)
        assert t == pytest.approx(1.0) and bound == "MXU"
        assert mfu == pytest.approx(1.0)
        # bandwidth-bound
        t, bound, _ = perf_model.roofline(1.0, cap.hbm_gbps * 1e9, cap)
        assert t == pytest.approx(1.0) and bound == "HBM"
        # exposed ICI adds serially
        from apex1_tpu.core.capability import ici_link_gbps
        link = ici_link_gbps("v5e")
        t2, bound2, _ = perf_model.roofline(
            1.0, cap.hbm_gbps * 1e9, cap,
            ici_exposed_bytes=2 * link * 1e9)
        assert t2 == pytest.approx(3.0) and bound2 == "ICI"

    def test_kernel_cases_formulas_stable(self):
        # the flash gpt2 fwd row and the linear_xent row, recomputed
        # by hand
        cases = {name: (f, b) for name, f, b
                 in perf_model.kernel_cases()}
        f, b = cases["flash gpt2 (16,12,1024,64) fwd"]
        assert f == 4 * 16 * 12 * 1024 * 1024 * 64 * 0.5
        assert b == (16 * 12 * 1024 * 64 * 2) * 2 \
            + 2 * 16 * 12 * 1024 * 64 * 2
        f, _ = cases["linear_xent gpt2 (16k,768,50k) f+b"]
        assert f == 6 * (16 * 1023) * 768 * 50432
        assert len(cases) == 11

    def test_sp_boundary_comms_matches_predict_comms_fused(self):
        # the SP-boundary comms arithmetic, recomputed inline
        from apex1_tpu.core.capability import (get_capability,
                                               ici_link_gbps)
        S, hid, ffn, n, gen = 8192, 4096, 14336, 4, "v5e"
        m = perf_model.sp_boundary_comms(gen, n, rows=S,
                                         out_width=hid, ffn=ffn)
        link, cap = ici_link_gbps(gen), get_capability(gen)
        chunk_rows = S // n
        hop = chunk_rows * hid * 4
        dot = 2 * chunk_rows * (ffn // n) * hid
        t_hop, t_dot = hop / (link * 1e9), dot / (cap.bf16_tflops
                                                  * 1e12)
        resid = n * max(0.0, t_hop - t_dot) * (link * 1e9)
        assert m["total"] == float(n * hop)
        assert m["exposed_overlap"] == pytest.approx(resid)
        assert m["exposed_fused"] == pytest.approx(hop + resid)

    def test_ring_comms_matches_predict_comms(self):
        from apex1_tpu.core.capability import (get_capability,
                                               ici_link_gbps)
        gen, n = "v5e", 4
        m = perf_model.ring_attention_comms(gen, n)
        link, cap = ici_link_gbps(gen), get_capability(gen)
        S_l = 16384 // n
        kv_hop = 2 * 1 * 4 * S_l * 64 * 2
        att = 4 * 1 * 32 * S_l * S_l * 64 * 0.5
        assert m["kv_hop"] == kv_hop
        assert m["t_att"] == pytest.approx(att / (cap.bf16_tflops
                                                  * 1e12))
        assert m["fwd_bytes"] == (n - 1) * kv_hop
        exp = (n - 1) * max(0.0, kv_hop / (link * 1e9)
                            - m["t_att"]) * (link * 1e9)
        assert m["exp_f_overlap"] == pytest.approx(exp)

    def test_sp_boundary_hop_width_decoupled_from_dot(self):
        # review fix: an all-gather boundary hops the INPUT activation
        # (width E, constant in tp) — not the dot's output shard. The
        # hop bytes must follow hop_width; the dot keeps out_width.
        E, n = 4096, 4
        m = perf_model.sp_boundary_comms(
            "v5e", n, rows=1024, local_k=E, out_width=1536 // n,
            acc_bytes=2, hop_width=E)
        assert m["hop"] == (1024 // n) * E * 2
        assert m["dot"] == 2 * (1024 // n) * E * (1536 // n)
        # default (None) keeps the reduce-scatter semantics —
        # predict_comms_fused's banked arithmetic is unchanged
        m2 = perf_model.sp_boundary_comms("v5e", n, rows=1024,
                                          local_k=E, out_width=512)
        assert m2["hop"] == (1024 // n) * 512 * 4

    def test_allreduce_bytes(self):
        assert perf_model.allreduce_bytes(100.0, 1) == 0.0
        assert perf_model.allreduce_bytes(100.0, 4) == \
            pytest.approx(150.0)
