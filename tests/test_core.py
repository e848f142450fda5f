"""M0 tests: mesh construction (≙ tests/L0/run_transformer/test_parallel_state.py
group math), precision policy (≙ tests/L0/run_amp cast tests), loss scaling
(≙ run_amp loss-scale tests), pytree/flat utilities."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex1_tpu.core import mesh as mesh_lib
from apex1_tpu.core import policy as policy_lib
from apex1_tpu.core import loss_scale as ls
from apex1_tpu.core import pytree as pt
from apex1_tpu.core.mesh import MeshConfig, make_mesh


class TestMesh:
    def test_resolve_wildcard(self):
        cfg = MeshConfig(dp=-1, tp=2).resolve(8)
        assert cfg.dp == 4 and cfg.tp == 2 and cfg.pp == 1
        assert cfg.shape == (4, 1, 1, 1, 1, 2)

    def test_resolve_exact(self):
        cfg = MeshConfig(dp=2, pp=2, tp=2).resolve(8)
        assert cfg.shape == (2, 1, 2, 1, 1, 2)

    def test_resolve_errors(self):
        with pytest.raises(ValueError):
            MeshConfig(dp=3, tp=2).resolve(8)
        with pytest.raises(ValueError):
            MeshConfig(dp=-1, tp=-1).resolve(8)

    def test_make_mesh_axes(self, devices):
        m = make_mesh(dp=2, tp=4)
        assert m.shape == {"dp": 2, "fsdp": 1, "pp": 1, "cp": 1,
                           "ep": 1, "tp": 4}
        assert mesh_lib.data_parallel_size(m) == 2

    def test_tp_ranks_contiguous(self, devices):
        # Megatron invariant: TP group = contiguous device ids (innermost
        # axis). parallel_state.initialize_model_parallel docstring contract.
        m = make_mesh(dp=2, tp=4)
        arr = np.asarray(m.devices).reshape(2, 4)
        ids = [[d.id for d in row] for row in arr]
        for row in ids:
            assert row == sorted(row)
            assert row[-1] - row[0] == 3

    def test_hybrid_mesh_dcn_dp_outer(self, devices):
        """Multi-slice mesh: each dp index must live on ONE slice so the
        dp gradient reduction decomposes into intra-slice ICI + one DCN
        exchange (SURVEY §5.8 fabric mapping)."""

        class FakeDev:
            def __init__(self, d, slice_index, i):
                self.slice_index = slice_index
                self.id = i
                self.process_index = slice_index
                self.platform = d.platform
                self.device_kind = d.device_kind

        fakes = [FakeDev(devices[i], i // 4, i) for i in range(8)]
        m = mesh_lib.make_hybrid_mesh(MeshConfig(dp=1, pp=2, tp=2),
                                      dcn_dp=2, devices=fakes)
        assert m.shape == {"dp": 2, "fsdp": 1, "pp": 2, "cp": 1,
                           "ep": 1, "tp": 2}
        arr = np.asarray(m.devices)
        for a in range(2):
            slices = {d.slice_index for d in arr[a].ravel()}
            assert slices == {a}, f"dp index {a} spans slices {slices}"

    def test_hybrid_mesh_granule_ids_runnable(self, devices):
        """granule_ids builds the slice-major dp order from REAL devices
        (virtual CPU devices carry no slice_index), so the hybrid mesh is
        runnable — a psum over the DCN-outer dp axis must execute."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as Ps

        devs = list(devices)[:8]
        m = mesh_lib.make_hybrid_mesh(
            MeshConfig(dp=1, pp=2, tp=2), dcn_dp=2, devices=devs,
            granule_ids=[i // 4 for i in range(8)])
        assert m.shape["dp"] == 2
        arr = np.asarray(m.devices)
        dp_ax = mesh_lib.MESH_AXES.index("dp")
        for a in range(2):
            ids = {d.id for d in np.take(arr, a, axis=dp_ax).ravel()}
            want = {d.id for d in devs[a * 4:(a + 1) * 4]}
            assert ids == want, f"dp index {a} not slice-major: {ids}"

        def f(x):
            return jax.lax.psum(x, "dp")

        out = jax.jit(jax.shard_map(
            f, mesh=m, in_specs=Ps("dp"), out_specs=Ps()))(
                jnp.arange(2, dtype=jnp.float32))
        assert float(out[0]) == 1.0  # 0 + 1 across the DCN-outer axis

        with pytest.raises(ValueError, match="granule"):
            mesh_lib.make_hybrid_mesh(
                MeshConfig(dp=1, pp=2, tp=2), dcn_dp=2, devices=devs,
                granule_ids=[0] * 8)

    def test_hybrid_mesh_single_slice_delegates(self, devices):
        m = mesh_lib.make_hybrid_mesh(dcn_dp=1, dp=2, tp=4)
        assert m.shape["dp"] == 2 and m.shape["tp"] == 4
        with pytest.raises(ValueError):
            mesh_lib.make_hybrid_mesh(dcn_dp=3, dp=1,
                                      devices=list(devices))

    def test_resource_spec(self):
        res = mesh_lib.MeshResource()
        spec = res.spec("batch", None, "heads")
        assert spec == jax.sharding.PartitionSpec(("dp", "fsdp"), None, "tp")

    def test_shard_batch(self, devices):
        m = make_mesh(dp=8)
        x = np.arange(64, dtype=np.float32).reshape(8, 8)
        y = mesh_lib.shard_batch(m, {"x": x})["x"]
        assert y.sharding.spec == jax.sharding.PartitionSpec(("dp", "fsdp"))
        np.testing.assert_array_equal(np.asarray(y), x)


class TestPolicy:
    def test_presets(self):
        o2 = policy_lib.get_policy("O2")
        assert o2.param_dtype == jnp.float32
        assert o2.compute_dtype == jnp.bfloat16
        assert o2.is_mixed and not o2.uses_loss_scaling
        o0 = policy_lib.get_policy("O0")
        assert not o0.is_mixed
        fp16 = policy_lib.get_policy("O2_fp16")
        assert fp16.loss_scale == "dynamic"

    def test_overrides(self):
        p = policy_lib.get_policy("O1", loss_scale=128.0,
                                  keep_norms_fp32=False)
        assert p.loss_scale == 128.0 and not p.keep_norms_fp32

    def test_unknown_raises(self):
        with pytest.raises(ValueError):
            policy_lib.get_policy("O9")

    def test_casts_skip_ints(self):
        p = policy_lib.get_policy("O1")
        tree = {"w": jnp.ones((2,), jnp.float32), "i": jnp.ones((2,), jnp.int32)}
        out = p.cast_to_compute(tree)
        assert out["w"].dtype == jnp.bfloat16
        assert out["i"].dtype == jnp.int32

    def test_cast_dtype_under_jit(self):
        # ≙ run_amp/test_basic_casts.py, but asserted on the traced program.
        p = policy_lib.get_policy("O1")

        def f(w, x):
            return x @ p.cast_to_compute(w)

        out = jax.eval_shape(f, jnp.ones((4, 4)), jnp.ones((2, 4), jnp.bfloat16))
        assert out.dtype == jnp.bfloat16


class TestLossScale:
    def test_dynamic_state_machine(self):
        # ≙ scaler.py semantics: ÷2 on overflow, ×2 after growth_interval.
        d = ls.DynamicLossScale(init_scale=2.0 ** 8, growth_interval=4)
        s = d.init()
        assert float(s.scale) == 256.0
        s = d.adjust(s, jnp.bool_(False))
        assert float(s.scale) == 128.0 and int(s.overflow_count) == 1
        assert int(s.growth_count) == 0
        for i in range(3):
            s = d.adjust(s, jnp.bool_(True))
            assert float(s.scale) == 128.0
        s = d.adjust(s, jnp.bool_(True))  # 4th clean step → grow
        assert float(s.scale) == 256.0 and int(s.growth_count) == 0

    def test_clamps(self):
        d = ls.DynamicLossScale(init_scale=2.0, min_loss_scale=1.0,
                                growth_interval=1, max_loss_scale=4.0)
        s = d.init()
        s = d.adjust(s, jnp.bool_(False))
        s = d.adjust(s, jnp.bool_(False))
        assert float(s.scale) == 1.0  # clamped at min
        for _ in range(5):
            s = d.adjust(s, jnp.bool_(True))
        assert float(s.scale) == 4.0  # clamped at max

    def test_all_finite(self):
        good = {"a": jnp.ones(3), "b": jnp.zeros(2)}
        bad = {"a": jnp.ones(3), "b": jnp.array([1.0, jnp.inf])}
        nan = {"a": jnp.array([jnp.nan]), "b": jnp.zeros(2)}
        assert bool(ls.all_finite(good))
        assert not bool(ls.all_finite(bad))
        assert not bool(ls.all_finite(nan))

    def test_scale_unscale_roundtrip(self):
        st = ls.StaticLossScale(1024.0)
        s = st.init()
        g = {"w": jnp.full((4,), 2.0, jnp.float32)}
        scaled = st.scale(jnp.float32(3.0), s)
        assert float(scaled) == 3.0 * 1024.0
        back = st.unscale({"w": g["w"] * 1024.0}, s)
        np.testing.assert_allclose(np.asarray(back["w"]), 2.0, rtol=1e-6)

    def test_select_tree_skip(self):
        old = {"w": jnp.zeros(2)}
        new = {"w": jnp.ones(2)}
        kept = ls.select_tree(jnp.bool_(False), new, old)
        np.testing.assert_array_equal(np.asarray(kept["w"]), 0.0)

    def test_jittable(self):
        d = ls.DynamicLossScale(growth_interval=2)

        @jax.jit
        def step(state, finite):
            return d.adjust(state, finite)

        s = d.init()
        s = step(s, jnp.bool_(True))
        s = step(s, jnp.bool_(False))
        assert float(s.scale) == 2.0 ** 15


class TestPytree:
    def test_flatten_roundtrip(self):
        tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
        flat, unflatten = pt.flatten_tree(tree)
        assert flat.shape == (10,)
        back = unflatten(flat)
        np.testing.assert_array_equal(np.asarray(back["a"]),
                                      np.asarray(tree["a"]))
        assert back["b"]["c"].dtype == jnp.bfloat16

    def test_global_norm(self):
        tree = {"a": jnp.full((3,), 2.0), "b": jnp.full((4,), 1.0)}
        g = pt.global_norm(tree)
        np.testing.assert_allclose(float(g), np.sqrt(3 * 4 + 4), rtol=1e-6)
        g2, per = pt.global_norm(tree, per_leaf=True)
        assert len(per) == 2
        np.testing.assert_allclose(float(per[1]), 2.0, rtol=1e-6)

    def test_named_tree_map(self):
        tree = {"layer": {"w": jnp.ones(2), "b": jnp.ones(1)}}
        names = []
        pt.named_tree_map(lambda n, x: names.append(n) or x, tree)
        assert names == ["layer/b", "layer/w"] or names == ["layer/w", "layer/b"]


class TestHysteresis:
    """``update_scale_hysteresis.cu`` semantics: the scale halves only when
    the hysteresis budget is exhausted by overflows; clean steps don't
    refill the budget (only an actual backoff does)."""

    def test_halves_only_after_budget_exhausted(self):
        import jax.numpy as jnp
        from apex1_tpu.core.loss_scale import DynamicLossScale
        sc = DynamicLossScale(init_scale=1024.0, hysteresis=3,
                              growth_interval=4)
        st = sc.init()
        st = sc.adjust(st, jnp.bool_(False))      # overflow 1
        assert float(st.scale) == 1024.0 and int(st.hysteresis_left) == 2
        st = sc.adjust(st, jnp.bool_(True))       # clean: budget unchanged
        assert int(st.hysteresis_left) == 2
        st = sc.adjust(st, jnp.bool_(False))      # overflow 2
        assert float(st.scale) == 1024.0 and int(st.hysteresis_left) == 1
        st = sc.adjust(st, jnp.bool_(False))      # overflow 3 -> halve
        assert float(st.scale) == 512.0
        # exhausted budget does NOT refill on backoff (reference: keeps
        # halving on every overflow until growth refills it)
        assert int(st.hysteresis_left) == 0
        st = sc.adjust(st, jnp.bool_(False))      # overflow 4 -> halve again
        assert float(st.scale) == 256.0
        assert int(st.overflow_count) == 4
        # 4 clean steps -> growth fires: scale x2 AND budget refills
        for _ in range(4):
            st = sc.adjust(st, jnp.bool_(True))
        assert float(st.scale) == 512.0
        assert int(st.hysteresis_left) == 3

    def test_default_hysteresis_is_classic(self):
        import jax.numpy as jnp
        from apex1_tpu.core.loss_scale import DynamicLossScale
        sc = DynamicLossScale(init_scale=64.0)
        st = sc.adjust(sc.init(), jnp.bool_(False))
        assert float(st.scale) == 32.0


class TestCapability:
    """≙ the reference's setup.py sm-arch gating, as a runtime data table
    (SURVEY.md §2 #62, §5.6)."""

    def test_table_lookup_and_detection(self):
        from apex1_tpu.core import capability as cap
        c = cap.get_capability("v5e")
        assert c.mxu == (128, 128) and not c.sparsecore
        assert cap.get_capability("v5p").sparsecore
        assert cap.vmem_budget("v5p") > cap.vmem_budget("v3")

    def test_target_generation_scopes_detection(self):
        from apex1_tpu.core import capability as cap
        assert cap.detect_generation() is None      # CPU backend
        with cap.target_generation("v5p"):
            assert cap.detect_generation() == "v5p"
            assert cap.get_capability().generation == "v5p"
            assert cap.vmem_budget() == cap.vmem_budget("v5p")
        assert cap.detect_generation() is None
        import pytest as _pytest
        with _pytest.raises(ValueError):
            with cap.target_generation("v99"):
                pass

    def test_no_default_row_without_a_chip(self):
        """A peak never comes from a default: with no chip and no named
        generation `get_capability()` raises; only the block planners'
        `vmem_budget()` names its CPU planning target."""
        import pytest as _pytest

        from apex1_tpu.core import capability as cap
        with _pytest.raises(cap.CapabilityError, match="no TPU attached"):
            cap.get_capability()
        with _pytest.raises(cap.CapabilityError):
            cap.ici_link_gbps()
        assert cap.vmem_budget() == cap.vmem_budget(
            cap.CPU_PLANNING_GENERATION)

    def test_device_kind_strings_as_jax_reports_them(self):
        from apex1_tpu.core import capability as cap
        for kind, gen in (("TPU v5 lite", "v5e"), ("TPU v5", "v5p"),
                          ("TPU v5p", "v5p"), ("TPU v6 lite", "v6e"),
                          ("TPU v4", "v4"), ("TPU v3", "v3")):
            assert cap._canonical(kind) == gen, kind
        assert cap._canonical("NVIDIA H100") is None

    def test_unknown_accelerator_kind_raises(self, monkeypatch):
        import types

        import jax
        import pytest as _pytest

        from apex1_tpu.core import capability as cap
        fake = types.SimpleNamespace(platform="tpu",
                                     device_kind="TPU v9 mega")
        monkeypatch.setattr(jax, "devices", lambda *a: [fake])
        cap._attached_generation.cache_clear()
        try:
            with _pytest.raises(cap.CapabilityError, match="v9 mega"):
                cap.get_capability()
        finally:
            cap._attached_generation.cache_clear()

    def test_require_gates(self):
        import pytest as _pytest

        from apex1_tpu.core import capability as cap
        cap.require("sparsecore", generation="v5p")
        with _pytest.raises(cap.CapabilityError):
            cap.require("sparsecore", generation="v5e")
        with _pytest.raises(cap.CapabilityError):
            cap.require("ici_3d", generation="v5e")
        with _pytest.raises(ValueError):
            cap.require("warp_specialization", generation="v5e")

    def test_unknown_generation(self):
        import pytest as _pytest

        from apex1_tpu.core import capability as cap
        with _pytest.raises(ValueError):
            cap.get_capability("v99")


class TestO1OpRegistration:
    """≙ amp.half_function / float_function / promote_function — the O1
    op-list extension surface (SURVEY #3), as policy-bound wrappers."""

    def test_casts(self):
        import jax
        import jax.numpy as jnp

        from apex1_tpu.core.policy import get_policy
        p = get_policy("O1")  # bf16 compute
        dtype_of = lambda f, *a: jax.eval_shape(f, *a).dtype
        x32 = jnp.zeros((4, 4), jnp.float32)
        xb = jnp.zeros((4, 4), jnp.bfloat16)
        matmul = lambda a, b: a @ b
        assert dtype_of(p.half_function(matmul), x32, x32) == jnp.bfloat16
        assert dtype_of(p.float_function(matmul), xb, xb) == jnp.float32
        # promote-widest: bf16 + fp32 -> fp32
        assert dtype_of(p.promote_function(matmul), xb, x32) == jnp.float32
        assert dtype_of(p.promote_function(matmul), xb, xb) == jnp.bfloat16
        # non-float args pass through untouched
        take = lambda a, i: a[i]
        got = p.half_function(take)(x32, jnp.int32(1))
        assert got.dtype == jnp.bfloat16

    def test_module_level_and_bound(self):
        import jax.numpy as jnp

        from apex1_tpu import amp as amp_lib
        from apex1_tpu.optim import fused_adam
        f = amp_lib.float_function(lambda x: x)
        assert f(jnp.zeros((2,), jnp.bfloat16)).dtype == jnp.float32
        # bound form follows the Amp's OWN policy (fp16 here, not bf16)
        a = amp_lib.Amp(tx=fused_adam(1e-3), opt_level="O1_fp16")
        g = a.half_function(lambda x: x)
        assert g(jnp.zeros((2,), jnp.float32)).dtype == jnp.float16
        h = amp_lib.half_function(lambda x: x, "O1_fp16")
        assert h(jnp.zeros((2,), jnp.float32)).dtype == jnp.float16
