"""Fused computation-collective forms (`ops.fused_collective`) vs their
decomposed PR 4 counterparts, on the 8-device virtual CPU mesh.

The pins, per form:

- fused SP matmuls (`fused_matmul_reduce_scatter` /
  `fused_all_gather_matmul`): BITWISE vs `mappings.matmul_reduce_scatter`
  / `all_gather_matmul` on BOTH dispatch paths (interpret Pallas and
  XLA composite), custom-VJP grads vs the decomposed VJPs, layer-level
  ``fused=`` plumbing, and the dependence-mode hlo_probe with the
  serialized rotate-then-dot form as the falsifiable negative control.
- all-gather-fused flash attention: BITWISE vs `ring_attention` on the
  XLA path (identical code), ulp-tight on the interpret path (the merge
  runs inside the kernel there; XLA CPU's fusion-context FMA
  contraction moves the last bit of `out_prev·w_a + out_t·w_b` — the
  components are bitwise in isolation), grads vs the ring VJP incl.
  GQA group-sum, segments, and cp=2, plus the dependence probe (the
  serialized ring is the shared negative control).
- fused vocab-parallel linear CE merge: BITWISE loss AND grads vs the
  decomposed 4-collective ladder on both paths. (No count of compiled
  all-reduces: XLA's combiner merges the ladder's three psums into one
  variadic all-reduce, so both forms compile to two, on the CPU and
  for a described v5e alike.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex1_tpu.parallel.ring_attention import (ring_attention,
                                               ring_attention_serial)
from apex1_tpu.core.mesh import make_mesh
from apex1_tpu.ops import fused_collective as fc
from apex1_tpu.ops._common import force_impl
from apex1_tpu.testing.hlo_probe import (assert_collective_overlap,
                                         check_collective_overlap,
                                         optimized_hlo)
from apex1_tpu.transformer import tensor_parallel as tp


@pytest.fixture()
def mesh(devices):
    return make_mesh(dp=2, tp=4)


def tp_sm(mesh, fn, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _bitwise(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestFusedMatmuls:
    """fused_matmul_reduce_scatter / fused_all_gather_matmul vs the
    decomposed PR 4 forms — the acceptance-critical bitwise pins."""

    S, IN, OUT = 32, 16, 24

    def _arrs(self, rng):
        x = jnp.asarray(rng.normal(size=(self.S, self.IN)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(self.IN, self.OUT)), jnp.float32)
        return x, w

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_mrs_bitwise_vs_decomposed(self, mesh, rng, impl):
        x, w = self._arrs(rng)
        specs = ((P(None, "tp"), P("tp", None)), P("tp", None))
        with force_impl(impl):
            a = tp_sm(mesh, lambda x, w: fc.fused_matmul_reduce_scatter(
                x, w, "tp", 0), *specs)(x, w)
            b = tp_sm(mesh, lambda x, w: tp.matmul_reduce_scatter(
                x, w, "tp", 0), *specs)(x, w)
        _bitwise(a, b)

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_agm_bitwise_vs_decomposed(self, mesh, rng, impl):
        x, w = self._arrs(rng)
        specs = ((P("tp", None), P(None, "tp")), P(None, "tp"))
        with force_impl(impl):
            a = tp_sm(mesh, lambda x, w: fc.fused_all_gather_matmul(
                x, w, "tp", 0), *specs)(x, w)
            b = tp_sm(mesh, lambda x, w: tp.all_gather_matmul(
                x, w, "tp", 0), *specs)(x, w)
        _bitwise(a, b)

    def test_rank3_operand_bitwise(self, mesh, rng):
        """The SP activations are (S, mb, hid) in the 3D step — the
        whole-tile kernel's rank-preserving dot must still match."""
        x = jnp.asarray(rng.normal(size=(self.S, 2, self.IN)),
                        jnp.float32)
        w = jnp.asarray(rng.normal(size=(self.IN, self.OUT)), jnp.float32)
        specs = ((P(None, None, "tp"), P("tp", None)), P("tp",))
        with force_impl("pallas"):
            a = tp_sm(mesh, lambda x, w: fc.fused_matmul_reduce_scatter(
                x, w, "tp", 0), *specs)(x, w)
            b = tp_sm(mesh, lambda x, w: tp.matmul_reduce_scatter(
                x, w, "tp", 0), *specs)(x, w)
        _bitwise(a, b)

    def test_serial_matches_overlapped_values(self, mesh, rng):
        """The serialized negative-control form computes the same
        gathered product (only its schedule differs)."""
        x, w = self._arrs(rng)
        specs = ((P("tp", None), P(None, "tp")), P(None, "tp"))
        with force_impl("pallas"):
            a = tp_sm(mesh,
                      lambda x, w: fc.fused_all_gather_matmul_serial(
                          x, w, "tp", 0), *specs)(x, w)
            b = tp_sm(mesh, lambda x, w: fc.fused_all_gather_matmul(
                x, w, "tp", 0), *specs)(x, w)
        _bitwise(a, b)

    def test_explicit_blocks_grid_path(self, mesh, rng):
        """Explicit (block_m, block_n) exercise the TILED kernel grid in
        interpret mode — allclose vs the decomposed form (tiling
        re-associates nothing: K is untiled, so this is tight)."""
        x, w = self._arrs(rng)
        specs = ((P(None, "tp"), P("tp", None)), P("tp", None))
        with force_impl("pallas"):
            a = tp_sm(mesh, lambda x, w: fc.fused_matmul_reduce_scatter(
                x, w, "tp", 0, 16, 128), *specs)(x, w)
            b = tp_sm(mesh, lambda x, w: tp.matmul_reduce_scatter(
                x, w, "tp", 0), *specs)(x, w)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("which", ["mrs", "agm"])
    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_grads_match_decomposed(self, mesh, rng, which, impl):
        """Custom-VJP parity: dx routes through the dual's fused form,
        dw through the re-gathered contraction — same math as the
        decomposed VJPs, so grads must be bitwise too."""
        x, w = self._arrs(rng)
        if which == "mrs":
            in_specs = (P(None, "tp"), P("tp", None))
            fused = lambda x, w: fc.fused_matmul_reduce_scatter(
                x, w, "tp", 0)
            dec = lambda x, w: tp.matmul_reduce_scatter(x, w, "tp", 0)
        else:
            in_specs = (P("tp", None), P(None, "tp"))
            fused = lambda x, w: fc.fused_all_gather_matmul(
                x, w, "tp", 0)
            dec = lambda x, w: tp.all_gather_matmul(x, w, "tp", 0)

        def grads(f):
            sm = tp_sm(mesh, lambda x, w: jnp.sum(f(x, w) ** 2),
                       in_specs, P())
            return jax.jit(jax.grad(lambda x, w: sm(x, w).sum(),
                                    argnums=(0, 1)))(x, w)

        with force_impl(impl):
            for a, b in zip(grads(fused), grads(dec)):
                _bitwise(a, b)

    def test_layer_fused_kwarg_parity(self, mesh, rng):
        """column/row SP paths with fused= on == overlap= numbers, and
        fused=+overlap= together is rejected."""
        x, w = self._arrs(rng)

        def col(**kw):
            return tp_sm(
                mesh,
                lambda x, w: tp.column_parallel_linear(
                    x, w, sequence_parallel_enabled=True,
                    axis_name="tp", **kw),
                (P("tp", None), P(None, "tp")), P(None, "tp"))(x, w)

        with force_impl("pallas"):
            _bitwise(col(fused=True), col(overlap=True))

        def row(**kw):
            return tp_sm(
                mesh,
                lambda x, w: tp.row_parallel_linear(
                    x, w, sequence_parallel_enabled=True,
                    axis_name="tp", **kw),
                (P(None, "tp"), P("tp", None)), P("tp", None))(x, w)

        with force_impl("pallas"):
            _bitwise(row(fused=True), row(overlap=True))
        with pytest.raises(ValueError, match="exclusive"):
            tp.column_parallel_linear(x, w, overlap=True, fused=True)
        with pytest.raises(ValueError, match="exclusive"):
            tp.row_parallel_linear(x, w, overlap=True, fused=True)

    def test_rdma_form_raises_off_tpu(self, rng):
        x = jnp.zeros((32, 128), jnp.float32)
        w = jnp.zeros((128, 128), jnp.float32)
        with pytest.raises(NotImplementedError, match="compiled-TPU"):
            fc.matmul_reduce_scatter_rdma(x, w, "tp")


class TestFusedMatmulProbes:
    """Dependence-mode overlap pins (the tier-1 half of the probe
    contract; tools/aot_check.py runs the async half on v5e
    executables)."""

    def _mlp(self, mesh, rng, fn_ag, fn_rs):
        x = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
        w1 = jnp.asarray(rng.normal(size=(16, 24)), jnp.float32)
        w2 = jnp.asarray(rng.normal(size=(24, 16)), jnp.float32)

        def local(x, w1, w2):
            h = fn_ag(x, w1, "tp", 0)
            return fn_rs(h.astype(jnp.float32), w2, "tp", 0)

        return tp_sm(mesh, local,
                     (P("tp"), P(None, "tp"), P("tp", None)),
                     P("tp")), (x, w1, w2)

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_fused_mlp_overlapped(self, mesh, rng, impl):
        with force_impl(impl):
            f, arrs = self._mlp(mesh, rng, fc.fused_all_gather_matmul,
                                fc.fused_matmul_reduce_scatter)
            rep = assert_collective_overlap(optimized_hlo(f, *arrs),
                                            expect_mode="dependence")
        assert len(rep.bodies) >= 2

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_serialized_agm_fails_probe(self, mesh, rng, impl):
        x = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(16, 24)), jnp.float32)
        with force_impl(impl):
            f = tp_sm(mesh,
                      lambda x, w: fc.fused_all_gather_matmul_serial(
                          x, w, "tp", 0),
                      (P("tp", None), P(None, "tp")), P(None, "tp"))
            rep = check_collective_overlap(optimized_hlo(f, x, w))
        assert rep.bodies and not rep.ok, rep.detail

    def test_fused_grad_overlapped(self, mesh, rng):
        """The custom VJPs route dx through the dual fused ring — the
        backward loop bodies must pass the dependence probe too."""
        with force_impl("pallas"):
            f, arrs = self._mlp(mesh, rng, fc.fused_all_gather_matmul,
                                fc.fused_matmul_reduce_scatter)

            def loss(x, w1, w2):
                return jnp.sum(f(x, w1, w2).astype(jnp.float32) ** 2)

            rep = assert_collective_overlap(
                optimized_hlo(jax.grad(loss, argnums=(0, 1, 2)), *arrs),
                expect_mode="dependence")
        assert len(rep.bodies) >= 2


class TestAllGatherFlashAttention:
    """all_gather_flash_attention vs ring_attention (its decomposed PR 4
    counterpart): same schedule, merge fused into the kernel epilogue."""

    def _qkv(self, rng, B=1, Hq=4, Hkv=4, S=128, D=32, dtype=jnp.float32):
        q = jnp.asarray(rng.normal(size=(B, Hq, S, D)), dtype)
        k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), dtype)
        v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), dtype)
        return q, k, v

    def _sm(self, cp, fn, n_extra=0):
        mesh = make_mesh(cp=cp, dp=1, devices=jax.devices()[:cp])
        spec = P(None, None, "cp", None)
        extra = (P(None, "cp"),) * n_extra
        return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 3 + extra,
                             out_specs=spec, check_vma=False)

    @pytest.mark.parametrize("causal", [False, True])
    def test_xla_path_bitwise_vs_ring(self, devices, rng, causal):
        q, k, v = self._qkv(rng)
        with force_impl("xla"):
            a = self._sm(4, lambda q, k, v: fc.all_gather_flash_attention(
                q, k, v, "cp", causal=causal))(q, k, v)
            b = self._sm(4, lambda q, k, v: ring_attention(
                q, k, v, "cp", causal=causal))(q, k, v)
        _bitwise(a, b)

    @pytest.mark.parametrize("causal", [False, True])
    def test_interpret_path_ulp_vs_ring(self, devices, rng, causal):
        """Interpret path: the merge runs INSIDE the kernel; XLA CPU's
        fusion-context FMA contraction moves at most the last bit of
        `out_prev*w_a + out_t*w_b` (components verified bitwise in
        isolation), so the pin is <= 2 ulp, not bitwise."""
        q, k, v = self._qkv(rng)
        with force_impl("pallas"):
            a = self._sm(4, lambda q, k, v: fc.all_gather_flash_attention(
                q, k, v, "cp", causal=causal))(q, k, v)
            b = self._sm(4, lambda q, k, v: ring_attention(
                q, k, v, "cp", causal=causal))(q, k, v)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)

    def test_gqa_and_cp2(self, devices, rng):
        q, k, v = self._qkv(rng, Hq=4, Hkv=2, S=64)
        for impl in ("xla", "pallas"):
            with force_impl(impl):
                a = self._sm(2, lambda q, k, v:
                             fc.all_gather_flash_attention(
                                 q, k, v, "cp", causal=True))(q, k, v)
                b = self._sm(2, lambda q, k, v: ring_attention(
                    q, k, v, "cp", causal=True))(q, k, v)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)

    def test_segments(self, devices, rng):
        q, k, v = self._qkv(rng, S=64)
        segs = jnp.asarray(
            rng.integers(0, 3, (1, 64)).cumsum(axis=-1) // 2, jnp.int32)
        for impl in ("xla", "pallas"):
            with force_impl(impl):
                a = self._sm(4, lambda q, k, v, s:
                             fc.all_gather_flash_attention(
                                 q, k, v, "cp", segment_ids=s),
                             n_extra=1)(q, k, v, segs)
                b = self._sm(4, lambda q, k, v, s: ring_attention(
                    q, k, v, "cp", segment_ids=s), n_extra=1)(q, k, v,
                                                              segs)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)

    @pytest.mark.parametrize("gqa", [False, True])
    def test_grads_vs_ring(self, devices, rng, gqa):
        """Custom-VJP grad parity incl. the GQA group-sum — the fused
        forward saves the same (out, lse) residuals the ring backward
        consumes, so gradients track the forward's ulp bound."""
        q, k, v = self._qkv(rng, Hq=4, Hkv=2 if gqa else 4, S=64)

        def grads(fn):
            sm = self._sm(2, lambda q, k, v: fn(q, k, v))

            def loss(q, k, v):
                return jnp.sum(sm(q, k, v).astype(jnp.float32) ** 2)

            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

        for impl in ("xla", "pallas"):
            with force_impl(impl):
                ga = grads(lambda q, k, v: fc.all_gather_flash_attention(
                    q, k, v, "cp", causal=True))
                gb = grads(lambda q, k, v: ring_attention(
                    q, k, v, "cp", causal=True))
            for a, b in zip(ga, gb):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_overlap_probe(self, devices, rng, impl):
        q, k, v = self._qkv(rng, S=64)
        with force_impl(impl):
            f = self._sm(4, lambda q, k, v: fc.all_gather_flash_attention(
                q, k, v, "cp", causal=True))
            rep = assert_collective_overlap(optimized_hlo(f, q, k, v),
                                            expect_mode="dependence")
        assert rep.ok
        # the serialized ring is the shared falsifiable negative control
        with force_impl(impl):
            g = self._sm(4, lambda q, k, v: ring_attention_serial(
                q, k, v, "cp", causal=True))
            srep = check_collective_overlap(optimized_hlo(g, q, k, v))
        assert srep.bodies and not srep.ok

    def test_dropout_rejected(self, devices, rng):
        q, k, v = self._qkv(rng, S=64)
        with pytest.raises(TypeError):
            fc.all_gather_flash_attention(q, k, v, "cp", dropout_p=0.1)


class TestFusedVocabParallelCE:
    """vocab_parallel_linear_cross_entropy(fused=True): packed final-
    vocab-tile stats + the 2-collective merge, vs the decomposed
    4-collective ladder."""

    T, H, V = 24, 16, 64

    def _arrs(self, rng):
        x = jnp.asarray(rng.normal(size=(self.T, self.H)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(self.V, self.H)) * 0.1,
                        jnp.float32)
        t = jnp.asarray(rng.integers(0, self.V, (self.T,)), jnp.int32)
        return x, w, t

    def _fn(self, mesh, fused, **kw):
        def run(x, w, t):
            return tp.vocab_parallel_linear_cross_entropy(
                x, w, t, axis_name="tp", fused=fused, **kw)

        return tp_sm(mesh, run, (P(), P("tp", None), P()), P())

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    @pytest.mark.parametrize("smoothing", [0.0, 0.1])
    def test_loss_bitwise(self, mesh, rng, impl, smoothing):
        x, w, t = self._arrs(rng)
        with force_impl(impl):
            a = self._fn(mesh, True, label_smoothing=smoothing)(x, w, t)
            b = self._fn(mesh, False, label_smoothing=smoothing)(x, w, t)
        _bitwise(a, b)

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_grads_bitwise(self, mesh, rng, impl):
        x, w, t = self._arrs(rng)
        with force_impl(impl):
            def grads(fused):
                f = self._fn(mesh, fused, padding_idx=0)
                return jax.jit(jax.grad(
                    lambda x, w: jnp.sum(f(x, w, t)),
                    argnums=(0, 1)))(x, w)

            for a, b in zip(grads(True), grads(False)):
                _bitwise(a, b)

    def test_packed_stats_bitwise(self, rng):
        """shard_stats_packed columns == shard_stats outputs (the same
        scratch reads leave the kernel through one stream)."""
        from apex1_tpu.ops.linear_xent import shard_stats, shard_stats_packed
        x = jnp.asarray(rng.normal(size=(self.T, self.H)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(32, self.H)) * 0.1, jnp.float32)
        t = jnp.asarray(rng.integers(0, 32, (self.T, 1)), jnp.int32)
        with force_impl("pallas"):
            sep = shard_stats(x, w, t, col_offset=32, num_classes=64)
            packed = shard_stats_packed(x, w, t, col_offset=32,
                                        num_classes=64)
        for i, s in enumerate(sep):
            _bitwise(packed[:, i], s)


class TestFusedTuningSpecs:
    """Registry entries for the new kernels: present, VMEM-gated, and
    consulted by the block resolution."""

    def test_specs_present(self):
        from apex1_tpu.tuning.registry import SPECS
        assert SPECS["fused_collective_matmul"].params == ("block_m",
                                                           "block_n")
        assert SPECS["fused_ag_flash"].params == ("block_q", "block_k")

    def test_vmem_model_rejects_huge_blocks(self):
        from apex1_tpu.core.capability import vmem_budget
        from apex1_tpu.tuning.registry import SPECS
        ok, _ = SPECS["fused_collective_matmul"].check(
            {"block_m": 8192, "block_n": 8192}, {"Kp": 8192}, 2,
            vmem_budget())
        assert not ok
        ok, _ = SPECS["fused_ag_flash"].check(
            {"block_q": 256, "block_k": 256}, {"Dp": 128, "Sb": 1024}, 2,
            vmem_budget())
        assert ok

    def test_table_lookup_consulted(self, tmp_path, monkeypatch):
        """A banked fused_collective_matmul winner is served by
        _cm_blocks (and an absent table falls through to the
        heuristic)."""
        from apex1_tpu import tuning
        monkeypatch.setenv("APEX1_TUNING_DIR", str(tmp_path))
        tuning.clear_cache()
        try:
            assert fc._cm_blocks(128, None, None, jnp.float32) == (256,
                                                                   512)
            tuning.record("fused_collective_matmul", {"Kp": 128},
                          "float32", {"block_m": 64, "block_n": 128})
            tuning.save("fused_collective_matmul")
            tuning.clear_cache()
            assert fc._cm_blocks(128, None, None, jnp.float32) == (64,
                                                                   128)
        finally:
            tuning.clear_cache()
