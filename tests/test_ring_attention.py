"""Ring attention (context parallelism) vs global attention on the 8-device
CPU mesh — fwd + grads, causal + segments (SURVEY.md §5.7 build obligation:
BASELINE config 5 long-context capability the reference lacks)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex1_tpu.core.mesh import make_mesh
from apex1_tpu.ops.attention import flash_attention
from apex1_tpu.parallel.ring_attention import ring_attention

B, H, S, D = 2, 2, 64, 16
SP = 4  # ring size


def _mk(rng, dtype=jnp.float32):
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype)
    return q, k, v


def _ring_fn(mesh, causal, with_segs=False):
    spec = P(None, None, "cp", None)
    segspec = P(None, "cp")
    in_specs = (spec, spec, spec) + ((segspec,) if with_segs else ())

    def local(q, k, v, *segs):
        return ring_attention(q, k, v, "cp", causal=causal,
                              segment_ids=segs[0] if segs else None)

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=spec))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_global(rng, causal, devices):
    mesh = make_mesh(cp=SP, dp=1, devices=devices[:SP])
    q, k, v = _mk(rng)
    got = _ring_fn(mesh, causal)(q, k, v)
    want = flash_attention(q, k, v, causal=causal)  # xla gold on cpu
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_ring_with_segments(rng, devices):
    mesh = make_mesh(cp=SP, dp=1, devices=devices[:SP])
    q, k, v = _mk(rng)
    seg = jnp.sort(jnp.asarray(rng.integers(0, 3, size=(B, S)), jnp.int32),
                   axis=1)
    got = _ring_fn(mesh, True, with_segs=True)(q, k, v, seg)
    want = flash_attention(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_grads_match_global(rng, causal, devices):
    mesh = make_mesh(cp=SP, dp=1, devices=devices[:SP])
    q, k, v = _mk(rng)
    ring = _ring_fn(mesh, causal)

    def loss_ring(q, k, v):
        return jnp.sum(jnp.square(ring(q, k, v)))

    def loss_global(q, k, v):
        return jnp.sum(jnp.square(flash_attention(q, k, v, causal=causal)))

    got = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_global, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_ring_gqa(rng, devices):
    mesh = make_mesh(cp=SP, dp=1, devices=devices[:SP])
    q = jnp.asarray(rng.normal(size=(B, 4, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, 2, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, 2, S, D)), jnp.float32)
    spec = P(None, None, "cp", None)
    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "cp", causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
    got = fn(q, k, v)
    want = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


class TestOverlappedSchedule:
    """The double-buffered rewrite against its anchors: bit-for-bit
    forward parity with the retained serialized schedule (same
    attend/merge order — only the permutes' dataflow moved), and grad
    parity with the global gold through BOTH backward paths (the
    custom-VJP overlapped ring and XLA's transpose of the scan)."""

    def test_fwd_bitwise_matches_serial(self, rng, devices):
        from apex1_tpu.parallel.ring_attention import ring_attention_serial
        mesh = make_mesh(cp=SP, dp=1, devices=devices[:SP])
        q, k, v = _mk(rng)
        seg = jnp.sort(jnp.asarray(rng.integers(0, 3, size=(B, S)),
                                   jnp.int32), axis=1)
        spec = P(None, None, "cp", None)
        segspec = P(None, "cp")

        def mk(fn):
            return jax.jit(jax.shard_map(
                lambda q, k, v, s: fn(q, k, v, "cp", causal=True,
                                      segment_ids=s),
                mesh=mesh, in_specs=(spec,) * 3 + (segspec,),
                out_specs=spec))

        got = mk(ring_attention)(q, k, v, seg)
        ser = mk(ring_attention_serial)(q, k, v, seg)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ser))

    @pytest.mark.parametrize("use_custom_vjp", [True, False])
    def test_grads_both_vjp_paths(self, rng, devices, use_custom_vjp):
        mesh = make_mesh(cp=SP, dp=1, devices=devices[:SP])
        q, k, v = _mk(rng)
        spec = P(None, None, "cp", None)
        ring = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "cp", causal=True,
                                           use_custom_vjp=use_custom_vjp),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)
        got = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(jnp.square(ring(q, k, v))),
            argnums=(0, 1, 2)))(q, k, v)
        want = jax.grad(
            lambda q, k, v: jnp.sum(jnp.square(
                flash_attention(q, k, v, causal=True))),
            argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)

    def test_gqa_grads_match_global(self, rng, devices):
        """GQA through the custom backward: the per-shard dk/dv group
        reduction must match the unsharded gold."""
        mesh = make_mesh(cp=SP, dp=1, devices=devices[:SP])
        q = jnp.asarray(rng.normal(size=(B, 4, S, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, 2, S, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, 2, S, D)), jnp.float32)
        spec = P(None, None, "cp", None)
        ring = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "cp", causal=True),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)
        got = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(jnp.square(ring(q, k, v))),
            argnums=(0, 1, 2)))(q, k, v)
        want = jax.grad(
            lambda q, k, v: jnp.sum(jnp.square(
                flash_attention(q, k, v, causal=True))),
            argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)

    def test_segment_grads_ride_the_bwd_ring(self, rng, devices):
        # A ring of TWO. With segment ids the backward ring has five
        # collective permutes in flight a step (dk, dv, k, v, kseg);
        # XLA:CPU runs each as a thunk that BLOCKS a thread in its
        # rendezvous, and has a thread a device plus one pool thread a
        # core. A ring of four can park 5 x 3 threads at permutes whose
        # fourth partner then finds none left (12 on 8 cores): the
        # rendezvous aborts the process after 40 s, ~1 run in 4 under
        # load. A ring of two parks at most 5. Several hops without
        # segments (four permutes, 4 x 3 <= 12) are the test above.
        mesh = make_mesh(cp=2, dp=1, devices=devices[:2])
        q, k, v = _mk(rng)
        seg = jnp.sort(jnp.asarray(rng.integers(0, 3, size=(B, S)),
                                   jnp.int32), axis=1)
        spec = P(None, None, "cp", None)
        ring = jax.shard_map(
            lambda q, k, v, s: ring_attention(q, k, v, "cp", causal=True,
                                              segment_ids=s),
            mesh=mesh, in_specs=(spec,) * 3 + (P(None, "cp"),),
            out_specs=spec)
        got = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(jnp.square(ring(q, k, v, seg))),
            argnums=(0, 1, 2)))(q, k, v)
        want = jax.grad(
            lambda q, k, v: jnp.sum(jnp.square(flash_attention(
                q, k, v, causal=True, segment_ids=seg))),
            argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)

    def test_pallas_step_backward_interpret(self, rng, devices):
        """Execute the PALLAS branch of the ring backward (interpret
        mode on the CPU mesh): the CPU suite otherwise only runs
        `_step_grads_xla`, while TPU training runs only
        `_step_grads_pallas` — a wiring bug in its res/lse-padding/
        dlse=0 handling must not ship numerics-untested."""
        from apex1_tpu.ops import force_impl
        mesh = make_mesh(cp=SP, dp=1, devices=devices[:SP])
        q = jnp.asarray(rng.normal(size=(1, 2, S, 16)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 1, S, 16)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 1, S, 16)), jnp.float32)
        spec = P(None, None, "cp", None)

        def local(q, k, v):
            with force_impl("pallas"):
                return ring_attention(q, k, v, "cp", causal=True)

        ring = jax.shard_map(local, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=spec, check_vma=False)
        got = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(jnp.square(ring(q, k, v))),
            argnums=(0, 1, 2)))(q, k, v)
        want = jax.grad(
            lambda q, k, v: jnp.sum(jnp.square(
                flash_attention(q, k, v, causal=True))),
            argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)

    def test_ring_size_two(self, rng, devices):
        """cp=2 exercises both peeled edges (empty scan bodies)."""
        mesh = make_mesh(cp=2, dp=1, devices=devices[:2])
        q, k, v = _mk(rng)
        spec = P(None, None, "cp", None)
        ring = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "cp", causal=True),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)
        got = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(jnp.square(ring(q, k, v))),
            argnums=(0, 1, 2)))(q, k, v)
        want = jax.grad(
            lambda q, k, v: jnp.sum(jnp.square(
                flash_attention(q, k, v, causal=True))),
            argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


class TestUlysses:
    """All-to-all sequence parallelism (≙ DeepSpeed Ulysses; SURVEY §2.6
    [absent] in apex): head-scatter attention over cp must equal
    unsharded flash attention on the full sequence."""

    def test_matches_unsharded(self, rng, devices):
        from jax.sharding import PartitionSpec as P

        from apex1_tpu.core.mesh import make_mesh
        from apex1_tpu.parallel.ulysses import ulysses_attention
        B, H, S, D = 2, 4, 64, 16
        mesh = make_mesh(cp=4, dp=1, devices=devices[:4])
        q, k, v = (jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
                   for _ in range(3))

        def f(q, k, v):
            return ulysses_attention(q, k, v, "cp", causal=True)

        got = jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=(P(None, None, "cp"),) * 3,
            out_specs=P(None, None, "cp"), check_vma=False))(q, k, v)
        want = flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_segment_ids_ride_along(self, rng, devices):
        from jax.sharding import PartitionSpec as P

        from apex1_tpu.core.mesh import make_mesh
        from apex1_tpu.parallel.ulysses import ulysses_attention
        B, H, S, D = 1, 4, 32, 8
        mesh = make_mesh(cp=4, dp=1, devices=devices[:4])
        q, k, v = (jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
                   for _ in range(3))
        segs = jnp.asarray(
            np.repeat(np.arange(4), 8)[None, :], jnp.int32)  # 4 docs

        def f(q, k, v, s):
            return ulysses_attention(q, k, v, "cp", causal=True,
                                     segment_ids=s)

        got = jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=((P(None, None, "cp"),) * 3 + (P(None, "cp"),)),
            out_specs=P(None, None, "cp"), check_vma=False))(q, k, v, segs)
        want = flash_attention(q, k, v, causal=True, segment_ids=segs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_head_divisibility_error(self, rng, devices):
        from jax.sharding import PartitionSpec as P

        from apex1_tpu.core.mesh import make_mesh
        from apex1_tpu.parallel.ulysses import ulysses_attention
        mesh = make_mesh(cp=4, dp=1, devices=devices[:4])
        q = jnp.ones((1, 2, 16, 8), jnp.float32)  # 2 heads, cp=4

        def f(q):
            return ulysses_attention(q, q, q, "cp")

        with pytest.raises(ValueError, match="divisible"):
            jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=(P(None, None, "cp"),),
                out_specs=P(None, None, "cp"), check_vma=False))(q)

    def test_ring_fallback_on_indivisible_heads(self, rng, devices):
        """fallback='ring' routes head counts ulysses cannot shard
        through the overlapped ring instead of raising — same numerics
        as unsharded flash."""
        from jax.sharding import PartitionSpec as P

        from apex1_tpu.core.mesh import make_mesh
        from apex1_tpu.parallel.ulysses import ulysses_attention
        mesh = make_mesh(cp=4, dp=1, devices=devices[:4])
        q = jnp.asarray(rng.normal(size=(1, 2, 32, 8)), jnp.float32)

        def f(q):
            return ulysses_attention(q, q, q, "cp", causal=True,
                                     fallback="ring")

        got = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P(None, None, "cp"),),
            out_specs=P(None, None, "cp"), check_vma=False))(q)
        want = flash_attention(q, q, q, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_llama_ulysses_cp(self, rng, devices):
        """Llama with cp_impl='ulysses': sharded forward == unsharded."""
        import dataclasses

        from jax.sharding import PartitionSpec as P

        from apex1_tpu.core.mesh import make_mesh
        from apex1_tpu.models.llama import Llama, LlamaConfig
        cfg = dataclasses.replace(LlamaConfig.tiny(), cp_impl="ulysses")
        mesh = make_mesh(cp=4, dp=1, devices=devices[:4])
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 32)),
                             jnp.int32)
        plain = Llama(cfg)
        sharded_model = Llama(cfg, seq_shard_axis="cp")
        params = plain.init(jax.random.key(0), tokens)["params"]
        want = plain.apply({"params": params}, tokens)

        def f(p, t):
            return sharded_model.apply({"params": p}, t)

        got = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P(), P(None, "cp")),
            out_specs=P(None, "cp"), check_vma=False))(params, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_grads_match_unsharded(self, rng, devices):
        """AD through the double all_to_all: dq/dk/dv under cp=4 equal
        the unsharded flash attention gradients."""
        from jax.sharding import PartitionSpec as P

        from apex1_tpu.core.mesh import make_mesh
        from apex1_tpu.parallel.ulysses import ulysses_attention
        B, H, S, D = 1, 4, 32, 8
        mesh = make_mesh(cp=4, dp=1, devices=devices[:4])
        q, k, v = (jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
                   for _ in range(3))

        smapped = jax.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, "cp", causal=True),
            mesh=mesh, in_specs=(P(None, None, "cp"),) * 3,
            out_specs=P(None, None, "cp"), check_vma=False)

        g_ep = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(smapped(q, k, v) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        g_ref = jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ep, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


class TestRingDropout:
    """In-kernel dropout over the ring (PR 5): the counter-based mask
    keys on GLOBAL positions via each shard's k_offset, so (a) serial
    and overlapped schedules drop identical weights, (b) the sharded
    result equals single-device flash dropout, (c) both custom-VJP
    paths agree. The tolerance is tight-allclose, not bitwise: the two
    schedules compile to different programs and differ by float
    rounding only (a wrong mask would differ by O(1) dropped weights)."""

    P_DROP, SEED = 0.2, 99

    def _run(self, mesh, fn, q, k, v, **kw):
        spec = P(None, None, "cp", None)

        def local(q, k, v):
            return fn(q, k, v, "cp", causal=True, dropout_p=self.P_DROP,
                      dropout_seed=self.SEED, **kw)

        return jax.jit(jax.shard_map(local, mesh=mesh,
                                     in_specs=(spec,) * 3,
                                     out_specs=spec))(q, k, v)

    def test_serial_overlapped_parity_and_flash_equivalence(self, rng,
                                                            devices):
        from apex1_tpu.parallel.ring_attention import ring_attention_serial
        mesh = make_mesh(cp=SP, dp=1, devices=devices[:SP])
        q, k, v = _mk(rng)
        o_ov = self._run(mesh, ring_attention, q, k, v)
        o_se = self._run(mesh, ring_attention_serial, q, k, v)
        np.testing.assert_allclose(o_ov, o_se, rtol=5e-6, atol=5e-7)
        # sharded == unsharded: the mask is a pure function of global
        # position, so context parallelism does not change WHICH
        # weights drop — only how the sum is sliced
        want = flash_attention(q, k, v, causal=True,
                               dropout_p=self.P_DROP,
                               dropout_seed=self.SEED)
        np.testing.assert_allclose(o_ov, want, rtol=2e-5, atol=2e-5)
        # and dropout actually happened
        plain = flash_attention(q, k, v, causal=True)
        assert not np.allclose(o_ov, plain, atol=1e-3)
        # causal-skip cond off: numerics identical
        o_ns = self._run(mesh, ring_attention, q, k, v,
                         skip_masked=False)
        np.testing.assert_allclose(o_ns, o_ov, rtol=1e-6, atol=1e-7)

    @pytest.mark.slow  # two full ring-backward compiles: check_all --all
    def test_grads_both_vjp_paths(self, rng, devices):
        mesh = make_mesh(cp=SP, dp=1, devices=devices[:SP])
        q, k, v = _mk(rng)
        spec = P(None, None, "cp", None)

        def grads(use_custom):
            def local(q, k, v):
                return ring_attention(q, k, v, "cp", causal=True,
                                      dropout_p=self.P_DROP,
                                      dropout_seed=self.SEED,
                                      use_custom_vjp=use_custom)

            sm = jax.shard_map(local, mesh=mesh, in_specs=(spec,) * 3,
                               out_specs=spec)
            return jax.grad(lambda q, k, v: jnp.sum(sm(q, k, v) ** 2),
                            (0, 1, 2))(q, k, v)

        for a, b in zip(grads(True), grads(False)):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)

