"""What PR 21 (first contact with the chip) changed below the main path:
no fallback that hides the device, a dropout seed Mosaic accepts, one
process per host, a runtime library built from the present source."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest


class TestNoHiddenFallback:
    def test_backend_failure_is_not_mapped_to_cpu(self, monkeypatch):
        """A backend that fails to initialise must raise out of the
        dispatch — `"cpu"` there would silently select the composites
        and interpret mode for the life of the process."""
        from apex1_tpu.ops import _common

        def boom():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(jax, "default_backend", boom)
        _common._default_backend.cache_clear()
        try:
            with pytest.raises(RuntimeError, match="Unable to initialize"):
                _common.on_tpu()
            with pytest.raises(RuntimeError):
                _common.use_pallas()
        finally:
            _common._default_backend.cache_clear()
        monkeypatch.undo()
        assert _common.on_tpu() is False        # the real CPU backend

    def test_only_the_tpu_platform_is_a_tpu(self, monkeypatch):
        from apex1_tpu.ops import _common
        for name, want in (("tpu", True), ("cpu", False), ("gpu", False)):
            monkeypatch.setattr(jax, "default_backend", lambda n=name: n)
            _common._default_backend.cache_clear()
            assert _common.on_tpu() is want
        monkeypatch.undo()
        _common._default_backend.cache_clear()


class TestOneProcessPerHost:
    def test_launch_refuses_local_accelerator_fanout(self, monkeypatch,
                                                     tmp_path):
        from apex1_tpu.parallel import multiproc
        script = tmp_path / "child.py"
        script.write_text("raise SystemExit(0)\n")
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(ValueError, match="one process per host"):
            multiproc.launch(str(script), num_processes=2)
        with pytest.raises(ValueError, match="one process per host"):
            multiproc.launch(str(script), num_processes=2,
                             env={"JAX_PLATFORMS": "tpu"})

    def test_cpu_clusters_and_single_process_still_launch(self,
                                                          monkeypatch,
                                                          tmp_path):
        from apex1_tpu.parallel import multiproc
        script = tmp_path / "child.py"
        script.write_text("raise SystemExit(0)\n")
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        assert multiproc.launch(str(script), num_processes=2,
                                cpu_devices_per_process=1) == 0
        assert multiproc.launch(str(script), num_processes=2,
                                env={"JAX_PLATFORMS": "cpu"}) == 0
        assert multiproc.launch(str(script), num_processes=1) == 0


class TestDropoutSeedWords:
    """Mosaic's `prng_seed` takes at most two words. The forward, dq and
    dkv kernels all seed with `_seed_words(seed, salt, row0, col0)` of
    the same four counters, so their hardware-PRNG masks are identical
    iff that is a pure function of exactly those counters (the on-chip
    identity itself: `tools/hw_numerics.py`)."""

    def test_compiled_path_seeds_with_two_words(self):
        from apex1_tpu.ops import stochastic as st

        def tile(seed, salt, row0, col0):
            return st.tile_keep_mask((8, 128), st.threshold_u32(0.1),
                                     seed, salt, row0, col0, interp=False)

        jaxpr = jax.make_jaxpr(tile)(*(jnp.int32(i) for i in range(4)))
        seeds = [e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "prng_seed"]
        assert len(seeds) == 1 and len(seeds[0].invars) == 2
        assert all(v.aval.dtype == jnp.int32 and v.aval.shape == ()
                   for v in seeds[0].invars)

    def test_pure_function_of_all_four_counters(self):
        from apex1_tpu.ops.stochastic import _seed_words
        base = (1234, 7, 512, 1024)
        w = tuple(int(x) for x in _seed_words(*base))
        # same counters -> same words: eager, jitted, traced
        assert w == tuple(int(x) for x in _seed_words(*base))
        jw = jax.jit(_seed_words)(*(jnp.int32(v) for v in base))
        assert w == tuple(int(x) for x in jw)
        # each counter moves the pair; (salt, row) are not interchangeable
        seen = {w}
        for i in range(4):
            other = list(base)
            other[i] += 1
            seen.add(tuple(int(x) for x in _seed_words(*other)))
        seen.add(tuple(int(x) for x in _seed_words(1234, 512, 7, 1024)))
        assert len(seen) == 6

    def test_tiles_draw_distinct_streams(self):
        from apex1_tpu.ops.stochastic import _seed_words
        words = {tuple(int(x) for x in _seed_words(99, h, r * 128, c * 128))
                 for h in range(4) for r in range(8) for c in range(8)}
        assert len(words) == 4 * 8 * 8


class TestRuntimeBuiltFromPresentSource:
    def test_artefact_is_keyed_on_source_and_host(self, monkeypatch,
                                                  tmp_path):
        from apex1_tpu import runtime as rt
        assert rt.native_available(), "g++ build of _runtime.cpp failed"
        key = rt._build_key()
        built = pathlib.Path(rt._DIR) / f"_runtime.{key}.so"
        assert built.exists()
        assert not (pathlib.Path(rt._DIR) / "_runtime.so").exists()
        # other source bytes -> another artefact name: a stale library
        # can never be picked up for edited source
        src2 = tmp_path / "_runtime.cpp"
        src2.write_bytes(pathlib.Path(rt._SRC).read_bytes() + b"\n// x\n")
        monkeypatch.setattr(rt, "_SRC", str(src2))
        assert rt._build_key() != key
        monkeypatch.undo()
        # another host -> another name: a checkout's ignored files get
        # copied between machines, -march=native code must not be
        monkeypatch.setattr(rt.platform, "node", lambda: "another-host")
        assert rt._build_key() != key

    def test_failed_build_warns_and_falls_back(self, monkeypatch,
                                               tmp_path):
        from apex1_tpu import runtime as rt
        bad = tmp_path / "_runtime.cpp"
        bad.write_text("this is not C++\n")
        monkeypatch.setattr(rt, "_SRC", str(bad))
        monkeypatch.setattr(rt, "_DIR", str(tmp_path))
        with pytest.warns(RuntimeWarning, match="NumPy fallbacks"):
            assert rt._build_library() is None
        assert not list(tmp_path.glob("*.so*"))
