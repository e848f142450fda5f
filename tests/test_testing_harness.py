"""`apex1_tpu.testing` — the importable test harness (≙
``apex/transformer/testing``): distributed_mesh context, global args,
standalone test models."""

import pytest
import jax
import jax.numpy as jnp
import numpy as np

from apex1_tpu import testing
from apex1_tpu.transformer import parallel_state


def test_distributed_mesh_context(devices):
    parallel_state.destroy_model_parallel()
    with testing.distributed_mesh(dp=2, tp=2, pp=2) as mesh:
        assert set(mesh.axis_names) >= {"dp", "tp", "pp"}
        assert parallel_state.get_tensor_model_parallel_world_size() == 2
        assert parallel_state.model_parallel_is_initialized()
    assert not parallel_state.model_parallel_is_initialized()


def test_global_args_roundtrip():
    a = testing.TestArgs(seq_length=16, hidden_size=32)
    testing.set_global_args(a)
    try:
        assert testing.get_args().seq_length == 16
    finally:
        testing.set_global_args(None)  # type: ignore[arg-type]
    assert testing.get_args().seq_length == 32  # defaults restored


@pytest.mark.slow
def test_standalone_models_train_one_step(devices):
    for build in (testing.standalone_gpt, testing.standalone_bert):
        model, batch, params, loss_fn = build()
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        assert np.isfinite(float(loss))
        assert all(np.all(np.isfinite(g)) for g in jax.tree.leaves(grads))


class TestCompileCachePolicy:
    """One cache policy (`testing.enable_persistent_compilation_cache`
    and its env-var form `child_cache_env`): an exported
    ``JAX_COMPILATION_CACHE_DIR`` is the operator's — presence, not
    truthiness (exported EMPTY = deliberately disabled) — and no
    directory is set in code; unset, the cache is the fixed
    ``<checkout>/.jax_cache``."""

    @pytest.fixture()
    def cache_cfg(self):
        import jax
        keep = (jax.config.jax_compilation_cache_dir,
                jax.config.jax_persistent_cache_min_compile_time_secs)
        yield jax.config
        jax.config.update("jax_compilation_cache_dir", keep[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          keep[1])

    def test_env_set_leaves_config_alone(self, monkeypatch, cache_cfg):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/op_cache")
        cache_cfg.update("jax_compilation_cache_dir", "sentinel")
        assert testing.enable_persistent_compilation_cache() == "sentinel"
        assert cache_cfg.jax_compilation_cache_dir == "sentinel"

    def test_env_unset_uses_fixed_checkout_dir(self, monkeypatch,
                                               cache_cfg):
        import os
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        got = testing.enable_persistent_compilation_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(repo, ".jax_cache")
        assert cache_cfg.jax_compilation_cache_dir == got
        assert testing.REPO_CACHE_DIR == got

    def test_child_exported_empty_dir_is_not_reenabled(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
        monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                           raising=False)
        out = testing.child_cache_env()
        assert "JAX_COMPILATION_CACHE_DIR" not in out  # inherit the disable
        assert out["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0.1"

    def test_child_unset_gets_checkout_dir(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        out = testing.child_cache_env()
        assert out["JAX_COMPILATION_CACHE_DIR"] == testing.REPO_CACHE_DIR

    def test_child_exported_dir_wins_and_is_inherited(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/op_cache")
        out = testing.child_cache_env()
        # dir reaches the child via dict(os.environ); no duplicate key
        assert "JAX_COMPILATION_CACHE_DIR" not in out
