"""Testing harness — reference ``apex/transformer/testing/``
(``commons.py``, ``distributed_test_base.py :: DistributedTestBase``,
``standalone_gpt.py``, ``standalone_bert.py``, ``global_vars.py``).

The reference spawns N NCCL processes per test
(``NcclDistributedTestBase``); the TPU-native harness gets N devices in
ONE process: ``--xla_force_host_platform_device_count`` yields a virtual
CPU mesh where every collective (psum/all_gather/ppermute/…) runs for
real (SURVEY.md §4.2.4). ``tests/conftest.py`` applies
`force_virtual_cpu_devices` before any backend is initialized.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import re
from typing import Optional

import numpy as np


def force_virtual_cpu_devices(n: int = 8) -> None:
    """Put N virtual CPU devices under this process — MUST run before the
    first backend use (≙ ``DistributedTestBase.setUpClass`` spawning its
    process group). A pre-existing device-count flag with a different
    count is replaced, not kept."""
    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n}"
    pat = r"--xla_force_host_platform_device_count=\d+"
    if re.search(pat, flags):
        flags = re.sub(pat, flag, flags)
    else:
        flags = f"{flags} {flag}".strip()
    os.environ["XLA_FLAGS"] = flags
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_threefry_partitionable", True)


#: the compile cache when ``JAX_COMPILATION_CACHE_DIR`` is not set: a
#: FIXED path inside the checkout (git-ignored) — the path is part of
#: the cache key's surroundings, so a directory that moves never hits
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

# 0.1, not the 1.0 JAX default: the tier-1 suite is hundreds of TINY-model
# programs whose XLA compiles land in the 0.1-0.5s band — above the
# threshold they were all recompiled every run. Sub-0.1s programs stay
# uncached: for those the disk round-trip costs about what it saves.
_MIN_COMPILE_SECS = 0.1


def enable_persistent_compilation_cache() -> str:
    """The one copy of the compile-cache policy (``chip_smoke.py``,
    ``tests/conftest.py`` and the tools all call this):
    where ``JAX_COMPILATION_CACHE_DIR`` is exported JAX already reads it
    and NO directory is set in code (exported empty = the operator
    disabling the cache); otherwise the cache is `REPO_CACHE_DIR`.
    Returns the directory in effect ("" when disabled)."""
    import jax

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      _MIN_COMPILE_SECS)
    return jax.config.jax_compilation_cache_dir or ""


def child_cache_env() -> dict:
    """Env-var form of :func:`enable_persistent_compilation_cache` for
    CHILD processes a test harness spawns (example smokes, multiproc
    clusters): an already-exported ``JAX_COMPILATION_CACHE_DIR`` is
    inherited untouched (exported EMPTY counts: that is the operator
    disabling the cache), otherwise the children get `REPO_CACHE_DIR`.
    Merge the returned dict into the child env."""
    out = {}
    if not os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        out["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = str(
            _MIN_COMPILE_SECS)
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        out["JAX_COMPILATION_CACHE_DIR"] = REPO_CACHE_DIR
    return out


def set_random_seed(seed: int):
    """``testing/commons.py :: set_random_seed`` — numpy + a JAX key."""
    import jax

    np.random.seed(seed)
    return jax.random.key(seed)


def assert_devices(n: int):
    import jax

    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devs)} — call "
            "force_virtual_cpu_devices() before any backend use")
    return devs[:n]


@contextlib.contextmanager
def distributed_mesh(dp: int = 1, tp: int = 1, pp: int = 1, cp: int = 1):
    """``DistributedTestBase`` analog: a mesh over virtual devices plus
    `transformer.parallel_state` initialized to match, torn down after."""
    from apex1_tpu.transformer import parallel_state

    n = dp * tp * pp * cp
    devices = assert_devices(n)
    if parallel_state.model_parallel_is_initialized():
        # never adopt leaked state: a (tp, pp) match says nothing about
        # dp/cp, and the documented postcondition (torn down on exit)
        # could not hold for state this context didn't create
        raise RuntimeError(
            "parallel_state already initialized — a previous test leaked "
            "global state; call destroy_model_parallel() first")
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=tp, pipeline_model_parallel_size=pp,
        context_parallel_size=cp, devices=devices)
    try:
        yield mesh
    finally:
        parallel_state.destroy_model_parallel()


@dataclasses.dataclass
class TestArgs:
    """``testing/global_vars.py`` + ``arguments.py`` analog: the knobs the
    reference's standalone models read from Megatron global args."""

    micro_batch_size: int = 2
    global_batch_size: int = 8
    seq_length: int = 32
    padded_vocab_size: int = 256
    num_layers: int = 2
    hidden_size: int = 64
    num_attention_heads: int = 4
    seed: int = 1234


_GLOBAL_ARGS: Optional[TestArgs] = None


def set_global_args(args: TestArgs) -> None:
    global _GLOBAL_ARGS
    _GLOBAL_ARGS = args


def get_args() -> TestArgs:
    """``global_vars.py :: get_args`` — defaults if unset."""
    return _GLOBAL_ARGS if _GLOBAL_ARGS is not None else TestArgs()


def standalone_gpt(args: Optional[TestArgs] = None):
    """``testing/standalone_gpt.py`` analog: (model, synthetic batch,
    params, loss_fn) at test scale."""
    import jax
    import jax.numpy as jnp

    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.gpt2 import GPT2, GPT2Config, gpt2_loss_fn

    a = args or get_args()
    cfg = GPT2Config.tiny(
        vocab_size=a.padded_vocab_size, max_seq_len=a.seq_length,
        num_layers=a.num_layers, num_heads=a.num_attention_heads,
        hidden_size=a.hidden_size, policy=get_policy("O1"))
    model = GPT2(cfg)
    rng = np.random.default_rng(a.seed)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size,
                     (a.micro_batch_size, a.seq_length)), jnp.int32)
    params = model.init(jax.random.key(a.seed), tokens)["params"]
    return model, tokens, params, gpt2_loss_fn(model)


def standalone_bert(args: Optional[TestArgs] = None):
    """``testing/standalone_bert.py`` analog."""
    import jax
    import jax.numpy as jnp

    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.bert import (BertConfig, BertPretrain,
                                       bert_pretrain_loss_fn)

    a = args or get_args()
    cfg = BertConfig.tiny(
        vocab_size=a.padded_vocab_size, max_seq_len=a.seq_length,
        num_layers=a.num_layers, num_heads=a.num_attention_heads,
        hidden_size=a.hidden_size, policy=get_policy("O1"))
    model = BertPretrain(cfg)
    rng = np.random.default_rng(a.seed)
    B, S = a.micro_batch_size, a.seq_length
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                              jnp.int32),
        "mlm_labels": jnp.asarray(
            np.where(rng.random((B, S)) < 0.15,
                     rng.integers(0, cfg.vocab_size, (B, S)), -1),
            jnp.int32),
        "nsp_labels": jnp.asarray(rng.integers(0, 2, (B,)), jnp.int32),
    }
    params = model.init(jax.random.key(a.seed), batch["tokens"])["params"]
    return model, batch, params, bert_pretrain_loss_fn(model)


def print_separator(message: str) -> None:
    """``testing/commons.py :: print_separator``."""
    print(f"{' ' + message + ' ':-^72}")
