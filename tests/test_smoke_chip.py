"""`chip_smoke.py`'s phases, run tiny on the virtual CPU mesh — the same
functions the chip run drives at GPT-2 125M — and the command line's
refusal to pass without a TPU."""

import os
import pathlib
import subprocess
import sys

import jax
import pytest

from apex1_tpu.core.policy import get_policy
from apex1_tpu.models.gpt2 import GPT2Config

_REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_REPO))

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def model():
    return chip_smoke.make_model(GPT2Config.tiny(policy=get_policy("O2")))


@pytest.fixture(scope="module")
def trained(model):
    # on CPU the composites run: no tpu_custom_call to require
    return chip_smoke.phase_train(model, batch=2, seq=32, steps=3,
                                  require_kernels=False)


def test_train_phase(trained, model):
    leaves = jax.tree_util.tree_leaves(trained)
    assert leaves and all(x.dtype == model.cfg.policy.compute_dtype
                          for x in leaves)


def test_serve_phase_dense_and_paged(model, trained, capsys):
    chip_smoke.phase_serve(model, trained, max_slots=2, max_len=64,
                           prefill_chunk=8, n_requests=3, new_tokens=6,
                           prompt_lens=(5, 9, 17))
    out = capsys.readouterr().out
    # the CPU guarantees the chip run is compared against: paged, dense
    # and solo-generate streams are bit-identical off-TPU
    assert "dense vs paged — 3/3 streams identical" in out
    assert "dense vs solo generate — 3/3 streams identical" in out
    assert "paged vs solo generate — 3/3 streams identical" in out
    assert out.count("pool donated") == 2


def test_ddp_phase_on_four_devices(model, devices):
    chip_smoke.phase_ddp(model, devices[:4], per_chip_batch=1, seq=32,
                         steps=3, require_kernels=False)


def test_train_phase_requires_kernels_by_default(model):
    with pytest.raises(AssertionError, match="no tpu_custom_call"):
        chip_smoke.phase_train(model, batch=2, seq=32, steps=1)


def test_command_line_fails_without_a_tpu():
    """No option lets it pass on CPU: non-zero exit, the device named
    first, and no result line."""
    r = subprocess.run(
        [sys.executable, str(_REPO / "chip_smoke.py")], cwd=_REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.startswith("chip_smoke: platform=cpu")
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr
