"""apex1_tpu — a TPU-native acceleration framework with the capabilities of
NVIDIA Apex (reference: mbrukman/apex-1).

This is NOT a port: the reference is a CUDA/C++/torch bolt-on library; this
package is a JAX/XLA/Pallas-first redesign of the same capability surface:

- ``apex1_tpu.amp``          — mixed-precision policies O0-O3, dynamic loss
                               scaling (reference: ``apex/amp``)
- ``apex1_tpu.optim``        — fused optimizers: Adam/LAMB/SGD/NovoGrad/
                               Adagrad, LARC, clip_grad (``apex/optimizers``,
                               ``apex/contrib/clip_grad``)
- ``apex1_tpu.ops``          — Pallas kernels: layer/RMS norm, scaled-masked
                               softmax, fused cross-entropy, RoPE, flash
                               attention, fused dense/MLP (``csrc/``,
                               ``apex/contrib/{fmha,multihead_attn,xentropy,
                               layer_norm}``)
- ``apex1_tpu.parallel``     — DDP-equivalent gradient sync, SyncBatchNorm,
                               ZeRO-style sharded optimizers
                               (``apex/parallel``, ``apex/contrib/optimizers``)
- ``apex1_tpu.transformer``  — tensor/pipeline/sequence parallelism over a
                               ``jax.sharding.Mesh`` (``apex/transformer``)
- ``apex1_tpu.models``       — reference model families used by the baseline
                               configs: GPT-2, BERT, Llama-3, ResNet-50
- ``apex1_tpu.runtime``      — C++ host-side runtime: pinned flat-buffer
                               packing and a prefetching data loader
                               (``csrc/flatten_unflatten.cpp``, examples'
                               loader)

Citations in docstrings use the survey convention ``path :: Symbol`` against
the upstream apex layout (see SURVEY.md §0 — the reference mount was empty at
survey time, so symbol anchors are the citation unit).
"""

__version__ = "0.1.0"


from apex1_tpu.core import mesh, policy, loss_scale  # noqa: F401
from apex1_tpu.core.mesh import (MeshConfig, make_hybrid_mesh,  # noqa: F401
                                 make_mesh)
from apex1_tpu.core.policy import PrecisionPolicy, get_policy  # noqa: F401
