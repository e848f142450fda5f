"""GPT-2 with a TIED embedding/LM-head split across pipeline stages —
the reference's embedding-group flow (`parallel_state
:: initialize_model_parallel` builds {first, last}-stage groups; the
schedules all-reduce tied word-embedding grads after each pipeline
step, SURVEY §3.4).

Mesh-native form: ONE shard_mapped train step over a pp mesh —
`schedules.pipeline_tied_apply` routes the tied table (embed on stage
0, LM head on stage P−1, partial-loss convention) and
`schedules.allreduce_embedding_grads` is the embedding-group
all-reduce. Transformer blocks are the pipeline stages.

``python examples/gpt2_pp_tied.py [--pp 4] [--steps 20] [--seq 64]``
(runs on the virtual CPU mesh; pass a real mesh size on hardware)
"""

import argparse
import os
import sys

# direct `python examples/...` puts examples/ (not the repo root) on the
# path; the smoke harness exec()s the source with no __file__ at all
# (nothing here may initialise a backend: under JAX_PLATFORMS=cpu this
# example calls force_virtual_cpu_devices in main, which must win the
# first backend init — an early default_backend() probe would pin 1 CPU
# device)
_root = (os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
         if "__file__" in globals() else os.getcwd())
sys.path.insert(0, _root)

from apex1_tpu.testing import force_virtual_cpu_devices  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pp", type=int, default=4)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--mb", type=int, default=2)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()
    # JAX_PLATFORMS=cpu builds the virtual CPU mesh; otherwise the live
    # devices run the pipeline (--pp must equal their count)
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        force_virtual_cpu_devices(max(args.pp, 2))

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as Ps

    from apex1_tpu.core.mesh import make_mesh
    from apex1_tpu.ops import (layer_norm,
                               scaled_upper_triang_masked_softmax,
                               softmax_cross_entropy_loss)
    from apex1_tpu.optim.fused_adam import FusedAdamState, fused_adam
    from apex1_tpu.transformer.pipeline_parallel import schedules

    P_, L, E, H = args.pp, args.layers, args.hidden, args.heads
    V, mb, M, S = args.vocab, args.mb, args.microbatches, args.seq
    assert L % P_ == 0, "--layers must divide by --pp"
    lps = L // P_
    D = E // H
    mesh = make_mesh(pp=P_)
    rng = np.random.default_rng(0)

    def w(*shape, scale=0.02):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    # per-stage transformer-block params, chunk-major (V=1, P, lps, ...)
    chunk = {
        "ln1_g": jnp.ones((1, P_, lps, E)), "ln1_b": jnp.zeros((1, P_, lps, E)),
        "wqkv": w(1, P_, lps, E, 3 * E), "wo": w(1, P_, lps, E, E),
        "ln2_g": jnp.ones((1, P_, lps, E)), "ln2_b": jnp.zeros((1, P_, lps, E)),
        "w1": w(1, P_, lps, E, 4 * E), "w2": w(1, P_, lps, 4 * E, E),
    }
    tied = {"wte": w(V, E), "wpe": w(S, E, scale=0.01)}

    def block(x, p):  # x: (mb, S, E)
        h = layer_norm(x, p["ln1_g"], p["ln1_b"])
        qkv = (h @ p["wqkv"]).reshape(mb, S, 3, H, D)
        q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
        s_ = jnp.einsum("bhqd,bhkd->bhqk", q, k)
        a = scaled_upper_triang_masked_softmax(s_, scale=1.0 / np.sqrt(D))
        o = jnp.einsum("bhqk,bhkd->bhqd", a, v)
        x = x + o.transpose(0, 2, 1, 3).reshape(mb, S, E) @ p["wo"]
        h = layer_norm(x, p["ln2_g"], p["ln2_b"])
        return x + jax.nn.gelu(h @ p["w1"]) @ p["w2"]

    def stage_fn(p_stage, x):
        for j in range(lps):
            x = block(x, jax.tree.map(lambda l, j=j: l[j], p_stage))
        return x

    def embed_fn(tied, tokens):  # (mb, S) -> (mb, S, E)
        return tied["wte"][tokens] + tied["wpe"][None]

    def make_head_fn(labels):
        def head_fn(tied, outs):  # (M, mb, S, E) -> (M,) mean CE
            logits = jnp.einsum("mbse,ve->mbsv", outs, tied["wte"])
            ce = softmax_cross_entropy_loss(
                logits[:, :, :-1].reshape(M * mb, S - 1, V),
                labels.reshape(M * mb, S)[:, 1:])
            return jnp.mean(ce.reshape(M, -1), axis=1)
        return head_fn

    tx = fused_adam(1e-3)
    params = {"chunk": chunk, "tied": tied}
    state = {"params": params, "opt": tx.init(params)}
    cspecs = jax.tree.map(lambda _: Ps(None, "pp"), chunk)
    pspecs = {"chunk": cspecs, "tied": {"wte": Ps(), "wpe": Ps()}}
    sspecs = {"params": pspecs,
              "opt": FusedAdamState(step=Ps(), exp_avg=pspecs,
                                    exp_avg_sq=pspecs)}

    def train_step(state, tokens):
        def scalar(params):
            local = jax.tree.map(lambda p: p[:, 0], params["chunk"])
            per_mb = schedules.pipeline_tied_apply(
                stage_fn, local, embed_fn, make_head_fn(tokens),
                params["tied"], tokens, broadcast_outputs=False)
            return jnp.mean(per_mb)  # PARTIAL over pp

        loss_part, grads = jax.value_and_grad(scalar)(state["params"])
        loss = jax.lax.psum(loss_part, "pp")
        # the embedding-group all-reduce: tied grads live on stage 0
        # (embedding use) and stage P-1 (head use); middle stages: zeros
        grads["tied"] = schedules.allreduce_embedding_grads(grads["tied"])
        updates, new_opt = tx.update(grads, state["opt"], state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        return {"params": new_params, "opt": new_opt}, loss

    tokens = jnp.asarray(rng.integers(0, V, (M, mb, S)), jnp.int32)
    # next-token targets come from the SAME tokens argument (shift inside
    # head_fn), so a new batch per step scores against its own labels

    step = jax.jit(jax.shard_map(
        train_step, mesh=mesh, in_specs=(sspecs, Ps()),
        out_specs=(sspecs, Ps()), check_vma=False), donate_argnums=0)

    for i in range(args.steps):
        state, loss = step(state, tokens)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  loss {float(loss):.4f}", flush=True)
    print("tied-embedding pipeline OK (embedding-group grads combined "
          f"across {P_} stages)")


if __name__ == "__main__":
    main()
