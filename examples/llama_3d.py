"""Llama under full 3D parallelism — dp × pp × tp (+ SP), the BASELINE
config-4 composition (`apex1_tpu.models.llama_3d`) as a runnable loop.

One `shard_map` train step: Megatron TP+SP blocks inside a scan+ppermute
pipeline (optionally interleaved, ``--chunks 2``), vocab-parallel
embedding + fused LM-head CE with embedding-group grad combination,
fused Adam on fp32 masters. ``JAX_PLATFORMS=cpu`` runs a tiny model on
the virtual CPU mesh; without it the live devices run the step (the
layout must cover exactly the host's chips); the same code compiles for
a v5p-32 class topology at 8B (`tools/aot_check.py --flagship`).

Two ways to pick the parallel layout:

- by hand: ``--dp 2 --pp 2 --tp 2`` etc. — every axis flag is
  validated against `apex1_tpu.planner.check_layout` BEFORE anything
  compiles, and an illegal combination exits loudly NAMING the broken
  rule (tp not dividing heads, pp exceeding layers, ...) instead of
  failing deep inside `shard_map`;
- by search: ``--plan auto`` hands the same model to the
  auto-parallel planner (`apex1_tpu.planner`), which enumerates the
  legal layouts for ``--devices`` chips, prices them with the
  calibrated cost model, and drives this loop from the winning plan —
  whose partition rules are verified against the model's own specs
  before training starts. ``--plan <path>`` replays a banked plan
  document instead of searching.

With ``--ckpt-dir`` the loop runs under the resilient runtime
(`apex1_tpu.resilience`, docs/robustness.md): every checkpoint banks
its producing ``apex1-plan-v1`` spec (hand layouts are turned into a
stated plan via `planner.plan_for_layout`, so EVERY checkpoint is
self-describing and reshardable), ``--resume auto`` continues from
the newest valid checkpoint (per-step-seeded batches ⇒ the data
position is one int in the manifest meta), a SIGTERM preemption hook
banks a final sync checkpoint and exits 75
(``APEX1_CHAOS_SIGTERM_STEP=<n>`` self-injects the kill), and
``--elastic`` survives a CHANGED fleet: on relaunch with a different
``--devices``, `resilience.elastic_resume` re-plans the surviving
chip count with the planner, reshards the checkpoint
(manifest-verified), and resumes — the checkpoint's banked plan, not
the axis flags, is the authority for the model.

``python examples/llama_3d.py [--dp 2 --pp 2 --tp 2] [--chunks 2]``
``python examples/llama_3d.py --plan auto [--devices 8]``
``python examples/llama_3d.py --elastic --ckpt-dir /tmp/ck --devices 4``
"""

import argparse
import json
import os
import sys
import time

# (nothing here may initialise a backend: under JAX_PLATFORMS=cpu this
# example calls force_virtual_cpu_devices in main, which must win the
# first backend init — an early default_backend() probe would pin 1 CPU
# device)
_root = (os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
         if "__file__" in globals() else os.getcwd())
sys.path.insert(0, _root)

from apex1_tpu import planner  # noqa: E402
from apex1_tpu.testing import force_virtual_cpu_devices  # noqa: E402


def _model_shape(args) -> planner.ModelShape:
    """The planner's view of the tiny example model — dims mirror the
    LlamaConfig.tiny(...) construction below (heads/kv fixed 4/2)."""
    return planner.ModelShape(
        name="llama3d-example", num_layers=args.layers,
        hidden_size=args.hidden, ffn_size=2 * args.hidden,
        num_heads=4, num_kv_heads=2, head_dim=args.hidden // 4,
        vocab_size=args.vocab, seq_len=args.seq,
        global_batch=args.microbatches * args.dp * args.ep,
        num_experts=4 if args.moe else 0, moe_top_k=2)


def _validate_hand_layout(args) -> None:
    """The satellite fix: the hand axis flags used to be checked only
    as a device product; every other rule surfaced as a shard_map or
    Llama3DConfig traceback. Now the planner's legality predicate
    rejects them up front, one named rule per line, exit 2."""
    layout = planner.Layout(
        dp=args.dp, pp=args.pp, cp=args.cp, ep=args.ep, tp=args.tp,
        num_microbatches=args.microbatches, microbatch_size=1,
        num_chunks=args.chunks, schedule=args.schedule)
    violations = planner.check_layout(_model_shape(args), layout)
    if violations:
        print("ILLEGAL LAYOUT — rejected by apex1_tpu.planner."
              "check_layout before compiling anything:",
              file=sys.stderr, flush=True)
        for v in violations:
            print(f"  [{v.rule}] {v.message}", file=sys.stderr,
                  flush=True)
        print("(see docs/planner.md for the rule catalogue; "
              "`--plan auto` searches only legal layouts)",
              file=sys.stderr, flush=True)
        sys.exit(2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--pp", type=int, default=2)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--chunks", type=int, default=1)
    ap.add_argument("--cp", type=int, default=1,
                    help="context parallel (ring attention seq shards)")
    ap.add_argument("--ep", type=int, default=1,
                    help="expert parallel (implies --moe)")
    ap.add_argument("--moe", action="store_true",
                    help="every FFN expert-routed (4 experts, top-2)")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--schedule", default="scan",
                    choices=("scan", "1f1b"),
                    help="pipeline schedule: scan (remat) or the true "
                         "staggered-fwd/bwd 1F1B (interleaved with "
                         "--chunks > 1)")
    ap.add_argument("--plan", default=None, metavar="auto|PATH",
                    help="'auto': search dp x pp x cp x ep x tp with "
                         "the calibrated planner instead of the axis "
                         "flags; PATH: replay a banked plan.json")
    ap.add_argument("--devices", type=int, default=None,
                    help="chip count for --plan auto / --elastic "
                         "(default: the product of the axis flags)")
    ap.add_argument("--seed", type=int, default=0,
                    help="data seed: batch i is a pure function of "
                         "(seed, i), so resume is exact")
    ap.add_argument("--ckpt-dir", default=None,
                    help="enable the resilient runtime: plan-banking "
                         "checkpoints + preemption hook + resume")
    ap.add_argument("--ckpt-every", type=int, default=2)
    ap.add_argument("--resume", default="auto", choices=("auto",
                                                         "never"))
    ap.add_argument("--elastic", action="store_true",
                    help="on relaunch, survive a changed --devices: "
                         "planner re-plan + manifest-verified "
                         "checkpoint reshard (needs --ckpt-dir)")
    args = ap.parse_args()
    if args.ep > 1:
        args.moe = True
    if args.elastic and not args.ckpt_dir:
        print("--elastic requires --ckpt-dir", file=sys.stderr,
              flush=True)
        sys.exit(2)

    elastic_src = None
    if args.elastic:
        from apex1_tpu.resilience import find_restorable

        elastic_src = find_restorable(args.ckpt_dir)

    plan = None
    if elastic_src is not None:
        # elastic relaunch: the checkpoint's banked plan is the
        # authority for the model AND the layout; the re-plan happens
        # after the backend comes up (the reshard needs arrays)
        n = args.devices or (args.dp * args.pp * args.tp * args.ep
                             * args.cp)
    elif args.plan:
        n = args.devices or (args.dp * args.pp * args.tp * args.ep
                             * args.cp)
        if args.plan == "auto":
            # zero stays off: the example's step shards optimizer
            # state like params (GSPMD); the dp-axis ZeRO split is
            # priced for 8B-scale plans, not exercised by this loop
            plan = planner.make_plan(_model_shape(args), n,
                                     allow_zero=False)
        else:
            plan = planner.load_plan(args.plan)
            n = plan["n_devices"]
            # a replayed plan must price THIS model: the schedule and
            # partition rules are only valid for the dims it priced
            mismatch = planner.check_plan_model(plan,
                                                _model_shape(args))
            if mismatch:
                raise SystemExit(
                    "plan/model mismatch — this plan was searched for "
                    "a different model than the flags describe:\n  "
                    + "\n  ".join(mismatch))
        m, sch = plan["mesh"], plan["schedule"]
        args.dp, args.pp, args.tp = m["dp"], m["pp"], m["tp"]
        args.cp, args.ep = m["cp"], m["ep"]
        args.microbatches = sch["num_microbatches"]
        args.chunks = sch["num_chunks"]
        args.schedule = sch["kind"]
        args.moe = args.moe or bool(plan["model"].get("num_experts"))
        pr = plan["predicted"]
        print(f"plan: mesh dp={m['dp']} pp={m['pp']} cp={m['cp']} "
              f"ep={m['ep']} tp={m['tp']} M={sch['num_microbatches']} "
              f"sp={plan['kernel_flags']['sp_boundary']} — "
              f"{pr['calibrated_step_ms']:.3f} ms/step calibrated "
              f"[{pr['calibration']['source']}], "
              f"{plan['search']['n_enumerated']} layouts searched, "
              f"{plan['search']['n_hbm_rejected']} over HBM",
              flush=True)
        if plan["zero"]["enabled"]:
            print("note: plan prices ZeRO optimizer sharding; this "
                  "example runs the GSPMD param-sharded default "
                  "(consumer: parallel.distributed_optimizer)",
                  flush=True)
    else:
        _validate_hand_layout(args)
        n = args.dp * args.pp * args.tp * args.ep * args.cp
    # JAX_PLATFORMS=cpu builds the n-device virtual CPU mesh; otherwise
    # the live devices run the step — on a TPU host the layout must
    # cover exactly its chips (make_mesh raises if it does not)
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        force_virtual_cpu_devices(max(n, 2))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.llama import LlamaConfig
    from apex1_tpu.models.llama_3d import (Llama3DConfig,
                                           chunk_param_specs,
                                           make_train_step,
                                           shared_param_specs,
                                           state_template)

    def mcfg_from_plan(p):
        """LlamaConfig for a plan's banked model dims — the elastic
        path's model authority (mirrors the flag-driven construction
        below; the plan carries dims, not the precision policy)."""
        pm = p["model"]
        kw = (dict(moe_every=1, num_experts=pm["num_experts"],
                   moe_top_k=pm["moe_top_k"], moe_capacity_factor=2.0)
              if pm.get("num_experts") else {})
        return LlamaConfig.tiny(
            num_layers=pm["num_layers"], max_seq_len=pm["seq_len"],
            vocab_size=pm["vocab_size"], num_heads=pm["num_heads"],
            num_kv_heads=pm["num_kv_heads"],
            hidden_size=pm["hidden_size"], ffn_size=pm["ffn_size"],
            policy=get_policy("O2"), **kw)

    decision = None
    if elastic_src is not None:
        from apex1_tpu.resilience.elastic import elastic_resume

        def make_template(p):
            return state_template(planner.llama3d_config_from_plan(
                p, mcfg_from_plan(p), learning_rate=3e-3,
                ignore_zero=True))

        from apex1_tpu.resilience import LayoutMismatch

        try:
            decision = elastic_resume(args.ckpt_dir, n_devices=n,
                                      make_template=make_template,
                                      planner_kw={"allow_zero": False})
        except (LayoutMismatch, planner.PlanError) as e:
            # e.g. a pre-elastic checkpoint without plan meta, or no
            # legal layout for the surviving chip count: the typed
            # message says what to do — no traceback needed
            print(str(e), file=sys.stderr, flush=True)
            sys.exit(2)
        plan = decision.plan
        m, sch = plan["mesh"], plan["schedule"]
        args.dp, args.pp, args.tp = m["dp"], m["pp"], m["tp"]
        args.cp, args.ep = m["cp"], m["ep"]
        args.microbatches = sch["num_microbatches"]
        args.chunks, args.schedule = sch["num_chunks"], sch["kind"]
        pm = plan["model"]
        args.layers, args.hidden = pm["num_layers"], pm["hidden_size"]
        args.seq, args.vocab = pm["seq_len"], pm["vocab_size"]
        args.moe = bool(pm.get("num_experts"))
        if decision.resharded:
            rep = decision.report
            print(f"elastic: fleet {decision.old_plan['n_devices']} "
                  f"-> {n} devices; re-planned and resharded "
                  f"({rep['n_restacked']} restacked / "
                  f"{rep['n_repacked']} repacked / {rep['n_copied']} "
                  f"copied leaves, digest-verified) -> "
                  f"{decision.path}", flush=True)
        else:
            print(f"elastic: fleet unchanged ({n} devices); plain "
                  f"resume from {decision.path}", flush=True)

    moe_kw = (dict(moe_every=1, num_experts=4, moe_top_k=2,
                   moe_capacity_factor=2.0) if args.moe else {})
    mcfg = (mcfg_from_plan(plan) if decision is not None
            else LlamaConfig.tiny(
                num_layers=args.layers, max_seq_len=args.seq,
                vocab_size=args.vocab, num_heads=4, num_kv_heads=2,
                hidden_size=args.hidden, ffn_size=2 * args.hidden,
                policy=get_policy("O2"), **moe_kw))
    if plan is not None:
        # ignore_zero: the note above told the user this loop runs the
        # unsharded optimizer; at tiny example scale that always fits
        cfg = planner.llama3d_config_from_plan(plan, mcfg,
                                               learning_rate=3e-3,
                                               ignore_zero=True)
    else:
        cfg = Llama3DConfig(model=mcfg, dp=args.dp, pp=args.pp,
                            tp=args.tp, cp=args.cp, ep=args.ep,
                            moe=args.moe, num_chunks=args.chunks,
                            num_microbatches=args.microbatches,
                            microbatch_size=1, learning_rate=3e-3,
                            schedule=args.schedule)
    step, state, _ = make_train_step(cfg)
    if plan is not None:
        # the emitted regex rules must reproduce the model's own
        # hand-written specs leaf-for-leaf — a plan that drifts from
        # the model is caught HERE, not as a wrong-layout slowdown
        got = planner.plan_param_specs(plan, state["params"])
        cspecs = chunk_param_specs(cfg)
        want = {"chunk": {k: cspecs[k]
                          for k in state["params"]["chunk"]},
                "shared": shared_param_specs()}
        if got != want:
            raise SystemExit(
                f"plan partition rules drifted from "
                f"models.llama_3d specs:\n got {got}\nwant {want}")
        print("plan verified: partition rules reproduce "
              "models.llama_3d specs", flush=True)
    mb_cols = cfg.microbatch_size * cfg.dp * cfg.ep
    global_batch = cfg.num_microbatches * mb_cols

    def batch_at(i):
        # batch i is a pure function of (seed, i), drawn in a
        # CANONICAL (global_batch, seq) layout and regrouped as
        # sequence g = m*B + b -> tokens[m, :, b]. An elastic re-plan
        # that changes the (M, B) factorization therefore still
        # trains the SAME sequences at step i — only the microbatch
        # grouping changes — and the checkpoint's data position stays
        # one int. (A layout-shaped draw would regroup the flat RNG
        # stream into different sequences.)
        r = np.random.default_rng([args.seed, i])
        canon = r.integers(0, args.vocab, (global_batch, args.seq))
        toks = canon.reshape(cfg.num_microbatches, mb_cols,
                             args.seq).transpose(0, 2, 1)
        tokens = jnp.asarray(toks, jnp.int32)
        return tokens, jnp.roll(tokens, -1, axis=1)

    ck = None
    pre = None
    start = 0
    if args.ckpt_dir:
        from apex1_tpu.resilience import (LayoutMismatch,
                                          PreemptionHandler,
                                          ResilientCheckpointer)
        from apex1_tpu.testing.chaos import sigterm_self_at

        if plan is not None:
            bank_plan = plan
            if plan.get("zero", {}).get("enabled"):
                # the banked spec must describe the STATE AS SAVED:
                # this loop runs the UNSHARDED optimizer
                # (ignore_zero=True above), so banking the plan's
                # zero flag verbatim would make a later elastic
                # re-plan require a ZeRO layout the checkpoint does
                # not have
                bank_plan = json.loads(json.dumps(plan))
                bank_plan["zero"]["enabled"] = False
                bank_plan["zero"]["note"] = (
                    "disabled at banking: the llama_3d loop ran the "
                    "unsharded optimizer (ignore_zero=True)")
        else:
            # hand layout: bank the STATED plan so every checkpoint
            # is self-describing and reshardable (the elastic
            # relaunch reads it, never the axis flags)
            bank_plan = planner.plan_for_layout(
                _model_shape(args),
                planner.Layout(dp=args.dp, pp=args.pp, cp=args.cp,
                               ep=args.ep, tp=args.tp,
                               num_microbatches=args.microbatches,
                               num_chunks=args.chunks,
                               schedule=args.schedule))
        ck = ResilientCheckpointer(args.ckpt_dir, keep=3,
                                   plan=bank_plan)
        pre = PreemptionHandler()
        chaos_at = os.environ.get("APEX1_CHAOS_SIGTERM_STEP")
        chaos_at = int(chaos_at) if chaos_at else None
        if decision is not None:
            state, man = ck.restore(template=state,
                                    path=decision.path)
            start = int(man.meta.get("data_step", 0))
            print(f"elastic resume at data step {start} "
                  f"(checkpoint step {man.step}, every leaf "
                  f"digest-verified)", flush=True)
        elif args.resume == "auto" and ck.latest_valid() is not None:
            try:
                state, man = ck.restore(template=state)
            except LayoutMismatch as e:
                print(f"{e}\n(hint: relaunch with --elastic to "
                      f"re-plan and reshard for the new layout)",
                      file=sys.stderr, flush=True)
                sys.exit(2)
            start = int(man.meta.get("data_step", man.step))
            print(f"resumed from step {man.step} "
                  f"(data step {start})", flush=True)

    print(f"mesh dp={cfg.dp} pp={cfg.pp} tp={cfg.tp} ep={cfg.ep} "
          f"cp={cfg.cp} "
          f"chunks={cfg.num_chunks} moe={cfg.moe} ({n} devices), "
          f"{args.layers}L x {args.hidden}h", flush=True)
    t0 = time.time()
    if pre is not None:
        pre.install()
    try:
        for i in range(start, args.steps):
            tokens, labels = batch_at(i)
            state, loss = step(state, tokens, labels)
            if i % 5 == 0 or i == args.steps - 1:
                print(f"step {i:3d}  loss {float(loss):.4f}",
                      flush=True)
            if ck is not None:
                if (i + 1) % args.ckpt_every == 0 or i + 1 == args.steps:
                    ck.save(int(state["step"]), state,
                            meta={"data_step": i + 1})
                sigterm_self_at(i + 1, chaos_at)
                if pre.triggered:
                    ck.wait()   # let the in-flight async save commit
                    ck.save_sync(int(state["step"]), state,
                                 meta={"data_step": i + 1,
                                       "preempted": True})
                    pre.exit_resumable(
                        f"preempted at data step {i + 1}")
        if ck is not None:
            ck.wait()
            ck.close()
    finally:
        if pre is not None:
            pre.uninstall()
    jax.block_until_ready(state)
    # where the state lives, and whether every replicated copy of a
    # shard (same index, different device) still agrees after the steps
    homes, agree = set(), True
    for leaf in jax.tree_util.tree_leaves(state):
        copies = {}
        for s in leaf.addressable_shards:
            homes.add(s.device)
            first = copies.setdefault(str(s.index), np.asarray(s.data))
            agree &= np.array_equal(first, np.asarray(s.data))
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    print(f"done in {time.time() - t0:.1f}s "
          f"(step counter = {int(state['step'])}); state shards on "
          f"{len(homes)} of {jax.device_count()} "
          f"{jax.devices()[0].platform} devices, replicated copies agree: "
          f"{agree}, peak bytes in use per device {peaks}")


if __name__ == "__main__":
    main()
