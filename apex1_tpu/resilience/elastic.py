"""Elastic resume — survive a mesh shrink/grow by re-planning on
purpose: detect the surviving device count, ask the planner
(`apex1_tpu.planner.make_plan`) for a fresh legal layout, reshard the
newest restorable checkpoint onto it (`resilience.reshard`,
manifest-verified end-to-end), and hand the training loop a plan it
can rebuild from.

This is the bridge ISSUE 14 names between PR 6 (bit-exact
single-topology resume: the manifest fingerprint rightly REFUSES a
silently changed program) and PR 12 (the planner knows a legal
dp×tp×pp×cp×ep for any chip count): the path that changes the
program ON PURPOSE, with every decision banked.

EVIDENCE DISCIPLINE (the PR 13 rule — an episode must be
reconstructable from banked telemetry alone): every decision emits an
obs-spine event (`apex1_tpu.obs.spine`, inert without
``APEX1_OBS_DIR``):

- ``elastic.detect``  — surviving device count, the checkpoint found,
  its step/data_step, its banked layout;
- ``elastic.replan``  — old and new plan specs (mesh strings + the
  full layout-identity `planner.plan_spec` dicts), the search size,
  the calibrated price of the pick;
- ``elastic.reshard`` — leaf counts per remap class
  (restacked/repacked/copied) and the output path;
- ``elastic.verify``  — the digest verdicts: source files + leaves
  verified, remap conservation checks, fresh tree digest count;
- ``elastic.resume``  — the path the loop should restore, and whether
  a reshard happened at all (same-layout relaunches take the plain
  resume path, banked as such).

THE DRILL (`drill`, ``python -m apex1_tpu.resilience.elastic
--drill`` = check_all's ``== elastic drill ==``, also pinned tier-1
in tests/test_elastic.py): train a tiny llama_3d on an 8-device CPU
mesh under a stated dp2·pp2·tp2 plan, kill it mid-run at a
seed-keyed step (`chaos.shrink_schedule` — committed checkpoints up
to the kill, in-flight work lost), then resume in a FRESH PROCESS
that owns exactly 4 devices — what a real relaunch on a shrunken
fleet is — through `elastic_resume` (planner re-plan + reshard), and
run a CONTROL there: an independent second reshard of the same
checkpoint (byte-identical leaf digests — the determinism pin)
restored into a fresh 4-device state and trained on the same banked
data order. The elastic leg's loss trajectory and final params must
match the control BIT-EXACTLY, and the episode summary is re-derived
in the parent from the spine events alone (both processes bank into
one obs dir) and checked against the leg's ground truth. What the
CPU drill does NOT prove: silicon wall-clock and real multi-host
orchestration — the ``--real`` in-process form (a TPU job cannot
boot a second process against chips it holds) carries that claim
once it runs on chips (docs/robustness.md
§ Elastic resume).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Optional

from apex1_tpu.checkpoint import CheckpointError
from apex1_tpu.resilience.checkpointer import (find_restorable,
                                               step_dir_name)
from apex1_tpu.resilience.manifest import Manifest, verify_files
from apex1_tpu.resilience.reshard import (LayoutMismatch, mesh_str,
                                          plan_meta, reshard_checkpoint)


@dataclasses.dataclass
class ElasticDecision:
    """What `elastic_resume` decided, with the evidence attached.
    ``path`` is the directory the loop should restore (the resharded
    checkpoint, or the source itself when no reshard was needed)."""

    ckpt_dir: str
    source: str                 # the checkpoint that was found
    path: str                   # what to restore from
    old_plan: dict
    plan: dict                  # the plan the resumed loop should run
    resharded: bool
    step: int
    data_step: Optional[int]
    manifest: Manifest          # manifest of `path`
    report: Optional[dict]      # reshard report (None when resharded
    #                             is False)


def elastic_resume(ckpt_dir: str | os.PathLike, *,
                   n_devices: Optional[int] = None,
                   make_template: Callable[[dict], Any],
                   generation: Optional[str] = None,
                   results_dir: Optional[str] = None,
                   out_root: Optional[str] = None,
                   planner_kw: Optional[dict] = None
                   ) -> ElasticDecision:
    """The elastic-resume driver. Finds the newest restorable
    checkpoint under ``ckpt_dir``, reads its banked producing plan
    (typed :class:`LayoutMismatch` when absent), and:

    - same device count ⇒ plain resume (``resharded=False``, the
      source path);
    - different count ⇒ ``planner.make_plan(model_shape, n_devices)``
      for a fresh legal plan, then a manifest-verified reshard of the
      checkpoint onto it.

    ``make_template(plan) -> host state pytree`` builds the SOURCE
    plan's state template (e.g. `models.llama_3d.state_template` of
    the plan-derived config) — mesh-free, so it works on the shrunken
    fleet. ``n_devices`` defaults to ``len(jax.devices())`` (detect
    the surviving fleet). ``planner_kw`` forwards to ``make_plan``;
    ``require_zero`` defaults to the SOURCE plan's zero setting — the
    re-plan searches ONLY layouts with the same optimizer-shard
    structure, because flipping it is a state-structure change the
    reshard refuses (no legal matching layout ⇒ a loud PlanError).
    Every decision is banked as an obs-spine event (module
    docstring)."""
    from apex1_tpu.obs import spine

    ckpt_dir = os.fspath(ckpt_dir)
    if n_devices is None:
        import jax

        n_devices = len(jax.devices())
    src = find_restorable(ckpt_dir)
    if src is None:
        raise CheckpointError(ckpt_dir,
                              "no valid checkpoint to resume from")
    man = verify_files(src)
    old_plan = plan_meta(man, src)
    data_step = man.meta.get("data_step")
    spine.emit("event", "elastic.detect", n_devices=int(n_devices),
               ckpt=src, step=int(man.step), data_step=data_step,
               mesh=mesh_str(old_plan),
               banked_devices=old_plan.get("n_devices"))

    if int(old_plan.get("n_devices", -1)) == int(n_devices):
        spine.emit("event", "elastic.resume", resharded=False,
                   path=src, mesh=mesh_str(old_plan),
                   step=int(man.step), data_step=data_step)
        return ElasticDecision(
            ckpt_dir=ckpt_dir, source=src, path=src,
            old_plan=old_plan, plan=old_plan, resharded=False,
            step=int(man.step), data_step=data_step, manifest=man,
            report=None)

    from apex1_tpu import planner

    shape = planner.model_shape_from_plan(old_plan)
    kw = dict(planner_kw or {})
    kw.setdefault("require_zero",
                  bool(old_plan.get("zero", {}).get("enabled")))
    gen = generation or old_plan.get("generation") or "v5e"
    new_plan = planner.make_plan(shape, int(n_devices), generation=gen,
                                 results_dir=results_dir, **kw)
    spine.emit("event", "elastic.replan",
               old_mesh=mesh_str(old_plan), new_mesh=mesh_str(new_plan),
               old_spec=planner.plan_spec(old_plan),
               new_spec=planner.plan_spec(new_plan),
               n_enumerated=new_plan["search"]["n_enumerated"],
               calibrated_step_ms=new_plan["predicted"]
               ["calibrated_step_ms"])

    root = out_root or os.path.join(ckpt_dir, "elastic")
    out_dir = os.path.join(
        root, f"{step_dir_name(man.step)}_to{int(n_devices)}dev")
    out_path, new_man, report = reshard_checkpoint(
        src, make_template(old_plan), new_plan, out_dir, manifest=man)
    spine.emit("event", "elastic.reshard", src=src, out=out_path,
               n_leaves=report["n_leaves"],
               n_restacked=report["n_restacked"],
               n_repacked=report["n_repacked"],
               n_copied=report["n_copied"],
               stack_from=report["stack_from"],
               stack_to=report["stack_to"])
    spine.emit("event", "elastic.verify", path=out_path,
               source_verified=True, files_verified=True,
               conserved=report["conserved"],
               n_conservation_checks=report["n_checks"],
               n_tree_digests=len(new_man.tree))
    spine.emit("event", "elastic.resume", resharded=True,
               path=out_path, mesh=mesh_str(new_plan),
               step=int(new_man.step), data_step=data_step)
    return ElasticDecision(
        ckpt_dir=ckpt_dir, source=src, path=out_path,
        old_plan=old_plan, plan=new_plan, resharded=True,
        step=int(new_man.step), data_step=data_step, manifest=new_man,
        report=report)


# -- the acceptance drill ---------------------------------------------------

def _drill_fixture(seed: int):
    """The drill's model/config constants, shared by BOTH sides of
    the process boundary (the n_from-device trainer and the
    n_to-device resume leg), so the two provably describe the same
    job. Returns ``(shape, cfg_of, make_template, batch_at)``."""
    from apex1_tpu import planner
    from apex1_tpu.core.policy import get_policy
    from apex1_tpu.models.llama import LlamaConfig

    hidden, seq, vocab, layers = 64, 32, 128, 4
    shape = planner.ModelShape(
        name="elastic-drill", num_layers=layers, hidden_size=hidden,
        ffn_size=2 * hidden, num_heads=4, num_kv_heads=2,
        head_dim=hidden // 4, vocab_size=vocab, seq_len=seq,
        global_batch=8)
    mcfg = LlamaConfig.tiny(
        num_layers=layers, max_seq_len=seq, vocab_size=vocab,
        num_heads=4, num_kv_heads=2, hidden_size=hidden,
        ffn_size=2 * hidden, policy=get_policy("O2"))

    def cfg_of(plan):
        return planner.llama3d_config_from_plan(plan, mcfg,
                                                learning_rate=3e-3,
                                                ignore_zero=True)

    def make_template(plan):
        from apex1_tpu.models.llama_3d import state_template

        return state_template(cfg_of(plan))

    def batch_at(i, cfg):
        # canonical (global_batch, seq) draw regrouped per the
        # layout's (M, B) factorization (sequence g = m*B + b), so
        # the pre-kill and post-reshard layouts train the SAME
        # sequences at step i — the "same data order" half of the
        # drill's claim (mirrors examples/llama_3d.py batch_at)
        import jax.numpy as jnp
        import numpy as np

        rng = np.random.default_rng([seed, i])
        cols = cfg.microbatch_size * cfg.dp * cfg.ep
        canon = rng.integers(
            0, vocab,
            (cfg.num_microbatches * cols, seq)).astype(np.int32)
        toks = canon.reshape(cfg.num_microbatches, cols,
                             seq).transpose(0, 2, 1)
        return jnp.asarray(toks), jnp.asarray(np.roll(toks, -1,
                                                      axis=1))

    return shape, cfg_of, make_template, batch_at


def _resume_leg(ckpt_dir: str, work: str, n_to: int, seed: int,
                steps_total: int, devices=None,
                verbose: bool = True) -> dict:
    """Drill phases 2+3: elastic resume on the SHRUNKEN fleet + the
    from-checkpoint control, asserted bit-exact. Runs in the shrunken
    fleet's own process in the tier-1/check_all drill (`drill` spawns
    a fresh n_to-device process — what a real relaunch is); the
    ``--real`` queue entry runs it in-process over ``devices[:n_to]``
    (a TPU job cannot boot a second process against held chips).
    Returns the leg's facts for the parent to cross-check against the
    banked spine events."""
    import jax
    import numpy as np

    from apex1_tpu.checkpoint import restore_checkpoint
    from apex1_tpu.core.mesh import make_mesh
    from apex1_tpu.models import llama_3d as l3d
    from apex1_tpu.resilience.checkpointer import ResilientCheckpointer
    from apex1_tpu.resilience.manifest import tree_entries, verify_tree

    def say(msg):
        if verbose:
            print(f"[elastic drill] {msg}", flush=True)

    # tiny compiles, zero cache value — and on XLA:CPU,
    # RELOADING a persistent-cached executable whose device assignment
    # is a proper subset of the visible devices is unreliable
    # (segfaults reproduced on this image), which the --real in-process
    # path would otherwise hit. Correctness beats cached seconds.
    cache_was = bool(jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        _shape, cfg_of, make_template, batch_at = _drill_fixture(seed)
        decision = elastic_resume(ckpt_dir, n_devices=n_to,
                                  make_template=make_template,
                                  planner_kw={"allow_zero": False})
        assert decision.resharded, \
            "drill expected a layout change; got a same-layout resume"
        plan_a, plan_b = decision.old_plan, decision.plan
        cfg_b = cfg_of(plan_b)
        devs = (list(devices) if devices is not None
                else jax.devices())[:n_to]
        mesh_b = make_mesh(dp=cfg_b.dp, pp=cfg_b.pp, cp=cfg_b.cp,
                           ep=cfg_b.ep, tp=cfg_b.tp, devices=devs)
        step_b, state_b_init, _ = l3d.make_train_step(cfg_b,
                                                      mesh=mesh_b)
        ck_b = ResilientCheckpointer(ckpt_dir, keep=8, plan=plan_b)
        state_e, man_e = ck_b.restore(template=state_b_init,
                                      path=decision.path)
        start = int(man_e.meta["data_step"])
        say(f"phase 2: elastic resume {mesh_str(plan_a)} -> "
            f"{mesh_str(plan_b)} at data step {start} "
            f"({decision.report['n_restacked']} restacked / "
            f"{decision.report['n_copied']} copied leaves, all "
            f"digest-verified)")
        losses_e = []
        for i in range(start, steps_total):
            t, lbl = batch_at(i, cfg_b)
            state_e, loss = step_b(state_e, t, lbl)
            losses_e.append(float(loss))
            ck_b.save(int(state_e["step"]), state_e,
                      meta={"data_step": i + 1})
        ck_b.close()

        # -- the 4-device from-checkpoint CONTROL ----------------------
        # independent second reshard of the same source: byte-identical
        # leaf digests = the determinism pin
        out2, man_c, _rep2 = reshard_checkpoint(
            decision.source, make_template(plan_a), plan_b,
            os.path.join(work, "control_reshard"))
        dig_e = [(e["path"], e["sha256"])
                 for e in decision.manifest.tree]
        dig_c = [(e["path"], e["sha256"]) for e in man_c.tree]
        assert dig_e == dig_c, \
            "reshard is not deterministic: two reshards of the same " \
            "(checkpoint, target plan) produced different leaf digests"
        state_c = restore_checkpoint(os.path.join(out2, "state"),
                                     template=make_template(plan_b))
        verify_tree(out2, state_c, man_c)
        losses_c = []
        for i in range(start, steps_total):
            t, lbl = batch_at(i, cfg_b)
            state_c, loss = step_b(state_c, t, lbl)
            losses_c.append(float(loss))

        assert losses_e == losses_c, \
            f"elastic loss trajectory diverged from the " \
            f"from-checkpoint control: {losses_e} != {losses_c}"
        pe = tree_entries(jax.device_get(state_e["params"]))
        pc = tree_entries(jax.device_get(state_c["params"]))
        assert pe == pc, "final params differ between the elastic " \
                         "leg and the control"
        say(f"bit-exact: {len(losses_e)} resumed steps match the "
            f"control (losses {['%.4f' % l for l in losses_e]})")
        return {
            "data_step": start, "n_to": n_to,
            "old_mesh": mesh_str(plan_a),
            "new_mesh": mesh_str(plan_b),
            "losses": losses_e,
            "n_leaves": decision.report["n_leaves"],
            "n_restacked": decision.report["n_restacked"],
            "n_tree_digests": len(decision.manifest.tree),
            "path": decision.path,
        }
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


def drill(n_from: int = 8, n_to: Optional[int] = None, *,
          seed: int = 20260804, steps_total: int = 6,
          work_dir: Optional[str] = None, verbose: bool = True,
          subprocess_resume: bool = True) -> dict:
    """The elastic acceptance drill (module docstring). Phase 1
    trains on ``n_from`` devices and dies mid-run; phases 2+3 (the
    elastic resume + its from-checkpoint control) run in a FRESH
    process that owns exactly ``n_to`` devices — what a real relaunch
    on a shrunken fleet is (``subprocess_resume=False`` runs them
    in-process over ``devices[:n_to]`` instead: the --real form,
    because a live TPU job cannot boot a second process against chips
    it holds). Raises ``AssertionError`` naming the broken property;
    returns the episode summary dict on success."""
    import contextlib
    import json
    import subprocess
    import sys
    import tempfile

    import jax

    from apex1_tpu import planner
    from apex1_tpu.core.mesh import make_mesh
    from apex1_tpu.models import llama_3d as l3d
    from apex1_tpu.obs import spine
    from apex1_tpu.resilience.checkpointer import ResilientCheckpointer
    from apex1_tpu.testing import chaos

    def say(msg):
        if verbose:
            print(f"[elastic drill] {msg}", flush=True)

    devices = jax.devices()
    if len(devices) < n_from:
        raise AssertionError(
            f"drill needs {n_from} devices, have {len(devices)}")
    kill_step, auto_to = chaos.shrink_schedule(
        seed, n_devices=n_from, lo=2, hi=max(3, steps_total - 1))
    n_to = n_to or auto_to

    shape, cfg_of, _make_template, batch_at = _drill_fixture(seed)
    if n_from == 8:
        # stated dp2·pp2·tp2 with an INTERLEAVED stack (num_chunks=2):
        # the planner never searches num_chunks > 1 (docs/planner.md
        # "does NOT do"), so any re-plan lands on chunks=1 and the
        # resume exercises a genuine (2,2,1)->(1,pp',lps') chunk-stack
        # remap, never a trivial copy
        lay_a = planner.Layout(dp=2, pp=2, tp=2, num_microbatches=4,
                               num_chunks=2)
        plan_a = planner.plan_for_layout(shape, lay_a)
    else:
        plan_a = planner.make_plan(shape, n_from, allow_zero=False)

    with contextlib.ExitStack() as stack:
        work = work_dir or stack.enter_context(
            tempfile.TemporaryDirectory(prefix="elastic_drill_"))
        obs_dir = os.path.join(work, "obs")
        old_env = os.environ.get("APEX1_OBS_DIR")
        os.environ["APEX1_OBS_DIR"] = obs_dir
        stack.callback(lambda: (
            os.environ.__setitem__("APEX1_OBS_DIR", old_env)
            if old_env is not None
            else os.environ.pop("APEX1_OBS_DIR", None)))
        ckdir = os.path.join(work, "ckpt")

        # -- phase 1: train on n_from devices, die mid-run --------------
        cfg_a = cfg_of(plan_a)
        mesh_a = make_mesh(dp=cfg_a.dp, pp=cfg_a.pp, cp=cfg_a.cp,
                           ep=cfg_a.ep, tp=cfg_a.tp,
                           devices=devices[:n_from])
        step_a, state_a, _ = l3d.make_train_step(cfg_a, mesh=mesh_a)
        say(f"phase 1: {mesh_str(plan_a)} — {steps_total} steps "
            f"planned, kill after {kill_step} committed saves")
        with ResilientCheckpointer(ckdir, keep=8, plan=plan_a) as ck_a:
            for i in range(steps_total):
                t, lbl = batch_at(i, cfg_a)
                state_a, _loss = step_a(state_a, t, lbl)
                if i < kill_step:
                    ck_a.save(int(state_a["step"]), state_a,
                              meta={"data_step": i + 1})
            ck_a.wait()
        # "kill": everything after the last committed save is lost —
        # steps [kill_step, steps_total) trained but never banked
        del state_a, step_a

        # -- phases 2+3: the shrunken fleet ----------------------------
        if subprocess_resume:
            # a REAL relaunch: a fresh process owning exactly n_to
            # devices (the submesh never exists there)
            repo = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
            out_json = os.path.join(work, "resume_leg.json")
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       APEX1_OBS_DIR=obs_dir)
            env["PYTHONPATH"] = repo + os.pathsep + env.get(
                "PYTHONPATH", "")
            cmd = [sys.executable, "-m",
                   "apex1_tpu.resilience.elastic", "--resume-leg",
                   "--ckpt-dir", ckdir, "--work", work,
                   "--to-devices", str(n_to), "--seed", str(seed),
                   "--steps", str(steps_total),
                   "--out-json", out_json]
            r = subprocess.run(cmd, env=env, cwd=repo,
                               capture_output=True, text=True,
                               timeout=600)
            if verbose and r.stdout:
                for line in r.stdout.splitlines():
                    if line.startswith("[elastic drill]"):
                        print(line, flush=True)
            if r.returncode != 0:
                raise AssertionError(
                    f"resume leg failed (rc={r.returncode}):\n"
                    f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
            with open(out_json) as f:
                leg = json.load(f)
        else:
            leg = _resume_leg(ckdir, work, n_to, seed, steps_total,
                              devices=devices, verbose=verbose)

        assert int(leg["data_step"]) == kill_step, \
            (leg["data_step"], kill_step)
        if n_from == 8:
            assert leg["n_restacked"] > 0, \
                "8-dev drill must exercise a real chunk-stack remap"
        assert len(leg["losses"]) >= 1          # resumed steps ran

        # -- phase 4: reconstruct the episode from banked events alone --
        events = []
        for name in sorted(os.listdir(obs_dir)):
            if name.endswith(".jsonl"):
                events += spine.read_events(
                    os.path.join(obs_dir, name))
        ev = {e["name"]: e for e in events
              if str(e.get("name", "")).startswith("elastic.")}
        for need in ("elastic.detect", "elastic.replan",
                     "elastic.reshard", "elastic.verify",
                     "elastic.resume"):
            assert need in ev, f"episode not reconstructable: {need} " \
                               f"missing from the spine"
        assert ev["elastic.detect"]["n_devices"] == n_to
        assert ev["elastic.detect"]["data_step"] == kill_step
        assert ev["elastic.replan"]["old_mesh"] == mesh_str(plan_a) \
            == leg["old_mesh"]
        assert ev["elastic.replan"]["new_mesh"] == leg["new_mesh"]
        assert (ev["elastic.reshard"]["n_leaves"]
                == leg["n_tree_digests"])
        assert ev["elastic.verify"]["conserved"] is True
        assert ev["elastic.resume"]["path"] == leg["path"]
        say("episode reconstructed from banked obs-spine events alone "
            "(detect -> replan -> reshard -> verify -> resume)")

        return {
            "kill_step": kill_step, "n_from": n_from, "n_to": n_to,
            "old_mesh": leg["old_mesh"], "new_mesh": leg["new_mesh"],
            "losses": leg["losses"],
            "n_leaves": leg["n_leaves"],
            "n_restacked": leg["n_restacked"],
            "events": sorted(ev),
        }


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--drill", action="store_true",
                    help="run the 8->4-device elastic acceptance "
                         "drill (CPU virtual mesh; the check_all "
                         "'== elastic drill ==' step)")
    ap.add_argument("--real", action="store_true",
                    help="use the live backend's devices (the "
                         "elastic_ab queue entry): shrink "
                         "n -> n/2 in-process; skip record below 2 "
                         "devices; falls back to the virtual CPU "
                         "form when JAX_PLATFORMS=cpu (rehearsal)")
    ap.add_argument("--from-devices", type=int, default=8)
    ap.add_argument("--to-devices", type=int, default=None)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--seed", type=int, default=20260804)
    # internal: the shrunken fleet's half of the drill (spawned by
    # drill() in its own n_to-device process)
    ap.add_argument("--resume-leg", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--ckpt-dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--work", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--out-json", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.resume_leg:
        from apex1_tpu.resilience.manifest import atomic_write_json
        from apex1_tpu.testing import force_virtual_cpu_devices

        force_virtual_cpu_devices(args.to_devices)
        leg = _resume_leg(args.ckpt_dir, args.work, args.to_devices,
                          args.seed, args.steps)
        atomic_write_json(args.out_json, leg)
        return 0

    if not args.drill:
        ap.print_help()
        return 0
    if args.real and os.environ.get("JAX_PLATFORMS",
                                    "").strip() != "cpu":
        import jax

        n = jax.device_count()
        if n < 2:
            print(f"[skip] elastic_ab: {n} device(s) — the shrink "
                  "drill needs >= 2 (record this window as skipped, "
                  "not failed)", flush=True)
            return 0
        n_from, n_to, sub = n, args.to_devices, False
    else:
        from apex1_tpu.testing import force_virtual_cpu_devices

        force_virtual_cpu_devices(args.from_devices)
        n_from, n_to, sub = args.from_devices, args.to_devices, True
    try:
        res = drill(n_from, n_to, seed=args.seed,
                    steps_total=args.steps, subprocess_resume=sub)
    except Exception as e:
        from apex1_tpu.planner import PlanError

        if args.real and isinstance(e, PlanError):
            # an odd live chip count can have no legal drill layout —
            # record the window as skipped, never as failed
            print(f"[skip] elastic_ab: no legal drill layout for "
                  f"{n_from} device(s): {e}", flush=True)
            return 0
        raise
    print(f"elastic drill OK: {res['old_mesh']} -> {res['new_mesh']} "
          f"(killed after step {res['kill_step']}, "
          f"{res['n_restacked']}/{res['n_leaves']} leaves restacked, "
          f"{len(res['losses'])} resumed steps bit-exact vs control, "
          f"episode reconstructed from {len(res['events'])} banked "
          f"event kinds)", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
