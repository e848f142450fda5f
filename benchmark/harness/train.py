"""Runner for cells of kind `train`: one compiled step with its state,
driven from the seed through its first steps (which the plain reference
follows), then handed — the same object — to the measured window.

The window counts WHOLE steps on exact intervals: it opens at a step's
completion and closes at the first completion at or after `--seconds`
later; the rate is tokens of the steps between the two over the measured
interval. The host keeps a few steps queued ahead of the one it waits
for, so the device never waits for the host to observe a completion.
"""

from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from benchmark.harness import builders, check, device, flops, stats

N_CHECKED_STEPS = 3
#: steps the host keeps queued ahead of the one it waits for: a host
#: hiccup shorter than this many steps never starves the device (with one
#: step ahead, one run in ~25 lost 1.2 s of a 30 s window to such a stall)
LOOKAHEAD_STEPS = 4


def import_program(cfg: dict) -> None:
    """The program's modules a training cell needs, imported inside the
    `import` phase of set-up."""
    import apex1_tpu.amp  # noqa: F401
    import apex1_tpu.core.mesh  # noqa: F401
    import apex1_tpu.optim.fused_adam  # noqa: F401
    import apex1_tpu.optim.fused_lamb  # noqa: F401
    builders.get(cfg).model("O2")


def make_step(cfg: dict, traffic: dict, devices: list) -> dict:
    """The jitted step and what it is called with, no array made yet
    (the AOT test lowers it for a described topology from here)."""
    from apex1_tpu.amp import Amp

    b = builders.get(cfg)
    n = len(devices)
    ddp = bool(traffic.get("ddp", False))
    amp_kw = dict(traffic["amp"])
    opt_level = amp_kw.pop("opt_level")
    model = b.model(opt_level)
    rows = int(traffic["per_chip_batch"]) * n
    seq = int(traffic["seq_len"])
    n_batches = max(int(traffic.get("n_batches", 4)), N_CHECKED_STEPS)
    amp = Amp(tx=builders.optimizer(traffic["optimizer"]),
              opt_level=opt_level,
              grad_psum_axes=("dp",) if ddp else (), **amp_kw)
    raw_step = amp.make_train_step(b.loss_fn(model))
    if hasattr(b, "shard_step"):
        # the family's own layout over the chips (builders' protocol)
        raw_step, repl, split = b.shard_step(raw_step, devices, traffic)
    elif ddp:
        from apex1_tpu.core.mesh import make_mesh
        mesh = make_mesh(dp=n, devices=list(devices))
        repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
        # check_vma=False: under the vma check `linear_cross_entropy`'s
        # custom VJP is rejected (chip_smoke.ddp_step, PR 21)
        raw_step = jax.shard_map(raw_step, mesh=mesh,
                                 in_specs=(P(), P("dp")),
                                 out_specs=(P(), P()), check_vma=False)
    else:
        if n != 1:
            raise ValueError("a cell on several chips needs ddp: true, or "
                             "a builder with shard_step")
        repl = split = jax.sharding.SingleDeviceSharding(devices[0])

    def make_batches(key):
        return [b.make_batch(jax.random.fold_in(key, i), rows, seq, traffic)
                for i in range(n_batches)]

    return dict(builder=b, amp=amp, shapes=b.param_shapes(model), rows=rows,
                seq=seq, groups=n if ddp else 1, repl=repl, split=split,
                make_batches=make_batches,
                step=jax.jit(raw_step, donate_argnums=0))


def abstract_args(pieces: dict) -> tuple:
    """(state, batch) as `ShapeDtypeStruct`s with their shardings."""
    def place(tree, sharding):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)
    p32 = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
        pieces["shapes"])
    state = jax.eval_shape(pieces["amp"].init, p32)
    batch = jax.eval_shape(pieces["make_batches"], jax.random.key(0))[0]
    return place(state, pieces["repl"]), place(batch, pieces["split"])


def build(cfg: dict, traffic: dict, devices: list, seed: int,
          pieces: dict | None = None):
    """(state, batches, pieces) — set-up's one object and its feed.
    `pieces` of an earlier seed are taken over (one process reading many
    seeds keeps its jitted step)."""
    pieces = pieces or make_step(cfg, traffic, devices)
    repl = pieces["repl"]

    def fresh_params(sharding=repl):
        return builders.make_params(pieces["shapes"], seed, jnp.float32,
                                    sharding)

    state = jax.jit(pieces["amp"].init, out_shardings=repl)(fresh_params())
    batches = jax.jit(pieces["make_batches"], out_shardings=pieces["split"])(
        jax.random.fold_in(builders.seed_key(seed), 0xDA7A))
    pieces["fresh_params"] = fresh_params
    pieces["seed"] = seed
    return state, batches, pieces


def program_readings(state, pieces, traffic, parts, first: bool):
    """What the check reads off the program's own state. After step 1
    (`first`): the gradient the optimizer got (m_1 = (1-b1) g) — its
    per-leaf norms, and the gradient itself copied to the HOST, where it
    waits for the reference without taking device memory. After the
    checked steps: the per-leaf norms of the parameters' change from the
    seed's weights."""
    b1 = traffic["optimizer"].get("b1", 0.9)

    def local(tree):        # one chip's copy (every chip holds the same)
        return jax.tree_util.tree_map(
            lambda x: x.addressable_shards[0].data, tree)

    if first:
        m = local(state.opt_state.exp_avg)
        f = jax.jit(lambda m: check.leaf_norms(
            jax.tree_util.tree_map(lambda x: x / (1 - b1), m), parts))
        grad = jax.tree_util.tree_map(
            lambda x: np.asarray(x, np.float32) / np.float32(1 - b1), m)
        return np.asarray(f(m)), grad
    # against the seed's weights, regenerated INSIDE the reduction: no
    # second set of weights is ever held on the device
    gen = builders.param_generator(pieces["shapes"], jnp.float32)
    f = jax.jit(lambda p, key: check.leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, p, gen(key)), parts))
    return np.asarray(f(local(state.params),
                        builders.seed_key(pieces["seed"])))


def first_steps(compiled, state, batches, pieces, traffic, reference):
    """(state, readings): the checked steps, through the window's own call
    and feed, with what the check reads off the program's state (leaves
    taken apart as the plain reference's `LEAF_PARTS` says)."""
    parts = getattr(reference, "LEAF_PARTS", None)
    losses = []
    for i in range(N_CHECKED_STEPS):
        state, metrics = compiled(state, batches[i])
        losses.append(float(metrics["loss"]))
        if i == 0:
            first_norms, first_grad = program_readings(
                state, pieces, traffic, parts, True)
    return state, {"losses": losses, "first_grad_norms": first_norms,
                   "first_grad": first_grad,
                   "change_norms": program_readings(state, pieces, traffic,
                                                    parts, False)}


def follow(prog: dict, pieces: dict, traffic: dict, host_batches: list,
           devices: list, reference, control: bool) -> dict:
    """The plain reference over the same steps, once the program's state
    is freed. Returns {"reference": ..., "control": ... or None}; `prog`
    (and the control) gain `first_grad_diff_norms` against the reference.
    The control runs first: its first gradient waits on the host as the
    program's does."""
    with jax.default_device(devices[0]):
        def run_ref(quant, **kw):
            return check.train_reference(
                reference, pieces["builder"].ref_cfg, pieces["fresh_params"],
                host_batches, pieces["groups"], traffic["optimizer"],
                traffic.get("reference_block_rows"), quant,
                devices=devices[:pieces["groups"]], **kw)

        firsts = {"program": prog.pop("first_grad")}
        ctl = None
        if control:
            ctl = run_ref(check.control_quant(True), keep_first=True)
            firsts["control"] = ctl.pop("first_grad")
        ref = run_ref(None, compare_first=firsts)
    diffs = ref.pop("first_grad_diff_norms")
    prog["first_grad_diff_norms"] = diffs["program"]
    if ctl is not None:
        ctl["first_grad_diff_norms"] = diffs["control"]
    return {"reference": ref, "control": ctl}


def run(cell: dict, cfg: dict, traffic: dict, args, phases, meter,
        devices: list, profiler=None) -> dict:
    seed, seconds = args.seed, float(args.seconds)
    state, batches, pieces = build(cfg, traffic, devices, seed)
    phases.lap("init")
    compiled = pieces["step"].lower(state, batches[0]).compile()
    phases.lap("compile")

    # the first steps, through the window's own call and feed
    state, prog = first_steps(compiled, state, batches, pieces, traffic,
                              args.reference)
    print("first steps: loss " + " ".join(f"{l:.4f}" for l in
                                          prog["losses"]), flush=True)
    phases.lap("warmup")
    # settle: two more steps so the pipeline below starts from steady
    # state (and every helper program above has been released)
    for i in range(2):
        state, metrics = compiled(state, batches[i % len(batches)])
    jax.block_until_ready(metrics["loss"])
    phases.lap("ramp")
    setup_s = phases.total()

    tokens_per_step = pieces["rows"] * pieces["seq"]
    traced = profiler is not None
    limit_steps = int(traffic.get("trace", {}).get("steps", 4)) \
        if traced else None
    mark = meter.mark()
    if traced:
        profiler.start()
    boundaries = []          # completions; the first one opens the window
    k = 0
    queued = collections.deque()
    with jax.profiler.TraceAnnotation("bench/window"):
        while True:
            with jax.profiler.TraceAnnotation("train/dispatch"):
                state, metrics = compiled(state, batches[k % len(batches)])
            k += 1
            queued.append(metrics["loss"])
            if len(queued) > LOOKAHEAD_STEPS:
                jax.block_until_ready(queued.popleft())
                boundaries.append(time.perf_counter())
                if limit_steps is not None:
                    if len(boundaries) - 1 >= limit_steps:
                        break
                elif boundaries[-1] - boundaries[0] >= seconds:
                    break
    jax.block_until_ready(metrics["loss"])     # the steps still queued
    if traced:
        profiler.stop()
    in_window = meter.since(mark)
    n_steps = len(boundaries) - 1
    interval = boundaries[-1] - boundaries[0]
    tok_s_chip = n_steps * tokens_per_step / interval / len(devices)
    step_ms = [1e3 * (b - a) for a, b in zip(boundaries, boundaries[1:])]
    skipped = int(metrics["skipped_steps"])
    finite = int(metrics["grads_finite"])
    final_loss = float(metrics["loss"])
    peak = device.memory_peak_bytes(devices)
    print(f"window: {n_steps} steps of {tokens_per_step} tokens in "
          f"{interval:.4f} s on {len(devices)} chip(s); host step p50 "
          f"{stats.percentile(step_ms, 50).value:.3f} ms (n={n_steps}); "
          f"compilations inside the window: {in_window['compiles']}; "
          f"skipped steps {skipped}; final loss {final_loss:.4f}",
          flush=True)

    # free the program's state, then the reference follows the same steps
    host_batches = [jax.tree_util.tree_map(np.asarray, b)
                    for b in batches[:N_CHECKED_STEPS]]
    del state, metrics, queued, compiled, batches
    pieces.pop("step")
    t0 = time.perf_counter()
    res = follow(prog, pieces, traffic, host_batches, devices,
                 args.reference, bool(args.control))
    limits = check.load_limits(cell["name"], args.root)
    if args.control:
        # the control stands in the program's place
        print("control: the reference with matmul operands in",
              check.control_quant(True), flush=True)
        rows = check.compare_training(res["control"], res["reference"],
                                      limits)
    else:
        rows = check.compare_training(prog, res["reference"], limits)
    all_finite = bool(np.isfinite(final_loss)) and finite == 1
    rows += [("compilations_in_window", in_window["compiles"], 0,
              in_window["compiles"] == 0),
             ("final_loss_or_grads_not_finite", int(not all_finite), 0,
              all_finite)]
    ok = check.print_rows(rows)
    print(f"check: reference followed {N_CHECKED_STEPS} steps in "
          f"{time.perf_counter() - t0:.1f} s (not in setup_s)", flush=True)

    fpt = pieces["builder"].train_flops_per_token(pieces["seq"])
    scalars = {
        "train_tok_s_chip": tok_s_chip, "setup_s": setup_s,
        "window.interval_s": interval, "window.steps": n_steps,
        "window.compiles": in_window["compiles"],
        "flops_per_token": fpt,
    }
    if devices[0].platform == "tpu":
        scalars["mfu_pct"] = flops.mfu_pct(
            tok_s_chip, fpt, device.peaks(devices[0].device_kind)
            ["bf16_flops"])
    return {"correct": bool(ok), "attempted": n_steps, "failed": skipped,
            "scalars": scalars, "series": {"host_step_ms": step_ms},
            "memory_peak_bytes": peak, "compared": rows}
