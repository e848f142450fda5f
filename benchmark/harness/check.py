"""The comparison that decides `correct`: what the timed path produced,
at the timed sizes, against the plain reference; each number beside its
limit (`benchmark/limits/<workload>.json`, set from readings recorded in
PERF.md). Nothing here imports the program under test.

`quant` turns the reference into the CONTROL: the same mathematics with
every matmul operand rounded through the next precision below the one the
configuration states. The benchmark's own runs never run it; `--control 1`
and the tests do.
"""

from __future__ import annotations

import inspect
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness.manifest import ROOT, load_module

tmap = jax.tree_util.tree_map


def load_limits(workload: str, root: str | None = None) -> dict:
    root = root or ROOT
    path = os.path.join(root, "benchmark", "limits", workload + ".json")
    with open(path) as f:
        return json.load(f)


def control_quant(on):
    """What the references take as `quant`: None for the sound float32
    run, the control's forward type (`references/lowprec.py`) for the
    lower-precision control."""
    if not on:
        return None
    return load_module(os.path.join(ROOT, "benchmark", "references",
                                    "lowprec.py"),
                       "benchmark_reference_lowprec").CONTROL_FORWARD


def leaf_norms(tree, parts: dict | None = None) -> jnp.ndarray:
    """L2 norm of every leaf, in tree order, float32. `parts` (a plain
    reference's `LEAF_PARTS`): {node name: n} — a leaf under such a node
    is n published tensors side by side on its last axis (the program's
    fused q|k|v), and each gets a norm of its own."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = jnp.asarray(x).astype(jnp.float32)
        n = max([1] + [c for k, c in (parts or {}).items() if any(
            str(getattr(node, "key", node)) == k for node in path)])
        x = x.reshape(-1, n, x.shape[-1] // n)
        out.append(jnp.sqrt(jnp.sum(jnp.square(x), axis=(0, 2))))
    return jnp.concatenate(out)


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray, live=None) -> float:
    """max over leaves of |prog - ref| / max(ref_leaf, median(ref)): the gap
    between the two NORMS (not the norm of the difference), measured
    against the leaf's own norm or the median leaf's, whichever is larger
    (some gradients are all but zero). `live`: a mask of the leaves that
    take part."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    gaps = np.abs(prog - ref) / np.maximum(ref, np.median(ref))
    return float(np.max(gaps if live is None else gaps[np.asarray(live)]))


#: a leaf whose reference gradient is under this share of the median
#: leaf's is DEAD (a key bias: softmax cancels it exactly), and Adam
#: turns the rounding noise there into full-size steps in any precision:
#: its change says nothing about the optimizer
DEAD_LEAF_SHARE = 1e-3


def live_leaves(ref_first_grad_norms: np.ndarray) -> np.ndarray:
    g = np.asarray(ref_first_grad_norms, np.float64)
    return g >= DEAD_LEAF_SHARE * np.median(g)


def rel_diff(diff_norms: np.ndarray, ref_norms: np.ndarray) -> float:
    """||prog - ref|| / ||ref|| over the whole tree, from per-leaf norms:
    first order in rounding noise, where a gap between norms is second."""
    d = np.asarray(diff_norms, np.float64)
    r = np.asarray(ref_norms, np.float64)
    return float(np.sqrt(np.sum(d * d) / np.sum(r * r)))


# ---- training ----------------------------------------------------------


def _local(tree):
    """One chip's copy of a replicated tree, as single-device arrays."""
    return tmap(lambda x: x.addressable_shards[0].data, tree)


def train_reference(ref, ref_cfg: dict, params, batches: list, groups: int,
                    opt_spec: dict, block_rows: int | None, quant=None,
                    devices=None, compare_first=None,
                    keep_first: bool = False) -> dict:
    """Follow the first len(batches) steps in float32: per step the loss;
    after the first the per-leaf norms of the gradient as the optimizer
    got it; after the last the per-leaf norms of the parameters' change.
    `params`: the seed's weights, or `make(sharding) -> weights`.

    `compare_first`: {name: tree on the host} of first gradients produced
    elsewhere (the program's; the control's); for each the per-leaf norms
    of its DIFFERENCE from this run's come back under
    `first_grad_diff_norms`. `keep_first`: also hand back this run's own
    first gradient, on the host.

    The global batch is `groups` data-parallel shards: the loss is the
    mean of the shards' own losses, as data-parallel training defines it.
    A shard goes through the reference in blocks of `block_rows` rows
    where the reference says its loss is a mean of row means (BLOCKABLE),
    so that it fits beside nothing else on one chip. Where the blocks of
    a step divide among `devices`, each chip takes its share of them at
    once and the means cross the chips: the same sums, a quarter of the
    time on four."""
    opt_mod = load_module(os.path.join(
        ROOT, "benchmark", "references", "optimizers.py"),
        "benchmark_reference_optimizers")
    update = opt_mod.OPTIMIZERS[opt_spec["name"]]
    opt_kw = {k: v for k, v in opt_spec.items() if k != "name"}
    b1 = opt_spec.get("b1", 0.9)

    rows = jax.tree_util.tree_leaves(batches[0])[0].shape[0]
    per = rows // groups
    blk = per
    if block_rows and getattr(ref, "BLOCKABLE", False):
        blk = min(block_rows, per)
        if per % blk:
            raise ValueError(f"{per} rows per shard not divisible by "
                             f"block_rows {blk}")
    n_blocks = rows // blk
    devices = list(devices or jax.tree_util.tree_leaves(params)[0].devices())
    n_par = len(devices) if n_blocks % len(devices) == 0 else 1
    mesh = jax.sharding.Mesh(np.array(devices[:n_par]), ("blk",))
    P = jax.sharding.PartitionSpec
    repl = jax.sharding.NamedSharding(mesh, P())
    split = jax.sharding.NamedSharding(mesh, P("blk"))

    def block_mean(p, b):
        l, g = jax.value_and_grad(
            lambda p: ref.loss(p, b, ref_cfg, quant))(p)
        return jax.lax.pmean(l, "blk"), jax.lax.pmean(g, "blk")

    vg = jax.jit(jax.shard_map(block_mean, mesh=mesh,
                               in_specs=(P(), P("blk")),
                               out_specs=(P(), P()), check_vma=False))
    parts = getattr(ref, "LEAF_PARTS", None)
    add = jax.jit(lambda a, b: tmap(jnp.add, a, b))
    step_fn = jax.jit(lambda p, g, s, w: update(
        p, tmap(lambda x: x * w, g), s, **opt_kw), donate_argnums=(0, 2))
    norms = jax.jit(lambda a: leaf_norms(a, parts))
    delta = jax.jit(lambda a, b: leaf_norms(tmap(jnp.subtract, a, b),
                                            parts))
    first_of = jax.jit(lambda s: opt_mod.first_gradient(s, b1))

    # the seed's weights are MADE where they are wanted, and made again at
    # the end for the change: no spare copy sits on a chip meanwhile
    make = params if callable(params) else (
        lambda sharding: jax.device_put(tmap(jnp.copy, params), sharding))
    p = tmap(lambda a: a.astype(jnp.float32), make(repl))
    state = opt_mod.init(p)         # p and its state are donated each step
    n_rounds = n_blocks // n_par
    losses, out = [], {"first_grad_diff_norms": {}}
    for i, batch in enumerate(batches):
        grads, round_losses = None, []
        for j in range(n_rounds):
            lo, hi = j * n_par * blk, (j + 1) * n_par * blk
            sub = jax.device_put(tmap(lambda a: a[lo:hi], batch), split)
            l, g = vg(p, sub)
            round_losses.append(l)
            grads = g if grads is None else add(grads, g)
        losses.append(float(np.mean([float(l) for l in round_losses])))
        p, state = step_fn(p, grads, state, 1.0 / n_rounds)
        if i == 0:
            first = _local(first_of(state))
            out["first_grad_norms"] = np.asarray(norms(first))
            for name, other in (compare_first or {}).items():
                other = jax.device_put(other, devices[0])
                out["first_grad_diff_norms"][name] = np.asarray(
                    delta(other, first))
                del other
            if keep_first:
                out["first_grad"] = jax.device_get(first)
            del first
    p0 = make(jax.sharding.SingleDeviceSharding(devices[0]))
    out.update(losses=losses, change_norms=np.asarray(delta(_local(p), p0)))
    return out


def compare_training(prog: dict, ref: dict, limits: dict) -> list:
    """[(name, value, limit, ok)] — every number beside its limit. `prog`
    carries `first_grad_diff_norms`: the per-leaf norms of its first
    gradient's difference from the reference's."""
    rows = []
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        v, limit = abs(a - b), limits["loss_abs_gap"]
        rows.append((f"loss_step{i}_abs_gap", v, limit, v <= limit))
    numbers = [
        ("first_grad_worst_leaf_gap", "first_grad_gap", worst_leaf_gap(
            prog["first_grad_norms"], ref["first_grad_norms"])),
        ("first_grad_rel_diff", "first_grad_rel_diff", rel_diff(
            prog["first_grad_diff_norms"], ref["first_grad_norms"])),
        ("param_change_worst_live_leaf_gap", "param_change_gap",
         worst_leaf_gap(prog["change_norms"], ref["change_norms"],
                        live_leaves(ref["first_grad_norms"]))),
    ]
    for name, key, v in numbers:
        rows.append((name, v, limits[key], v <= limits[key]))
    return rows


# ---- serving -----------------------------------------------------------


def pick_sample(finished: list, k: int, seed: int) -> list:
    """`k` of the finished requests, drawn from the seed, the longest
    (prompt + served tokens) always among them. `finished`: dicts with
    `prompt` and `tokens` arrays."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda i: -(
        len(finished[i]["prompt"]) + len(finished[i]["tokens"])))
    chosen = [order[0]]
    rest = [i for i in range(len(finished)) if i != order[0]]
    rng = np.random.default_rng([int(seed), 7])
    rng.shuffle(rest)
    chosen += rest[:max(k - 1, 0)]
    return [finished[i] for i in chosen]


def _products_below_float32(jaxpr) -> list:
    """Every product (`dot_general`, a convolution) of a jaxpr, the inner
    ones of its loops and branches too, with an operand that is not
    float32 (or float64): `name(type, type)` of each."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("dot_general", "conv_general_dilated"):
            types = [jnp.dtype(v.aval.dtype) for v in eqn.invars]
            if any(t not in (jnp.float32, jnp.float64) for t in types):
                found.append(f"{eqn.primitive.name}"
                             f"({', '.join(map(str, types))})")
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _products_below_float32(sub)
    return found


def require_float32_reference(logits_fn, *args) -> None:
    """The yardstick is float32 whatever type the weights are stored in:
    raises where the sound reference's `logits_fn(*args)` (traced, not
    run) multiplies anything below float32 or returns logits below it. A
    reference that multiplied the bfloat16 leaves as stored would compare
    the served precision with itself, and no control could fail."""
    closed = jax.make_jaxpr(logits_fn)(*args)
    low = _products_below_float32(closed.jaxpr)
    out = [str(a.dtype) for a in closed.out_avals
           if a.dtype not in (jnp.float32, jnp.float64)]
    if low or out:
        raise TypeError(
            "the plain reference must compute in float32: it upcasts the "
            "weights it is handed as stored. Products below float32: "
            f"{sorted(set(low))}; logits of type: {out}")


def serve_gaps(ref, ref_cfg: dict, params, sample: list, pad_len: int,
               n_out: int, quant=None, rows_per_call: int = 4) -> dict:
    """One reference forward over each prompt with its served tokens
    (right-padded to `pad_len`: causal, so padding changes no earlier
    position). For every served token, how far its reference logit lies
    below the reference's best at that position; the widest is compared.
    With `quant`, also the same gap for the token the lower-precision
    reference puts first at each of those positions.

    The reference gets `params` AS STORED (the served type; it upcasts
    what it multiplies, a large one a layer at a time), so the weights are
    on the device once. Where its `logits` takes `positions` `(B, n)`, it
    is asked for the `(B, n, V)` logits at the compared positions alone;
    where not, for all of them, and they are gathered here. A reference
    whose sound path computes below float32 is refused
    (`require_float32_reference`)."""
    at_positions = "positions" in inspect.signature(ref.logits).parameters

    def logits_at(p, toks, pos, q):
        if at_positions:
            return ref.logits(p, toks, ref_cfg, q, positions=pos)
        return jnp.take_along_axis(ref.logits(p, toks, ref_cfg, q),
                                   pos[..., None], axis=1)

    def gaps(p, toks, pos, served):
        at = logits_at(p, toks, pos, None)
        best = at.max(-1)
        got = jnp.take_along_axis(at, served[..., None], -1)[..., 0]
        out = {"gap": best - got, "std": at.std(-1)}
        if quant is not None:
            first = jnp.argmax(logits_at(p, toks, pos, quant), -1)
            out["control_gap"] = best - jnp.take_along_axis(
                at, first[..., None], -1)[..., 0]
        return out

    fn = jax.jit(gaps)
    n_out = max(n_out, max(len(s["tokens"]) for s in sample))

    def ids(n):
        return jax.ShapeDtypeStruct((rows_per_call, n), jnp.int32)

    require_float32_reference(lambda p, t, at: logits_at(p, t, at, None),
                              params, ids(pad_len), ids(n_out))
    res = {"gap": [], "std": [], "control_gap": []}
    n_tokens = 0
    for i in range(0, len(sample), rows_per_call):
        chunk = sample[i:i + rows_per_call]
        n_real = len(chunk)
        while len(chunk) < rows_per_call:        # one shape, one compile
            chunk = chunk + [chunk[-1]]
        toks = np.zeros((len(chunk), pad_len), np.int32)
        pos = np.zeros((len(chunk), n_out), np.int32)
        served = np.zeros((len(chunk), n_out), np.int32)
        mask = np.zeros((len(chunk), n_out), bool)
        for r, s in enumerate(chunk):
            lp, lt = len(s["prompt"]), len(s["tokens"])
            seq = np.concatenate([s["prompt"], s["tokens"]])[:pad_len]
            toks[r, :len(seq)] = seq
            pos[r, :lt] = np.arange(lp - 1, lp - 1 + lt)
            served[r, :lt] = s["tokens"]
            mask[r, :lt] = r < n_real
        out = fn(params, toks, pos, served)
        n_tokens += int(mask.sum())
        for k in res:
            if k in out:
                res[k].append(np.where(mask, np.asarray(out[k]), 0.0))
    summary = {"n_requests": len(sample), "n_tokens": n_tokens,
               "widest_gap": float(max(a.max() for a in res["gap"])),
               "logit_std": float(np.mean([a[a > 0].mean()
                                           for a in res["std"]]))}
    if res["control_gap"]:
        summary["control_widest_gap"] = float(
            max(a.max() for a in res["control_gap"]))
    return summary


def compare_serving(gaps: dict, limits: dict) -> list:
    v = gaps["widest_gap"]
    return [("served_token_widest_logit_gap", v, limits["logit_gap"],
             v <= limits["logit_gap"])]


def print_rows(rows: list) -> bool:
    ok = True
    for name, value, limit, passed in rows:
        print(f"check: {name} = {value:.6g} (limit {limit:.6g}) "
              f"{'ok' if passed else 'FAIL'}", flush=True)
        ok = ok and bool(passed)
    return ok
