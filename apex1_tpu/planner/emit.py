"""Plan emission — the winner as an EXECUTABLE spec.

A plan is a plain JSON document (schema ``apex1-plan-v1``) carrying
everything a consumer needs to run the chosen layout without asking
the planner anything else:

- ``mesh``: the five axis degrees for `core.mesh.make_mesh`;
- ``partition_rules``: regex -> PartitionSpec rules over flattened
  param paths (the SNIPPETS.md [2] ``match_partition_rules`` pattern),
  consumed through `parallel.specs.specs_from_rules` — pinned by test
  to reproduce `models.llama_3d.chunk_param_specs` /
  ``shared_param_specs`` leaf-for-leaf on the CPU mesh;
- ``schedule``: microbatch count/size, chunks, scan-vs-1f1b;
- ``kernel_flags``: the SP-boundary schedule (``overlap=`` vs
  ``fused=`` — PR 9's knobs) each consumer should flip;
- ``zero``: whether (and over which axis) the optimizer state shards,
  via `parallel.distributed_optimizer.shard_opt_state_specs`;
- ``predicted`` / ``memory`` / ``search``: the pricing evidence, so a
  plan is auditable after the fact.

DETERMINISM CONTRACT: `plan_json` is byte-identical for identical
inputs — sorted keys, no timestamps, no environment probes. The only
external input is the banked ``calibration.json``, whose identity
rides in ``provenance`` (pinned by tests/test_planner.py).

Serialization of a PartitionSpec entry: ``None`` -> null, an axis
name -> string, a multi-axis dim -> list of strings.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional

from apex1_tpu.planner.layouts import Layout, ModelShape

PLAN_SCHEMA = "apex1-plan-v1"


# -- partition rules -------------------------------------------------------

def spec_to_json(entries):
    return [list(e) if isinstance(e, (tuple, list)) else e
            for e in entries]


def spec_from_json(entries):
    from jax.sharding import PartitionSpec as P

    return P(*[tuple(e) if isinstance(e, list) else e
               for e in entries])


def partition_rules(moe: bool) -> list:
    """Regex -> spec-json rules for the llama_3d stacked param tree
    (paths ``chunk/<leaf>`` / ``shared/<leaf>``), first match wins:
    col-parallel stacks shard their last dim over tp,
    row-parallel their second-to-last, expert stacks over ep, norms
    and router replicated beyond the pp stage axis, embedding/head
    rows over tp. The stacked chunk leaves carry the
    (chunk, pp, layer) prefix — hence the leading (None, pp, None)."""
    rules = [
        [r"chunk/(attn_norm|mlp_norm)$", [None, "pp", None, None]],
    ]
    if moe:
        rules += [
            [r"chunk/wg$", [None, "pp", None, None, None]],
            [r"chunk/(w_moe1|w_moe2)$",
             [None, "pp", None, "ep", None, None]],
        ]
    rules += [
        [r"chunk/(wq|wk|wv|w_gate|w_up)$",
         [None, "pp", None, None, "tp"]],
        [r"chunk/(wo|w_down)$", [None, "pp", None, "tp", None]],
        [r"shared/(emb|head)$", ["tp", None]],
        [r"shared/final_norm$", []],
    ]
    return rules


def rules_to_specs(rules):
    """((regex, PartitionSpec), ...) ready for
    `parallel.specs.specs_from_rules` (lazy jax import — the plan
    itself never needs jax)."""
    return tuple((pat, spec_from_json(spec)) for pat, spec in rules)


def plan_param_specs(plan: dict, params):
    """PartitionSpec tree for a param tree, from the PLAN's rules —
    the consumer-side path (llama_3d --plan auto verifies this tree
    against the model's own hand-written specs before training)."""
    from jax.sharding import PartitionSpec as P

    from apex1_tpu.parallel.specs import specs_from_rules

    return specs_from_rules(
        params, rules_to_specs(plan["partition_rules"]["rules"]),
        default=spec_from_json(plan["partition_rules"]["default"]))


# -- plan document ---------------------------------------------------------

def build_plan(shape: ModelShape, layout: Layout, price: dict,
               mem: dict, *, generation: str, search: dict,
               provenance: Optional[dict] = None) -> dict:
    gib = 2.0 ** 30
    return {
        "schema": PLAN_SCHEMA,
        "generation": generation,
        "n_devices": layout.n_devices,
        "model": dataclasses.asdict(shape),
        "mesh": {"dp": layout.dp, "pp": layout.pp, "cp": layout.cp,
                 "ep": layout.ep, "tp": layout.tp},
        "schedule": {"kind": layout.schedule,
                     "num_microbatches": layout.num_microbatches,
                     "microbatch_size": layout.microbatch_size,
                     "num_chunks": layout.num_chunks},
        "kernel_flags": {"sp_boundary": layout.sp_mode},
        "zero": {"enabled": layout.zero, "axis": "dp",
                 "consumer": "parallel.distributed_optimizer."
                             "shard_opt_state_specs"},
        "partition_rules": {"rules": partition_rules(shape.moe),
                            "default": []},
        "predicted": price,
        "memory": {k: round(v / gib, 4) if k != "fits" else v
                   for k, v in mem.items()},
        "search": search,
        "provenance": provenance or {},
    }


def plan_json(plan: dict) -> str:
    """THE serialization — sorted keys, fixed indent, trailing
    newline. Byte-identical for identical plans (the determinism
    pin)."""
    return json.dumps(plan, indent=1, sort_keys=True) + "\n"


def save_plan(plan: dict, path: str) -> str:
    from apex1_tpu.resilience.manifest import atomic_write_text

    atomic_write_text(path, plan_json(plan))
    return path


def load_plan(path: str) -> dict:
    """Parse + schema-check a banked plan. Raises ValueError (never a
    raw traceback from a foreign file) on anything but a v1 plan."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError as e:
        raise ValueError(f"plan file unreadable: {path}: {e}") from e
    except json.JSONDecodeError as e:
        raise ValueError(f"plan file is not JSON: {path}: {e}") from e
    if not isinstance(doc, dict) or doc.get("schema") != PLAN_SCHEMA:
        raise ValueError(
            f"not an {PLAN_SCHEMA} plan: {path} "
            f"(schema={doc.get('schema') if isinstance(doc, dict) else None!r})")
    return doc


#: the ModelShape dims a replayed plan must agree on before its
#: schedule/rules may drive a model (global_batch deliberately
#: excluded: the plan's schedule IS the batch authority on replay)
PLAN_MODEL_KEYS = ("num_layers", "hidden_size", "ffn_size", "seq_len",
                   "vocab_size", "num_heads", "num_kv_heads",
                   "num_experts", "moe_top_k")


def check_plan_model(plan: dict, shape: ModelShape) -> list:
    """Mismatches between a plan's banked model dims and the model a
    consumer is about to drive with it — the validation
    ``examples/llama_3d.py --plan`` applies (empty list = safe to
    consume)."""
    pm = plan.get("model", {})
    return [f"{k}: plan={pm.get(k)} model={getattr(shape, k)}"
            for k in PLAN_MODEL_KEYS
            if pm.get(k) != getattr(shape, k)]


#: the plan fields that define LAYOUT IDENTITY — two checkpoints are
#: layout-compatible (restorable into each other's state without a
#: reshard) iff their plan_spec dicts are equal. Pricing/provenance
#: fields are deliberately excluded: a re-search against a newer
#: calibration table that lands on the same layout is the SAME spec.
PLAN_SPEC_KEYS = ("schema", "n_devices", "mesh", "schedule", "zero",
                  "model")


def plan_spec(plan: dict) -> dict:
    """The layout-identity subset of a plan document (see
    `PLAN_SPEC_KEYS`) — what `resilience.ResilientCheckpointer` banks
    compares, and what `resilience.elastic_resume` checks to decide
    "same layout, plain resume" vs "re-plan + reshard"."""
    out = {}
    for k in PLAN_SPEC_KEYS:
        v = plan.get(k)
        out[k] = dict(v) if isinstance(v, dict) else v
    z = out.get("zero")
    if isinstance(z, dict):
        # the consumer pointer is documentation, not identity
        out["zero"] = {"enabled": bool(z.get("enabled")),
                       "axis": z.get("axis")}
    return out


def model_shape_from_plan(plan: dict) -> ModelShape:
    """Round-trip the banked model dims back into a `ModelShape` — the
    input `search.make_plan` needs to re-plan the SAME model for a
    different chip count (elastic resume reads the checkpoint's plan
    meta, never the command line, for the model)."""
    pm = dict(plan["model"])
    fields = {f.name for f in dataclasses.fields(ModelShape)}
    unknown = set(pm) - fields
    if unknown or not set(pm) >= {"name", "num_layers"}:
        raise ValueError(
            f"plan model dims do not round-trip into ModelShape "
            f"(unknown keys {sorted(unknown)})")
    return ModelShape(**pm)


def layout_from_plan(plan: dict) -> Layout:
    m, s = plan["mesh"], plan["schedule"]
    return Layout(dp=m["dp"], pp=m["pp"], cp=m["cp"], ep=m["ep"],
                  tp=m["tp"],
                  num_microbatches=s["num_microbatches"],
                  microbatch_size=s["microbatch_size"],
                  num_chunks=s["num_chunks"], schedule=s["kind"],
                  zero=plan["zero"]["enabled"],
                  sp_mode=plan["kernel_flags"]["sp_boundary"])


def llama3d_config_from_plan(plan: dict, model_cfg,
                             learning_rate: float = 1e-4,
                             ignore_zero: bool = False):
    """The plan as a runnable `models.llama_3d.Llama3DConfig` — the
    bridge `examples/llama_3d.py --plan` drives end-to-end.
    ``model_cfg`` is the LlamaConfig the
    plan's ModelShape was derived from (the plan carries dims, not
    weights-level config like the precision policy).

    A ``zero``-enabled plan is REFUSED by default: its HBM fit
    verdict divided the optimizer state by dp, and Llama3DConfig has
    no ZeRO wiring — executing it unsharded can OOM where the plan
    said "fits". Pass ``ignore_zero=True`` only when the consumer has
    stated it runs the unsharded optimizer anyway (and has the
    memory). The ``kernel_flags.sp_boundary`` knob is advisory here
    too: llama_3d's stage runs the default mappings; the flag exists
    for consumers that flip ``overlap=``/``fused=``."""
    from apex1_tpu.models.llama_3d import Llama3DConfig

    if plan.get("zero", {}).get("enabled") and not ignore_zero:
        raise ValueError(
            "plan has zero (ZeRO-1 optimizer sharding) enabled — its "
            "HBM fit assumed opt-state/dp, which Llama3DConfig does "
            "not implement; re-plan with allow_zero=False, or pass "
            "ignore_zero=True if the unsharded optimizer provably "
            "fits (consumer: parallel.distributed_optimizer)")
    lay = layout_from_plan(plan)
    moe = bool(plan["model"].get("num_experts", 0))
    return Llama3DConfig(
        model=model_cfg, dp=lay.dp, pp=lay.pp, tp=lay.tp, cp=lay.cp,
        ep=lay.ep, moe=moe, num_chunks=lay.num_chunks,
        num_microbatches=lay.num_microbatches,
        microbatch_size=lay.microbatch_size,
        learning_rate=learning_rate, schedule=lay.schedule)
