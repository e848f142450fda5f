"""apex1_tpu.planner — the calibration-driven auto-parallel planner.

ROADMAP item 1 (AMP, arXiv 2210.07297; ZeRO axis from arXiv
2004.13336): instead of hand-picking dp x pp x cp x ep x tp, SEARCH
it — enumerate the legal layouts for a model on a chip topology
(`layouts`), prune by the analytic per-chip HBM model (`memory`),
price each survivor with the repo's own roofline + comms models
corrected by the banked silicon calibration (`cost` over
`apex1_tpu.perf_model` + `obs.calibrate`), and emit the winner as an
executable plan document (`emit`): mesh axes, regex partition rules
feeding `parallel.specs.specs_from_rules`, microbatch schedule, and
the SP-boundary kernel flags.

The repo's first subsystem that CHOOSES configurations instead of
measuring ones a human chose. Consumers: ``examples/llama_3d.py
--plan auto`` and ``tools/aot_check.py``'s planner gate (AOT HBM truth
for the pick).

No module under this package imports jax at module level — the whole
legality / memory / pricing path runs under a ``tools/lint.py``-style
stub parent with no jax installed at all; only plan CONSUMPTION
(`emit.plan_param_specs`, `emit.llama3d_config_from_plan`,
`memory.aot_memory_analysis`) reaches jax, lazily. CLI: ``python -m
apex1_tpu.planner`` (--smoke is the check_all gate). Contracts and
caveats: docs/planner.md.
"""

from apex1_tpu.planner.cost import (calibration_factor, price_layout,
                                    step_flops)
from apex1_tpu.planner.emit import (PLAN_SCHEMA, PLAN_SPEC_KEYS,
                                    build_plan, check_plan_model,
                                    layout_from_plan,
                                    llama3d_config_from_plan, load_plan,
                                    model_shape_from_plan,
                                    partition_rules, plan_json,
                                    plan_param_specs, plan_spec,
                                    rules_to_specs, save_plan)
from apex1_tpu.planner.layouts import (BANKED_SHAPES, SP_MODES, Layout,
                                       ModelShape, Violation,
                                       check_layout, enumerate_layouts)
from apex1_tpu.planner.memory import (fit_check, hbm_breakdown,
                                      params_per_device)
from apex1_tpu.planner.search import (PlanError, make_plan,
                                      plan_for_layout, search_layouts)

__all__ = [
    "BANKED_SHAPES", "Layout", "ModelShape", "PLAN_SCHEMA",
    "PLAN_SPEC_KEYS",
    "PlanError", "SP_MODES", "Violation", "build_plan",
    "calibration_factor", "check_layout", "check_plan_model",
    "enumerate_layouts",
    "fit_check", "hbm_breakdown", "layout_from_plan",
    "llama3d_config_from_plan", "load_plan", "make_plan",
    "model_shape_from_plan",
    "params_per_device", "partition_rules", "plan_for_layout",
    "plan_json",
    "plan_param_specs", "plan_spec", "price_layout", "rules_to_specs",
    "save_plan",
    "search_layouts", "step_flops",
]
