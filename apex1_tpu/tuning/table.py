"""Shape-keyed kernel tuning tables — persisted block-size winners.

The table replaces "one heuristic plus an env var" block selection with
persistent, measured state: ``tools/tune_kernels.py`` sweeps block-size
candidates **in one process** (the blocks are static kernel arguments,
so the jit cache keys on them — no fresh-process-per-candidate), writes
the winners here, and every Pallas entry point consults the table at
trace time before falling back to its analytic heuristic.

Entries are keyed on

    kernel name x TPU generation (``core.capability``) x operand dtype
    x the kernel's padded dims (``registry.KernelSpec.dims``)

so a winner swept for bf16 flash attention at head-dim 128 on v5e never
leaks to fp32, to head-dim 576, or to a v5p chip. On disk each kernel
owns one JSON file under ``perf_results/tuning/`` (override with
``APEX1_TUNING_DIR``):

    {"schema": 1, "kernel": "flash_attention",
     "entries": {"v5e|bfloat16|Dp=128":
                 {"blocks": {"block_q": 512, "block_k": 512},
                  "time_ms": 1.84, "backend": "tpu",
                  "timing": "measured"}}}

Lookup is fail-safe by construction — a missing dir, corrupt file,
unknown generation, misaligned block, or VMEM-over-budget entry (the
``registry`` cost model against the RECORDED generation's
``vmem_budget``) all degrade to a miss, and the caller's heuristic
takes over. ``timing: "interpret"`` entries (swept off-TPU, where only
the plumbing is meaningful) are served off-TPU but never on real
silicon. ``validate_tables`` re-checks every in-repo file strictly for
the ``tools/check_all.sh`` gate.
"""

from __future__ import annotations

import json
import os
from typing import Any, Mapping

import numpy as np

from apex1_tpu.core.capability import (CPU_PLANNING_GENERATION,
                                       detect_generation, get_capability,
                                       vmem_budget)
from apex1_tpu.tuning.registry import SPECS


def _on_tpu() -> bool:
    # lazy: ops._common imports the tuning package at module scope (the
    # reverse edge at import time would be a cycle)
    from apex1_tpu.ops._common import on_tpu
    return on_tpu()


_SCHEMA = 1

# process-wide cache: {"dir": str|None, "tables": {kernel: {key: entry}},
# "problems": [str]} — populated lazily on first lookup, dropped by
# clear_cache() (tests, APEX1_TUNING_DIR changes, post-sweep reloads)
_STATE: dict[str, Any] = {"dir": None, "tables": None, "problems": None}


def default_tuning_dir() -> str:
    """``APEX1_TUNING_DIR`` if set, else ``<repo>/perf_results/tuning``
    (the package's parent directory is the repo root)."""
    env = os.environ.get("APEX1_TUNING_DIR", "").strip()
    if env:
        return env
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), "perf_results", "tuning")


def clear_cache() -> None:
    """Drop the in-memory tables (next lookup reloads from disk)."""
    _STATE.update(dir=None, tables=None, problems=None)


def canonical_dtype(dtype) -> str:
    """Canonical dtype name for table keys ('bfloat16', 'float32',
    'int8', ...). Accepts strings, numpy/jax dtypes, and scalar types."""
    return np.dtype(dtype).name


def canonical_generation(generation: str | None = None) -> str:
    """Table-key generation: explicit > detected chip >
    ``CPU_PLANNING_GENERATION`` (what ``core.capability.vmem_budget``
    plans blocks for on the CPU backend, so CPU-validated lookups agree
    with that planning path)."""
    return generation or detect_generation() or CPU_PLANNING_GENERATION


def make_key(dims: Mapping[str, int], dtype,
             generation: str | None = None) -> str:
    """Canonical entry key: ``<gen>|<dtype>|<k=v,...>`` with dims sorted
    by name. ``dims`` must be the kernel's PADDED dims (the values the
    block planner actually sees), per ``registry.KernelSpec.dims``."""
    gen = canonical_generation(generation)
    dt = canonical_dtype(dtype)
    body = ",".join(k + "=" + str(int(v)) for k, v in sorted(dims.items()))
    return gen + "|" + dt + "|" + body


def parse_key(key: str) -> tuple[str, str, dict[str, int]]:
    """Inverse of :func:`make_key`; raises ValueError on malformed keys."""
    parts = key.split("|")
    if len(parts) != 3:
        raise ValueError(f"malformed tuning key {key!r}")
    gen, dt, body = parts
    dims: dict[str, int] = {}
    for item in body.split(","):
        name, _, val = item.partition("=")
        if not name or not val:
            raise ValueError(f"malformed dims in tuning key {key!r}")
        dims[name] = int(val)
    return gen, dt, dims


def _load_file(path: str, kernel: str) -> dict[str, dict]:
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") != _SCHEMA:
        raise ValueError(f"unsupported schema {doc.get('schema')!r}")
    if doc.get("kernel") != kernel:
        raise ValueError(f"kernel field {doc.get('kernel')!r} != filename")
    entries = doc.get("entries")
    if not isinstance(entries, dict):
        raise ValueError("entries must be an object")
    return entries


def _tables() -> dict[str, dict[str, dict]]:
    """Lazily load every ``<kernel>.json`` in the tuning dir. Unreadable
    files become recorded problems (see ``load_problems``), never
    exceptions — a corrupt table must not take down a training run."""
    d = default_tuning_dir()
    if _STATE["tables"] is not None and _STATE["dir"] == d:
        return _STATE["tables"]
    tables: dict[str, dict[str, dict]] = {}
    problems: list[str] = []
    if os.path.isdir(d):
        for name in sorted(os.listdir(d)):
            if not name.endswith(".json"):
                continue
            kernel = name[:-5]
            path = os.path.join(d, name)
            try:
                tables[kernel] = _load_file(path, kernel)
            except Exception as e:  # fail-safe: degrade to a miss
                problems.append(f"{path}: {type(e).__name__}: {e}")
    _STATE.update(dir=d, tables=tables, problems=problems)
    return tables


def load_problems() -> list[str]:
    """Parse problems swallowed by the lazy loader (for diagnostics)."""
    _tables()
    return list(_STATE["problems"])


def _entry_blocks(kernel: str, entry: Mapping, dims: Mapping[str, int],
                  dtype_name: str, generation: str, *,
                  serving: bool = True) -> dict[str, int] | None:
    """Validated blocks of one entry, or None if the entry is unusable:
    wrong/missing params, misaligned values, an unknown generation, or a
    VMEM estimate over the recorded generation's budget. ``serving``
    additionally rejects interpret-timed entries on real TPUs (lookup
    path); ``validate_tables`` checks structure only."""
    spec = SPECS.get(kernel)
    if spec is None:
        return None
    blocks = entry.get("blocks")
    if not isinstance(blocks, Mapping):
        return None
    out: dict[str, int] = {}
    for p in spec.params:
        v = blocks.get(p)
        if not isinstance(v, int) or isinstance(v, bool) or v <= 0 \
                or v % spec.align:
            return None
        out[p] = v
    try:
        get_capability(generation)
        es = np.dtype(dtype_name).itemsize
        ok, _est = spec.check(out, dims, es, vmem_budget(generation))
    except Exception:
        return None
    if not ok:
        return None
    # off-TPU (interpret-mode) timings order nothing on real silicon:
    # serve them only where they were measured
    if serving and _on_tpu() and entry.get("timing") != "measured":
        return None
    return out


def lookup(kernel: str, dims: Mapping[str, int], dtype,
           generation: str | None = None) -> dict[str, int] | None:
    """Validated block dict for (kernel, generation, dtype, padded dims),
    or None on miss/invalid — the caller then falls back env > heuristic
    (see the per-op precedence in docs/ops.md)."""
    try:
        key = make_key(dims, dtype, generation)
    except Exception:
        return None
    entry = _tables().get(kernel, {}).get(key)
    if entry is None:
        return None
    return _entry_blocks(kernel, entry, dims, canonical_dtype(dtype),
                         canonical_generation(generation))


def record(kernel: str, dims: Mapping[str, int], dtype,
           blocks: Mapping[str, int], *, time_ms: float | None = None,
           generation: str | None = None,
           extra: Mapping[str, Any] | None = None) -> tuple[str, dict]:
    """Install a winner in the in-memory table (visible to subsequent
    ``lookup`` calls immediately); ``save`` persists it. Records the
    backend and whether the timing was real silicon or interpret mode."""
    if kernel not in SPECS:
        raise ValueError(f"unknown tunable kernel {kernel!r}; "
                         f"known: {sorted(SPECS)}")
    spec = SPECS[kernel]
    missing = [p for p in spec.params if p not in blocks]
    if missing:
        raise ValueError(f"{kernel} entry missing block params {missing}")
    key = make_key(dims, dtype, generation)
    try:
        import jax
        backend = jax.default_backend()
    except Exception:
        backend = "unknown"
    entry: dict[str, Any] = {
        "blocks": {p: int(blocks[p]) for p in spec.params},
        "time_ms": None if time_ms is None else round(float(time_ms), 4),
        "backend": backend,
        "timing": "measured" if _on_tpu() else "interpret",
    }
    if extra:
        entry.update(extra)
    _tables().setdefault(kernel, {})[key] = entry
    return key, entry


def save(kernel: str, dir: str | None = None) -> str:
    """Write ``kernel``'s table to ``<dir>/<kernel>.json`` (merging over
    any entries already on disk that this process never loaded — two
    sweep runs for different kernels/shapes compose). Returns the path."""
    d = dir or default_tuning_dir()
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, kernel + ".json")
    entries: dict[str, dict] = {}
    if os.path.exists(path):
        try:
            entries = _load_file(path, kernel)
        except Exception:
            entries = {}  # unreadable file: the fresh write repairs it
    entries.update(_tables().get(kernel, {}))
    doc = {"schema": _SCHEMA, "kernel": kernel,
           "entries": {k: entries[k] for k in sorted(entries)}}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def validate_tables(dir: str | None = None) -> list[str]:
    """STRICT validation of every ``*.json`` table in ``dir`` for the
    ``check_all.sh`` gate: file parses, schema/kernel fields match, every
    key parses against a known generation, and every entry's blocks pass
    the registry VMEM model for its recorded capability. Returns the
    list of problems (empty = clean)."""
    d = dir or default_tuning_dir()
    problems: list[str] = []
    if not os.path.isdir(d):
        return problems  # no tables yet is a valid state
    for name in sorted(os.listdir(d)):
        if not name.endswith(".json"):
            continue
        path = os.path.join(d, name)
        kernel = name[:-5]
        if kernel not in SPECS:
            problems.append(f"{path}: not a known tunable kernel "
                            f"(known: {sorted(SPECS)})")
            continue
        try:
            entries = _load_file(path, kernel)
        except Exception as e:
            problems.append(f"{path}: {type(e).__name__}: {e}")
            continue
        for key, entry in entries.items():
            try:
                gen, dt, dims = parse_key(key)
            except ValueError as e:
                problems.append(f"{path}: {e}")
                continue
            missing = [k for k in SPECS[kernel].dims if k not in dims]
            if missing:
                problems.append(f"{path}: {key}: missing dims {missing}")
                continue
            if _entry_blocks(kernel, entry, dims, dt, gen,
                             serving=False) is None:
                problems.append(
                    f"{path}: {key}: entry invalid (blocks "
                    f"{entry.get('blocks')!r} misaligned/over the "
                    f"{gen} VMEM budget, or unknown generation)")
    return problems
