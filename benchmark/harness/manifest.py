"""`BENCHMARK.json` and the data files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found here BY NAME:

- ``benchmark/configs/<config>.json``      (the `file` of the config entry)
- ``benchmark/traffic/<traffic>.json``
- ``benchmark/layer_metrics/<metric>.json`` (+ optional ``<metric>.py``)
- ``benchmark/references/<config>.py``
- ``benchmark/builders/<builder>.py``      (the config file's `builder`)
- ``benchmark/limits/<workload>.json``

so a later PR adds files and entries and edits nothing that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


class ManifestError(ValueError):
    pass


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def find(manifest: dict, section: str, name: str) -> dict:
    for entry in manifest[section]:
        if entry["name"] == name:
            return entry
    raise ManifestError(f"BENCHMARK.json has no {section} entry {name!r}")


def load_config(manifest: dict, name: str, root: str = ROOT) -> dict:
    entry = find(manifest, "configs", name)
    cfg = _load_json(os.path.join(root, entry["file"]))
    cfg["_name"] = name
    cfg["_root"] = root         # where its builder and reference are found
    return cfg


def load_traffic(name: str, root: str = ROOT) -> dict:
    path = os.path.join(root, "benchmark", "traffic", name + ".json")
    if not os.path.exists(path):
        raise ManifestError(f"no traffic file {path}")
    t = _load_json(path)
    t["_name"] = name
    return t


def load_module(path: str, modname: str):
    """Import a file by path (its name may carry a `-`)."""
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(config_name: str, root: str = ROOT):
    path = os.path.join(root, "benchmark", "references", config_name + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"no plain reference {path}")
    return load_module(
        path, "benchmark_reference_" + re.sub(r"\W", "_", config_name))


def load_builder(name: str, root: str = ROOT):
    """The family's glue to the system under test, as a module whose
    `Builder` class `harness/builders.py` describes."""
    path = os.path.join(root, "benchmark", "builders", name + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"no builder {path}")
    return load_module(
        path, "benchmark_builder_" + re.sub(r"\W", "_", name))


def cell_metrics(manifest: dict, workload: str, section: str) -> list:
    """The metrics of `section` that this cell reports: those with no
    `workloads` key, or whose key lists the cell. For per-layer metrics
    without a key, only those whose `moves` the cell itself reports."""
    e2e_here = {m["name"] for m in manifest["end_to_end"]
                if "workloads" not in m or workload in m["workloads"]}
    if section == "end_to_end":
        return [m for m in manifest["end_to_end"] if m["name"] in e2e_here]
    out = []
    for m in manifest["per_layer"]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e_here:
            out.append(m)
    return out


def load_layer_metric(name: str, root: str = ROOT) -> dict:
    """The metric's own file: what it reads. An optional module of the
    same name supplies ``read(ctx) -> float | None`` for what the generic
    reader cannot express."""
    base = os.path.join(root, "benchmark", "layer_metrics", name)
    spec = _load_json(base + ".json")
    if os.path.exists(base + ".py"):
        spec["_module"] = load_module(
            base + ".py", "benchmark_metric_" + re.sub(r"\W", "_", name))
    return spec


def validate(manifest: dict, root: str = ROOT) -> None:
    """The contract's character and cross-reference rules, so a bad file
    is refused here before any run. Raises ManifestError."""
    def bad(msg):
        raise ManifestError(msg)

    if set(manifest) != TOP_KEYS:
        bad(f"top-level keys {sorted(manifest)} != {sorted(TOP_KEYS)}")
    if not 1 <= int(manifest["run_seconds"]) <= 51:
        bad("run_seconds outside 1..51")
    cmd = manifest["command"]
    if not 1 <= len(cmd) <= 32 or any(
            not 1 <= len(w) <= 200 or "\n" in w or "\t" in w for w in cmd):
        bad("command malformed")
    paths = manifest["paths"]
    if not 1 <= len(paths) <= 16:
        bad("paths count")
    for p in paths:
        if (not re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
                or p.startswith("/") or ".." in p.split("/")):
            bad(f"path {p!r}")

    def check_name(n):
        if not NAME_RE.match(n):
            bad(f"name {n!r} breaks the character rules")

    def check_line(s, what):
        if not 1 <= len(s) <= 200 or "\n" in s or "\t" in s:
            bad(f"{what} {s!r} must be 1..200 characters on one line")

    seen = set()
    configs = {}
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad(f"config keys {sorted(c)}")
        check_name(c["name"])
        check_line(c["source"], "source")
        check_line(c["why"], "why")
        if len(c["reduced"]) > 16:
            bad("reduced too long")
        for k in c["reduced"]:
            check_name(k)
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in paths):
            bad(f"config file {c['file']} outside paths")
        if not os.path.exists(os.path.join(root, c["file"])):
            bad(f"config file {c['file']} missing")
        if c["name"] in configs:
            bad(f"config {c['name']} twice")
        configs[c["name"]] = c
    if len({c["file"] for c in manifest["configs"]}) != len(configs):
        bad("two configs share a file")
    cells = {}
    pairs = set()
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad(f"workload keys {sorted(w)}")
        for k in ("name", "config", "traffic"):
            check_name(w[k])
        check_line(w["why"], "why")
        if w["chips"] not in (1, 4):
            bad("chips must be 1 or 4")
        if w["config"] not in configs:
            bad(f"workload {w['name']} names unknown config {w['config']}")
        if (w["config"], w["traffic"]) in pairs or w["name"] in cells:
            bad(f"workload {w['name']} repeats")
        pairs.add((w["config"], w["traffic"]))
        cells[w["name"]] = w
        load_traffic(w["traffic"], root)
        cfg_file = _load_json(os.path.join(root, configs[w["config"]]["file"]))
        load_reference(cfg_file.get("reference", w["config"]), root)
        if "builder" not in cfg_file:
            bad(f"config {w['config']} names no builder")
        load_builder(cfg_file["builder"], root)
        if not os.path.exists(os.path.join(
                root, "benchmark", "limits", w["name"] + ".json")):
            bad(f"no limits file for cell {w['name']}")
    if not 1 <= len(cells) <= 24:
        bad("1..24 workloads")
    used = {w["config"] for w in cells.values()}
    if used != set(configs):
        bad(f"configs not used by a cell: {sorted(set(configs) - used)}")
    n4 = sum(w["chips"] == 4 for w in cells.values())
    if n4 > max(1, len(cells) // 4):
        bad("too many four-chip cells")
    e2e = {}
    for m in manifest["end_to_end"]:
        if not set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"} or not {
                "name", "unit", "better", "bound", "source"} <= set(m):
            bad(f"end_to_end keys {sorted(m)}")
        check_name(m["name"])
        if not UNIT_RE.match(m["unit"]):
            bad(f"unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad("better")
        if m["source"] not in ("host_clock", "device_trace"):
            bad(f"end_to_end source {m['source']}")
        if not 0.01 <= m["bound"] <= 0.1:
            bad(f"bound {m['bound']} outside 0.01..0.1")
        for wl in m.get("workloads", ()):
            if wl not in cells:
                bad(f"{m['name']} lists unknown cell {wl}")
        if m["name"] in e2e or m["name"] in seen:
            bad(f"metric {m['name']} twice")
        e2e[m["name"]] = m
        seen.add(m["name"])
    if "setup_s" not in e2e or "workloads" in e2e["setup_s"]:
        bad("setup_s must be reported by every cell")
    if not 1 <= len(manifest["per_layer"]) <= 128:
        bad("1..128 per_layer metrics")
    for m in manifest["per_layer"]:
        if not set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"} or not {
                "name", "unit", "better", "source", "layer",
                "moves"} <= set(m):
            bad(f"per_layer keys {sorted(m)}")
        check_name(m["name"])
        check_line(m["layer"], "layer")
        if not UNIT_RE.match(m["unit"]):
            bad(f"unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad("better")
        if m["source"] not in SOURCES:
            bad(f"source {m['source']}")
        if m["moves"] not in e2e:
            bad(f"{m['name']} moves unknown metric {m['moves']}")
        if m["name"] in seen:
            bad(f"metric {m['name']} twice")
        seen.add(m["name"])
        for wl in m.get("workloads", ()):
            if wl not in cells:
                bad(f"{m['name']} lists unknown cell {wl}")
            if m["moves"] not in {
                    x["name"] for x in cell_metrics(manifest, wl,
                                                    "end_to_end")}:
                bad(f"{m['name']}: cell {wl} does not report {m['moves']}")
        load_layer_metric(m["name"], root)
    for name in cells:
        if len(cell_metrics(manifest, name, "end_to_end")) < 2:
            bad(f"cell {name} reports no end-to-end metric besides setup_s")
        if not cell_metrics(manifest, name, "per_layer"):
            bad(f"cell {name} reports no per-layer metric")
    if len(json.dumps(manifest)) > 64 * 1024:
        bad("BENCHMARK.json over 64 KiB")
