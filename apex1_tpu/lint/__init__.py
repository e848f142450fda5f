"""graftlint — AST static analysis for this repo's JAX hazard classes.

The framework's invariants (no retraces after warmup, no host syncs on
the decode chain, use-once PRNG keys, donation discipline) are exactly
the properties JAX
never enforces statically — they regress silently and cost a TPU
session to rediscover. graftlint walks ``apex1_tpu/``, ``tools/`` and
``examples/``, resolves imports well enough to know what is
jit-reachable, and exits nonzero on any unsuppressed finding: a gate,
not a style checker.

Entry points::

    from apex1_tpu.lint import lint_paths, lint_sources
    res = lint_paths(["apex1_tpu", "tools", "examples"], root=REPO)
    res.unsuppressed()        # -> [Finding]  (gate on this)
    res.as_dict()             # -> the --json payload

``kernels=True`` additionally runs the APX2xx kernel/collective
analyzer (``lint.kernels``: the Pallas semaphore/DMA protocol
model-checker, mesh/axis consistency, and the shared-VMEM budget
pass) — the surface tier-1 can never execute. ``protocols=True``
additionally runs the APX3xx serving control-plane model checker
(``lint.protocols``: bounded exhaustive exploration of the scheduler/
replica/frontend/disagg/autopilot state machines, parameterized by
guards extracted from the real source).

CLI: ``python tools/lint.py [--json] [--changed] [--kernels]
[--protocols] [paths...]``. Rule catalogue + suppression grammar:
``docs/lint.md``.

The lint machinery is stdlib ``ast`` only — no new deps, no jax, no
device touch; the whole repo lints in ~1s. (``tools/lint.py`` loads
this subpackage through a stub parent so even the CLI never pays the
package ``__init__``'s jax import.) When a ``cache`` path is given —
the CLI does this by default — two memo tiers keep the gate cheap as
the file count grows: file-level parses keyed by (mtime_ns, size), and
a whole-run result memo keyed by the full signature vector + flags, so
the repo-wide no-change rerun costs one ``stat`` per file (~1s
end-to-end past 160 files instead of re-walking every AST).
"""

from __future__ import annotations

import dataclasses
import os
import pickle
from typing import Dict, List, Optional, Sequence, Tuple

from apex1_tpu.lint.core import (Finding, ModuleSource, RULE_SLUGS,
                                 apply_suppressions, canonical_rule,
                                 parse_module, unused_suppressions)
from apex1_tpu.lint.project import Project, build_project  # noqa: F401
from apex1_tpu.lint.rules import RULES

__all__ = ["Finding", "LintResult", "RULES", "RULE_SLUGS",
           "canonical_rule", "collect_files", "lint_files",
           "lint_paths", "lint_sources", "module_name_for"]

_SKIP_DIRS = {"__pycache__", ".git", ".pytest_cache", "build", "dist",
              ".claude"}

#: bump when ModuleSource/Suppression shapes change — stale caches are
#: discarded wholesale, never migrated.
_CACHE_VERSION = 1


@dataclasses.dataclass
class LintResult:
    findings: List[Finding]
    n_files: int
    unused: List[Tuple[str, int, str]]   # (path, line, rules) — info only
    kernels: bool = False                # APX2xx family included?
    protocols: bool = False              # APX3xx family included?

    def unsuppressed(self) -> List[Finding]:
        return [f for f in self.findings if not f.suppressed]

    def suppressed(self) -> List[Finding]:
        return [f for f in self.findings if f.suppressed]

    @property
    def ok(self) -> bool:
        return not self.unsuppressed()

    def as_dict(self) -> dict:
        per_rule: Dict[str, int] = {}
        for f in self.unsuppressed():
            per_rule[f.rule] = per_rule.get(f.rule, 0) + 1
        rules = list(RULES)
        if self.kernels:
            from apex1_tpu.lint.kernels import KERNEL_RULES
            rules = rules + list(KERNEL_RULES)
        if self.protocols:
            from apex1_tpu.lint.protocols import PROTOCOL_RULES
            rules = rules + list(PROTOCOL_RULES)
        return {
            "tool": "graftlint",
            "rules": {r.code: {"slug": r.slug, "summary": r.summary}
                      for r in rules},
            "n_files": self.n_files,
            "ok": self.ok,
            "counts": {"unsuppressed": len(self.unsuppressed()),
                       "suppressed": len(self.suppressed()),
                       "per_rule": per_rule},
            "findings": [f.as_dict() for f in self.findings],
            "unused_suppressions": [
                {"path": p, "line": ln, "rules": r}
                for p, ln, r in self.unused],
        }


def module_name_for(path: str, root: Optional[str] = None) -> str:
    """Dotted module name for a file: ``apex1_tpu/ops/rope.py`` ->
    ``apex1_tpu.ops.rope``; unknown layouts get a best-effort name
    (only the ``apex1_tpu``-package names carry semantics)."""
    p = os.path.abspath(path)
    if root:
        try:
            rel = os.path.relpath(p, os.path.abspath(root))
        except ValueError:
            rel = os.path.basename(p)
    else:
        # find the package root by walking up from an apex1_tpu segment
        parts = p.split(os.sep)
        rel = os.sep.join(parts[parts.index("apex1_tpu"):]) \
            if "apex1_tpu" in parts else os.path.basename(p)
    rel = rel[:-3] if rel.endswith(".py") else rel
    name = rel.replace(os.sep, ".")
    if name.endswith(".__init__"):
        name = name[:-len(".__init__")]
    elif name == "__init__":
        name = ""
    return name


def collect_files(paths: Sequence[str],
                  root: Optional[str] = None) -> List[str]:
    files: List[str] = []
    for p in paths:
        full = os.path.join(root, p) if root and not os.path.isabs(p) \
            else p
        if os.path.isfile(full):
            if full.endswith(".py"):
                files.append(full)
            continue
        for dirpath, dirnames, filenames in os.walk(full):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in _SKIP_DIRS)
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    files.append(os.path.join(dirpath, fn))
    return files


def _display_path(path: str, root: Optional[str]) -> str:
    if not root:
        return path
    try:
        rel = os.path.relpath(os.path.abspath(path),
                              os.path.abspath(root))
    except ValueError:
        return path
    return path if rel.startswith("..") else rel


# ---------------------------------------------------------------------------
# on-disk cache, two tiers, both keyed by (mtime_ns, size):
#
#   runs     {(kernels, protocols, root): (sig_vector, pickled LintResult)}
#            — whole-run memo. When NO file in the target set changed,
#            the banked result is returned without unpickling a single
#            AST: the repo-wide no-change run costs one stat() per file.
#   entries  {abspath: ((mtime_ns, size), ModuleSource)} — per-file
#            parse memo for incremental runs, stored as a nested pickle
#            blob so the fast path above never pays its deserialize.
#
# Wrong, stale, or corrupt caches are silently IGNORED (fail-open to a
# fresh parse); writes are atomic and best-effort. The known limit of
# the key: editing a file within one mtime granule while preserving its
# size defeats both tiers — same contract as ccache/mypy.
# ---------------------------------------------------------------------------

_CACHE_ERRS = (OSError, pickle.PickleError, EOFError, AttributeError,
               ImportError, IndexError, TypeError)


def _load_cache(path: Optional[str]) -> Tuple[Dict, Optional[bytes]]:
    """-> (runs, entries_blob). The blob stays opaque bytes here —
    ``_entries_from_blob`` deserializes it only on a run-memo miss."""
    if not path:
        return {}, None
    try:
        with open(path, "rb") as fh:
            data = pickle.load(fh)
        if (isinstance(data, dict)
                and data.get("version") == _CACHE_VERSION
                and isinstance(data.get("runs"), dict)
                and isinstance(data.get("entries_blob"),
                               (bytes, type(None)))):
            return data["runs"], data["entries_blob"]
    except _CACHE_ERRS:
        pass
    return {}, None


def _entries_from_blob(blob: Optional[bytes]) -> Dict:
    if not blob:
        return {}
    try:
        entries = pickle.loads(blob)
        if isinstance(entries, dict):
            return entries
    except _CACHE_ERRS:
        pass
    return {}


def _save_cache(path: Optional[str], runs: Dict, entries: Dict) -> None:
    if not path:
        return
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        blob = pickle.dumps(entries, protocol=pickle.HIGHEST_PROTOCOL)
        with open(tmp, "wb") as fh:
            pickle.dump({"version": _CACHE_VERSION, "runs": runs,
                         "entries_blob": blob},
                        fh, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, path)
    except _CACHE_ERRS:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _reset_run_state(mod: ModuleSource) -> None:
    """Suppression `used` bits and error-finding suppression flags are
    per-RUN state mutated by apply_suppressions — a cache-hit module
    must start the run pristine."""
    for sup in mod.suppressions:
        sup.used = False
    for f in mod.errors:
        f.suppressed = False
        f.reason = None


def lint_files(files: Sequence[str], root: Optional[str] = None,
               kernels: bool = False, protocols: bool = False,
               cache: Optional[str] = None) -> LintResult:
    runs, blob = _load_cache(cache)
    run_key = (bool(kernels), bool(protocols),
               os.path.abspath(root) if root else "")

    # tier 1: whole-run memo — one stat() per file, no AST unpickle
    sigs: List[Tuple[str, Tuple[int, int]]] = []
    for f in files:
        try:
            st = os.stat(f)
        except OSError:
            sigs = []
            break
        sigs.append((os.path.abspath(f),
                     (int(st.st_mtime_ns), int(st.st_size))))
    sig_vector = tuple(sigs)
    if cache and sigs:
        hit = runs.get(run_key)
        if hit is not None and hit[0] == sig_vector:
            try:
                res = pickle.loads(hit[1])
                if isinstance(res, LintResult):
                    return res
            except _CACHE_ERRS:
                pass

    # tier 2: per-file parse memo
    cached = _entries_from_blob(blob)
    entries: Dict = {}
    mods: List[ModuleSource] = []
    unreadable: List[Finding] = []
    for f in files:
        disp = _display_path(f, root)
        key = os.path.abspath(f)
        try:
            st = os.stat(f)
        except OSError as e:
            unreadable.append(Finding("APX001", disp, 1, 0,
                                      f"cannot read file: {e}"))
            continue
        sig = (int(st.st_mtime_ns), int(st.st_size))
        hit = cached.get(key)
        if hit is not None and hit[0] == sig and hit[1].path == disp:
            mod = hit[1]
            _reset_run_state(mod)
            mods.append(mod)
            entries[key] = hit
            continue
        try:
            with open(f, "r", encoding="utf-8") as fh:
                text = fh.read()
        except OSError as e:
            unreadable.append(Finding("APX001", disp, 1, 0,
                                      f"cannot read file: {e}"))
            continue
        mod = parse_module(disp, text, module_name_for(f, root))
        mods.append(mod)
        entries[key] = (sig, mod)
    res = _lint_modules(mods, kernels=kernels, protocols=protocols)
    res.findings.extend(unreadable)
    if cache:
        if sigs and not unreadable:
            runs[run_key] = (
                sig_vector,
                pickle.dumps(res, protocol=pickle.HIGHEST_PROTOCOL))
        _save_cache(cache, runs, entries)
    return res


def lint_paths(paths: Sequence[str], root: Optional[str] = None,
               kernels: bool = False, protocols: bool = False,
               cache: Optional[str] = None) -> LintResult:
    return lint_files(collect_files(paths, root), root,
                      kernels=kernels, protocols=protocols, cache=cache)


def lint_sources(named_sources: Dict[str, Tuple[str, str]],
                 kernels: bool = False,
                 protocols: bool = False) -> LintResult:
    """``{path: (modname, text)}`` -> LintResult. The in-memory entry
    point the tests drive fixtures through. ``kernels=True`` adds the
    APX2xx kernel/collective analyzer, ``protocols=True`` the APX3xx
    serving-protocol model checker."""
    mods = [parse_module(path, text, modname)
            for path, (modname, text) in named_sources.items()]
    return _lint_modules(mods, kernels=kernels, protocols=protocols)


def _lint_modules(mods: Sequence[ModuleSource], kernels: bool = False,
                  protocols: bool = False) -> LintResult:
    project = Project(list(mods))
    by_path: Dict[str, ModuleSource] = {m.path: m
                                        for m in project.modules}
    findings: List[Finding] = []
    for mod in project.modules:
        findings.extend(mod.errors)
    for rule in RULES:
        findings.extend(rule.check(project))
    if kernels:
        from apex1_tpu.lint.kernels import check_kernels
        findings.extend(check_kernels(project))
    if protocols:
        from apex1_tpu.lint.protocols import check_protocols
        findings.extend(check_protocols(project))
    out: List[Finding] = []
    for f in findings:
        mod = by_path.get(f.path)
        if mod is not None:
            apply_suppressions(mod, [f])
        out.append(f)
    out.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    unused = []
    for mod in project.modules:
        for s in unused_suppressions(mod):
            unused.append((mod.path, s.line, ",".join(s.rules)))
    return LintResult(findings=out, n_files=len(project.modules),
                      unused=unused, kernels=kernels,
                      protocols=protocols)
