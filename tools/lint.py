#!/usr/bin/env python
"""graftlint CLI — the repo's static JAX-hazard gate.

Usage::

    python tools/lint.py                  # lint apex1_tpu/ tools/ examples/
    python tools/lint.py --kernels        # + APX2xx kernel/collective pass
    python tools/lint.py --protocols      # + APX3xx serving-protocol pass
    python tools/lint.py --json           # machine-readable (baseline bank)
    python tools/lint.py --changed        # only files changed vs merge-base
    python tools/lint.py path/to/file.py  # explicit targets
    python tools/lint.py --list-rules

Exit codes: 0 clean (suppressed findings are fine — each carries a
mandatory reason), 1 unsuppressed findings, 2 usage/internal error.

Parses AND whole-run results are cached in ``.graftlint_cache`` keyed
by (mtime_ns, size) so the repo-wide no-change rerun stays ~1s as the
tree grows (one stat per file); ``--no-cache`` disables it.

The gate also runs as the ``== graftlint ==`` step of
``tools/check_all.sh`` and inside tier-1 via
``tests/test_lint.py::test_repo_self_check``. Rule catalogue and the
suppression grammar: docs/lint.md.
"""

import argparse
import json
import os
import subprocess
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CACHE_PATH = os.path.join(REPO, ".graftlint_cache")


def _import_lint():
    """Import ``apex1_tpu.lint`` WITHOUT executing the package
    ``__init__`` (which imports jax —
    ~4s of startup the stdlib-ast linter doesn't need). A stub parent
    module with the real ``__path__`` lets the import machinery find
    the subpackage while skipping the parent's body. ``apex1_tpu.core``
    gets the same stub so the ``--kernels`` budget pass can read
    ``core.capability``'s generation table (itself jax-free; only
    chip *detection* touches jax, and the analyzer passes the planning
    generation explicitly) without running ``core/__init__``'s mesh
    imports. CLI-process-only: the lint subpackage and
    ``apex1_tpu.vmem_model`` import nothing else from apex1_tpu, and
    in-process users (tests, check_all's pytest) import the real
    package normally. tests/test_lint_kernels.py and
    tests/test_lint_protocols.py pin the whole CLI jax-free by running
    it against a poisoned ``jax`` module."""
    for name, sub in (("apex1_tpu", ""), ("apex1_tpu.core", "core")):
        if name not in sys.modules:
            stub = types.ModuleType(name)
            stub.__path__ = [os.path.join(REPO, "apex1_tpu", sub)
                             if sub else os.path.join(REPO, "apex1_tpu")]
            sys.modules[name] = stub
    import apex1_tpu.lint as lint
    return lint


DEFAULT_ROOTS = ["apex1_tpu", "tools", "examples"]

#: candidate refs for the --changed diff base, tried in order. The
#: point (vs plain HEAD): on a feature branch with commits, HEAD-only
#: diffing silently skips everything already committed on the branch —
#: the pre-commit gate must see the whole branch delta.
_BASE_REFS = ("@{upstream}", "origin/main", "origin/master", "main",
              "master")


def merge_base():
    """SHA of the merge-base of HEAD and the first resolvable base
    ref, or "HEAD" when none resolves (detached/fresh/remoteless
    repos keep the old vs-HEAD behavior)."""
    for ref in _BASE_REFS:
        try:
            proc = subprocess.run(
                ["git", "merge-base", "HEAD", ref], cwd=REPO,
                capture_output=True, text=True, check=True)
        except (subprocess.CalledProcessError, OSError):
            continue
        sha = proc.stdout.strip()
        if sha:
            return sha
    return "HEAD"


def changed_files(base=None):
    """Repo-relative .py files touched vs the merge-base (committed on
    the branch, staged, unstaged, and untracked) — the pre-commit
    scope."""
    base = merge_base() if base is None else base
    out = set()
    for args in (["git", "diff", "--name-only", base],
                 ["git", "ls-files", "--others", "--exclude-standard"]):
        try:
            proc = subprocess.run(args, cwd=REPO, capture_output=True,
                                  text=True, check=True)
        except (subprocess.CalledProcessError, OSError) as e:
            print(f"graftlint: --changed needs git: {e}",
                  file=sys.stderr)
            raise SystemExit(2)
        out.update(ln.strip() for ln in proc.stdout.splitlines()
                   if ln.strip())
    keep = []
    for f in sorted(out):
        if not f.endswith(".py"):
            continue
        top = f.split("/", 1)[0]
        if top in DEFAULT_ROOTS and os.path.exists(
                os.path.join(REPO, f)):
            keep.append(f)
    return keep


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="graftlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*",
                    help=f"files/dirs to lint (default: "
                         f"{' '.join(DEFAULT_ROOTS)})")
    ap.add_argument("--json", action="store_true",
                    help="emit the full JSON report on stdout")
    ap.add_argument("--changed", action="store_true",
                    help="lint only files changed vs the merge-base "
                         "(plus untracked) under the default roots")
    ap.add_argument("--kernels", action="store_true",
                    help="also run the APX2xx kernel/collective "
                         "analyzer (Pallas semaphore/DMA protocol "
                         "model-check, mesh consistency, VMEM budget)")
    ap.add_argument("--protocols", action="store_true",
                    help="also run the APX3xx serving-protocol model "
                         "checker (bounded exhaustive exploration of "
                         "the scheduler/replica/frontend/disagg/"
                         "autopilot state machines)")
    ap.add_argument("--no-cache", action="store_true",
                    help="disable the on-disk (mtime,size) parse cache")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--show-suppressed", action="store_true",
                    help="also print suppressed findings (text mode)")
    args = ap.parse_args(argv)

    lint = _import_lint()

    if args.list_rules:
        from apex1_tpu.lint.kernels import KERNEL_RULES
        from apex1_tpu.lint.protocols import PROTOCOL_RULES
        for r in (list(lint.RULES) + list(KERNEL_RULES)
                  + list(PROTOCOL_RULES)):
            print(f"{r.code}  {r.slug:16s} {r.summary}")
        return 0

    cache = None if args.no_cache else CACHE_PATH
    if args.changed:
        if args.paths:
            ap.error("--changed and explicit paths are exclusive")
        files = changed_files()
        if not files:
            if not args.json:
                print("graftlint: no changed .py files under "
                      + ", ".join(DEFAULT_ROOTS))
            else:
                print(json.dumps({"tool": "graftlint", "ok": True,
                                  "n_files": 0, "findings": []}))
            return 0
        res = lint.lint_files([os.path.join(REPO, f) for f in files],
                              root=REPO, kernels=args.kernels,
                              protocols=args.protocols, cache=cache)
    else:
        # fail CLOSED on bad targets: a typoed path in a CI job must
        # not read as a passing gate forever
        for p in args.paths:
            full = p if os.path.isabs(p) else os.path.join(REPO, p)
            if not os.path.exists(full):
                print(f"graftlint: no such path: {p}", file=sys.stderr)
                return 2
        res = lint.lint_paths(args.paths or DEFAULT_ROOTS, root=REPO,
                              kernels=args.kernels,
                              protocols=args.protocols, cache=cache)
        if args.paths and res.n_files == 0:
            print("graftlint: the given paths contain no .py files",
                  file=sys.stderr)
            return 2

    if args.json:
        print(json.dumps(res.as_dict(), indent=2))
        return 0 if res.ok else 1

    shown = res.findings if args.show_suppressed else res.unsuppressed()
    for f in shown:
        print(f.render())
    for path, line, rules in res.unused:
        print(f"{path}:{line}: note: unused suppression for {rules}")
    n_bad = len(res.unsuppressed())
    n_sup = len(res.suppressed())
    print(f"graftlint: {res.n_files} files, {n_bad} finding"
          f"{'s' if n_bad != 1 else ''}"
          f" ({n_sup} suppressed with reasons)")
    return 0 if res.ok else 1


if __name__ == "__main__":
    sys.exit(main())
