"""graftlint core: findings, the suppression grammar, per-file parsing.

The linter is stdlib-``ast`` only (no new deps) so it runs anywhere the
repo does — including the no-TPU CI image. Everything here is
runtime-free: no jax import, no device touch.

Suppression grammar (per line, reason MANDATORY)::

    x = jax.device_get(y)  # graftlint: allow(APX101) -- metrics drain, off hot path
    # graftlint: allow(prng-reuse, APX102) -- fixture: intentional reuse
    y = jax.random.normal(key)

A suppression comment on a code line covers findings anchored to that
line; a comment-ONLY line covers the next line (for lines too long to
carry the comment). Rules are named by code (``APX101``) or slug
(``host-sync``). A malformed suppression — missing ``--``, empty
reason, unknown rule — is itself a finding (``APX000 bad-suppression``)
and cannot be suppressed: the grammar is the audit trail, so it must
stay parseable.

Reachability markers (same placement rules)::

    def _debug_dump(...):   # graftlint: cold -- host-side debug helper
    def _step_body(...):    # graftlint: hot -- driven by the serving loop

``hot`` force-marks a function as traced (linted as a jit body) when
the call graph can't see the connection; ``cold`` severs it (e.g. a
callback that only ever runs under ``jax.pure_callback``). Both take a
mandatory reason too — a reachability override is as load-bearing as a
suppression.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import re
import tokenize
from typing import Dict, List, Optional, Set, Tuple

#: rule code -> slug. The registry in ``rules/__init__.py`` holds the
#: checker callables; this table exists so suppressions can be validated
#: without importing the rule modules (core must not depend on rules).
RULE_SLUGS: Dict[str, str] = {
    "APX000": "bad-suppression",
    "APX001": "parse-error",
    "APX101": "host-sync",
    "APX102": "retrace",
    "APX103": "prng-reuse",
    "APX104": "donation",
    # APX2xx: the kernel/collective analyzer (lint/kernels/, opt-in
    # via lint_*(kernels=True) / `tools/lint.py --kernels`)
    "APX201": "sem-protocol",
    "APX202": "dma-race",
    "APX203": "kernel-hang",
    "APX204": "ring-guard",
    "APX205": "ppermute-perm",
    "APX206": "axis-binding",
    "APX207": "exclusive-knobs",
    "APX208": "vmem-budget",
    "APX209": "kernel-binding",
    # APX3xx: the serving control-plane protocol model checker
    # (lint/protocols/, opt-in via lint_*(protocols=True) /
    # `tools/lint.py --protocols`)
    "APX301": "protocol-model",
    "APX302": "double-decode",
    "APX303": "qos-inversion",
    "APX304": "cancel-resurrect",
    "APX305": "stranded-result",
    "APX306": "capacity-leak",
    "APX307": "ladder",
    "APX308": "unbanked-transition",
}

_SLUG_TO_CODE = {v: k for k, v in RULE_SLUGS.items()}


def canonical_rule(token: str) -> Optional[str]:
    """``'APX101'`` or ``'host-sync'`` -> ``'APX101'``; None if unknown."""
    token = token.strip()
    up = token.upper()
    if up in RULE_SLUGS:
        return up
    return _SLUG_TO_CODE.get(token.lower())


@dataclasses.dataclass
class Finding:
    rule: str                    # "APX101"
    path: str                    # repo-relative where possible
    line: int
    col: int
    message: str
    suppressed: bool = False
    reason: Optional[str] = None  # the suppression's reason when suppressed

    @property
    def slug(self) -> str:
        return RULE_SLUGS.get(self.rule, "?")

    def as_dict(self) -> dict:
        d = {"rule": self.rule, "slug": self.slug, "path": self.path,
             "line": self.line, "col": self.col, "message": self.message,
             "suppressed": self.suppressed}
        if self.reason is not None:
            d["reason"] = self.reason
        return d

    def render(self) -> str:
        tag = " [suppressed]" if self.suppressed else ""
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"({self.slug}) {self.message}{tag}")


@dataclasses.dataclass
class Suppression:
    line: int                    # line the comment sits on
    target_line: int             # line whose findings it covers
    rules: Tuple[str, ...]       # canonical codes
    reason: str
    used: bool = False


_DIRECTIVE = re.compile(r"#\s*graftlint:\s*(.*)$")
_ALLOW = re.compile(r"allow\(([^)]*)\)\s*(?:--\s*(.*))?$")
_MARKER = re.compile(r"(hot|cold)\b\s*(?:--\s*(.*))?$")


@dataclasses.dataclass
class ModuleSource:
    """One parsed file plus its suppression/marker side tables."""

    path: str                    # as given (display)
    modname: str                 # dotted module name, "" if unknown
    text: str
    tree: Optional[ast.Module]
    suppressions: List[Suppression]
    hot_lines: Dict[int, int]    # marker target line -> comment line
    cold_lines: Dict[int, int]   # marker target line -> comment line
    errors: List[Finding]        # APX000/APX001 raised during parse

    def suppression_for(self, finding: Finding) -> Optional[Suppression]:
        for sup in self.suppressions:
            if sup.target_line == finding.line and finding.rule in sup.rules:
                return sup
        return None


def _comment_lines(text: str):
    """Yield (line, col, comment_text, target_line) via tokenize — the
    only way to find comments without tripping on '#' inside strings.

    ``target_line`` is the line a directive on this comment governs:
    the comment's own line when code precedes it, otherwise the next
    line that carries CODE (a standalone directive above a def may be
    followed by more comment lines before the def itself)."""
    code_lines: Set[int] = set()
    comments: List[Tuple[int, int, str]] = []
    try:
        for tok in tokenize.generate_tokens(io.StringIO(text).readline):
            if tok.type == tokenize.COMMENT:
                comments.append((tok.start[0], tok.start[1], tok.string))
            elif tok.type not in (tokenize.NL, tokenize.NEWLINE,
                                  tokenize.INDENT, tokenize.DEDENT,
                                  tokenize.ENCODING, tokenize.ENDMARKER):
                code_lines.add(tok.start[0])
    except (tokenize.TokenError, IndentationError, SyntaxError):
        pass  # the ast parse reports the real error
    for line, col, comment in comments:
        if line in code_lines:
            target = line
        else:
            later = [ln for ln in code_lines if ln > line]
            target = min(later) if later else line + 1
        yield line, col, comment, target


def parse_module(path: str, text: str, modname: str = "") -> ModuleSource:
    errors: List[Finding] = []
    try:
        tree = ast.parse(text, filename=path)
    except SyntaxError as e:
        errors.append(Finding("APX001", path, e.lineno or 1,
                              e.offset or 0, f"syntax error: {e.msg}"))
        tree = None

    suppressions: List[Suppression] = []
    hot_lines: Dict[int, int] = {}
    cold_lines: Dict[int, int] = {}
    for line, col, comment, target in _comment_lines(text):
        m = _DIRECTIVE.search(comment)
        if not m:
            continue
        body = m.group(1).strip()
        am = _ALLOW.match(body)
        if am:
            raw_rules = [t for t in (s.strip() for s in
                                     am.group(1).split(",")) if t]
            reason = (am.group(2) or "").strip()
            codes = []
            bad = None
            for tok in raw_rules:
                code = canonical_rule(tok)
                if code is None:
                    bad = f"unknown rule {tok!r}"
                    break
                codes.append(code)
            if not raw_rules:
                bad = "allow() names no rules"
            if not reason:
                bad = bad or "missing '-- reason' (reason is mandatory)"
            if bad:
                errors.append(Finding(
                    "APX000", path, line, col,
                    f"bad suppression: {bad} in {comment.strip()!r}"))
                continue
            suppressions.append(Suppression(line=line, target_line=target,
                                            rules=tuple(codes),
                                            reason=reason))
            continue
        mm = _MARKER.match(body)
        if mm:
            reason = (mm.group(2) or "").strip()
            if not reason:
                errors.append(Finding(
                    "APX000", path, line, col,
                    f"bad marker: '{mm.group(1)}' needs '-- reason'"))
                continue
            (hot_lines if mm.group(1) == "hot" else
             cold_lines)[target] = line
            continue
        errors.append(Finding(
            "APX000", path, line, col,
            f"unrecognized graftlint directive {body!r} "
            f"(expected allow(RULE,...) -- reason, hot -- reason, "
            f"or cold -- reason)"))
    return ModuleSource(path=path, modname=modname, text=text, tree=tree,
                        suppressions=suppressions, hot_lines=hot_lines,
                        cold_lines=cold_lines, errors=errors)


def apply_suppressions(mod: ModuleSource,
                       findings: List[Finding]) -> List[Finding]:
    """Mark findings covered by a suppression; APX000/APX001 never
    suppress (they ARE the suppression machinery's own errors)."""
    out = []
    for f in findings:
        if f.rule not in ("APX000", "APX001"):
            sup = mod.suppression_for(f)
            if sup is not None:
                f.suppressed = True
                f.reason = sup.reason
                sup.used = True
        out.append(f)
    return out


def unused_suppressions(mod: ModuleSource) -> List[Suppression]:
    return [s for s in mod.suppressions if not s.used]
