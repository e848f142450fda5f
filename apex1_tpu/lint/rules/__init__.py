"""graftlint rule registry.

A rule is ``(code, slug, summary, check)`` where ``check(project) ->
list[Finding]``. Rules see the whole :class:`~apex1_tpu.lint.project.
Project` (hot set, jit sites, import aliases) and must anchor each
finding to the line of the offending node so per-line suppressions
land. To add a rule: write ``check`` in a new module here, register the
code/slug in ``core.RULE_SLUGS``, append to ``RULES``, document it in
``docs/lint.md``, and give it a positive + negative + suppressed
fixture in ``tests/test_lint.py`` (the self-check test will hold you to
a clean dogfood run).
"""

from __future__ import annotations

from typing import Callable, List, NamedTuple

from apex1_tpu.lint.core import Finding
from apex1_tpu.lint.project import Project
from apex1_tpu.lint.rules import donation, host_sync, prng, retrace


class Rule(NamedTuple):
    code: str
    slug: str
    summary: str
    check: Callable[[Project], List[Finding]]


RULES = [
    Rule("APX101", "host-sync",
         "host synchronization inside a traced/hot function",
         host_sync.check),
    Rule("APX102", "retrace",
         "retrace hazards: bad static_argnums/argnames, trace-time "
         "clocks and f-strings, python branches on traced values",
         retrace.check),
    Rule("APX103", "prng-reuse",
         "a PRNG key consumed twice without split/fold_in between",
         prng.check),
    Rule("APX104", "donation",
         "a donate_argnums buffer read after the donating call",
         donation.check),
]
