"""The prose names only files that exist. For README.md, each file under
docs/ and the verify skill: every path a document names — a `.py`,
`.sh`, `.json` or `.md` under one of this repo's directories, or a bare
script name in backticks or a code block — is a file of this checkout.
(A file of the REFERENCE, which the documents map from, counts where
SURVEY.md, its inventory, names it.) It is what keeps a deleted tool
deleted in the documents that sent readers to it.

A `*`, a `<placeholder>` or a `{a,b}` in a path is matched as a glob; a
`::test` or `:line` behind a path is not part of it. A bare `name.py` or
`name.sh` (no directory in front: `engine.py`, `chip_smoke.py`) must be
the name of some file in the repo.
"""

import functools
import glob
import itertools
import os
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
DOCS = ([REPO / "README.md"] + sorted((REPO / "docs").glob("*.md"))
        + [REPO / ".claude" / "skills" / "verify" / "SKILL.md"])

_DIRS = "tools|apex1_tpu|benchmark|tests|examples|perf_results|docs"
_FENCE = re.compile(r"^```.*?^```", re.S | re.M)
_SPAN = re.compile(r"``([^`]+)``|`([^`]+)`")
_ROOTED = re.compile(
    rf"(?<![\w/.-])(?:{_DIRS})/[\w./*<>{{}},-]*\.(?:py|sh|json|md)\b")
_BARE = re.compile(r"(?<![\w/.<>*{}-])[\w-]+\.(?:py|sh)\b")
_SKIP_DIRS = {".git", ".jax_cache", "_checkout", "chiprun_out",
              "__pycache__"}


def _braces(path):
    """`a/{b,c}.py` -> [`a/b.py`, `a/c.py`]."""
    parts = re.split(r"\{([^{}]*)\}", path)
    choices = [p.split(",") if i % 2 else [p] for i, p in enumerate(parts)]
    return ["".join(c) for c in itertools.product(*choices)]


@functools.cache
def _known():
    """(names of the repo's scripts, the reference's inventory)."""
    names = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = set(dirs) - _SKIP_DIRS       # not walked
        names.update(f for f in files if f.endswith((".py", ".sh")))
    return names, (REPO / "SURVEY.md").read_text()


def _missing(doc):
    text, (names, reference) = doc.read_text(), _known()
    gone = [path for m in _ROOTED.finditer(text)
            for path in _braces(re.sub(r"<[^<>]*>", "*", m.group(0)))
            if not glob.glob(str(REPO / path)) and path not in reference]
    code = _FENCE.findall(text) + [
        a or b for a, b in _SPAN.findall(_FENCE.sub("", text))]
    gone += [m.group(0) for span in code
             for m in _BARE.finditer(_ROOTED.sub(" ", span))
             if m.group(0) not in names and m.group(0) not in reference]
    return sorted(set(gone))


@pytest.mark.parametrize("doc", DOCS,
                         ids=[str(d.relative_to(REPO)) for d in DOCS])
def test_every_path_a_document_names_exists(doc):
    assert _missing(doc) == [], f"{doc.relative_to(REPO)} names files " \
        f"that are not in the repo"
