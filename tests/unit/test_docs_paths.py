"""The documents a reader is sent to first name only files that exist."""

import os
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
_PATH = re.compile(r"^[\w.\-/]+$")
_SUFFIXES = (".py", ".json", ".jsonl", ".md", ".sh", ".cpp")


def _made_at_run_time():
    """Directories ``.gitignore`` lists: what a run leaves behind."""
    lines = (REPO / ".gitignore").read_text().split()
    return tuple(line for line in lines if line.endswith("/"))


def _checkout_files(ignored):
    """Every file of the checkout as ``/a/b/c.py``, run-time directories
    and ``.git`` left out."""
    skip = {d.rstrip("/") for d in ignored} | {".git"}
    files = []
    for root, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip]
        files += ["/" + os.path.relpath(os.path.join(root, n), REPO)
                  for n in names]
    return files


def _without_reference_column(text):
    """A table with a ``Reference`` column (README's component map) names
    the upstream project's files there: that column is not this checkout."""
    out, drop = [], None
    for line in text.splitlines():
        cells = line.split("|")
        if not line.startswith("|"):
            drop = None
        elif drop is None:
            heads = [c.strip() for c in cells]
            drop = heads.index("Reference") if "Reference" in heads else -1
        if drop is not None and 0 < drop < len(cells):
            cells[drop] = ""
        out.append("|".join(cells))
    return "\n".join(out)


def _named_paths(text):
    """Backticked paths: directories (``a/``, ``a/b/``) and files by suffix
    (``a/b.py``, ``b.json``), as the documents write them: relative to the
    root or to the package directory they are talking about."""
    for token in re.findall(r"`([^`\n]+)`", _without_reference_column(text)):
        token = token.split("::")[0].split(":")[0].strip()
        if not _PATH.match(token) or token.startswith(("/", "-", ".")):
            continue
        if token.endswith(_SUFFIXES + ("/",)):
            yield token


@pytest.mark.parametrize("doc", ["README.md",
                                 ".claude/skills/verify/SKILL.md"])
def test_docs_name_files_that_exist(doc):
    ignored = _made_at_run_time()
    files = _checkout_files(ignored)
    missing = []
    for path in sorted(set(_named_paths((REPO / doc).read_text()))):
        if path.startswith(ignored):
            continue
        if path.endswith("/"):
            found = any(f"/{path}" in f for f in files)
        else:
            found = any(f.endswith(f"/{path}") for f in files)
        if not found:
            missing.append(path)
    assert not missing, f"{doc} names files that are not in the checkout"
