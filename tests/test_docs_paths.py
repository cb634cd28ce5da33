"""The documents name what exists.

Two lints over the prose a reader starts from: every backticked path to a
source file resolves, and the user guide's "Environment knobs" table and
the ``DSLIB_*`` names the code reads agree both ways.  ``PERF.md``,
``CHANGES.md`` and ``ROADMAP.md`` are history (they cite scratch files and
deleted ones) and are not linted."""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "docs/user_guide.md", "docs/design.md",
        "docs/migration.md"]
# where a document's relative path may be anchored
ANCHORS = ["", "dislib_tpu", "tests", "docs"]

_TICKED = re.compile(r"`([^`\n]+)`")
# a repo-relative path to a source file, with or without ::symbol or :line
_PATH = re.compile(r"^([\w.-]+(?:/[\w.-]+)*\.(?:py|json|md|sh))"
                   r"(?:::[\w.:]+|:\d+(?:[-–]\d+)?)?$")
_KNOB = re.compile(r"DSLIB_[A-Z0-9_]+")
_KNOB_LITERAL = re.compile(r"""["'](DSLIB_[A-Z0-9_]+)["']""")


def _read(rel):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
        return f.read()


def _path_tokens(text):
    for m in _TICKED.finditer(text):
        token = m.group(1).strip()
        if token.startswith("git show "):       # `git show <rev>:<path>`
            continue
        hit = _PATH.match(token)
        if hit:
            yield hit.group(1)


@pytest.mark.parametrize("doc", DOCS)
def test_backticked_paths_exist(doc):
    paths = sorted(set(_path_tokens(_read(doc))))
    missing = [p for p in paths
               if not any(os.path.isfile(os.path.join(ROOT, a, p))
                          for a in ANCHORS)]
    assert not missing, f"{doc} names files that do not exist: {missing}"


def _sources(*rels):
    for rel in rels:
        top = os.path.join(ROOT, rel)
        if os.path.isfile(top):
            yield rel
            continue
        for d, _, files in os.walk(top):
            for f in files:
                if f.endswith((".py", ".sh")):
                    yield os.path.relpath(os.path.join(d, f), ROOT)


def _knobs_read(*rels):
    """``DSLIB_*`` names that code under ``rels`` reads: a whole quoted
    literal in Python (as in ``os.environ.get("<name>")``,
    ``_env_int("<name>", 8)``, ``"<name>" in env``), any mention in a
    shell script."""
    names = set()
    for rel in _sources(*rels):
        pat = _KNOB if rel.endswith(".sh") else _KNOB_LITERAL
        names.update(pat.findall(_read(rel)))
    return names


def _knob_table():
    text = _read("docs/user_guide.md")
    table = text[text.index("\n## Environment knobs"):]
    rows = [ln.split("|")[1] for ln in table.splitlines()
            if ln.startswith("| `")]
    return {name for row in rows for name in _KNOB.findall(row)}


def test_every_documented_knob_is_read():
    read = _knobs_read("dislib_tpu", "benchmark", "tools", "chip_smoke.py",
                       "tests/conftest.py")
    stale = sorted(_knob_table() - read)
    assert not stale, f"the knob table lists names nothing reads: {stale}"


def test_every_knob_the_package_reads_is_documented():
    missing = sorted(_knobs_read("dislib_tpu") - _knob_table())
    assert not missing, \
        f"docs/user_guide.md's knob table lacks a row for: {missing}"
