"""The documents and the Makefile name only what is in the tree.

One case per tracked document a user is sent to (README, docs/,
docs/tutorials/, PARITY.md, the verify skill) and one per Makefile
target: every repo-relative ``*.py`` path and every ``make <target>``
named there exists.  A deleted tool that a document still tells the
reader to run fails its document's case.
"""

import fnmatch
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

DOCUMENTS = (
    ["README.md", "PARITY.md", ".claude/skills/verify/SKILL.md"]
    + sorted(os.path.relpath(p, REPO) for pattern in
             ("docs/*.md", "docs/tutorials/*.md")
             for p in glob.glob(os.path.join(REPO, pattern)))
)

# Named in a document and rightly not in the tree.
NOT_OF_THIS_TREE = {
    "Events.py",  # pyDCOP's own file (PARITY.md's left column)
    # the file the tutorial's reader writes
    "pydcop_tpu/algorithms/mydsa.py",
}

# Left behind by building, testing and running (.gitignore).
UNTRACKED_DIRS = {".git", "__pycache__", ".cache", ".pytest_cache",
                  ".hypothesis", "chiprun_out", "_checkout", "_proof"}

PY_PATH = re.compile(r"(?<![\w./*{}-])((?:[\w.*-]+/)*[\w*-]+\.py)\b")
# `make target` in a code span, or at the start of a line of a block.
MAKE_TARGET = re.compile(r"(?:^|`)make ([a-z][\w-]*)", re.MULTILINE)
MAKEFILE_RULE = re.compile(r"^([.\w-]+):(.*)$")


@pytest.fixture(scope="module")
def tree():
    files = set()
    for root, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in UNTRACKED_DIRS]
        rel = os.path.relpath(root, REPO)
        files.update(os.path.normpath(os.path.join(rel, n))
                     for n in names)
    return files


def _makefile_rules():
    """``{target: (prerequisites, recipe text)}``."""
    rules, target = {}, None
    with open(os.path.join(REPO, "Makefile"), encoding="utf-8") as f:
        for line in f:
            rule = MAKEFILE_RULE.match(line)
            if rule:
                target = rule.group(1)
                rules[target] = (rule.group(2).split(), "")
            elif line.startswith("\t") and target:
                prereqs, recipe = rules[target]
                rules[target] = (prereqs, recipe + line)
            elif not line.startswith("#"):
                target = None
    return rules


RULES = _makefile_rules()


def _missing_paths(text, tree, beside=""):
    """The ``*.py`` paths of ``text`` that name no file: a path with
    a directory is looked up from the root, from the package and from
    the document's own directory; a bare file name anywhere."""
    names = {os.path.basename(f) for f in tree}
    missing = []
    for path in sorted(set(PY_PATH.findall(text)) - NOT_OF_THIS_TREE):
        if "/" not in path:
            found = fnmatch.filter(names, path)
        else:
            found = [f for base in ("", "pydcop_tpu", beside)
                     for f in fnmatch.filter(
                         tree, os.path.normpath(
                             os.path.join(base, path)))]
        if not found:
            missing.append(path)
    return missing


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_files_and_targets_in_the_tree(
        document, tree):
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        text = f.read()
    assert _missing_paths(
        text, tree, beside=os.path.dirname(document)) == []
    assert sorted(set(MAKE_TARGET.findall(text)) - set(RULES)) == []


@pytest.mark.parametrize("target", sorted(RULES))
def test_makefile_target_names_only_files_and_targets_in_the_tree(
        target, tree):
    prerequisites, recipe = RULES[target]
    assert _missing_paths(recipe, tree) == []
    called = re.findall(r"\$\(MAKE\) ([\w-]+)", recipe)
    assert sorted(set(prerequisites + called) - set(RULES)) == []


def test_the_guard_sees_a_deleted_tool(tree):
    """A document that names a tool no longer in the tree, a bare
    name and a path, and a target the Makefile lacks."""
    text = ("run `python no_such_tool.py`, then "
            "`tools/no_such_gate.py`;\nmake no-such-target\n"
            "`engine/compile.py` and `tools/*_smoke.py` are there")
    assert _missing_paths(text, tree) == [
        "no_such_tool.py", "tools/no_such_gate.py"]
    assert set(MAKE_TARGET.findall(text)) - set(RULES) == {
        "no-such-target"}
