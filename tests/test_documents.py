"""The documents name only what is in the tree.

A document that sends its reader to a file or a make target that is gone
costs more than no document (PR 44 found 32 mentions of a harness nobody
ran).  Every path a document quotes under the repo's own directories, every
bare ``name.py`` and every ``make <target>`` must resolve.  History (what a
PR deleted) belongs in CHANGES.md, ROADMAP.md and PERF.md, which are not
checked.

No jax, no shadow_tpu: text against the tree.
"""

import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

DOCUMENTS = (
    ["README.md", "Makefile", ".claude/skills/verify/SKILL.md"]
    + sorted(p.relative_to(REPO).as_posix() for p in (REPO / "docs").glob("*.md"))
)

#: the directories a quoted path is checked under (and searched for a bare
#: ``name.py``): what git tracks, never a run's leavings
ROOTS = ("scripts", "tests", "benchmarks", "examples", "docs", "shadow_tpu")

_SPAN = re.compile(r"`([^`\n]+)`")
_FENCE = re.compile(r"^```.*?^```", re.M | re.S)
_MAKE = re.compile(r"(?:\bmake|\$\(MAKE\))((?:\s+-[A-Za-z]+(?:\s+[\w./]+)?)*)\s+([a-z][\w-]*)")
_TARGET = re.compile(r"^([A-Za-z][\w-]*)\s*:(?!=)", re.M)


def _spans(name: str, text: str) -> list[str]:
    """What a document quotes: for markdown, its back-quoted spans and the
    lines of its fenced blocks; for the Makefile, every line."""
    if name == "Makefile":
        return text.splitlines()
    fenced = [ln for blk in _FENCE.findall(text) for ln in blk.splitlines()]
    return fenced + _SPAN.findall(_FENCE.sub("", text))


def _tree_names() -> set[str]:
    names = {p.name for p in REPO.glob("*.py")}
    for root in ROOTS + ("native",):
        names |= {p.name for p in (REPO / root).rglob("*.py")}
    return names


def _targets(makefile: Path) -> set[str]:
    return set(_TARGET.findall(makefile.read_text()))


def _path_fault(token: str) -> str | None:
    """None when ``token`` (``path[:line[-line]]`` or ``path::test[::test]``)
    resolves, else what does not."""
    path, _, test = token.partition("::")
    m = re.fullmatch(r"(.+?):(\d+)(?:[-–,]\s*\d+)*", path)
    line = None
    if m:
        path, line = m.group(1), int(m.group(2))
    if any(c in path for c in "<>{}$"):
        return None  # a pattern for the reader to fill in, not a name
    if "*" in path:
        return None if list(REPO.glob(path)) else f"{path} matches nothing"
    target = REPO / path
    if not target.exists():
        return f"{path} is not in the tree"
    if line is not None and line > len(target.read_text().splitlines()):
        return f"{path} has no line {line}"
    if test:
        leaf = test.split("::")[-1].split("[")[0]
        if not re.search(rf"\b(?:def|class) {re.escape(leaf)}\b", target.read_text()):
            return f"{path} has no {leaf}"
    return None


@pytest.mark.parametrize("name", DOCUMENTS)
def test_documents_name_only_files_that_exist(name):
    text = (REPO / name).read_text()
    tree_names = _tree_names()
    faults = []
    for span in _spans(name, text):
        for flags, target in _MAKE.findall(span):
            sub = re.search(r"-C\s+(\S+)", flags)
            makefile = REPO / (sub.group(1) if sub else "") / "Makefile"
            if not makefile.exists() or target not in _targets(makefile):
                faults.append(f"make {flags.strip()} {target}: no such target")
        for word in span.split():
            word = word.strip("\"'()[],;.:")
            if word.startswith(tuple(r + "/" for r in ROOTS)):
                fault = _path_fault(word)
                if fault:
                    faults.append(fault)
            elif re.fullmatch(r"[\w.-]+\.py", word) and word not in tree_names:
                faults.append(f"{word}: no file of that name in the tree")
    assert faults == []
