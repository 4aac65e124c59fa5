"""The documents name only what the tree holds.

One case a document: every back-ticked token that names a file of this
repo (``*.py``, ``*.sh``, ``*.json``, ``*.md``; a path from the root, a
package-relative one such as ``serve/batcher.py``, or a bare file name)
resolves in the tree.  And one for the README's environment reference:
every back-ticked all-caps name there is one the program reads.  A
harness, record or variable that is deleted with its mentions left behind
fails here.  ``ROADMAP.md``, ``CHANGES.md`` and ``PERF.md`` are out of it:
they speak of history.  Nothing here touches jax."""

import ast
import functools
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "llm_weighted_consensus_tpu"
DOCUMENTS = (
    "README.md",
    "DESIGN.md",
    "KNOWN_FAILURES.md",
    "PARITY.md",
    ".claude/skills/verify/SKILL.md",
)
# directories a checkout grows by running, never by committing
SKIPPED_DIRS = {
    ".git", "__pycache__", ".pytest_cache", ".hypothesis", ".jax_cache",
    "chiprun_out", "_chipcheck", "_benchcheck", ".bench_work",
}
SPAN = re.compile(r"`([^`\n]+)`")
FILE = re.compile(r"[A-Za-z0-9_.][A-Za-z0-9_./-]*\.(?:py|sh|json|md)")
KNOB = re.compile(r"[A-Z][A-Z0-9]*(?:_[A-Z0-9]+)+")


@functools.lru_cache(maxsize=None)
def tree_files() -> frozenset:
    out = set()
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in SKIPPED_DIRS]
        rel = os.path.relpath(base, ROOT)
        for name in files:
            out.add(os.path.normpath(os.path.join(rel, name)))
    return frozenset(out)


def named_files(text: str) -> list:
    """The file names inside back-ticked spans: a span may hold a command
    line (`` `python bench/run.py --workload W` ``) or a test id
    (`` `tests/test_x.py::test_y` ``), so every word of it is looked at."""
    names = []
    for span in SPAN.findall(text):
        for word in span.split():
            word = word.split("::")[0].rstrip(".,;:)")
            if any(c in word for c in "*<>{}$[]|=") or word.startswith(
                ("/", "~", "-", "http")
            ):
                continue  # a wildcard, a placeholder or a path elsewhere
            if FILE.fullmatch(word):
                names.append(word)
    return names


def resolves(name: str, files: frozenset) -> bool:
    name = os.path.normpath(name)
    if name in files or os.path.join(PACKAGE, name) in files:
        return True
    # a trailing part of a path: `rules/lwc011_config_readme_drift.py`,
    # or a bare file name such as `roofline.json`
    tail = os.sep + name
    return any(path.endswith(tail) for path in files)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_file_a_document_names_is_in_the_tree(document):
    path = os.path.join(ROOT, document)
    if not os.path.exists(path):
        pytest.skip(f"{document} is not in this checkout")
    with open(path, encoding="utf-8") as handle:
        names = named_files(handle.read())
    assert names, f"{document} names no file: the reader has rotted"
    files = tree_files()
    missing = sorted({n for n in names if not resolves(n, files)})
    assert not missing, (
        f"{document} names files that are not in the tree: {missing}"
    )


def read_names(path: str, skip_assignments: tuple = ()) -> set:
    """Every knob-shaped string literal of a module, but for those in the
    assignments named: the names a module lists in order to REFUSE them are
    not names it reads."""
    with open(path, encoding="utf-8") as handle:
        tree = ast.parse(handle.read())
    skipped = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id in skip_assignments
            for t in node.targets
        ):
            skipped.update(id(n) for n in ast.walk(node))
    names = set()
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Constant)
            and isinstance(node.value, str)
            and id(node) not in skipped
        ):
            names.update(KNOB.findall(node.value))
    return names


def test_readme_environment_names_are_names_the_program_reads():
    with open(os.path.join(ROOT, "README.md"), encoding="utf-8") as handle:
        readme = handle.read()
    start = readme.index("## Configuration reference (env)")
    rest = readme[start + 2 :]
    end = rest.find("\n## ")
    section = rest if end < 0 else rest[:end]
    named = {
        span
        for span in SPAN.findall(section)
        if KNOB.fullmatch(span)
    }
    assert len(named) > 40, "the environment reference was not found"
    package = os.path.join(ROOT, PACKAGE)
    config = read_names(
        os.path.join(package, "serve", "config.py"),
        skip_assignments=("_REMOVED_NAMES",),
    )
    analysis = set()
    for name in os.listdir(os.path.join(package, "analysis")):
        if name.endswith(".py"):
            analysis |= read_names(os.path.join(package, "analysis", name))
    unread = sorted(
        name
        for name in named
        if name not in (analysis if name.startswith("ANALYSIS_") else config)
    )
    assert not unread, (
        f"README.md documents names serve/config.py does not read: {unread}"
    )
