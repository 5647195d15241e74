"""The documents are pinned to the tree.

``README.md``, ``PERF.md``, ``MIGRATION.md`` and the verify skill are
what a new session reads before the code. Each section of them may cite
only what exists: a path resolves, a ``pio`` subcommand is in the
parser, a ``PIO_*`` name is read somewhere in the program, the
benchmark or the tests. And the
other way round: every environment name a package reads and every
subcommand the parser knows is documented. Cases are per section and
per package, never per name, so deleting a knob or a subcommand later
costs no test.

``CHANGES.md``, ``ROADMAP.md``, ``SURVEY.md`` and ``ISSUE.md`` are
history or another session's and are not checked. Inside a checked
section, ``<!-- records: history -->`` marks the rest of that section
as history (``PERF.md`` §6's condensed past).
"""

import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "predictionio_tpu")
#: where the documents' relative paths start from, as they write them
BASES = ("", "predictionio_tpu", "benchmarks")
PATH_SUFFIXES = (".py", ".md", ".json", ".jsonl", ".cpp", ".h", ".sh",
                 ".template")
HISTORY_MARK = "<!-- records: history -->"
#: records this tree no longer holds (the pre-chip measurement stack and
#: the round notes); the patterns are spelled here and nowhere else
DELETED_RECORDS = re.compile(
    r"bench\.py|benchcmp|bench-compare|BENCH_r0|PIO_BENCH_"
    r"|ROUND[0-9]\.md|VERDICT|ADVICE\.md")
#: files a user writes or the program leaves behind at run time: cited
#: by name, never part of the checkout
NOT_IN_THE_CHECKOUT = {
    "engine.json", "template.json", "best.json", "manifest.json",
    "pio-env.sh", "slo.json",
}

PIO_NAME = re.compile(r"PIO_[A-Z0-9_]+")
#: the top-level packages of the program, one case each below
PACKAGES = sorted(
    d for d in os.listdir(PACKAGE)
    if os.path.isfile(os.path.join(PACKAGE, d, "__init__.py")))


def _read(path: str) -> str:
    with open(os.path.join(ROOT, path), encoding="utf-8") as f:
        return f.read()


def sections(path: str, levels: tuple) -> list:
    """``(heading, text)`` for the stretch before the first heading
    (``(top)``) and for every heading whose level is in ``levels``;
    deeper headings belong to the section above them, fenced code is
    never a heading, and what follows ``HISTORY_MARK`` inside a
    section is left out."""
    out, title, lines, fenced = [], "(top)", [], False
    for line in _read(path).splitlines():
        if line.lstrip().startswith("```"):
            fenced = not fenced
        m = None if fenced else re.match(r"(#{1,6}) +(.*)", line)
        if m and len(m.group(1)) in levels:
            out.append((title, "\n".join(lines)))
            title, lines = m.group(2).strip(), []
        else:
            lines.append(line)
    out.append((title, "\n".join(lines)))
    return [(t, text.split(HISTORY_MARK)[0]) for t, text in out
            if text.strip()]


def cited(text: str):
    """What a stretch of markdown cites, from its back-quoted tokens
    and the lines of its fenced blocks: ``(paths, subcommands,
    names)``; a name that ends in ``_`` is a prefix."""
    paths, subcommands, names = set(), set(), set()
    fences = re.split(r"^ *```.*$", text, flags=re.MULTILINE)
    tokens = [line for block in fences[1::2] for line in block.splitlines()]
    for prose in fences[0::2]:
        tokens += re.findall(r"`([^`]+)`", prose)
    for token in tokens:
        for m in re.finditer(r"(PIO_[A-Z0-9_]+)(<?)", token):
            name = m.group(1)
            names.add(name if name.endswith("_") or not m.group(2)
                      else name + "_")
        m = re.match(r"(?:[A-Z_]+=\S+\s+)*(?:bin/)?pio\s+([a-z][a-z-]*)\b",
                     token.strip())
        if m:
            subcommands.add(m.group(1))
        for word in token.split():
            word = re.sub(r"(::.*|:[0-9][0-9,:–-]*)$", "", word)
            if (word.startswith(("/", "-", "http", "~", "$", "<", "."))
                    or not re.fullmatch(r"[A-Za-z0-9_.*/-]+", word)):
                continue
            stem = word.split("*")[0]
            if word.endswith(PATH_SUFFIXES) or (
                    "/" in stem and os.path.isdir(
                        os.path.join(ROOT, stem.split("/")[0]))):
                paths.add(word)
    return paths, subcommands, names


def resolves(path: str) -> bool:
    if os.path.basename(path) in NOT_IN_THE_CHECKOUT:
        return True
    if "*" in path:
        return any(glob.glob(os.path.join(ROOT, base, path))
                   for base in BASES)
    if any(os.path.exists(os.path.join(ROOT, base, path))
           for base in BASES):
        return True
    # a bare file name, as in "`cli.py`": anywhere in the checkout
    return "/" not in path and path in _basenames()


@functools.lru_cache(maxsize=None)
def _basenames() -> frozenset:
    """Every file name in the checkout, build outputs left out."""
    found = set()
    for _, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in (
            ".git", "__pycache__", "chiprun_out", ".pio_run", "_build",
            ".parent", ".change")]
        found.update(files)
    return frozenset(found)


@functools.lru_cache(maxsize=None)
def parser_subcommands() -> frozenset:
    import argparse

    from predictionio_tpu.tools.cli import build_parser

    for action in build_parser()._actions:
        if isinstance(action, argparse._SubParsersAction):
            return frozenset(action.choices)
    raise AssertionError("pio's parser has no subcommands")


def files_under(*roots: str):
    """Every source file under ``roots`` (files or directories, from
    the repo's root), build outputs and this file left out."""
    for root in roots:
        root = os.path.join(ROOT, root)
        if os.path.isfile(root):
            yield root
            continue
        for base, dirs, names in os.walk(root):
            dirs[:] = [d for d in dirs if d not in ("__pycache__", "_build")]
            for name in names:
                if name != os.path.basename(__file__):
                    yield os.path.join(base, name)


@functools.lru_cache(maxsize=None)
def names_read_in(*roots: str) -> frozenset:
    """Every ``PIO_*`` name in the sources under ``roots``: in Python
    wherever it stands (names are built, looked up and documented
    there), in C++ only as ``getenv``'s argument (the rest are include
    guards)."""
    names = set()
    for path in files_under(*roots):
        if path.endswith(".py"):
            pattern = PIO_NAME
        elif path.endswith((".cpp", ".h")):
            pattern = re.compile(r'getenv\("(PIO_[A-Z0-9_]+)"')
        else:
            continue
        with open(path, encoding="utf-8") as f:
            for m in pattern.finditer(f.read()):
                names.add(m.group(m.lastindex or 0))
    return frozenset(names)


def _matches(name: str, known) -> bool:
    """``name`` is one of ``known``, or either is a prefix (ends in
    ``_``) of the other."""
    return name in known or any(
        (k.endswith("_") and name.startswith(k))
        or (name.endswith("_") and k.startswith(name)) for k in known)


# -- every section cites only what exists -------------------------------------

#: each document with the heading levels that divide it into cases
#: (none: the whole document is one)
DOCUMENTS = (
    ("README.md", (2, 3)),
    ("PERF.md", (2,)),
    ("MIGRATION.md", ()),
    (".claude/skills/verify/SKILL.md", ()),
)
SECTIONS = [pytest.param(text, id=f"{doc}:{title}")
            for doc, levels in DOCUMENTS
            for title, text in sections(doc, levels)]


@pytest.mark.parametrize("text", SECTIONS)
def test_section_cites_only_what_exists(text):
    paths, subcommands, names = cited(text)
    missing = sorted(p for p in paths if not resolves(p))
    assert not missing, f"paths that do not exist: {missing}"
    unknown = sorted(subcommands - parser_subcommands())
    assert not unknown, f"not subcommands of pio: {unknown}"
    read = names_read_in("predictionio_tpu", "chip_smoke.py", "benchmarks",
                         "tests")
    unread = sorted(n for n in names if not _matches(n, read))
    assert not unread, f"names nothing reads: {unread}"


# -- and what exists is documented --------------------------------------------

@pytest.mark.parametrize("package", PACKAGES)
def test_every_environment_name_is_documented(package):
    documented = set(PIO_NAME.findall(
        _read("README.md") + _read("conf/pio-env.sh.template")))
    undocumented = sorted(
        n for n in names_read_in(os.path.join("predictionio_tpu", package))
        if not _matches(n, documented))
    assert not undocumented, (
        f"predictionio_tpu/{package} reads {undocumented}: one line each "
        "in the README section of the subsystem that reads them")


def test_every_subcommand_is_documented():
    """Parser and README, both ways."""
    _, shown, _ = cited(_read("README.md"))
    known = parser_subcommands()
    assert not known - shown, (
        f"subcommands the README never shows as `pio <name>`: "
        f"{sorted(known - shown)}")
    assert not shown - known, sorted(shown - known)


def test_no_module_cites_a_deleted_record():
    hits = []
    for path in files_under("predictionio_tpu", "chip_smoke.py", "bin"):
        with open(path, encoding="utf-8", errors="replace") as f:
            for n, line in enumerate(f, 1):
                if DELETED_RECORDS.search(line):
                    hits.append(f"{os.path.relpath(path, ROOT)}:{n}: "
                                f"{line.strip()[:100]}")
    assert not hits, "\n".join(hits)
