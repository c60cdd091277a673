"""Every file a document names exists.

One case a document (``README.md``, ``PARITY.md``, each ``docs/*.md``):
every repo-relative path in backticks, every script a fenced block
runs with ``python``, every ``python -m chainermn_tpu.…`` module and
every relative link target must be in the tree.  ``PERF.md``,
``ROADMAP.md`` and ``CHANGES.md`` are histories that rightly name files
that are gone, and are not read.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "PARITY.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

#: A path is held to the tree when it starts in one of these …
TOP_LEVEL = ("chainermn_tpu", "tests", "docs", "examples", "benchmarks",
             "chipbench", "csrc")
#: … or is a bare name of one of these kinds (a run's outputs, such as
#: ``steps.jsonl``, are not the tree's).
SOURCE_SUFFIXES = (".py", ".md", ".cpp")
#: What a build or a run leaves (``.gitignore``) is not the tree's either.
BUILT_SUFFIXES = (".so", ".whl")
PATH_CHARS = re.compile(r"[A-Za-z0-9_.\-/]+")
FENCED = re.compile(r"```.*?```", re.S)


@pytest.fixture(scope="module")
def tree():
    """The root's files and everything under ``TOP_LEVEL``, repo-relative,
    without dot-directories and caches (scratch copies of other commits
    beside them must not vouch for a file)."""
    names = {n for n in os.listdir(REPO)
             if n in TOP_LEVEL or os.path.isfile(os.path.join(REPO, n))}
    for top in TOP_LEVEL:
        for root, dirs, files in os.walk(os.path.join(REPO, top)):
            dirs[:] = [d for d in dirs
                       if not d.startswith(".") and d != "__pycache__"]
            rel = os.path.relpath(root, REPO)
            names.update(os.path.join(rel, n) for n in dirs + files)
    return names


def _in_tree(token, tree):
    """``token`` is a path of the tree, whole or from some directory down
    (``communicators/packing.py`` for the package's)."""
    token = os.path.normpath(token)
    return token in tree or any(t.endswith("/" + token) for t in tree)


def _path_of(word):
    """The path ``word`` names, or None where it names none."""
    word = word.strip("()[],;:'\"")
    word = word.split("::")[0]                  # tests/x.py::test_name
    word = re.sub(r":[0-9,\-]+$", "", word)     # file.py:12 / :12-30
    word = word.rstrip(".")
    parts = word.rstrip("/").split("/")
    if (not PATH_CHARS.fullmatch(word) or word.startswith(("/", "-"))
            or any(part.startswith(".") for part in parts)
            or word.endswith(BUILT_SUFFIXES)):
        return None
    if len(parts) > 1 or word.endswith("/"):
        named = word.endswith("/") or "." in parts[-1]
        return word if named and parts[0] in TOP_LEVEL else None
    return word if word.endswith(SOURCE_SUFFIXES) else None


def named_paths(text):
    for span in re.findall(r"`([^`\n]+)`", FENCED.sub("", text)):
        for word in span.split():
            path = _path_of(word)
            if path is not None:
                yield path
    for block in FENCED.findall(text):
        yield from re.findall(r"\bpython3?\s+([^\s\-][^\s]*\.py)\b", block)


def named_modules(text):
    yield from re.findall(
        r"python3?\s+-m\s+(chainermn_tpu(?:\.\w+)+)", text)
    for span in re.findall(r"`(tools\.\w+)[ `]", text):
        yield "chainermn_tpu." + span


def link_targets(text):
    for target in re.findall(r"\]\(([^)\s#]+)(?:#[^)]*)?\)", text):
        if "://" not in target:
            yield target


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_files_that_exist(document, tree):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    missing = sorted({p for p in named_paths(text) if not _in_tree(p, tree)})
    for module in named_modules(text):
        stem = module.replace(".", "/")
        if stem + ".py" not in tree and stem not in tree:
            missing.append(f"python -m {module}")
    here = os.path.dirname(document)
    for target in link_targets(text):
        if os.path.normpath(os.path.join(here, target)) not in tree:
            missing.append(f"link {target}")
    assert not missing, (
        f"{document} names what is not in the tree: {missing}")


def test_index_links_every_page():
    """``docs/index.md`` is how a reader finds a page: each is linked."""
    with open(os.path.join(REPO, "docs", "index.md")) as f:
        linked = set(link_targets(f.read()))
    pages = {os.path.basename(d) for d in DOCUMENTS
             if d.startswith("docs/") and d != "docs/index.md"}
    assert pages and pages <= linked, sorted(pages - linked)
