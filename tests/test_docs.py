"""The documents name files that exist.

Every back-quoted token of ``README.md``, ``docs/*.md`` and the verify skill
that starts with a top-level name of this repo (a directory, or a ``*.py``
at the root), or with a directory of the package, has to name a file or a
directory of the tree; a bare ``name.py`` has to be some file's name. A
document that still points at a deleted script, test or module fails here,
not in a reader's shell.
"""

import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "neuronx_distributed_inference_tpu")
DOCUMENTS = ["README.md", *sorted(
    os.path.relpath(p, ROOT) for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))
), ".claude/skills/verify/SKILL.md"]

TOP_DIRS = ("neuronx_distributed_inference_tpu", "tests", "benchmark", "scripts", "docs", "examples")
PACKAGE_DIRS = tuple(sorted(
    d for d in os.listdir(PACKAGE)
    if os.path.isdir(os.path.join(PACKAGE, d)) and not d.startswith("_")
))
#: names a document may give a file it tells the reader to WRITE
PLACEHOLDERS = re.compile(r"[<>{}$]|\.\.\.|/path/|/tmp/")


@functools.lru_cache(maxsize=1)
def file_names():
    """The name of every file under the repo's top-level directories and at
    its root (not of scratch directories a session leaves beside them)."""
    names = {f for f in os.listdir(ROOT) if os.path.isfile(os.path.join(ROOT, f))}
    for top in TOP_DIRS:
        for _, dirs, files in os.walk(os.path.join(ROOT, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            names.update(files)
    return names


def paths_named(text):
    """(token, path to look for) of every back-quoted token, and every line
    of a fenced block, that the rule covers."""
    fenced = [line for block in re.findall(r"```.*?```", text, flags=re.S)
              for line in block.splitlines()[1:-1]]
    inline = re.findall(r"`([^`\n]+)`", re.sub(r"```.*?```", "", text, flags=re.S))
    for token in inline + fenced:
        for word in token.split():
            word = word.split("::")[0].rstrip(".,:;)")
            if PLACEHOLDERS.search(word):
                continue
            head = word.split("/", 1)[0]
            if "/" in word and head in TOP_DIRS:
                yield token, os.path.join(ROOT, word)
            elif "/" in word and head in PACKAGE_DIRS and re.search(r"\.(py|json|md|sh)$", word):
                yield token, os.path.join(PACKAGE, word)
            elif re.fullmatch(r"\w+\.py", word):
                yield token, word


def test_the_documents_are_the_eight():
    assert len(DOCUMENTS) == 8, DOCUMENTS


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    names = file_names()
    missing = []
    for token, path in paths_named(text):
        if os.sep not in path:
            found = path in names
        else:
            found = bool(glob.glob(path)) or bool(glob.glob(path + ".py"))
        if not found:
            missing.append(token)
    assert not missing, f"{document} names what is not in the tree: {sorted(set(missing))}"
