"""Every file a document or a module names is in the tree.

The documents are README.md and docs/*.md; a name is a repo path
(``scripts/…``, ``fedtorch_tpu/…``, ``tests/…``, ``benchmark/…``,
``docs/…``, ``examples/…``) or a bare ``*.py`` / ``*.md`` / ``*.sh`` /
upper-case ``*.json`` (the root's records). Lower-case ``*.json`` names
are files a run writes into its run directory and are not checked."""
import functools
import os
import re
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    "docs/" + f for f in os.listdir(os.path.join(REPO, "docs"))
    if f.endswith(".md"))
# the FedTorch reference's own launchers, which the documents map onto
# this repo's
REFERENCE_FILES = {"main.py", "main_centered.py", "run_mpi.py"}

_PATH = re.compile(
    r"(?<![\w/.<-])((?:scripts|fedtorch_tpu|tests|benchmark|docs|examples)"
    r"/[\w./-]*\w/?)")
_BARE = re.compile(
    r"(?<![\w/.<{*-])([A-Za-z_][\w-]*\.(?:py|md|sh)|[A-Z][A-Z0-9_]*\.json)"
    r"\b(?![\w/])")
# a module may name the reference's files (``resnet.py:209``): only its
# scripts/ paths and root records are held to the tree
_MODULE = re.compile(
    r"(?<![\w/.-])(scripts/\w+\.(?:py|sh)|[A-Z][A-Z0-9_]*\.json)\b")


@functools.lru_cache(maxsize=None)
def _tree() -> frozenset:
    out = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard"],
        cwd=REPO, text=True, capture_output=True)
    if out.returncode == 0 and out.stdout.strip():
        files = set(out.stdout.split())
    else:   # a copy that is not a checkout: the files on disk
        files = set()
        for d, subdirs, fs in os.walk(REPO):
            subdirs[:] = [x for x in subdirs if not x.startswith(".")
                          and x not in ("artifacts", "chiprun_out")]
            files |= {os.path.relpath(os.path.join(d, f), REPO)
                      for f in fs}
    return frozenset(f for f in files
                     if os.path.exists(os.path.join(REPO, f)))


def missing(names) -> list:
    """Of ``names``, those that are neither a path of the tree (file or
    directory) nor, written bare, the name of a file somewhere in it."""
    files = _tree()
    dirs = {os.path.dirname(f) for f in files}
    paths = files | dirs | {os.path.dirname(d) for d in dirs}
    bare = {os.path.basename(f) for f in files} | REFERENCE_FILES
    return sorted({n for n in names if n.rstrip("/") not in paths
                   and ("/" in n or n not in bare)})


def _read(path: str) -> str:
    with open(os.path.join(REPO, path)) as f:
        return f.read()


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_files_in_the_tree(doc):
    text = _read(doc)
    assert missing(_PATH.findall(text) + _BARE.findall(text)) == []


def test_package_names_only_records_and_scripts_in_the_tree():
    bad = {f: miss for f in sorted(_tree())
           if f.startswith("fedtorch_tpu/") and f.endswith(".py")
           and (miss := missing(_MODULE.findall(_read(f))))}
    assert bad == {}
