"""The operator documents describe the tree that exists.

1. A document that tells the reader to open or run a Python file names
   one that is there: a path in backticks, or a word on a `python`
   command line, that ends in `.py` resolves against the root,
   `paddle_tpu/` or `tests/`; a bare file name in backticks is that of
   some tracked file, and on a command line that of a root script.
2. Every script under `tools/` is named by one of those documents or
   run by a test: a tool nobody documents and nothing exercises is the
   next thing every reader has to learn to ignore.
"""

import functools
import glob
import os
import re

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DOCS = ("README.md", "SERVING.md", "RESILIENCE.md", "PROFILE.md",
         "PARITY.md", "ANALYSIS.md")
_BASES = ("", "paddle_tpu", "tests")
_PY = re.compile(r"[\w./-]+\.py\b")
_COMMAND = re.compile(r"^\s*(?:\$ )?(?:[A-Z_]+=\S+ )*python3? (.*)$")
_SKIP_DIRS = {".git", "__pycache__", "chiprun_out", "bench_out"}
_READERS_OWN = {"train.py"}     # stands for the reader's training script


@functools.lru_cache(maxsize=None)
def _read(name):
    with open(os.path.join(_REPO, name)) as f:
        return f.read()


@functools.lru_cache(maxsize=None)
def _file_names():
    names = set()
    for _, dirs, files in os.walk(_REPO):
        dirs[:] = [d for d in dirs
                   if d not in _SKIP_DIRS and not d.startswith(".")]
        names.update(f for f in files if f.endswith(".py"))
    return names


def _resolves(path):
    return any(os.path.isfile(os.path.join(_REPO, base, path))
               for base in _BASES)


def _named_files(text):
    """(token, must_be_at_root) for every Python file the text names."""
    out = []
    for line in text.splitlines():
        m = _COMMAND.match(line)
        if m:
            out += [(t, True) for t in _PY.findall(m.group(1))]
        for span in re.findall(r"`([^`\n]+)`", line):
            out += [(t, False) for t in _PY.findall(span)]
    return out


@pytest.mark.parametrize("doc", _DOCS)
def test_doc_names_only_python_files_that_exist(doc):
    names = _file_names()
    missing = set()
    for token, at_root in _named_files(_read(doc)):
        token = token[2:] if token.startswith("./") else token
        if token in _READERS_OWN:
            continue
        if "/" in token or at_root:
            ok = _resolves(token)
        else:
            ok = token in names
        if not ok:
            missing.add(token)
    assert not missing, f"{doc} names files that do not exist: " \
        f"{sorted(missing)}"


_TOOLS = sorted(os.path.basename(p) for p in
                glob.glob(os.path.join(_REPO, "tools", "*.py")))


@pytest.mark.parametrize("tool", _TOOLS)
def test_tool_is_documented_or_tested(tool):
    path = f"tools/{tool}"
    if any(path in _read(doc) for doc in _DOCS):
        return
    module = re.compile(
        rf"{re.escape(tool)}|^\s*(?:import|from) {tool[:-3]}\b", re.M)
    tests = glob.glob(os.path.join(_REPO, "tests", "**", "*.py"),
                      recursive=True)
    assert any(module.search(_read(os.path.relpath(t, _REPO)))
               for t in tests if os.path.basename(t) != "test_docs.py"), \
        f"{path} is named by none of {_DOCS} and run by no test"
