"""Every repository path a document names exists.

A case for each of README.md, docs/*.md, the CI workflow and
scripts/trace.sh: a path under one of the repository's directories, or
a bare ``*.py`` name, must point at a file of the checkout.  A path
given relative to ``hotstuff_tpu/`` (``consensus/core.py``) counts as
found there.  What a run writes (``logs/``, ``chiprun_out/``) and the
reference implementation's own layout are not repository paths and are
not checked.
"""

from __future__ import annotations

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the directories whose files a document may name
TOP_DIRS = (
    "hotstuff_tpu", "benchmark", "chipbench", "scripts", "native",
    "tests", "docs", "results", "plots",
)  # fmt: skip
SUFFIXES = "py|sh|md|cpp|json|yml|txt|png"

PATH = re.compile(
    rf"(?<![\w/.<>*-])((?:[\w-]+/)*[\w-][\w.-]*\.(?:{SUFFIXES}))(?![\w/*<>-])"
)

DOCUMENTS = (
    ["README.md"]
    + sorted(
        os.path.relpath(p, REPO)
        for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
    )
    + [".github/workflows/ci.yml", "scripts/trace.sh"]
)


@functools.cache
def _package_dirs() -> set[str]:
    return {
        name
        for name in os.listdir(os.path.join(REPO, "hotstuff_tpu"))
        if os.path.isdir(os.path.join(REPO, "hotstuff_tpu", name))
    }


@functools.cache
def _python_basenames() -> set[str]:
    names = {n for n in os.listdir(REPO) if n.endswith(".py")}
    for top in TOP_DIRS:
        for _dir, _subdirs, files in os.walk(os.path.join(REPO, top)):
            names.update(n for n in files if n.endswith(".py"))
    return names


def dangling(text: str) -> list[str]:
    packages = _package_dirs()
    basenames = _python_basenames()
    missing = []
    for path in sorted(set(PATH.findall(text))):
        first, _, rest = path.partition("/")
        if not rest:
            # a bare name: only ``*.py`` is checked, anywhere in the tree
            if path.endswith(".py") and path not in basenames:
                missing.append(path)
        elif first in TOP_DIRS:
            if not os.path.exists(os.path.join(REPO, path)):
                missing.append(path)
        elif first in packages:
            if not os.path.exists(os.path.join(REPO, "hotstuff_tpu", path)):
                missing.append(path)
    return missing


@pytest.mark.parametrize("document", DOCUMENTS)
def test_named_paths_exist(document):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    assert dangling(text) == [], f"{document} names files that do not exist"


def test_a_dangling_path_is_found():
    text = "see `scripts/no_such_check.py`, `consensus/core.py`, `gone.py`"
    assert dangling(text) == ["gone.py", "scripts/no_such_check.py"]
