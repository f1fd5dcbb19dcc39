"""What a document names must exist: every ``make <target>`` is a target
of the Makefile, every repo path ending in .py, .json or .md that stands
in code (a backtick span or a fenced block) is a file of this tree.

A path counts as found where it stands from the repo root, from
``spacedrive_tpu/`` (the documents' habit for package modules:
``ops/cas.py``), or, for a bare file name in a components table, anywhere
under the tracked directories. Absolute paths (``/root/reference``),
upstream sources (``core/src/...rs``) and globs are out of scope; files a
run writes or a user supplies are listed by name below, nothing else is
let through.
"""

import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "COMPONENTS.md"] + sorted(
    glob.glob("docs/*.md", root_dir=ROOT))

#: written by a command or dropped in by a user; never tracked
NOT_TRACKED = {
    "debug-bundle.json",  # `make debug-bundle`, `sdx debug-bundle --out`
    "BENCH_SCALE.json",  # bench_scale.py's output, git-ignored
    # the cacophony vector file docs/security.md tells a user to supply
    "vectors.json",
    "tests/data/noise_vectors.json",
}

_FENCE = re.compile(r"^```.*?^```", re.M | re.S)
_SPAN = re.compile(r"`([^`\n]+)`")
_MAKE = re.compile(r"(?:^|[\s;&(])make ([a-z][a-z0-9-]*)", re.M)
_PATH = re.compile(r"[\w./*<>{},~-]+\.(?:py|json|md)\b")


def _code(text: str) -> list[str]:
    blocks = _FENCE.findall(text)
    return blocks + _SPAN.findall(_FENCE.sub("", text))


@functools.lru_cache(maxsize=None)
def _make_targets() -> frozenset:
    with open(os.path.join(ROOT, "Makefile")) as f:
        return frozenset(re.findall(r"^([a-z][a-z0-9-]*):", f.read(), re.M))


@functools.lru_cache(maxsize=None)
def _file_names() -> frozenset:
    names = {f for f in os.listdir(ROOT)
             if os.path.isfile(os.path.join(ROOT, f))}
    for top in ("spacedrive_tpu", "tests", "tools", "benchmark", "docs"):
        for _dir, dirs, files in os.walk(os.path.join(ROOT, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            names.update(files)
    return frozenset(names)


def _found(path: str) -> bool:
    return (
        path in NOT_TRACKED
        or os.path.isfile(os.path.join(ROOT, path))
        or os.path.isfile(os.path.join(ROOT, "spacedrive_tpu", path))
        or ("/" not in path and path in _file_names())
    )


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_what_exists(doc):
    with open(os.path.join(ROOT, doc)) as f:
        code = _code(f.read())
    targets = {t for c in code for t in _MAKE.findall(c)}
    paths = {
        p for c in code for p in _PATH.findall(c)
        if not p.startswith(("/", "~")) and not set(p) & set("*<>{},")
    }
    missing = sorted(f"make {t}" for t in targets - _make_targets())
    missing += sorted(p for p in paths if not _found(p))
    assert missing == [], f"{doc} names what the tree does not have"
