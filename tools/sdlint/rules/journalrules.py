"""Index-journal rules.

SD012  journal-bypassing stat / full read in indexer pipelines

The incremental-indexing contract (docs/performance.md "Incremental
indexing") is that the walker/identifier/media/duplicates orchestration
layers consult the per-location index journal BEFORE touching a file:
stats go through ``journal.stat_identity`` (whose result is what a
journal verdict is judged against; ``journal.fd_identity`` is its form
for the descriptor a file unknown to the journal is read through) and
reads only happen for files the journal did not vouch for. A direct ``os.stat`` or an unbounded
``open(...).read()`` in those modules is a byte the journal can never
save — and, worse, a verdict computed against a *different* stat than
the one recorded.

Scope (path-based): ``location/indexer/``, ``object/file_identifier/``,
``object/media/job.py``, ``object/media/thumbnail/actor.py``,
``object/duplicates.py``, ``object/orphan_remover.py``. The journal
module itself (``location/indexer/journal.py``) is the allowlisted
owner of the raw stat. Leaf codec/extractor modules (thumbnail
process/store, media_data) are intentionally out of scope: they do the
work the journal decided must happen.

Flags:

- calls to ``os.stat`` / ``os.lstat`` / ``os.fstat`` /
  ``os.path.getsize`` / ``os.path.getmtime`` (``dirent.stat`` from
  ``os.scandir`` is exempt — the walker's single stat per entry IS the
  journal's input; the stat of an open descriptor goes through
  ``journal.fd_identity``);
- whole-file reads: a no-arg ``.read()`` chained directly onto
  ``open(...)``, or ``Path.read_bytes()`` / ``Path.read_text()``.
"""

from __future__ import annotations

import ast
from typing import Iterator

from ..core import FileContext, Finding, call_name, rule

#: path fragments this rule governs (posix-style, as analyze_paths sees)
SCOPED_FRAGMENTS = (
    "location/indexer/",
    "object/file_identifier/",
    "object/media/job.py",
    "object/media/thumbnail/actor.py",
    "object/duplicates.py",
    "object/orphan_remover.py",
)

#: modules allowed to stat directly — the journal owns the raw stat
ALLOWLIST_FRAGMENTS = ("location/indexer/journal.py",)

_STAT_CALLS = {
    "os.stat",
    "os.lstat",
    "os.fstat",
    "os.path.getsize",
    "os.path.getmtime",
}

_PATH_READ_TAILS = {"read_bytes", "read_text"}


def _in_scope(path: str) -> bool:
    if any(frag in path for frag in ALLOWLIST_FRAGMENTS):
        return False
    return any(frag in path for frag in SCOPED_FRAGMENTS)


def _is_open_read(call: ast.Call) -> bool:
    """``open(...).read()`` with no length bound — a whole-file read."""
    if call.args or call.keywords:
        return False  # bounded read(n) is a deliberate partial read
    fn = call.func
    if not (isinstance(fn, ast.Attribute) and fn.attr == "read"):
        return False
    target = fn.value
    return (
        isinstance(target, ast.Call)
        and call_name(target) in ("open", "io.open")
    )


@rule(
    "SD012",
    "journal-bypass",
    "direct os.stat / whole-file read in journal-governed indexer "
    "pipelines — route stats through location.indexer.journal."
    "stat_identity and reads through a journal consult, or the warm "
    "pass pays for bytes the journal should have saved",
)
def check_journal_bypass(ctx: FileContext) -> Iterator[Finding]:
    if not _in_scope(ctx.path):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        if name in _STAT_CALLS:
            yield ctx.finding(
                "SD012",
                node,
                f"`{name}` bypasses the index journal: use "
                "location.indexer.journal.stat_identity (the stat a "
                "journal verdict is judged against; fd_identity for "
                "an open descriptor) instead",
            )
            continue
        if _is_open_read(node):
            yield ctx.finding(
                "SD012",
                node,
                "unbounded `open(...).read()` in a journal-governed "
                "pipeline: consult the index journal first so vouched "
                "files are never re-read",
            )
            continue
        fn = node.func
        if (
            isinstance(fn, ast.Attribute)
            and fn.attr in _PATH_READ_TAILS
            and not node.args
            and not node.keywords
        ):
            yield ctx.finding(
                "SD012",
                node,
                f"`.{fn.attr}()` whole-file read in a journal-governed "
                "pipeline: consult the index journal first so vouched "
                "files are never re-read",
            )
