"""Filesystem walker with injected DB fetchers.

Parity: ref:core/src/location/indexer/walk.rs — breadth-first walk over
a to_walk queue (:119-200), per-entry rule application and the
accept-by-children state machine (:476-586), ancestor backfill (:616-
661), symlink skip, existing-row diffing into to_create/to_update
(:334-430), and per-directory to_remove fetching (:664-680).

The DB is injected as plain callables (exactly the reference's
generics-based design) so the walker unit-tests hermetically. The
walker times them all the same: a walk call's seconds are split six
ways on `sd_indexer_walk_seconds{part}` (`_WalkClock`), four of them
also child spans of whatever span the caller holds open (`walk`).
"""

from __future__ import annotations

import logging
import os
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable

from ...files.isolated_path import FilePathMetadata, IsolatedFilePathData
from ...telemetry import metrics as _tm
from ...telemetry import span
from .rules import IndexerRule, RuleKind

logger = logging.getLogger(__name__)

TO_WALK_QUEUE_INITIAL_CAPACITY = 32
WALKER_PATHS_BUFFER_INITIAL_CAPACITY = 512


@dataclass
class WalkedEntry:
    iso_file_path: IsolatedFilePathData
    metadata: FilePathMetadata | None
    pub_id: bytes = field(default_factory=lambda: uuid.uuid4().bytes)
    object_id: int | None = None  # set for to_update entries
    # index-journal verdict for file entries ("hit"|"miss"|"invalidated"|
    # "bypassed"; None when no journal was consulted) — a non-hit on a
    # to_update entry tells the job to clear cas_id so the identifier
    # re-hashes the changed content
    journal_verdict: str | None = None

    def key(self):
        return self.iso_file_path


@dataclass
class ToWalkEntry:
    path: str
    parent_dir_accepted_by_its_children: bool | None = None
    maybe_parent: str | None = None


@dataclass
class WalkResult:
    walked: list[WalkedEntry]                 # to create
    to_update: list[WalkedEntry]              # changed vs DB
    to_walk: list[ToWalkEntry]                # remaining when limit hit
    to_remove: list[dict[str, Any]]           # DB rows no longer on disk
    errors: list[Exception]
    paths_and_sizes: dict[str, int]           # dir -> accumulated bytes


# fetcher signatures (injected):
#   file_paths_db_fetcher(iso_paths) -> rows with keys
#       {pub_id, object_id, inode, hidden, date_modified, size_in_bytes_bytes,
#        materialized_path, name, extension, is_dir}
#   to_remove_db_fetcher(parent_iso, found_iso_paths) -> rows
#       {pub_id, cas_id, object_id, ...}
#   journal_check(iso, metadata) -> verdict string — the index-journal
#       consult for every walked FILE (location/indexer/journal.py);
#       injected like the DB fetchers so the walker stays hermetic
FilePathsFetcher = Callable[[list[IsolatedFilePathData]], list[dict]]
ToRemoveFetcher = Callable[[IsolatedFilePathData, list[IsolatedFilePathData]], list[dict]]
JournalCheck = Callable[[IsolatedFilePathData, FilePathMetadata], str]


class _WalkClock:
    """One walk call's seconds by part. `scan`, `journal`, `fetch` and
    `diff` are their spans' durations; `rules` and `remove_query` are
    clock pairs round every `IndexerRule.apply_all` and every
    `to_remove_db_fetcher` inside `scan`, summed here and never spans
    (one an entry, one a directory). Observed once, at the call's end,
    `scan` less the two it holds, so the six add up to the call."""

    __slots__ = ("scan", "rules", "remove_query", "journal", "fetch", "diff")

    def __init__(self) -> None:
        self.scan = self.rules = self.remove_query = 0.0
        self.journal = self.fetch = self.diff = 0.0

    def observe(self) -> None:
        # literal labels at the site (sdlint SD007)
        seconds = _tm.INDEXER_WALK_SECONDS
        seconds.observe(self.scan - self.rules - self.remove_query,
                        part="scan")
        seconds.observe(self.rules, part="rules")
        seconds.observe(self.remove_query, part="remove_query")
        seconds.observe(self.journal, part="journal")
        seconds.observe(self.fetch, part="fetch")
        seconds.observe(self.diff, part="diff")


def walk(
    root: str | os.PathLike,
    indexer_rules: list[IndexerRule],
    iso_file_path_factory: Callable[[str, bool], IsolatedFilePathData],
    file_paths_db_fetcher: FilePathsFetcher,
    to_remove_db_fetcher: ToRemoveFetcher,
    update_notifier: Callable[[str, int], None] | None = None,
    limit: int = 100_000,
    initial_accepted_by_children: bool | None = None,
    journal_check: JournalCheck | None = None,
) -> WalkResult:
    """Full recursive walk from `root` (ref:walk.rs:119-200). When the
    limit is hit, the remaining dirs come back in `to_walk` so callers
    can continue in later steps (ref keep_walking, walk.rs:200)."""
    root = os.fspath(root)
    to_walk: list[ToWalkEntry] = [ToWalkEntry(root, initial_accepted_by_children, None)]
    indexed_paths: dict[IsolatedFilePathData, WalkedEntry] = {}
    errors: list[Exception] = []
    paths_and_sizes: dict[str, int] = {}
    to_remove: list[dict] = []
    clock = _WalkClock()

    with span("scan") as scan:
        while to_walk:
            entry = to_walk.pop(0)
            entry_size, removed = _inner_walk_single_dir(
                root, entry, indexer_rules, iso_file_path_factory,
                to_remove_db_fetcher, indexed_paths, to_walk, errors,
                update_notifier, clock,
            )
            to_remove.extend(removed)
            paths_and_sizes[entry.path] = paths_and_sizes.get(entry.path, 0) + entry_size
            if entry.maybe_parent is not None:
                paths_and_sizes[entry.maybe_parent] = (
                    paths_and_sizes.get(entry.maybe_parent, 0) + entry_size
                )
            if len(indexed_paths) >= limit:
                break
    clock.scan = scan.duration

    walked, to_update = _filter_existing_paths(
        indexed_paths, file_paths_db_fetcher, journal_check, clock
    )
    clock.observe()
    return WalkResult(walked, to_update, to_walk, to_remove, errors, paths_and_sizes)


def walk_single_dir(
    root: str | os.PathLike,
    indexer_rules: list[IndexerRule],
    iso_file_path_factory: Callable[[str, bool], IsolatedFilePathData],
    file_paths_db_fetcher: FilePathsFetcher,
    to_remove_db_fetcher: ToRemoveFetcher,
    journal_check: JournalCheck | None = None,
) -> WalkResult:
    """Shallow walk (one directory, no recursion) — the light-rescan
    path (ref:walk.rs:265 walk_single_dir, shallow.rs)."""
    root = os.fspath(root)
    indexed_paths: dict[IsolatedFilePathData, WalkedEntry] = {}
    errors: list[Exception] = []
    clock = _WalkClock()
    with span("scan") as scan:
        size, removed = _inner_walk_single_dir(
            root, ToWalkEntry(root), indexer_rules, iso_file_path_factory,
            to_remove_db_fetcher, indexed_paths, None, errors, None, clock,
        )
    clock.scan = scan.duration
    walked, to_update = _filter_existing_paths(
        indexed_paths, file_paths_db_fetcher, journal_check, clock
    )
    clock.observe()
    return WalkResult(walked, to_update, [], removed, errors, {root: size})


def _inner_walk_single_dir(
    root: str,
    entry: ToWalkEntry,
    indexer_rules: list[IndexerRule],
    iso_file_path_factory: Callable[[str, bool], IsolatedFilePathData],
    to_remove_db_fetcher: ToRemoveFetcher,
    indexed_paths: dict[IsolatedFilePathData, WalkedEntry],
    maybe_to_walk: list[ToWalkEntry] | None,
    errors: list[Exception],
    update_notifier: Callable[[str, int], None] | None,
    clock: _WalkClock,
) -> tuple[int, list[dict]]:
    path = entry.path
    try:
        iso_to_walk = iso_file_path_factory(path, True)
    except Exception as e:  # noqa: BLE001
        errors.append(e)
        return 0, []
    try:
        dir_entries = list(os.scandir(path))
    except OSError as e:
        errors.append(e)
        return 0, []

    paths_buffer: dict[IsolatedFilePathData, WalkedEntry] = {}

    for dirent in dir_entries:
        accept_by_children_dir = entry.parent_dir_accepted_by_its_children
        current_path = dirent.path

        if update_notifier is not None:
            update_notifier(current_path, len(indexed_paths) + len(paths_buffer))

        t_rules = time.perf_counter()
        rules_per_kind = IndexerRule.apply_all(indexer_rules, current_path)
        clock.rules += time.perf_counter() - t_rules

        # rejected by any reject-glob (ref:walk.rs:519-527)
        if any(not ok for ok in rules_per_kind.get(RuleKind.REJECT_FILES_BY_GLOB, [])):
            continue

        try:
            st = dirent.stat(follow_symlinks=False)
            if dirent.is_symlink():
                continue  # symlinks hard-ignored (ref:walk.rs:540)
            is_dir = dirent.is_dir(follow_symlinks=False)
        except OSError as e:
            errors.append(e)
            continue

        if is_dir:
            # reject dir + children entirely (ref:walk.rs:546-557)
            if any(
                not ok
                for ok in rules_per_kind.get(
                    RuleKind.REJECT_IF_CHILDREN_DIRECTORIES_ARE_PRESENT, []
                )
            ):
                continue
            accept_results = rules_per_kind.get(
                RuleKind.ACCEPT_IF_CHILDREN_DIRECTORIES_ARE_PRESENT
            )
            if accept_results is not None:
                if any(accept_results):
                    accept_by_children_dir = True
                if accept_by_children_dir is None:
                    accept_by_children_dir = False
            if maybe_to_walk is not None:
                maybe_to_walk.append(
                    ToWalkEntry(current_path, accept_by_children_dir, path)
                )

        # rejected when accept-globs exist and none matched (ref:walk.rs:588-597)
        accepts = rules_per_kind.get(RuleKind.ACCEPT_FILES_BY_GLOB)
        if accepts is not None and all(not a for a in accepts):
            continue

        if accept_by_children_dir is None or accept_by_children_dir:
            try:
                iso = iso_file_path_factory(current_path, is_dir)
                metadata = FilePathMetadata.from_path(current_path, st)
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                continue
            paths_buffer[iso] = WalkedEntry(iso, metadata)

            # ancestor backfill up to (not incl.) root (ref:walk.rs:616-661)
            ancestor = os.path.dirname(current_path)
            while ancestor != root and len(ancestor) > len(root):
                try:
                    aiso = iso_file_path_factory(ancestor, True)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                    break
                if aiso in indexed_paths or aiso in paths_buffer:
                    break
                try:
                    ameta = FilePathMetadata.from_path(ancestor)
                except OSError as e:
                    errors.append(e)
                    ancestor = os.path.dirname(ancestor)
                    continue
                paths_buffer[aiso] = WalkedEntry(aiso, ameta)
                ancestor = os.path.dirname(ancestor)

    t_remove = time.perf_counter()
    try:
        to_remove = to_remove_db_fetcher(iso_to_walk, list(paths_buffer.keys()))
    except Exception as e:  # noqa: BLE001
        errors.append(e)
        to_remove = []
    clock.remove_query += time.perf_counter() - t_remove

    entry_size = sum(
        w.metadata.size_in_bytes for w in paths_buffer.values() if w.metadata
    )
    indexed_paths.update(paths_buffer)
    return entry_size, to_remove


def _filter_existing_paths(
    indexed_paths: dict[IsolatedFilePathData, WalkedEntry],
    file_paths_db_fetcher: FilePathsFetcher,
    journal_check: JournalCheck | None,
    clock: _WalkClock,
) -> tuple[list[WalkedEntry], list[WalkedEntry]]:
    """Split into (to_create, to_update) against existing DB rows
    (ref:walk.rs:334-430): an existing row updates when inode, mtime
    (±1 ms) or hidden changed — directory sizes are ignored. Every FILE
    entry additionally gets its index-journal verdict (the per-file
    hit/miss/invalidated stream a warm pass is measured by). Three
    spans, their seconds left on `clock`: the consults, the lookup,
    the comparison."""
    if not indexed_paths:
        return [], []
    if journal_check is not None:
        with span("journal") as consults:
            for iso, entry in indexed_paths.items():
                if not iso.is_dir and entry.metadata is not None:
                    try:
                        entry.journal_verdict = journal_check(iso, entry.metadata)
                    except Exception:  # noqa: BLE001 - journal must not kill walks
                        logger.exception("journal_check failed")
                        entry.journal_verdict = None
        clock.journal = consults.duration
    with span("fetch") as lookup:
        try:
            rows = file_paths_db_fetcher(list(indexed_paths.keys()))
        except Exception:  # noqa: BLE001 - treat fetch failure as "no rows"
            logger.exception("file_paths_db_fetcher failed; treating all as new")
            rows = []
    clock.fetch = lookup.duration

    from ...db.database import blob_u64

    with span("diff") as comparison:
        in_db: dict[IsolatedFilePathData, dict] = {}
        for row in rows:
            iso = IsolatedFilePathData.from_db_row(
                row.get("location_id", 0),
                row["materialized_path"],
                row["name"],
                row["extension"],
                bool(row["is_dir"]),
            )
            in_db[iso] = row

        to_create: list[WalkedEntry] = []
        to_update: list[WalkedEntry] = []
        for iso, entry in indexed_paths.items():
            row = in_db.get(iso)
            if row is None:
                to_create.append(entry)
                continue
            meta = entry.metadata
            if meta is None or row.get("inode") is None:
                continue
            changed = (
                blob_u64(row["inode"]) != meta.inode
                or _mtime_differs(row.get("date_modified"), meta)
                or row.get("hidden") is None
                or bool(row["hidden"]) != meta.hidden
            )
            if changed:
                entry.pub_id = row["pub_id"]
                entry.object_id = row.get("object_id")
                to_update.append(entry)
    clock.diff = comparison.duration
    return to_create, to_update


def _mtime_differs(stored: str | None, meta: FilePathMetadata) -> bool:
    if stored is None:
        return True
    import datetime as _dt

    try:
        old = _dt.datetime.fromisoformat(stored)
    except ValueError:
        return True
    delta = meta.modified_at - old
    return abs(delta.total_seconds()) > 0.001
