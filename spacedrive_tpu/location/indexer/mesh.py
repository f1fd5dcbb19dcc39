"""Mesh-parallel indexing — one location's identify work, partitioned
across library peers.

The coordinating node walks + saves the location locally (the walk is
metadata-only and cheap — the bytes are the bottleneck), then splits
the resulting orphan file_paths into **journal-keyed shards**: each
entry carries the file-path key ``(materialized_path, name, ext)``
plus the stat identity ``(inode, dev, mtime_ns, size)``, so every
executor — local or remote — consults its OWN index journal before
reading a byte, and a peer that indexed this location before skips its
vouched files exactly like a warm local pass.

Execution is identical on every node (:func:`execute_shard`):

1. journal consult per entry (hit ⇒ reuse the vouched cas, zero I/O);
2. read + batch-hash the rest (device when available, the same
   ``ops.cas`` path the identifier job uses);
3. link objects with **deterministic pub_ids**
   (``object/file_identifier/link.py``) and emit the cas/object sync
   ops — results merge through the existing HLC/LWW path, so a
   twice-executed shard (lease expiry, claim race, peer death after
   sync but before ``complete``) converges instead of corrupting;
4. vouch the journal strictly AFTER the sync write committed, shipping
   ``(identity, cas, chunk-cache)`` back in ``complete`` so the
   coordinator's journal ends bit-identical to a single-node pass.

``distribute_location_index`` is the coordinator entry point: publish
→ announce → self-steal locally through the task system (the
coordinator is just another worker of its own board) → expire and
re-pool dead peers' leases → done when every shard completed. Chips
spanning hosts join through ``parallel.mesh.multihost_init`` (no-op
without a cluster env — the ``jax.distributed`` seam tests/test_multihost.py
exercises).
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
import uuid
from typing import Any

from ...files.isolated_path import full_path_from_db_row
from ...ops import cas
from ...telemetry import metrics as _tm
from ...telemetry import span
from ...telemetry.events import WORK_EVENTS
from ...tasks.task import ExecStatus, Interrupter, Task
from . import journal as _journal

logger = logging.getLogger(__name__)

#: files per shard — small enough that a slow peer's lease stays short,
#: large enough that one claim amortizes a wire round-trip
SHARD_FILES = 128


def shard_files_default() -> int:
    return int(os.environ.get("SD_WORK_SHARD_FILES", str(SHARD_FILES)))


# --- shard building (coordinator) -----------------------------------------


def build_shard_entries(library: Any, location: dict) -> list[dict]:
    """Journal-keyed entries for every orphan file_path of a location:
    identity captured here (one stat per file) so peers can journal-
    match without trusting our verdicts."""
    rows = library.db.query(
        "SELECT * FROM file_path WHERE object_id IS NULL AND cas_id IS NULL "
        "AND is_dir = 0 AND location_id = ? ORDER BY id",
        (location["id"],),
    )
    entries: list[dict] = []
    for row in rows:
        full = full_path_from_db_row(location["path"], row)
        ident = _journal.stat_identity(full)
        from ...db.database import blob_u64

        entries.append({
            "pub_id": row["pub_id"].hex(),
            "mat": row["materialized_path"],
            "name": row["name"],
            "ext": row["extension"] or "",
            "size": blob_u64(row["size_in_bytes_bytes"]) or 0,
            "identity": (
                [ident.inode, ident.dev, ident.mtime_ns, ident.size]
                if ident is not None else None
            ),
        })
    return entries


def make_session(library: Any, location: dict, *,
                 shard_files: int | None = None,
                 lease_max_s: float | None = None) -> Any:
    """Split a location's orphan entries into a published-ready
    WorkSession."""
    from ...p2p.work import LEASE_MAX_S, WorkSession, WorkShard

    entries = build_shard_entries(library, location)
    n = max(1, shard_files or shard_files_default())
    session = WorkSession(
        id=uuid.uuid4().hex,
        library_id=library.id,
        location_pub=location["pub_id"].hex(),
        lease_max_s=lease_max_s if lease_max_s is not None else LEASE_MAX_S,
    )
    for i in range(0, len(entries), n):
        shard_id = f"{session.id[:8]}-{i // n:04d}"
        session.shards[shard_id] = WorkShard(
            id=shard_id, entries=entries[i:i + n]
        )
    return session


# --- shard execution (any node) -------------------------------------------


def _execute_shard_sync(library: Any, location: dict, entries: list[dict],
                        backend: str) -> list[dict]:
    """Worker-thread half of shard execution: journal consult → read →
    batch hash → link + vouch. Returns wire-shippable per-file results
    ``{pub_id, cas_id, ext, identity, chunks}``."""
    journal = _journal.IndexJournal(library.db)
    loc_id = location["id"]
    loc_path = location["path"]
    results: list[dict] = []
    messages: list[bytes] = []
    msg_results: list[dict] = []  # result dicts awaiting a cas
    to_record: list[tuple] = []   # journal vouches, written post-commit
    # stat pass first, then ONE batched journal consult for the whole
    # shard — the per-file SELECT was the GIL-bound floor ROADMAP PR 9
    # called out (128-entry shard = 128 round-trips into SQLite)
    stats: list[tuple[dict, "_journal.Identity | None"]] = []
    for e in entries:
        row = {"materialized_path": e["mat"], "name": e["name"],
               "extension": e["ext"], "is_dir": False}
        full = full_path_from_db_row(loc_path, row)
        stats.append((e, _journal.stat_identity(full)))
    consults = journal.consult_many(loc_id, [
        ((e["mat"], e["name"], e["ext"]), ident)
        for e, ident in stats
        if ident is not None and ident.size > 0
    ])
    for e, ident in stats:
        key = (e["mat"], e["name"], e["ext"])
        row = {"materialized_path": e["mat"], "name": e["name"],
               "extension": e["ext"], "is_dir": False}
        full = full_path_from_db_row(loc_path, row)
        result = {
            "pub_id": e["pub_id"], "ext": e["ext"], "cas_id": None,
            "identity": (
                [ident.inode, ident.dev, ident.mtime_ns, ident.size]
                if ident is not None else None
            ),
            "chunks": None,
        }
        results.append(result)
        if ident is None:
            continue  # vanished/unreadable: the next walk removes it
        if ident.size == 0:
            result["cas_id"] = ""
            to_record.append((key, ident, "", None, None))
            continue
        verdict, entry = consults.get(key, (_journal.MISS, None))
        if verdict == _journal.HIT and entry.cas_id:
            result["cas_id"] = entry.cas_id
            result["chunks"] = (
                entry.chunks.to_payload() if entry.chunks is not None
                else None
            )
            journal.bytes_saved(cas.message_len(ident.size),
                                location_id=loc_id)
            continue
        try:
            msg = cas.read_message(full, ident.size)
        except OSError as exc:
            logger.debug("mesh shard: unreadable %s: %s", full, exc)
            result["identity"] = None  # no vouch for an unreadable file
            continue
        messages.append(msg)
        msg_results.append(result)
        cache = cas.build_chunk_cache(msg)
        to_record.append((key, ident, result, cache, entry))
        result["chunks"] = cache.to_payload()
    if messages:
        t_hash = time.perf_counter()
        with span("mesh.shard_hash", nbytes=sum(len(m) for m in messages)):
            cas_ids = cas.cas_ids(messages, backend)
        # feed the same stage series the identifier job feeds, so
        # autotune.observed_files_per_s (the lease-sizing throughput
        # self-report) stays honest about mesh-executed files too
        _tm.IDENTIFIER_STAGE_SECONDS.observe(
            time.perf_counter() - t_hash, stage="hash")
        _tm.INDEX_BYTES_HASHED.inc(sum(len(m) for m in messages))
        for result, cas_hex in zip(msg_results, cas_ids):
            result["cas_id"] = cas_hex
    _tm.IDENTIFIER_FILES.inc(len(entries))

    # link + sync write FIRST, then the journal vouch (truth discipline:
    # a crash in between costs a redundant rehash, never a lie)
    from ...object.file_identifier.link import apply_cas_results

    t_db = time.perf_counter()
    apply_cas_results(library, results)
    records = []
    for key, ident, cas_or_result, cache, carry in to_record:
        cas_hex = (
            cas_or_result["cas_id"] if isinstance(cas_or_result, dict)
            else cas_or_result
        )
        if cas_hex is not None:  # "" = vouched-empty sentinel
            records.append((key, ident, cas_hex, cache, carry))
    journal.record_many(loc_id, records)
    _tm.IDENTIFIER_STAGE_SECONDS.observe(
        time.perf_counter() - t_db, stage="db")
    return results


async def resolve_location(library: Any, location_pub: str | None) -> dict:
    """Wait for a session's location row to exist on this replica. The
    row syncs like any other; a replica that has not ingested it yet
    nudges its ingest actor and waits briefly — a still-missing
    location raises, the caller skips, and the lease expires back to
    the pool. Shared by every stage executor (identify here, the rest
    in ``stages.py``)."""
    location = None
    loc_pub_bytes = bytes.fromhex(location_pub) if location_pub else None
    for attempt in range(20):
        if loc_pub_bytes is not None:
            location = library.db.find_one("location", pub_id=loc_pub_bytes)
        if location is not None and location.get("path"):
            break
        # the location create op may still be in flight: pull now
        actor = getattr(library, "ingest", None)
        if actor is not None:
            actor.notify()
        await asyncio.sleep(0.05)
    if location is None or not location.get("path"):
        raise RuntimeError(f"location {location_pub} not replicated here yet")
    return location


async def execute_shard(node: Any, library: Any, location_pub: str | None,
                        entries: list[dict], backend: str | None = None) \
        -> list[dict]:
    """Execute one identify shard against this node's replica (the
    stage-generic entry point is ``stages.execute_stage_shard``)."""
    location = await resolve_location(library, location_pub)
    if backend is None:
        backend = "auto" if getattr(node, "use_device", False) else "cpu"
    return await asyncio.to_thread(
        _execute_shard_sync, library, location, entries, backend
    )


class ShardTask(Task):
    """Local shard execution as a task-system unit: the coordinator's
    self-steal loop dispatches these so queue-wait/occupancy telemetry
    and priority preemption cover mesh work like any other work. Stage-
    typed: the task routes to its shard's execution leg."""

    def __init__(self, node: Any, library: Any, location_pub: str,
                 entries: list[dict], backend: str | None = None,
                 stage: str = "identify.hash"):
        super().__init__()
        self.node = node
        self.library = library
        self.location_pub = location_pub
        self.entries = entries
        self.backend = backend
        self.stage = stage
        self.output: list[dict] | None = None

    async def run(self, interrupter: Interrupter) -> ExecStatus:
        if interrupter.check() is not None:
            return ExecStatus.CANCELED
        from .stages import execute_stage_shard

        self.output = await execute_stage_shard(
            self.node, self.library, self.location_pub, self.stage,
            self.entries, self.backend,
        )
        return ExecStatus.DONE


# --- result merge (coordinator, from `complete` bodies) -------------------


def apply_remote_results(node: Any, session: Any, results: list[dict]) -> int:
    """Merge a peer's shipped shard results into this node's replica:
    cas/object rows via the idempotent linker, then journal vouches
    keyed by the identity the executor hashed under — the coordinator's
    journal converges to what a single-node pass would have written,
    without waiting for the peer's sync ops."""
    library = node.libraries.get(session.library_id)
    if library is None:
        return 0
    location = library.db.find_one(
        "location", pub_id=bytes.fromhex(session.location_pub)
    )
    if location is None:
        return 0
    from ...object.file_identifier.link import apply_cas_results

    clean = [r for r in results if isinstance(r, dict)]
    # emit_ops=False: the executing peer already minted the CRDT ops
    # (before its complete) — this is the direct-apply fast path, sync
    # remains the authoritative carrier
    apply_cas_results(library, clean, emit_ops=False)
    journal = _journal.IndexJournal(library.db)
    records = []
    for r in clean:
        ident_raw = r.get("identity")
        cas_hex = r.get("cas_id")
        if ident_raw is None or cas_hex is None:
            continue
        try:
            ident = _journal.Identity(*(int(x) for x in ident_raw))
        except (TypeError, ValueError):
            continue
        chunks = None
        if r.get("chunks") is not None:
            chunks = cas.ChunkCache.from_payload(r["chunks"])
        row = library.db.find_one(
            "file_path", pub_id=bytes.fromhex(str(r["pub_id"]))
        )
        if row is None or row.get("materialized_path") is None:
            continue  # create op not applied yet; peer's vouch suffices
        records.append((_journal.key_of(row), ident, cas_hex, chunks, None))
    journal.record_many(location["id"], records)
    return len(records)


# --- the coordinator loop -------------------------------------------------


async def distribute_location_index(
    node: Any,
    library: Any,
    location_id: int,
    *,
    shard_files: int | None = None,
    lease_max_s: float | None = None,
    backend: str | None = None,
    run_indexer: bool = True,
    deadline_s: float = 600.0,
) -> dict[str, Any]:
    """Walk locally, partition the identify work, and drive it to
    completion across the mesh. Returns pass stats (shards by executor,
    files, seconds). Degrades to a plain local pass when no peers are
    reachable — announce failures and refused claims only mean every
    shard ends up self-stolen."""
    from ...parallel.mesh import multihost_init

    t0 = time.perf_counter()
    location = library.db.find_one("location", id=location_id)
    if location is None or not location.get("path"):
        raise ValueError(f"location {location_id} not found")

    if run_indexer:
        from ...jobs.manager import JobBuilder
        from .job import IndexerJob

        await JobBuilder(IndexerJob({"location_id": location_id})).spawn(
            node.jobs, library
        )
        await node.jobs.wait_idle()

    # chips spanning hosts: join the jax.distributed cluster when the
    # env names one (no-op single-host; tests/test_multihost.py is the
    # seam proving the initialized path hashes correctly)
    multihost_init()

    session = make_session(
        library, location, shard_files=shard_files, lease_max_s=lease_max_s
    )
    return await _drive_session(
        node, library, session, backend=backend, deadline_s=deadline_s,
        t0=t0,
    )


async def distribute_location_stages(
    node: Any,
    library: Any,
    location_id: int,
    stage_ids: list[str],
    *,
    shard_files: int | None = None,
    lease_max_s: float | None = None,
    backend: str | None = None,
    deadline_s: float = 600.0,
) -> dict[str, Any]:
    """Distribute any set of post-identify pipeline stages for one
    location as ONE multi-stage session (stage ids from
    ``parallel/scheduler.py``). The stage drivers' distribute paths
    (thumbnail actor, media processor, duplicates pHash, embed) are
    thin wrappers over this. Degrades exactly like
    ``distribute_location_index``: with no P2P runtime every stage
    shard runs inline here, which IS today's pure-local pass in shard
    clothing."""
    from .stages import make_stage_session

    t0 = time.perf_counter()
    location = library.db.find_one("location", id=location_id)
    if location is None or not location.get("path"):
        raise ValueError(f"location {location_id} not found")
    session = make_stage_session(
        library, location, stage_ids,
        shard_files=shard_files, lease_max_s=lease_max_s,
    )
    return await _drive_session(
        node, library, session, backend=backend, deadline_s=deadline_s,
        t0=t0,
    )


async def _drive_session(
    node: Any, library: Any, session: Any, *,
    backend: str | None, deadline_s: float, t0: float,
) -> dict[str, Any]:
    """Drive a published-ready session to completion: publish →
    announce → self-steal through the task system → retire. Shared by
    the identify pass and the stage-typed distribute paths."""
    from .stages import execute_stage_shard

    manager = getattr(node, "p2p", None)
    plane = getattr(manager, "work", None)
    total_files = sum(len(s.entries) for s in session.shards.values())
    by_stage: dict[str, int] = {}
    for sh in session.shards.values():
        by_stage[sh.stage] = by_stage.get(sh.stage, 0) + 1
    if plane is None:
        # no P2P runtime: run every shard inline (still shard-shaped so
        # the journal/link/vouch path is identical)
        for shard in session.shards.values():
            await execute_stage_shard(
                node, library, session.location_pub, shard.stage,
                shard.entries, backend,
            )
        return {
            "session": session.id, "shards": len(session.shards),
            "files": total_files, "local_shards": len(session.shards),
            "remote_shards": 0, "peers": {}, "stages": by_stage,
            "seconds": round(time.perf_counter() - t0, 3),
        }

    plane.board.publish(session)
    acks = await plane.announce(session)
    WORK_EVENTS.emit("distribute_start", session=session.id,
                     shards=len(session.shards), peers_acked=acks)

    deadline = time.monotonic() + deadline_s
    local_shards = 0
    try:
        while not session.all_done():
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"mesh session {session.id} incomplete after "
                    f"{deadline_s}s ({session.pending()} shards pending)"
                )
            _session, grant, _lease = plane.board.claim(
                session.id, "local", max_shards=1, local=True,
            )
            if not grant:
                # everything is leased out (or done): wait for completes
                # / lease expiries; expire_leases runs inside claim()
                await asyncio.sleep(0.05)
                continue
            # normally one shard; an injected claim race can append a
            # duplicate-leased one — execute everything granted so a
            # shard re-leased to "local" (exempt from expiry) can never
            # strand
            handles = [
                (shard, node.task_system.dispatch(ShardTask(
                    node, library, session.location_pub, shard.entries,
                    backend, stage=shard.stage,
                )))
                for shard in grant
            ]
            for shard, handle in handles:
                result = await handle.wait()
                if result.error is not None:
                    raise result.error
                outcome = plane.board.complete(
                    session.id, shard.id, "local", local=True
                )
                if outcome == "completed":
                    local_shards += 1
    finally:
        # success or abandonment: drop the session from the board — the
        # shard entry lists duplicate the location's file metadata, and
        # a nightly coordinator must not accumulate one copy per pass
        # (workers see "done" and stop; late results still ride sync)
        plane.board.retire(session.id)

    by_peer: dict[str, int] = {}
    for shard_id, pid in session.completed_by.items():
        from ...telemetry.peers import peer_label

        label = "local" if pid == "local" else peer_label(pid)
        by_peer[label] = by_peer.get(label, 0) + 1
    stats = {
        "session": session.id,
        "shards": len(session.shards),
        "files": total_files,
        "local_shards": local_shards,
        "remote_shards": len(session.shards) - local_shards,
        "peers": by_peer,
        "stages": by_stage,
        "seconds": round(time.perf_counter() - t0, 3),
    }
    WORK_EVENTS.emit(
        "distribute_done",
        session=stats["session"],
        shards=stats["shards"],
        files=stats["files"],
        remote=stats["remote_shards"],
    )
    return stats
