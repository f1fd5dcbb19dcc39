"""FileIdentifierJob — cas_id hashing + object linking, TPU-batched.

Parity: ref:core/src/object/file_identifier/ — orphan query with cursor
pagination (file_identifier_job.rs:56-165), CHUNK_SIZE = 100 files per
step (mod.rs:33-34), FileMetadata::new = fs metadata + kind resolve +
cas_id (mod.rs:57-96), then cas_id sync updates + object
dedupe/create/connect (mod.rs:98-350).

TPU-first: where the reference hashes ≤100 files concurrently on CPU
cores (join_all), each step here assembles the sampled messages on the
host and hashes the whole chunk as ONE device batch (Pallas/XLA BLAKE3)
— the batch dim replaces task-level concurrency. The chunk size is
raised accordingly (devices want bigger batches), configurable via
init["chunk_size"].
"""

from __future__ import annotations

import logging
import os
import time
from typing import Any

from ...db.database import blob_u64, escape_like, new_pub_id, now_iso
from ...files.isolated_path import full_path_from_db_row as _row_full_path
from ...files.isolated_path import materialized_prefix
from .link import kind_for_row as _kind_for_row
from ...jobs import StatefulJob
from ...jobs.job import JobContext, JobError, StepResult
from ...jobs.manager import register_job
from ...location.indexer import journal as _journal
from ...ops import cas
from ...parallel import autotune as _autotune
from ...telemetry import metrics as _tm
from ...telemetry import span

logger = logging.getLogger(__name__)

# Window/depth sizing lives in the per-workload "identify"
# PipelinePolicy (parallel/autotune.py): the static base is
# IDENTIFY_DEVICE_WINDOW rows per accelerator (a v5e-8 window is 8192
# rows dp-sharded so every chip hashes a warm 1024-row shard from ONE
# dispatch) with feeder.pipeline_depth windows in flight; the
# closed-loop controller widens/narrows both from observed feeder
# wait and occupancy. CPU backends keep the reference's
# 100-row parity chunk (autotune.IDENTIFY_CPU_WINDOW, ref:mod.rs:34).


def orphan_where_clause(sub_path_mat: str | None = None) -> str:
    """Orphan = no object, not identified yet, real file
    (ref:file_identifier_job.rs orphan_path_filters)."""
    base = (
        "object_id IS NULL AND cas_id IS NULL AND is_dir = 0 "
        "AND location_id = ?"
    )
    if sub_path_mat is not None:
        base += " AND materialized_path LIKE ? ESCAPE '\\'"
    return base


def _open_and_read(full: str, size: int, want_identity: bool):
    """One open for a file's identity and its sampled bytes: →
    (identity, or None where the caller has the path's already;
    message; seconds inside the `fstat`). The descriptor is closed on
    every exit; an unreadable file or a short read is an OSError."""
    fd = os.open(full, os.O_RDONLY)
    try:
        ident, fstat_s = None, 0.0
        if want_identity:
            t_stat = time.perf_counter()
            ident = _journal.fd_identity(fd)
            fstat_s = time.perf_counter() - t_stat
        return ident, cas.read_message_fd(fd, size), fstat_s
    finally:
        os.close(fd)


@register_job
class FileIdentifierJob(StatefulJob):
    """init: {location_id, sub_path?, backend?, chunk_size?}"""

    NAME = "file_identifier"
    INVALIDATES = ("search.paths", "search.objects")
    IS_BATCHED = True
    _pipeline = None  # runtime-only window pipeline (never serialized)

    async def init_job(self, ctx: JobContext) -> None:
        async with span("identify.init"):
            self._init(ctx)

    def _init(self, ctx: JobContext) -> None:
        library = ctx.library
        loc_id = self.init["location_id"]
        location = library.db.find_one("location", id=loc_id)
        if location is None:
            raise JobError(f"location {loc_id} not found")

        backend = self.init.get("backend", "auto")
        if backend in ("tpu", "device", "auto"):
            from ...parallel.mesh import accelerator_count

            # the STATIC base sizes the step estimate; live windows are
            # re-read from the policy per fetch (an autotuned window may
            # grow — fewer windows than steps, the extras no-op — or
            # shrink — execute_step drains via more_steps)
            default_chunk = (
                _autotune.IDENTIFY_DEVICE_WINDOW * accelerator_count()
            )
        else:
            default_chunk = _autotune.IDENTIFY_CPU_WINDOW
        chunk = self.init.get("chunk_size") or default_chunk

        params: list[Any] = [loc_id]
        where = orphan_where_clause(self.init.get("sub_path") and self.init["sub_path"])
        if self.init.get("sub_path"):
            params.append(escape_like(materialized_prefix(self.init['sub_path'])) + "%")
        total = library.db.count("file_path", where, tuple(params))

        self.data.update(
            location_id=loc_id,
            location_path=location["path"],
            backend=backend,
            chunk_size=chunk,
            cursor=0,
        )
        n_steps = (total + chunk - 1) // chunk
        for _ in range(n_steps):
            self.steps.append({"kind": "identify"})
        self.run_metadata.update(
            total_orphan_paths=total, created_objects=0, linked_objects=0,
            hash_time=0.0, db_time=0.0,
            journal_hits=0, journal_dirty_rehash=0,
        )
        ctx.progress(
            task_count=n_steps,
            message=f"identifying {total} orphan paths", phase="identifying",
        )

    def _fetch_window(self, library, cursor: int):
        """Read+dispatch stage: one cursor window of rows, their sampled
        bytes, and — on the device path — the hash batch already
        dispatched (async) so back-to-back windows pipeline transfers.
        Runs on a worker thread; disk I/O never blocks the loop.

        The index journal is consulted BEFORE any byte is read, and
        once a window: its rows for the window's keys come in one read
        after the page, and each file is judged in memory as the loop
        comes to it, so the loop itself never takes the connection's
        lock (link-commit of the window before holds it meanwhile).
        A file the journal holds an entry for is judged against
        `stat_identity` of its path: a `hit` reuses the vouched cas_id
        with zero I/O; an invalidated entry with a chunk cache and an
        unchanged message length takes the host dirty-range rehash
        (only dirty chunks pay BLAKE3, zero bytes shipped to the
        device). A file the journal holds no entry for has nothing to
        be judged against: it is opened once, and its identity comes
        from the descriptor its bytes are read through."""
        d = self.data
        params: list[Any] = [d["location_id"]]
        where = orphan_where_clause(self.init.get("sub_path"))
        if self.init.get("sub_path"):
            params.append(escape_like(materialized_prefix(self.init['sub_path'])) + "%")
        limit = self._window_limit()
        # cursor pagination by id (ref:file_identifier_job.rs:126-165)
        with span("identify.page"):
            rows = library.db.query(
                f"SELECT * FROM file_path WHERE {where} AND id > ? ORDER BY id LIMIT ?",
                tuple(params) + (cursor, limit),
            )
        loc_path = d["location_path"]
        loc_id = d["location_id"]
        journal = _journal.IndexJournal(library.db)
        metas: list[dict | None] = []
        messages: list[bytes] = []
        msg_rows: list[dict] = []
        resolved: dict[int, str] = {}  # row id -> cas from journal/dirty-range
        # row id -> (key, identity, cas, chunk cache, prior entry) to
        # vouch after commit; the prior entry lets an unchanged-content
        # re-record (mtime-only touch) keep its thumb/media/phash vouches
        to_record: dict[int, tuple] = {}
        jstats = {"hit": 0, "dirty": 0, "dirty_chunks": 0}
        # the row loop is one span per window; what it does per file is
        # timed into locals and observed once per window: the span less
        # these five is the loop's own Python
        read_s = chunk_cache_s = stat_s = journal_s = rehash_s = 0.0
        n_sampled = 0  # messages in the sampled layout (file over 100 KiB)
        n_by_path = n_by_fd = 0  # identities taken, by the call that gave them
        with span("identify.rows"):
            t_journal = time.perf_counter()
            keys = [_journal.key_of(row) for row in rows]
            known = journal.fetch_rows(loc_id, keys)
            journal_s += time.perf_counter() - t_journal
            for row, key in zip(rows, keys):
                full = _row_full_path(loc_path, row)
                size = blob_u64(row["size_in_bytes_bytes"]) or 0
                has_entry = known is not None and key in known
                ident = None
                if has_entry or size == 0:
                    t_stat = time.perf_counter()
                    ident = _journal.stat_identity(full)
                    stat_s += time.perf_counter() - t_stat
                    n_by_path += ident is not None
                if size == 0:
                    metas.append({"row": row, "cas_id": None})
                    # journal the empty file (cas sentinel "") so warm-pass
                    # walks get a `hit` instead of an eternal miss
                    if ident is not None:
                        to_record[row["id"]] = (key, ident, "", None, None)
                    continue
                entry = None
                if ident is not None or not has_entry:
                    # the walker already counted this file's verdict this
                    # pass — don't double-count the invalidation here
                    t_journal = time.perf_counter()
                    verdict, entry = journal.judge(
                        loc_id, key, known, ident, count_invalidated=False
                    )
                    vouched = verdict == _journal.HIT and entry.cas_id
                    if vouched:
                        journal.bytes_saved(cas.message_len(size),
                                            location_id=loc_id)
                    journal_s += time.perf_counter() - t_journal
                    if vouched:
                        # vouched: skip the read, the hash, and the transfer
                        resolved[row["id"]] = entry.cas_id
                        jstats["hit"] += 1
                        metas.append({"row": row, "cas_id": "journal"})
                        continue
                t_read = time.perf_counter()
                fstat_s = 0.0
                try:
                    fd_ident, msg, fstat_s = _open_and_read(
                        full, size, want_identity=not has_entry)
                except OSError as e:
                    metas.append(None)
                    logger.debug("identifier: unreadable %s: %s", full, e)
                    continue
                finally:
                    stat_s += fstat_s
                    read_s += time.perf_counter() - t_read - fstat_s
                if fd_ident is not None:
                    ident = fd_ident
                    n_by_fd += 1
                if (
                    ident is not None
                    and entry is not None
                    and entry.chunks is not None
                    and entry.chunks.msg_len == len(msg)
                    and len(msg) > cas.CHUNK_LEN
                ):
                    t_rehash = time.perf_counter()
                    try:
                        rehashed = cas.dirty_range_rehash(msg, entry.chunks)
                    except ValueError:
                        rehashed = None
                    rehash_s += time.perf_counter() - t_rehash
                    if rehashed is not None:
                        cas_id, cache, n_dirty, hashed = rehashed
                        resolved[row["id"]] = cas_id
                        to_record[row["id"]] = (key, ident, cas_id, cache, entry)
                        journal.bytes_saved(len(msg) - hashed,
                                            location_id=loc_id)
                        _tm.INDEX_BYTES_HASHED.inc(hashed)
                        jstats["dirty"] += 1
                        jstats["dirty_chunks"] += n_dirty
                        metas.append({"row": row, "cas_id": "journal"})
                        continue
                messages.append(msg)
                msg_rows.append(row)
                n_sampled += size > cas.MINIMUM_FILE_SIZE
                metas.append({"row": row, "cas_id": "pending"})
                if ident is not None:
                    # cas filled in post-hash; digest-only chunk cache so the
                    # FIRST in-place modification can already diff chunks
                    t_cache = time.perf_counter()
                    cache = cas.build_chunk_cache(msg)
                    chunk_cache_s += time.perf_counter() - t_cache
                    to_record[row["id"]] = (key, ident, None, cache, entry)
        _tm.IDENTIFIER_STAGE_SECONDS.observe(read_s, stage="read")
        _tm.IDENTIFIER_STAGE_SECONDS.observe(chunk_cache_s,
                                             stage="chunk_cache")
        _tm.IDENTIFIER_STAGE_SECONDS.observe(stat_s, stage="stat")
        _tm.IDENTIFIER_STAGE_SECONDS.observe(journal_s, stage="journal")
        _tm.IDENTIFIER_STAGE_SECONDS.observe(rehash_s, stage="rehash")
        _tm.IDENTIFIER_MESSAGES.inc(n_sampled, layout="sampled")
        _tm.IDENTIFIER_MESSAGES.inc(len(messages) - n_sampled, layout="whole")
        _tm.IDENTIFIER_IDENTITY.inc(n_by_fd, source="descriptor")
        _tm.IDENTIFIER_IDENTITY.inc(n_by_path, source="path")
        backend = d["backend"]
        use_device = backend in ("tpu", "device") or (
            backend == "auto" and cas._device_available()
        )
        if use_device and messages:
            dispatch_exc: Exception | None = None
            try:
                fin = cas.cas_ids_begin(messages)  # async dispatch NOW
            except Exception as exc:  # noqa: BLE001 - surfaced by finisher
                fin, dispatch_exc = None, exc

            def finisher(fin=fin, messages=messages, backend=backend,
                         dispatch_exc=dispatch_exc):
                # JAX dispatch is async — device failures usually surface
                # at materialization, so the fallback wraps the FINISH.
                # Explicit "tpu" stays strict: the job fails with the
                # device's own error. "auto" degrades to host, counted.
                if fin is not None:
                    try:
                        return fin()
                    except Exception:
                        if backend != "auto":
                            raise
                        logger.warning("device hashing failed; host fallback",
                                       exc_info=True)
                elif backend != "auto":
                    raise RuntimeError(
                        "device dispatch failed") from dispatch_exc
                _tm.CAS_BACKEND_FALLBACK.inc()
                return cas.cas_ids(messages, "cpu")

        else:
            finisher = lambda: cas.cas_ids(messages, backend)
        return (rows, metas, messages, msg_rows, finisher, resolved,
                to_record, jstats, limit)

    def _window_limit(self) -> int:
        """Rows for the next cursor window. An explicit init
        ``chunk_size`` pins it; device backends read the LIVE
        "identify" PipelinePolicy (the autotuner's seam — each fetch
        sees the current window sizing); CPU backends keep the
        reference parity chunk recorded at init."""
        d = self.data
        if self.init.get("chunk_size"):
            return d["chunk_size"]
        if d["backend"] in ("tpu", "device", "auto"):
            from ...parallel.mesh import accelerator_count

            return _autotune.policy("identify").identify_window_rows(
                accelerator_count()
            )
        return d["chunk_size"]

    async def execute_step(self, ctx: JobContext, step: dict, step_number: int) -> StepResult:
        import asyncio

        from ...parallel import WindowPipeline
        from ...parallel.mesh import accelerator_count

        library = ctx.library
        d = self.data
        if self._pipeline is None:
            # The producer chains cursor windows back-to-back: window
            # N+1's disk reads and device dispatch start as soon as N's
            # reads finish, so up to feeder-depth transfers are in
            # flight while this step's hashes complete and its DB writes
            # run (SURVEY §7 hard part #2). Fetches are side-effect-free,
            # so a pause/resume simply re-reads in-flight windows. The
            # depth is a LIVE policy read (autotuner seam): each parked
            # window re-checks the current bound.
            def fetch(cursor):
                window = self._fetch_window(library, cursor)
                rows = window[0]
                if not rows:
                    return None
                return rows[-1]["id"], window

            self._pipeline = WindowPipeline(
                fetch, d["cursor"],
                depth=lambda: _autotune.policy("identify").feeder_depth(
                    accelerator_count()
                ),
                # window[2] = the sampled messages riding the H2D link
                measure=lambda w: sum(len(m) for m in w[2]),
            )

        t0 = time.perf_counter()
        window = await asyncio.to_thread(self._pipeline.take)
        take_time = time.perf_counter() - t0
        if window is None:
            return StepResult()
        (rows, metas, messages, msg_rows, finisher, resolved, to_record,
         jstats, limit) = window
        d["cursor"] = rows[-1]["id"]

        _tm.IDENTIFIER_BATCH_FILL.observe(len(rows) / limit)
        msg_bytes = sum(len(m) for m in messages)
        async with span("identify.hash", nbytes=msg_bytes) as hash_span:
            cas_ids = await asyncio.to_thread(finisher)
            if jstats["hit"] or jstats["dirty"]:
                # journal verdict on the trace: how much of this window
                # the journal spared the device
                hash_span.annotate(
                    journal_hits=jstats["hit"],
                    journal_dirty_rehash=jstats["dirty"],
                    journal_dirty_chunks=jstats["dirty_chunks"],
                )
        _tm.INDEX_BYTES_HASHED.inc(msg_bytes)
        # run_metadata keeps its historical take+finish meaning; the
        # STAGE metric must cover only the finisher, or feeder wait
        # (its own series) would masquerade as device-hash time
        hash_time = time.perf_counter() - t0
        _tm.IDENTIFIER_STAGE_SECONDS.observe(hash_span.duration,
                                             stage="hash")

        by_row_id = {r["id"]: c for r, c in zip(msg_rows, cas_ids)}
        by_row_id.update(resolved)

        t1 = time.perf_counter()
        async with span("identify.db"):
            created, linked = self._link_objects(library, rows, by_row_id)
            # journal vouches ONLY after the cas/object sync write
            # committed: a crash in between costs a redundant rehash on
            # resume, never a journal entry ahead of the DB
            records = []
            for row_id, (key, ident, cas_hex, cache, carry) in to_record.items():
                if cas_hex is None:
                    cas_hex = by_row_id.get(row_id)
                if cas_hex is not None:  # "" = vouched-empty sentinel
                    records.append((key, ident, cas_hex, cache, carry))
            with span("journal.record"):
                _journal.IndexJournal(library.db).record_many(
                    d["location_id"], records
                )
        db_time = time.perf_counter() - t1
        _tm.IDENTIFIER_STAGE_SECONDS.observe(db_time, stage="db")
        _tm.IDENTIFIER_FILES.inc(len(rows))
        # the per-batch device vs host split the TPU capacity model
        # needs: finisher = device materialization; window wait + DB
        # linking = host
        _tm.PIPELINE_DEVICE_SECONDS.observe(hash_span.duration,
                                            pipeline="identify")
        _tm.PIPELINE_HOST_SECONDS.observe(take_time + db_time,
                                          pipeline="identify")

        errors = [f"unreadable file_path {r['id']}" for m, r in zip(metas, rows) if m is None]
        # the step count was estimated from the STATIC window at init;
        # if the autotuner shrank windows mid-job there are more windows
        # than steps — on the last step, keep draining until the cursor
        # is exhausted (an extra step against a dry pipeline no-ops)
        more_steps = [] if self.steps else [{"kind": "identify"}]
        return StepResult(
            errors=errors,
            more_steps=more_steps,
            metadata={
                "created_objects": self.run_metadata["created_objects"] + created,
                "linked_objects": self.run_metadata["linked_objects"] + linked,
                "hash_time": round(self.run_metadata["hash_time"] + hash_time, 4),
                "db_time": round(self.run_metadata["db_time"] + db_time, 4),
                "journal_hits": (
                    self.run_metadata.get("journal_hits", 0) + jstats["hit"]
                ),
                "journal_dirty_rehash": (
                    self.run_metadata.get("journal_dirty_rehash", 0)
                    + jstats["dirty"]
                ),
            },
        )

    def _link_objects(
        self, library, rows: list[dict], cas_by_row_id: dict[int, str]
    ) -> tuple[int, int]:
        """cas_id updates + object dedupe/create/connect in one sync
        write (ref:mod.rs:157-347)."""
        sync = library.sync
        ops = []
        created = linked = 0

        # existing objects for these cas_ids
        distinct = sorted({c for c in cas_by_row_id.values()})
        existing: dict[str, tuple[int, bytes]] = {}
        if distinct:
            qmarks = ",".join("?" for _ in distinct)
            for row in library.db.query(
                f"SELECT fp.cas_id, fp.object_id, o.pub_id AS object_pub FROM file_path fp "
                f"JOIN object o ON o.id = fp.object_id "
                f"WHERE fp.cas_id IN ({qmarks}) AND fp.object_id IS NOT NULL",
                tuple(distinct),
            ):
                existing.setdefault(row["cas_id"], (row["object_id"], row["object_pub"]))

        new_objects: dict[str, tuple[bytes, dict]] = {}  # cas -> (obj pub_id, row)
        updates: list[tuple[dict, str, int | None, bytes | None]] = []
        for row in rows:
            cas_id = cas_by_row_id.get(row["id"])
            if cas_id is None:
                continue
            if cas_id in existing:
                obj_id, obj_pub = existing[cas_id]
                updates.append((row, cas_id, obj_id, obj_pub))
                linked += 1
            elif cas_id in new_objects:
                updates.append((row, cas_id, None, new_objects[cas_id][0]))
                linked += 1
            else:
                obj_pub = new_pub_id()
                new_objects[cas_id] = (obj_pub, row)
                updates.append((row, cas_id, None, obj_pub))
                created += 1

        date_created = now_iso()
        obj_rows: dict[bytes, int] = {}

        def writes(conn):
            # create missing objects
            for cas_id, (obj_pub, src_row) in new_objects.items():
                kind = _kind_for_row(src_row)
                cur = conn.execute(
                    "INSERT INTO object (pub_id, kind, date_created) VALUES (?,?,?)",
                    (obj_pub, int(kind), date_created),
                )
                obj_rows[obj_pub] = cur.lastrowid
            # connect + cas updates
            for row, cas_id, obj_id, obj_pub in updates:
                if obj_id is None and obj_pub is not None:
                    obj_id = obj_rows.get(obj_pub)
                conn.execute(
                    "UPDATE file_path SET cas_id = ?, object_id = ? WHERE id = ?",
                    (cas_id, obj_id, row["id"]),
                )

        for cas_id, (obj_pub, src_row) in new_objects.items():
            kind = _kind_for_row(src_row)
            ops.extend(
                sync.shared_create(
                    "object", obj_pub.hex(),
                    [("kind", int(kind)), ("date_created", date_created)],
                )
            )
        for row, cas_id, _obj_id, obj_pub in updates:
            rid = row["pub_id"].hex()
            ops.append(sync.shared_update("file_path", rid, "cas_id", cas_id))
            if obj_pub is not None:
                ops.append(
                    sync.shared_update("file_path", rid, "object_id", obj_pub.hex())
                )

        sync.write_ops(ops, writes)
        return created, linked

    def cleanup(self) -> None:
        """Every exit path (done/pause/cancel/fail) stops the window
        pipeline and keeps its stats."""
        if self._pipeline is not None:
            stats = self._pipeline.stats
            self.run_metadata["prefetch_hits"] = stats.prefetch_hits
            self.run_metadata["prefetch_misses"] = stats.prefetch_misses
            self._pipeline.close()
            self._pipeline = None

    async def finalize(self, ctx: JobContext) -> Any:
        self.cleanup()
        ctx.progress(message="identification complete", phase="done")
        return dict(self.run_metadata)


