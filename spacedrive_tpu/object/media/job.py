"""MediaProcessorJob — thumbnails + EXIF rows + labeler batches.

Parity: ref:core/src/object/media/media_processor/job.rs — init
dispatches ALL thumbnails to the node-wide thumbnailer actor (:148-170),
optionally enqueues an image-labeler batch (:176-196); steps are chunks
of 10 files of EXIF extraction plus WaitThumbnails/WaitLabels
rendezvous steps (:83-88, :199-230).
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import time
from typing import Any

import hashlib

from ...db.database import blob_u64, escape_like
from ...files.isolated_path import full_path_from_db_row as _full_path
from ...files.isolated_path import materialized_prefix
from ...jobs import StatefulJob
from ...jobs.job import JobContext, JobError, StepResult
from ...jobs.manager import register_job
from ...location.indexer import journal as _journal
from ...telemetry import span
from .media_data import ImageMetadata

logger = logging.getLogger(__name__)

BATCH_SIZE = 10  # ref:media_processor/job.rs:50


def _media_digest(cols: dict) -> str:
    """Stable digest of an extracted media_data row — the journal's
    "this metadata is already in the DB" vouch."""
    canon = repr(sorted(cols.items())).encode()
    return hashlib.blake2b(canon, digest_size=8).hexdigest()

# extensions we can thumbnail / extract exif from (decodable subset of
# the reference's FILTERED_{IMAGE,VIDEO}_EXTENSIONS; videos get a
# keyframe thumb, ref:media_processor/job.rs + thumbnail/process.rs:463)
from .images import HEIF_EXTENSIONS, heif_available
from .thumbnail.process import (
    DOC_EXTENSIONS,
    IMAGE_EXTENSIONS,
    VIDEO_EXTENSIONS,
)

THUMBNAILABLE_EXTENSIONS = (
    tuple(IMAGE_EXTENSIONS) + tuple(VIDEO_EXTENSIONS) + tuple(DOC_EXTENSIONS)
)
# PIL reads the first five; the EXIF item of a HEIF container is read
# through libheif (`images.heif_container`), so those only where it loads
EXIF_EXTENSIONS = ("jpg", "jpeg", "png", "tiff", "webp") + (
    tuple(sorted(HEIF_EXTENSIONS)) if heif_available() else ())
# media_data rows extract for EXIF-bearing images AND videos
# (ref:media_data_extractor.rs images; video facts via the decoder)
MEDIA_DATA_EXTENSIONS = EXIF_EXTENSIONS + tuple(VIDEO_EXTENSIONS)


class _EmbedPlanes:
    """The embedder's input planes made from the frames the thumbnailer
    decoded for this job's batch, so the embed step does not open the
    files again. `offer` is the batch's sink (thumbnail/actor.py
    FrameSink, called on the decode worker threads); `take` is the
    embed step's. Keyed by cas_id, `uint8` as the resize gives them
    (3,072 B an image), and only for rows the job will embed, each kept
    until the last of them has taken it (copies share a cas_id and so a
    plane). In memory only, never in the job's serialised state, empty
    once `close`d: at most 3,072 B x the job's embeddable rows, 300 MB
    at 100,000 photos."""

    def __init__(self, cas_ids: list[str]):
        self._lock = threading.Lock()
        self._uses = collections.Counter(cas_ids)  # takes still to come
        self._claimed: set[str] = set()  # a plane is made or being made
        self._planes: dict[str, Any] = {}
        self._closed = False

    def __len__(self) -> int:
        return len(self._planes)

    def offer(self, cas_id: str, frame: Any, scale: int) -> None:
        from ...models import embedder as _embedder

        with self._lock:
            if (self._closed or cas_id in self._claimed
                    or self._uses[cas_id] <= 0):
                return
            self._claimed.add(cas_id)
        plane = _embedder.plane_from_frame(frame, scale)
        with self._lock:
            if not self._closed:
                self._planes[cas_id] = plane

    def take(self, cas_id: str) -> Any:
        """The `uint8` plane for one row, or None where none arrived."""
        with self._lock:
            plane = self._planes.get(cas_id)
            self._uses[cas_id] -= 1
            if self._uses[cas_id] <= 0:
                self._planes.pop(cas_id, None)
            return plane

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._planes.clear()


@register_job
class MediaProcessorJob(StatefulJob):
    """init: {location_id, sub_path?, backend?}"""

    NAME = "media_processor"
    INVALIDATES = ("search.paths", "labels.list", "search.semantic")
    IS_BATCHED = True

    # planes from the thumbnailer's frames (`_EmbedPlanes`); a job that
    # was resumed from its serialised state has none and decodes
    _planes: _EmbedPlanes | None = None

    def cleanup(self) -> None:
        if self._planes is not None:
            self._planes.close()
            self._planes = None

    async def init_job(self, ctx: JobContext) -> None:
        async with span("media.init"):
            await self._init(ctx)

    async def _init(self, ctx: JobContext) -> None:
        library = ctx.library
        loc_id = self.init["location_id"]
        location = library.db.find_one("location", id=loc_id)
        if location is None:
            raise JobError(f"location {loc_id} not found")
        self.data.update(location_id=loc_id, location_path=location["path"])

        qmarks = ",".join("?" for _ in THUMBNAILABLE_EXTENSIONS)
        sub_filter = ""
        params: list[Any] = [loc_id, *THUMBNAILABLE_EXTENSIONS]
        if self.init.get("sub_path"):
            sub_filter = " AND materialized_path LIKE ? ESCAPE '\\'"
            params.append(escape_like(materialized_prefix(self.init['sub_path'])) + "%")
        rows = library.db.query(
            f"SELECT id, pub_id, cas_id, object_id, materialized_path, name, "
            f"extension, size_in_bytes_bytes "
            f"FROM file_path WHERE location_id = ? AND is_dir = 0 "
            f"AND object_id IS NOT NULL AND cas_id IS NOT NULL "
            f"AND extension IN ({qmarks}){sub_filter}",
            tuple(params),
        )

        # consult the index journal per row BEFORE dispatching work: a
        # fresh entry vouching this exact cas_id skips the thumbnail
        # dispatch (thumb already stored) and the EXIF re-extract —
        # the warm-pass "never re-thumbnail an unchanged byte" half.
        # Off-loop: the loop stats + SELECTs once per media file, which
        # on a 100k-file location would stall the event loop for seconds
        # (the identifier runs its consults inside to_thread the same way)
        import asyncio

        journal = _journal.IndexJournal(library.db)
        loc_path = self.data["location_path"]

        def consult_all() -> dict[int, "_journal.JournalEntry | None"]:
            out: dict[int, "_journal.JournalEntry | None"] = {}
            for r in rows:
                # count_invalidated=False: the walker already judged
                # changed files this pass — don't double-count here
                verdict, entry = journal.lookup(
                    loc_id, _journal.key_of(r),
                    _journal.stat_identity(_full_path(loc_path, r)),
                    count_invalidated=False,
                )
                out[r["id"]] = (
                    entry
                    if verdict == _journal.HIT and entry is not None
                    and entry.cas_id == r["cas_id"]
                    else None
                )
            return out

        vouched = await asyncio.to_thread(consult_all)

        # semantic embedding stage (SD_EMBED=0 ⇒ a true no-op: no
        # steps, no DB writes, no sync ops — today's pipeline exactly).
        # Its rows are chosen before the thumbnails are dispatched: the
        # batch's decode stage makes the planes of exactly these.
        from ...models import embedder as _embedder

        embed_rows = []
        if _embedder.enabled():
            from ...telemetry import metrics as _tm

            for r in rows:
                if (r["extension"] or "").lower() not in IMAGE_EXTENSIONS:
                    continue
                entry = vouched[r["id"]]
                if entry is not None and entry.embed:
                    # journal vouched: unchanged bytes are never
                    # re-read, never re-embedded
                    journal.bytes_saved(
                        blob_u64(r["size_in_bytes_bytes"]) or 0,
                        location_id=loc_id,
                    )
                    _tm.EMBED_FILES.inc(result="skipped")
                    continue
                embed_rows.append(r)

        # dispatch remaining thumbnails up-front to the node thumbnailer
        # actor (ref:job.rs:148-156); the job only awaits counts later.
        thumbnailer = getattr(getattr(library, "node", None), "thumbnailer", None)
        dispatched = 0
        thumb_batch_id = 0
        thumb_vouch: list[list] = []  # keys to vouch post-rendezvous
        if thumbnailer is not None and rows:
            batch = []
            for r in rows:
                entry = vouched[r["id"]]
                if entry is not None and entry.thumb:
                    journal.bytes_saved(
                        blob_u64(r["size_in_bytes_bytes"]) or 0,
                        location_id=loc_id,
                    )
                    continue
                batch.append((r["cas_id"], _full_path(loc_path, r)))
                thumb_vouch.append(
                    [*_journal.key_of(r), r["cas_id"]]
                )
            if batch:
                sink = None
                if embed_rows:
                    self._planes = _EmbedPlanes(
                        [r["cas_id"] for r in embed_rows])
                    sink = self._planes.offer
                thumb_batch_id = thumbnailer.new_indexed_thumbnails_batch(
                    library.id, batch, background=False, sink=sink
                )
            dispatched = len(batch)
        self.data["thumbs_dispatched"] = dispatched

        exif_rows = []
        for r in rows:
            if (r["extension"] or "").lower() not in MEDIA_DATA_EXTENSIONS:
                continue
            entry = vouched[r["id"]]
            if entry is not None and entry.media_digest is not None:
                journal.bytes_saved(
                    blob_u64(r["size_in_bytes_bytes"]) or 0,
                    location_id=loc_id,
                )
                continue
            exif_rows.append(r)
        for i in range(0, len(exif_rows), BATCH_SIZE):
            chunk = exif_rows[i:i + BATCH_SIZE]
            self.steps.append(
                {
                    "kind": "extract_media_data",
                    "ids": [(r["id"], r["object_id"]) for r in chunk],
                }
            )
        if dispatched:
            self.steps.append(
                {
                    "kind": "wait_thumbnails",
                    "count": dispatched,
                    "batch_id": thumb_batch_id,
                    # journal vouches written AFTER the rendezvous, and
                    # only for thumbs verifiably in the store — so the
                    # journal can never claim a thumb a crash swallowed
                    "vouch": thumb_vouch,
                }
            )
        if embed_rows:
            from ...parallel import autotune as _autotune
            from ...parallel import mesh as _mesh

            chunk_rows = _autotune.policy("embed").embed_chunk_rows(
                _mesh.accelerator_count()
            )
            for i in range(0, len(embed_rows), chunk_rows):
                chunk = embed_rows[i:i + chunk_rows]
                self.steps.append(
                    {
                        "kind": "embed",
                        "ids": [(r["id"], r["object_id"]) for r in chunk],
                    }
                )

        labeler = getattr(getattr(library, "node", None), "image_labeler", None)
        label_rows = [
            r for r in rows if (r["extension"] or "").lower() in IMAGE_EXTENSIONS
        ]
        if labeler is not None and label_rows:
            loc_path = self.data["location_path"]
            batch_id = labeler.new_batch(
                library,
                [
                    {"file_path_id": r["id"], "object_id": r["object_id"],
                     "path": _full_path(loc_path, r)}
                    for r in label_rows
                ],
            )
            self.steps.append({"kind": "wait_labels", "batch_id": batch_id})

        self.run_metadata.update(
            media_data_extracted=0, media_data_skipped=0,
            thumbnails_dispatched=dispatched, embeddings_written=0,
        )
        ctx.progress(
            message=f"processing media for {len(rows)} files", phase="media"
        )

    async def execute_step(self, ctx: JobContext, step: dict, step_number: int) -> StepResult:
        kind = step["kind"]
        if kind == "extract_media_data":
            with span("media.extract"):
                return self._extract_media_data(ctx, step)
        if kind == "embed":
            import asyncio

            # decode + device forward + commit are all blocking; the
            # loop keeps serving other jobs meanwhile
            return await asyncio.to_thread(self._embed_files, ctx, step)
        if kind == "wait_thumbnails":
            return await self._wait_thumbnails(ctx, step)
        if kind == "wait_labels":
            return await self._wait_labels(ctx, step)
        return StepResult()

    def _extract_media_data(self, ctx: JobContext, step: dict) -> StepResult:
        from ...telemetry import metrics as _tm

        library = ctx.library
        loc_path = self.data["location_path"]
        loc_id = self.data["location_id"]
        journal = _journal.IndexJournal(library.db)
        extracted = skipped = 0
        for fp_id, object_id in step["ids"]:
            row = library.db.find_one("file_path", id=fp_id)
            if row is None or object_id is None:
                skipped += 1
                continue
            full = _full_path(loc_path, row)
            ext = (row["extension"] or "").lower()
            if ext in VIDEO_EXTENSIONS:
                from .media_data import VideoMetadata

                with span("video") as probe:
                    meta = VideoMetadata.from_path(full)
                _tm.MEDIA_EXTRACT_SECONDS.observe(
                    probe.duration, kind="video")
            elif ext in HEIF_EXTENSIONS:
                with span("heif") as read:
                    meta = ImageMetadata.from_path(full)
                _tm.MEDIA_EXTRACT_SECONDS.observe(read.duration, kind="heif")
            else:
                t0 = time.perf_counter()
                meta = ImageMetadata.from_path(full)
                _tm.MEDIA_EXTRACT_SECONDS.observe(
                    time.perf_counter() - t0, kind="image")
            if meta is None:
                skipped += 1
                # still a vouch: "probed, nothing extractable" — stops
                # warm passes from re-reading EXIF-less files forever
                journal.vouch_media(
                    loc_id, _journal.key_of(row), row["cas_id"], ""
                )
                continue
            cols = meta.to_row(object_id)
            library.db.upsert("media_data", {"object_id": object_id}, **{
                k: v for k, v in cols.items() if k != "object_id"
            })
            extracted += 1
            # vouch ordered after the media_data upsert committed
            journal.vouch_media(
                loc_id, _journal.key_of(row), row["cas_id"],
                _media_digest(cols),
            )
        return StepResult(
            metadata={
                "media_data_extracted": self.run_metadata["media_data_extracted"] + extracted,
                "media_data_skipped": self.run_metadata["media_data_skipped"] + skipped,
            }
        )

    def _embed_files(self, ctx: JobContext, step: dict) -> StepResult:
        """One embedding chunk: the planes (the thumbnailer's frames
        gave them where this job's batch decoded the file; what is left
        is decoded here) →
        one padded device forward (ops/embed_jax, DeviceLadder
        demotion inside) → object_embedding rows + their CRDT ops in
        ONE transaction via sync.write_ops, so the vectors replicate
        live like any other shared model. Journal vouches are written
        strictly AFTER that commit."""
        import numpy as np

        from ...ops import embed_jax
        from ...telemetry import metrics as _tm

        library = ctx.library
        loc_path = self.data["location_path"]
        loc_id = self.data["location_id"]
        journal = _journal.IndexJournal(library.db)

        items: list[tuple[dict, int, str]] = []  # (row, object_id, path)
        errors = 0
        for fp_id, object_id in step["ids"]:
            row = library.db.find_one("file_path", id=fp_id)
            if row is None or object_id is None:
                errors += 1
                continue
            items.append((row, object_id, _full_path(loc_path, row)))
        if not items:
            if errors:
                _tm.EMBED_FILES.inc(errors, result="error")
            return StepResult()

        with span("embed.decode") as stage:
            planes = self._planes_for_embed(items)
        _tm.EMBED_STAGE_SECONDS.observe(stage.duration, stage="decode")

        batch_rows: list[tuple[dict, int]] = []
        batch_imgs: list[np.ndarray] = []
        for (row, object_id, _path), img in zip(items, planes):
            if img is None:
                errors += 1
                continue
            batch_rows.append((row, object_id))
            batch_imgs.append(img)
        if errors:
            _tm.EMBED_FILES.inc(errors, result="error")
        if not batch_imgs:
            return StepResult()

        with span("embed.forward") as stage:
            vectors = embed_jax.embed_batch(np.stack(batch_imgs))
        _tm.EMBED_STAGE_SECONDS.observe(stage.duration, stage="forward")

        with span("embed.write") as stage:
            written = self._write_embeddings(
                library, journal, loc_id, batch_rows, vectors)
        _tm.EMBED_STAGE_SECONDS.observe(stage.duration, stage="write")
        return StepResult(
            metadata={
                "embeddings_written":
                    self.run_metadata.get("embeddings_written", 0) + written,
            }
        )

    def _write_embeddings(self, library, journal, loc_id: int,
                          batch_rows: list, vectors) -> int:
        """The write stage of one embedding chunk; → rows written."""
        from ...db.database import now_iso
        from ...models import embedder as _embedder
        from ...telemetry import metrics as _tm
        from ..search import index as _search_index

        sync = library.sync
        stamp = now_iso()
        ops = []
        writes: list[tuple[int, bytes]] = []
        for (row, object_id), vec in zip(batch_rows, vectors):
            obj = library.db.find_one("object", id=object_id)
            if obj is None:
                _tm.EMBED_FILES.inc(result="error")
                continue
            blob = _embedder.vector_to_blob(vec)
            writes.append((object_id, blob))
            ops.extend(sync.shared_create(
                "object_embedding", obj["pub_id"].hex(),
                [
                    ("vector", blob),
                    ("dim", _embedder.EMBED_DIM),
                    ("model", _embedder.MODEL_NAME),
                    ("date_calculated", stamp),
                ],
            ))

        def db_writes(conn) -> None:
            for object_id, blob in writes:
                conn.execute(
                    "INSERT INTO object_embedding (object_id, vector, dim, "
                    "model, date_calculated) VALUES (?,?,?,?,?) "
                    "ON CONFLICT (object_id) DO UPDATE SET "
                    "vector=excluded.vector, dim=excluded.dim, "
                    "model=excluded.model, "
                    "date_calculated=excluded.date_calculated",
                    (object_id, blob, _embedder.EMBED_DIM,
                     _embedder.MODEL_NAME, stamp),
                )

        if writes:
            sync.write_ops(ops, db_writes)
            # vouches ordered after the durable commit: a crash between
            # commit and vouch re-embeds once, never vouches a phantom
            for (row, _object_id), _vec in zip(batch_rows, vectors):
                journal.vouch_embed(
                    loc_id, _journal.key_of(row), row["cas_id"]
                )
            _tm.EMBED_FILES.inc(len(writes), result="embedded")
            _search_index.refresh(library)
        return len(writes)

    def _planes_for_embed(self, items: list[tuple[dict, int, str]]) -> list:
        """One f32 plane (or None: undecodable) per (row, object_id,
        path): taken from `_planes` where the thumbnailer's decode
        stage made one, decoded by `_decode_for_embed` for the rest.
        `sd_embed_planes_total{source}` counts the planes by origin."""
        from ...models import embedder as _embedder
        from ...telemetry import metrics as _tm

        holder = self._planes
        planes: list = []
        for row, _object_id, _path in items:
            shared = None if holder is None else holder.take(row["cas_id"])
            planes.append(
                None if shared is None else _embedder.input_plane(shared))
        rest = [i for i, plane in enumerate(planes) if plane is None]
        if rest:
            own = self._decode_for_embed([items[i][2] for i in rest])
            for i, plane in zip(rest, own):
                planes[i] = plane
            _tm.EMBED_PLANES.inc(
                sum(plane is not None for plane in own), source="own")
        if len(rest) < len(items):
            _tm.EMBED_PLANES.inc(len(items) - len(rest), source="shared")
        return planes

    def _decode_for_embed(self, paths: list[str]) -> list:
        """The embedding decode leg: models/embedder.decode_image (a
        JPEG at `images.draft_jpeg`'s DCT scale, everything else as PIL
        opens it), counted in `sd_embed_decode_total`."""
        from ...models import embedder as _embedder

        return [_embedder.decode_image(p) for p in paths]

    async def _wait_thumbnails(self, ctx: JobContext, step: dict) -> StepResult:
        """Rendezvous with the thumbnailer actor (ref:job.rs:83-88
        WaitThumbnails step) — per dispatched batch, so unrelated
        background thumbnail work can't stall this job. After a resume
        the id is from a dead process; `wait_batch` treats unknown ids
        as done (the actor re-queues persisted work on its own).

        After the rendezvous, journal-vouch each dispatched thumbnail
        that is VERIFIABLY in the store (`store.exists`, never the
        actor's counters): the vouch is ordered after the webp landed on
        disk, so a `thumbnail.persist` crash between store and the
        actor's own state journal can leave the actor re-doing work but
        never leaves this journal claiming an absent thumb."""
        thumbnailer = getattr(getattr(ctx.library, "node", None), "thumbnailer", None)
        if thumbnailer is not None:
            await thumbnailer.wait_batch(step.get("batch_id", 0))
            journal = _journal.IndexJournal(ctx.library.db)
            loc_id = self.data["location_id"]
            lib_id = str(ctx.library.id)
            for mat, name, ext, cas_hex in step.get("vouch", []):
                if thumbnailer.store.exists(lib_id, cas_hex):
                    journal.vouch_thumb(loc_id, (mat, name, ext), cas_hex)
        return StepResult()

    async def _wait_labels(self, ctx: JobContext, step: dict) -> StepResult:
        labeler = getattr(getattr(ctx.library, "node", None), "image_labeler", None)
        if labeler is not None:
            await labeler.wait_batch(step["batch_id"])
        return StepResult()

    async def finalize(self, ctx: JobContext) -> Any:
        ctx.progress(message="media processing complete", phase="done")
        return dict(self.run_metadata)


async def distribute_media(
    node: Any, library: Any, location_id: int, **kwargs: Any,
) -> dict[str, Any]:
    """Distribute one location's media-metadata extraction as
    stage-typed WORK shards (parallel/scheduler.py STAGE_MEDIA). The
    ``media_data`` table is node-local, so the shipped column results
    are the convergence carrier; each node recomputes its journal
    digest against its own object_id exactly like a local pass."""
    from ...location.indexer.mesh import distribute_location_stages
    from ...parallel import scheduler as _scheduler

    return await distribute_location_stages(
        node, library, location_id, [_scheduler.STAGE_MEDIA], **kwargs
    )


async def distribute_embeddings(
    node: Any, library: Any, location_id: int, **kwargs: Any,
) -> dict[str, Any]:
    """Distribute one location's semantic-embedding pass as stage-typed
    WORK shards (parallel/scheduler.py STAGE_EMBED): executors decode
    the planes, run the seed-deterministic forward in
    one device batch, mint the same CRDT ops a local pass would, and
    ship the vector blobs back for direct apply. No-op session when
    SD_EMBED is disabled."""
    from ...location.indexer.mesh import distribute_location_stages
    from ...parallel import scheduler as _scheduler

    return await distribute_location_stages(
        node, library, location_id, [_scheduler.STAGE_EMBED], **kwargs
    )
