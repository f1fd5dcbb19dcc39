"""Duplicate detection — pHash job + grouping query.

BASELINE.json config 5. The job walks image objects that lack an
`object.phash`, decodes the *originals* (JPEG draft mode decodes at
1/8 DCT scale, so this is cheap and avoids the distance inflation of
re-hashing webp-q30 thumbnails; the thumbnail is only the fallback),
batches 32×32 grayscale planes, and runs the device pHash
(ops/phash_jax.py). `find_duplicates` then groups objects by Hamming
distance via blockwise MXU matmuls. Exact-duplicate grouping by cas_id
(the reference's only dedup, ref:core/src/object/file_identifier
object reuse by cas_id) falls out of the same query.
"""

from __future__ import annotations

import logging
import os
from typing import Any

import numpy as np

from ..files.kind import ObjectKind
from ..jobs import StatefulJob
from ..jobs.job import JobContext, StepResult
from ..jobs.manager import register_job
from ..location.indexer import journal as _journal
from ..ops import phash_jax

logger = logging.getLogger(__name__)

CHUNK = 64


@register_job
class DuplicateDetectorJob(StatefulJob):
    """init: {location_id?, threshold?} — hashes image objects missing
    a phash; finalize records the duplicate groups found."""

    NAME = "duplicate_detector"
    IS_BATCHED = True

    async def init_job(self, ctx: JobContext) -> None:
        db = ctx.library.db
        conds = ["o.kind = ?", "o.phash IS NULL", "fp.cas_id IS NOT NULL"]
        params: list[Any] = [int(ObjectKind.Image)]
        if self.init.get("location_id"):
            conds.append("fp.location_id = ?")
            params.append(int(self.init["location_id"]))
        rows = db.query(
            "SELECT o.id AS object_id, fp.cas_id, fp.location_id, "
            "fp.materialized_path, fp.name, fp.extension, fp.is_dir, "
            "fp.size_in_bytes_bytes "
            "FROM object o JOIN file_path fp ON fp.object_id = o.id "
            f"WHERE {' AND '.join(conds)} GROUP BY o.id",
            params,
        )
        for off in range(0, len(rows), CHUNK):
            self.steps.append({"rows": rows[off : off + CHUNK]})
        self.run_metadata.update(hashed=0, skipped=0)
        ctx.progress(
            task_count=len(self.steps),
            message=f"hashing {len(rows)} images",
            phase="phash",
        )

    def _location(self, ctx: JobContext, location_id: int) -> dict | None:
        locs = self.data.setdefault("_loc_cache", {})
        loc = locs.get(location_id)
        if loc is None:
            loc = ctx.library.db.find_one("location", id=location_id)
            locs[location_id] = loc
        return loc

    def _decode_gray(self, ctx: JobContext, row: dict) -> np.ndarray | None:
        """Original-first decode: JPEG draft mode pulls a 1/8-scale DCT
        decode, so cost stays low while avoiding the distance inflation
        of re-hashing webp-q30 (possibly upscaled) thumbnails; the
        thumbnail is the fallback when the original is gone/undecodable."""
        from PIL import Image

        loc = self._location(ctx, row["location_id"])
        if loc is not None:
            from ..files.isolated_path import full_path_from_db_row

            path = full_path_from_db_row(loc["path"], row)
            try:
                with Image.open(path) as img:
                    if img.format == "JPEG":
                        img.draft("RGB", (phash_jax.DCT_SIZE, phash_jax.DCT_SIZE))
                    return phash_jax.to_gray32(np.asarray(img.convert("RGBA")))
            except Exception:
                pass
        node = getattr(ctx.library, "node", None)
        if node is not None:
            thumb = node.thumbnailer.store.path_for(
                str(ctx.library.id), row["cas_id"]
            )
            if os.path.exists(thumb):
                try:
                    with Image.open(thumb) as img:
                        return phash_jax.to_gray32(
                            np.asarray(img.convert("RGBA"))
                        )
                except Exception:
                    pass
        return None

    async def execute_step(self, ctx: JobContext, step: dict, step_number: int) -> StepResult:
        import asyncio

        from ..db.database import blob_u64

        rows = step["rows"]
        journal = _journal.IndexJournal(ctx.library.db)

        def consult(r: dict):
            """Journal-vouched pHash: skip the original's full decode
            when a fresh entry for this exact cas already carries one."""
            from ..files.isolated_path import full_path_from_db_row

            loc = self._location(ctx, r["location_id"])
            if loc is None:
                return None
            # count_invalidated=False: the walker already counted this
            # pass's invalidations — keep the hit rate per-file
            verdict, entry = journal.lookup(
                r["location_id"], _journal.key_of(r),
                _journal.stat_identity(full_path_from_db_row(loc["path"], r)),
                count_invalidated=False,
            )
            if (
                verdict == _journal.HIT and entry is not None
                and entry.phash is not None and entry.cas_id == r["cas_id"]
            ):
                journal.bytes_saved(blob_u64(r["size_in_bytes_bytes"]) or 0,
                                    location_id=r["location_id"])
                return entry.phash
            return None

        def decode_all():
            cached, grays = [], []
            for r in rows:
                ph = consult(r)
                cached.append(ph)
                grays.append(
                    None if ph is not None else self._decode_gray(ctx, r)
                )
            return cached, grays

        cached, grays = await asyncio.to_thread(decode_all)
        ok = [
            (r, g) for r, g, c in zip(rows, grays, cached)
            if g is not None and c is None
        ]
        reused = [(r, c) for r, c in zip(rows, cached) if c is not None]
        skipped = len(rows) - len(ok) - len(reused)
        updates: list[tuple[bytes, int]] = [
            (ph, row["object_id"]) for row, ph in reused
        ]
        hashed_pairs: list[tuple[dict, bytes]] = []
        if ok:
            batch = np.stack([g for _r, g in ok])
            hashes = await asyncio.to_thread(phash_jax.phash_batch, batch)
            for (row, _g), h in zip(ok, hashes):
                updates.append((h.tobytes(), row["object_id"]))
                hashed_pairs.append((row, h.tobytes()))
        if updates:
            ctx.library.db.executemany(
                "UPDATE object SET phash = ? WHERE id = ?", updates
            )
            # journal writes ordered after the phash rows committed —
            # inside the `if updates:` guard so the commit provably
            # dominates the vouch (hashed_pairs ⊆ updates, so this
            # moves no work; sdlint SD017 checks the dominance)
            for row, ph in hashed_pairs:
                journal.record_phash(
                    row["location_id"], _journal.key_of(row),
                    row["cas_id"], ph
                )
        self.run_metadata["hashed"] += len(ok)
        self.run_metadata["reused"] = self.run_metadata.get("reused", 0) + len(reused)
        self.run_metadata["skipped"] += skipped
        ctx.progress(completed_task_count=step_number + 1)
        return StepResult()

    async def finalize(self, ctx: JobContext) -> Any:
        import asyncio

        self.data.pop("_loc_cache", None)  # not serializable state
        groups = await asyncio.to_thread(
            find_duplicates, ctx.library, int(self.init.get("threshold", 8))
        )
        self.run_metadata["duplicate_groups"] = len(groups)
        return {
            "hashed": self.run_metadata["hashed"],
            "duplicate_groups": len(groups),
        }


def find_duplicates(library: Any, threshold: int = 8) -> list[dict[str, Any]]:
    """Near-duplicate groups over all hashed objects + exact cas_id
    groups. Returns [{object_ids, kind: 'near'|'exact'}]."""
    rows = library.db.query(
        "SELECT id, phash FROM object WHERE phash IS NOT NULL"
    )
    near = phash_jax.duplicate_groups(
        [(r["id"], r["phash"]) for r in rows], threshold=threshold
    )
    out = [{"object_ids": g, "kind": "near"} for g in near]
    exact = library.db.query(
        "SELECT cas_id, GROUP_CONCAT(DISTINCT object_id) AS ids FROM file_path "
        "WHERE cas_id IS NOT NULL AND object_id IS NOT NULL "
        "GROUP BY cas_id HAVING COUNT(DISTINCT object_id) > 1"
    )
    for r in exact:
        out.append(
            {
                "object_ids": [int(i) for i in r["ids"].split(",")],
                "kind": "exact",
            }
        )
    # enrich with the file_path rows so clients can render the groups
    from ..db.database import blob_u64

    all_ids = sorted({oid for g in out for oid in g["object_ids"]})
    by_object: dict[int, list[dict[str, Any]]] = {}
    for off in range(0, len(all_ids), 900):  # SQLite bind-variable limit
        chunk = all_ids[off:off + 900]
        qmarks = ",".join("?" * len(chunk))
        for row in library.db.query(
            f"SELECT object_id, name, extension, materialized_path, cas_id, "
            f"size_in_bytes_bytes FROM file_path WHERE object_id IN ({qmarks})",
            tuple(chunk),
        ):
            by_object.setdefault(row["object_id"], []).append({
                "name": row["name"],
                "extension": row["extension"],
                "materialized_path": row["materialized_path"],
                "cas_id": row["cas_id"],
                "size_in_bytes": blob_u64(row["size_in_bytes_bytes"]) or 0,
            })
    for g in out:
        g["files"] = [f for oid in g["object_ids"] for f in by_object.get(oid, [])]
    return out


async def distribute_phash(
    node: Any, library: Any, location_id: int, **kwargs: Any,
) -> dict[str, Any]:
    """Distribute one location's duplicates-pHash pass as stage-typed
    WORK shards (parallel/scheduler.py STAGE_PHASH): executors reuse
    journal-vouched hashes, gray-decode the rest, DCT
    in one device batch, and ship the 8-byte hashes back — the
    local-only ``object.phash`` column converges via the shipped
    results. With no P2P runtime this IS a local pass in shard
    clothing."""
    from ..location.indexer.mesh import distribute_location_stages
    from ..parallel import scheduler as _scheduler

    return await distribute_location_stages(
        node, library, location_id, [_scheduler.STAGE_PHASH], **kwargs
    )
