"""The comparison that decides `correct`: what the timed passes left in
their data directories, read back with sqlite3 and PIL alone and held
against the plain references in `reference/`, layer by layer: the walk
(every generated file and no other), identify (cas_ids), the object link
(copies share one object), the CRDT log, thumbnails, embeddings, in a
rescan cell the rewritten, added and deleted files of every pass, and
that the pass used the chip. Every number compared has a limit; exact
comparisons have the limit 0. PERF.md §2 gives the readings the two
other limits were set from.

A configuration's kinds of file (`generators/common.py`) bring their own
guarantees: a kind module's `compare(c, state) -> set of rels` is called
once for every data directory checked, after the comparisons here. It
adds numbers under names of its own, each beside its limit, and returns
the files that lack their final state.
"""

from __future__ import annotations

import os
import sqlite3
import struct
import time

import numpy as np

from .generators.common import entries_of, read_from_disk, seed_words
from .reference import blake3_np, cas_layout, media

#: mean |difference| of 255 between a stored webp and the reference
#: pixels, worst image of the sample. Sound runs read 2.1-3.3 (webp
#: quality 30 alone costs that much); the control (EXIF orientation not
#: applied) reads 56 or more.
THUMB_GAP_LIMIT = 10.0
#: largest |difference| between a stored embedding and the float64
#: forward pass. Sound runs (bfloat16 operands) read up to 6.4e-3; the
#: control (float8 e4m3 operands) reads 0.11 or more.
EMBED_GAP_LIMIT = 0.03
#: images compared pixel by pixel and vector by vector in each pass
MEDIA_SAMPLE = 8
#: the names this file compares under; a kind may add none of them, or
#: it could loosen a limit by adding it again
OWN_NAMES = frozenset((
    "compiles_in_window", "pallas_mode_not_tpu", "jobs_not_completed",
    "ladder_level", "device_fallbacks", "device_stamp_differs",
    "journal_mode_not_wal", "walk_missing", "walk_extra", "cas_mismatch",
    "object_unlinked", "object_link_errors", "objects_not_distinct_cas",
    "crdt_ops_missing", "thumbnail_missing", "thumbnail_wrong_size",
    "embedding_missing", "thumbnail_pixel_gap", "embedding_gap",
    "rescan_stale", "rescan_not_deleted", "rescan_file_count_off"))


def plain_message(entry: dict) -> bytes:
    """The cas_id message of a plain file, from its size and content
    seed alone (the bytes `generators.common.write_plain` writes)."""
    rng = np.random.default_rng(entry["content"])
    parts = [struct.pack("<Q", entry["size"])]
    parts += [rng.bytes(ln) for _off, ln in cas_layout.ranges(entry["size"])]
    return b"".join(parts)


def reference_cas(location: str, entries: list[dict],
                  kinds: dict | None = None) -> dict[str, str]:
    """rel → cas_id; plain files from their seeds, images and the files
    a kind wrote from disk. Entries with the same size and content are
    hashed once (a kind's files one by one: what else of the entry their
    bytes depend on is the kind's to know)."""
    out: dict[str, str] = {}
    todo: dict[tuple, list[dict]] = {}
    for e in entries:
        own = e["rel"] if e.get("kind") else None
        todo.setdefault((e["size"], tuple(e["content"]), own), []).append(e)
    groups = list(todo.values())
    for lo in range(0, len(groups), 4096):
        part = groups[lo:lo + 4096]
        messages = [
            cas_layout.message(os.path.join(location, g[0]["rel"]))
            if read_from_disk(g[0], kinds) else plain_message(g[0])
            for g in part]
        for g, digest in zip(part, blake3_np.hash_many(
                messages, cas_layout.CAS_HEX // 2)):
            for e in g:
                out[e["rel"]] = digest.hex()
    return out


def _rel(row) -> str:
    ext = f".{row['extension']}" if row["extension"] else ""
    return (row["materialized_path"] + row["name"] + ext).lstrip("/")


def library_db(data_dir: str) -> sqlite3.Connection:
    lib_dir = os.path.join(data_dir, "libraries")
    name = next(n for n in sorted(os.listdir(lib_dir)) if n.endswith(".db"))
    db = sqlite3.connect(f"file:{os.path.join(lib_dir, name)}?mode=ro",
                         uri=True)
    db.row_factory = sqlite3.Row
    return db


def _record_id(pub_id: bytes) -> bytes:
    """The CRDT log's record_id of a row: msgpack str8 of the hex pub_id."""
    return b"\xd9\x20" + pub_id.hex().encode()


def probe(data_dir: str, location: str, changes: dict) -> dict:
    """Right after a rescan pass: the rows of the files that pass
    changed, and the count of file rows. A few dozen indexed lookups."""
    db = library_db(data_dir)
    try:
        rows = {}
        for kind in ("rewritten", "added", "deleted"):
            for e in changes[kind]:
                head, name = os.path.split(e["rel"])
                stem, ext = os.path.splitext(name)
                row = db.execute(
                    "SELECT cas_id, object_id FROM file_path WHERE "
                    "materialized_path = ? AND name = ? AND extension = ?",
                    (f"/{head}/" if head else "/", stem, ext.lstrip("."))
                ).fetchone()
                rows[e["rel"]] = None if row is None else (
                    row["cas_id"], row["object_id"])
        files = db.execute(
            "SELECT count(*) FROM file_path WHERE is_dir = 0").fetchone()[0]
        return {"rows": rows, "files": files}
    finally:
        db.close()


def hashed_files(passes: list[dict], manifest: list[dict],
                 traffic: dict) -> dict:
    """Files the timed passes had to hash, and the bytes their cas_ids
    need: message bytes in, 32 digest bytes out (counted from the
    location, not from the padded shapes the program dispatched)."""
    if traffic.get("mutate"):
        entries = [e for p in passes
                   for e in p["changes"]["rewritten"] + p["changes"]["added"]]
    else:
        entries = manifest * len(passes)
    return {"files": len(entries),
            "bytes": sum(cas_layout.message_len(e["size"]) + 32
                         for e in entries)}


class Compared:
    """Numbers compared, each beside its limit."""

    def __init__(self) -> None:
        self.numbers: dict[str, list[float]] = {}
        self._owner: dict[str, str] = {}

    def worst(self, name: str, value: float, limit: float) -> None:
        """Keep the largest reading of `name` over the passes."""
        old = self.numbers.get(name)
        if old is None or value > old[0]:
            self.numbers[name] = [value, limit]

    def add(self, name: str, value: float, limit: float) -> None:
        old = self.numbers.get(name, [0, limit])
        self.numbers[name] = [old[0] + value, limit]

    def scoped(self, kind: str) -> "_Scoped":
        """What a kind's `compare` gets: `worst` and `add` under names
        that neither this file nor another kind compares under."""
        return _Scoped(self, kind)

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.numbers.values())

    def lines(self) -> list[str]:
        return [f"compared {name} = {v:.6g} (limit {lim:g}) "
                f"{'ok' if v <= lim else 'FAIL'}"
                for name, (v, lim) in self.numbers.items()]


class _Scoped:
    def __init__(self, c: Compared, kind: str) -> None:
        self._c, self._kind = c, kind

    def _own(self, name: str) -> None:
        holder = "check.py" if name in OWN_NAMES else \
            self._c._owner.setdefault(name, self._kind)
        if holder != self._kind:
            raise SystemExit(
                f"benchmark: the kind {self._kind!r} compares under "
                f"{name!r}, a name {holder} holds")

    def worst(self, name: str, value: float, limit: float) -> None:
        self._own(name)
        self._c.worst(name, value, limit)

    def add(self, name: str, value: float, limit: float) -> None:
        self._own(name)
        self._c.add(name, value, limit)


def media_sample(images: list[dict], seed: int) -> list[dict]:
    """At most MEDIA_SAMPLE images drawn from the seed: one of every
    (format, orientation) kind first, the largest file always."""
    if not images:
        return []
    rng = np.random.default_rng(seed_words(seed, 0x6D656469))
    order = [images[int(i)] for i in rng.permutation(len(images))]
    largest = max(images, key=lambda e: e["size"])
    picked = [largest]
    kinds = {(largest["image"]["format"], largest["image"]["orientation"])}
    for e in order:
        kind = (e["image"]["format"], e["image"]["orientation"])
        if kind not in kinds:
            kinds.add(kind)
            picked.append(e)
    for e in order:
        if len(picked) >= MEDIA_SAMPLE:
            break
        if e not in picked:
            picked.append(e)
    return picked[:MEDIA_SAMPLE]


def media_references(location: str, sample: list[dict], target_px: int,
                     control: str | None = None) -> dict[str, dict]:
    """rel → {"pixels", "vector"} for the sampled images, one full-size
    decode each. `control` names the reference computed a notch below."""
    out = {}
    for e in sample:
        rgba, orientation = media.decode_rgba(os.path.join(location, e["rel"]))
        if control == "thumbnail":
            orientation = 1  # the guarantee broken: EXIF orientation
        out[e["rel"]] = {
            "pixels": media.thumbnail_pixels(rgba, orientation, target_px),
            "vector": media.embed_forward(
                media.embed_plane(rgba)[None], control == "embedding")[0],
        }
    return out


def _stored_thumbnails(data_dir: str) -> dict[str, str]:
    stored = {}
    for d, _dirs, names in os.walk(os.path.join(data_dir, "thumbnails")):
        stored.update({n: os.path.join(d, n) for n in names})
    return stored


def _check_state(c: Compared, data_dir: str, location: str,
                 entries: list[dict], want_cas: dict[str, str],
                 config: dict, sample: list[dict], refs: dict,
                 exact_objects: bool, kinds: dict, seed: int) -> set[str]:
    """One data directory against the location's expected state; → the
    files that lack their final state. `exact_objects`: a fresh library
    holds exactly one object per distinct content (a rescanned one may
    still hold the objects of rewritten and deleted files)."""
    bad: set[str] = set()
    db = library_db(data_dir)
    try:
        c.worst("journal_mode_not_wal", float(db.execute(
            "PRAGMA journal_mode").fetchone()[0].lower() != "wal"), 0)
        rows = {_rel(r): r for r in db.execute(
            "SELECT materialized_path, name, extension, cas_id, object_id, "
            "pub_id FROM file_path WHERE is_dir = 0")}
        want = {e["rel"] for e in entries}
        missing = want - set(rows)
        c.add("walk_missing", len(missing), 0)
        c.add("walk_extra", len(set(rows) - want), 0)
        bad |= missing
        present = want & set(rows)

        wrong = {rel for rel in present
                 if rows[rel]["cas_id"] != want_cas[rel]}
        c.add("cas_mismatch", len(wrong), 0)
        unlinked = {rel for rel in present
                    if rows[rel]["object_id"] is None}
        c.add("object_unlinked", len(unlinked), 0)
        bad |= wrong | unlinked

        # copies share one object, different content never does
        by_cas: dict[str, set] = {}
        by_object: dict[int, set] = {}
        for rel in present:
            r = rows[rel]
            if r["object_id"] is not None:
                by_cas.setdefault(want_cas[rel], set()).add(r["object_id"])
                by_object.setdefault(r["object_id"], set()).add(want_cas[rel])
        split = sum(1 for objs in by_cas.values() if len(objs) > 1)
        merged = sum(1 for cas in by_object.values() if len(cas) > 1)
        c.add("object_link_errors", split + merged, 0)
        objects = db.execute("SELECT count(*) FROM object").fetchone()[0]
        if exact_objects:
            c.worst("objects_not_distinct_cas",
                    abs(objects - len(by_object)), 0)

        # the CRDT log: a create per row, a cas_id and an object_id
        # update per identified file, a create per object and embedding
        ops: dict[tuple, set] = {}
        for model, kind, record in db.execute(
                "SELECT model, kind, record_id FROM crdt_operation WHERE "
                "kind IN ('c', 'u:cas_id', 'u:object_id')"):
            ops.setdefault((model, kind), set()).add(bytes(record))
        records = {_record_id(bytes(rows[rel]["pub_id"]))
                   for rel in present}
        crdt_missing = sum(
            len(records - ops.get(("file_path", kind), set()))
            for kind in ("c", "u:cas_id", "u:object_id"))
        crdt_missing += max(0, objects - len(ops.get(("object", "c"), ())))
        embeddings = db.execute(
            "SELECT count(*) FROM object_embedding").fetchone()[0]
        crdt_missing += max(
            0, embeddings - len(ops.get(("object_embedding", "c"), ())))
        c.add("crdt_ops_missing", crdt_missing, 0)

        images = [e for e in entries if e.get("image")]
        comparing = {name: mod for name, mod in kinds.items()
                     if hasattr(mod, "compare")}
        stored = _stored_thumbnails(data_dir) if images or comparing else {}
        if images:
            target = config["upstream"]["thumbnail"]["target_px"]
            vectors = {_rel(r): r["vector"] for r in db.execute(
                "SELECT fp.materialized_path, fp.name, fp.extension, "
                "e.vector FROM file_path fp JOIN object_embedding e ON "
                "e.object_id = fp.object_id WHERE fp.is_dir = 0")}
            from PIL import Image

            no_thumb, wrong_size, no_vector = set(), set(), set()
            for e in images:
                thumb = stored.get(want_cas[e["rel"]] + ".webp")
                im = e["image"]
                if thumb is None:
                    no_thumb.add(e["rel"])
                else:
                    with Image.open(thumb) as t:
                        if t.format != "WEBP" or t.size != media.thumbnail_size(
                                im["w"], im["h"], im["orientation"], target):
                            wrong_size.add(e["rel"])
                blob = vectors.get(e["rel"])
                if blob is None or len(blob) != 4 * media.EMBED_DIM or \
                        not np.isfinite(np.frombuffer(blob, "<f4")).all():
                    no_vector.add(e["rel"])
            c.add("thumbnail_missing", len(no_thumb), 0)
            c.add("thumbnail_wrong_size", len(wrong_size), 0)
            c.add("embedding_missing", len(no_vector), 0)
            bad |= no_thumb | wrong_size | no_vector
            for e in sample:
                rel = e["rel"]
                if rel in no_thumb or rel in no_vector:
                    continue
                with open(stored[want_cas[rel] + ".webp"], "rb") as f:
                    c.worst("thumbnail_pixel_gap", media.thumbnail_gap(
                        f.read(), refs[rel]["pixels"]), THUMB_GAP_LIMIT)
                c.worst("embedding_gap", media.embed_gap(
                    np.frombuffer(vectors[rel], "<f4"), refs[rel]["vector"]),
                    EMBED_GAP_LIMIT)

        for name, mod in comparing.items():
            bad |= set(mod.compare(c.scoped(name), {
                "data_dir": data_dir, "location": location,
                "entries": entries_of(entries, name),
                "rows": rows, "want_cas": want_cas, "stored": stored,
                "config": config, "seed": seed, "db": db}))
    finally:
        db.close()
    return bad


def decide(config: dict, traffic: dict, location: str, manifest: list[dict],
           passes: list[dict], seed: int, *, compiles_in_window: int,
           stamp: dict, kinds: dict | None = None) -> dict:
    """→ {"correct", "failed", "compared", "lines", "seconds"}; `kinds`
    are the configuration's kind modules by name."""
    t0 = time.perf_counter()
    kinds = kinds or {}
    c = Compared()

    # the pass used the chip, and nothing stalled or left it
    from spacedrive_tpu.ops import blake3_pallas

    c.worst("compiles_in_window", compiles_in_window, 0)
    if stamp["platform"] == "tpu":
        c.worst("pallas_mode_not_tpu",
                float(blake3_pallas.pallas_mode() != "tpu"), 0)
    for p in passes:
        s = p["summary"]
        jobs = s["jobs"]
        not_completed = sum(jobs.get(j) != "COMPLETED" for j in
                            ("indexer", "file_identifier", "media_processor"))
        c.add("jobs_not_completed", not_completed + s["jobs_failed"], 0)
        c.worst("ladder_level", s["ladder_level"], 0)
        c.add("device_fallbacks", s["cas_backend_fallbacks"]
              + s["thumbnail_cpu_fallbacks"] + s["thumbnail_errors"], 0)
        c.worst("device_stamp_differs", float(s["device"] != stamp), 0)

    target = config["upstream"]["thumbnail"]["target_px"]
    sample = media_sample([e for e in manifest if e.get("image")], seed)
    refs = media_references(location, sample, target)
    want_cas = reference_cas(location, manifest, kinds)
    failed = 0
    if traffic["fresh_data_dir"]:
        for p in passes:
            failed += len(_check_state(c, p["data_dir"], location, manifest,
                                       want_cas, config, sample, refs, True,
                                       kinds, seed))
    else:
        # every pass's own changes, as probed right after it ...
        for p in passes:
            ch, rows = p["changes"], p["probe"]["rows"]
            changed = ch["rewritten"] + ch["added"]
            cas = {e["rel"]: d.hex() for e, d in zip(changed, blake3_np.hash_many(
                [plain_message(e) for e in changed], cas_layout.CAS_HEX // 2))}
            stale = {rel for rel, want in cas.items()
                     if rows[rel] is None or rows[rel][0] != want
                     or rows[rel][1] is None}
            kept = {e["rel"] for e in ch["deleted"] if rows[e["rel"]] is not None}
            c.add("rescan_stale", len(stale), 0)
            c.add("rescan_not_deleted", len(kept), 0)
            c.worst("rescan_file_count_off",
                    abs(p["probe"]["files"] - p["offered"]), 0)
            failed += len(stale) + len(kept)
        # ... and the whole library as the last pass left it
        failed += len(_check_state(c, passes[-1]["data_dir"], location,
                                   manifest, want_cas, config, sample, refs,
                                   False, kinds, seed))
    return {"correct": c.correct, "failed": failed, "compared": c.numbers,
            "lines": c.lines(), "seconds": round(time.perf_counter() - t0, 2)}
