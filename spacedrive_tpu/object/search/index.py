"""Per-library vector index — memmap-backed cosine top-k.

Layout: one L2-normalized f32 [N, EMBED_DIM] matrix plus an aligned
object-id map, built from `object_embedding` rows and maintained
incrementally from BOTH write sides:

- local writes: the media pipeline's embed stage calls
  :func:`refresh` after its `sync.write_ops` commit;
- sync-applied ops: p2p/manager's ingest `on_applied` hook calls
  :func:`on_embeddings_applied`, so a replica's index converges with
  its DB without polling.

Incremental maintenance keys off (id watermark, date_calculated
stamp): new rows append, LWW-updated rows overwrite in place, and a
shrinking table (object deletes cascade) triggers a full rebuild. A
row whose vector blob fails strict validation (wrong width, non-finite
values — e.g. a poisoned sync op) is skipped ALONE and counted; it
never wedges maintenance for the other rows.

The matrix persists next to the library DB (`<db>.searchidx/`) and is
memmapped back on load, so a 100k-vector index costs an open() —
not a 50 MB SELECT — per process start. Scoring is one [N, D] @ [D]
matmul + top-k: jitted on-device by default, with a host numpy path
(identical ranking — stable tie-break by lower row index, matching
`lax.top_k`) behind the `search.query` fault point.
"""

from __future__ import annotations

import functools
import json
import logging
import os
import threading
from typing import Any

import numpy as np

from ...models import embedder as _embedder

logger = logging.getLogger(__name__)


def _normalize(vec: np.ndarray) -> np.ndarray:
    n = float(np.linalg.norm(vec))
    if n <= 0.0 or not np.isfinite(n):
        return np.zeros_like(vec)
    return (vec / np.float32(n)).astype(np.float32)


@functools.cache
def _score_fn():
    """Lazily built jitted cosine scorer (jax imported on first use).
    Returns (scores, indices) for the top-k rows."""
    import jax

    @functools.partial(jax.jit, static_argnames=("k",))
    def score(matrix, probe, k: int):
        import jax.numpy as jnp

        s = matrix @ probe.astype(jnp.float32)
        return jax.lax.top_k(s, k)

    return score


class LibraryIndex:
    """The per-library matrix + id map. Thread-safe: the serve layer
    queries from executor threads while the pipeline and the ingest
    hook refresh."""

    def __init__(self, library: Any):
        self._library = library
        self._lock = threading.Lock()
        self._matrix: np.ndarray = np.zeros(
            (0, _embedder.EMBED_DIM), np.float32
        )
        self._ids: list[int] = []
        self._pos: dict[int, int] = {}
        self._watermark = 0  # max object_embedding.id folded in
        self._stamp = ""     # max date_calculated folded in (ISO text)
        self._loaded = False

    # ---- persistence ---------------------------------------------------

    def _dir(self) -> str | None:
        path = getattr(self._library.db, "path", ":memory:")
        if path == ":memory:":
            return None
        return path + ".searchidx"

    def _load_persisted(self) -> None:
        d = self._dir()
        if d is None:
            return
        meta_p = os.path.join(d, "meta.json")
        vec_p = os.path.join(d, "vectors.f32")
        try:
            with open(meta_p, encoding="utf-8") as f:
                meta = json.load(f)
            ids = [int(i) for i in meta["ids"]]
            dim = int(meta.get("dim", 0))
            if dim != _embedder.EMBED_DIM:
                return  # model width changed → rebuild from the DB
            mm = np.memmap(vec_p, dtype="<f4", mode="r",
                           shape=(len(ids), dim))
            self._matrix = mm
            self._ids = ids
            self._pos = {oid: i for i, oid in enumerate(ids)}
            self._watermark = int(meta.get("watermark", 0))
            self._stamp = str(meta.get("stamp", ""))
        except (OSError, ValueError, KeyError, json.JSONDecodeError):
            pass  # torn sidecar → rebuilt from the DB below

    def _persist(self) -> None:
        d = self._dir()
        if d is None:
            return
        try:
            os.makedirs(d, exist_ok=True)
            vec_p = os.path.join(d, "vectors.f32")
            tmp = vec_p + ".tmp"
            np.ascontiguousarray(
                self._matrix, dtype="<f4"
            ).tofile(tmp)
            os.replace(tmp, vec_p)
            meta = {
                "dim": _embedder.EMBED_DIM,
                "ids": self._ids,
                "watermark": self._watermark,
                "stamp": self._stamp,
            }
            tmp = os.path.join(d, "meta.json.tmp")
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(meta, f)
            os.replace(tmp, os.path.join(d, "meta.json"))
            # re-open memmapped so steady-state queries read the OS
            # page cache, not a private heap copy
            self._matrix = np.memmap(
                vec_p, dtype="<f4", mode="r",
                shape=(len(self._ids), _embedder.EMBED_DIM),
            )
        except OSError:
            logger.exception("search index persist failed (non-fatal)")

    # ---- maintenance ---------------------------------------------------

    def refresh(self) -> int:
        """Fold new/updated `object_embedding` rows in; returns the
        vector count. Incremental: only rows past the (id, stamp)
        watermarks are read on a warm call."""
        from ...telemetry import metrics as _tm

        with self._lock:
            if not self._loaded:
                self._load_persisted()
                self._loaded = True
            db = self._library.db
            total = db.query_one(
                "SELECT COUNT(*) AS n FROM object_embedding"
            )["n"]
            if total < len(self._ids):
                # shrink (object deletes cascade): rebuild from scratch
                self._matrix = np.zeros((0, _embedder.EMBED_DIM), np.float32)
                self._ids = []
                self._pos = {}
                self._watermark = 0
                self._stamp = ""
            rows = db.query(
                "SELECT id, object_id, vector, date_calculated "
                "FROM object_embedding WHERE id > ? "
                "OR (date_calculated IS NOT NULL AND date_calculated > ?) "
                "ORDER BY id",
                (self._watermark, self._stamp),
            )
            if not rows:
                _tm.SEARCH_INDEX_VECTORS.set(float(len(self._ids)))
                return len(self._ids)
            fresh: list[np.ndarray] = []
            fresh_ids: list[int] = []
            matrix = np.asarray(self._matrix)
            for r in rows:
                self._watermark = max(self._watermark, int(r["id"]))
                if r["date_calculated"]:
                    self._stamp = max(self._stamp, str(r["date_calculated"]))
                vec = _embedder.blob_to_vector(r["vector"])
                if vec is None:
                    # corrupt/poisoned row: skipped alone — the rest of
                    # the batch still lands
                    logger.warning(
                        "object_embedding row %s has an invalid vector; "
                        "skipped", r["id"],
                    )
                    continue
                vec = _normalize(vec)
                pos = self._pos.get(r["object_id"])
                if pos is not None:
                    if matrix.base is not None or not matrix.flags.writeable:
                        matrix = matrix.copy()
                    matrix[pos] = vec
                else:
                    self._pos[r["object_id"]] = len(self._ids) + len(fresh_ids)
                    fresh_ids.append(int(r["object_id"]))
                    fresh.append(vec)
            if fresh:
                matrix = np.concatenate(
                    [matrix, np.stack(fresh)], axis=0
                ) if matrix.size else np.stack(fresh)
                self._ids.extend(fresh_ids)
            self._matrix = matrix.astype(np.float32, copy=False)
            self._persist()
            _tm.SEARCH_INDEX_VECTORS.set(float(len(self._ids)))
            return len(self._ids)

    # ---- scoring -------------------------------------------------------

    def query(self, probe: np.ndarray, k: int = 10) -> list[tuple[int, float]]:
        """Top-k (object_id, cosine) for a probe vector. Device scoring
        by default; any device failure (or an injected `search.query`
        fault) demotes to the host path, which ranks identically."""
        from ...telemetry import metrics as _tm
        from ...utils import faults as _faults

        with self._lock:
            matrix = np.asarray(self._matrix)
            ids = list(self._ids)
        if not ids:
            return []
        probe = _normalize(np.asarray(probe, np.float32))
        k = min(int(k), len(ids))
        if k <= 0:
            return []
        try:
            spec = _faults.hit("search.query")
            if spec is not None:
                if spec.mode == "raise":
                    raise _faults.InjectedFault(
                        "injected device failure (search)")
                if spec.mode == "xla":
                    raise _faults.device_error("search.query")
            scores, idxs = _score_fn()(matrix, probe, k=k)
            scores = np.asarray(scores)
            idxs = np.asarray(idxs)
            _tm.SEARCH_QUERIES.inc(path="device")
        except Exception:  # noqa: BLE001 - host fallback ranks identically
            s = matrix @ probe
            # stable sort on -s breaks ties by lower row index — the
            # same order lax.top_k returns
            idxs = np.argsort(-s, kind="stable")[:k]
            scores = s[idxs]
            _tm.SEARCH_QUERIES.inc(path="host")
        return [(ids[int(i)], float(v)) for i, v in zip(idxs, scores)]

    def __len__(self) -> int:
        with self._lock:
            return len(self._ids)


# process-wide registry, keyed by (db path, library id) like the
# journal's runtime counters — Library objects are transient wrappers
_INDEXES: dict[tuple[str, str], LibraryIndex] = {}
_INDEXES_LOCK = threading.Lock()


def _key(library: Any) -> tuple[str, str]:
    return (str(getattr(library.db, "path", ":memory:")), str(library.id))


def get_index(library: Any) -> LibraryIndex:
    key = _key(library)
    with _INDEXES_LOCK:
        idx = _INDEXES.get(key)
        if idx is None:
            idx = LibraryIndex(library)
            _INDEXES[key] = idx
        else:
            # re-point at the live Library (a reloaded library carries
            # a fresh db handle for the same path)
            idx._library = library
        return idx


def drop_index(library: Any) -> None:
    """Forget a closing library's index, so that neither its vectors nor
    the library (and the node behind it) outlive the close."""
    with _INDEXES_LOCK:
        _INDEXES.pop(_key(library), None)


def refresh(library: Any) -> int:
    return get_index(library).refresh()


def on_embeddings_applied(library: Any) -> None:
    """Ingest `on_applied` leg: fold sync-applied embedding rows into
    the replica's index. Failures are contained — index maintenance
    must never wedge the ingest actor."""
    try:
        get_index(library).refresh()
    except Exception:  # noqa: BLE001 - maintenance is best-effort
        logger.exception("search index refresh after sync apply failed")


def query(library: Any, probe: np.ndarray, k: int = 10) -> list[tuple[int, float]]:
    idx = get_index(library)
    idx.refresh()
    return idx.query(probe, k=k)


def probe_for(library: Any, text: str) -> np.ndarray | None:
    """Resolve a CLI/API query string to a probe vector: an existing
    image path embeds directly; otherwise the string is matched against
    stored label names and the probe is the centroid of the labeled
    objects' vectors. None = unresolvable."""
    if os.path.exists(text):
        img = _embedder.decode_image(text)
        if img is None:
            return None
        from ...ops import embed_jax

        return embed_jax.embed_batch(img[None, ...])[0]
    row = library.db.query_one(
        "SELECT id FROM label WHERE name = ?", (text,)
    )
    if row is None:
        return None
    obj_ids = [
        r["object_id"] for r in library.db.query(
            "SELECT object_id FROM label_on_object WHERE label_id = ?",
            (row["id"],),
        )
    ]
    if not obj_ids:
        return None
    idx = get_index(library)
    idx.refresh()
    with idx._lock:
        vecs = [
            np.asarray(idx._matrix)[idx._pos[oid]]
            for oid in obj_ids if oid in idx._pos
        ]
    if not vecs:
        return None
    return _normalize(np.mean(np.stack(vecs), axis=0))
